"""The EmbeddingTable facade — the only embedding API models touch.

One frozen dataclass wraps an :class:`EmbeddingConfig` and exposes
``.init(key)`` / ``.make_buffers(store)`` / ``.embed`` / ``.embed_fields`` /
``.embed_bag`` / ``.materialize_rows`` / ``.param_count`` / ``.describe()``.
Scheme (allocation policy) and backend (split / fused / sharded) are both
resolved per call through the registry and ``backends.resolve_backend`` —
this module never branches on a kind string, which is what lets a new scheme
register itself from its own module with zero edits here.

The module-level functions are the functional form of the same API
(``repro.core.embedding`` re-exports them for back-compat).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.embed import backends as bke
from repro.embed.config import EmbeddingConfig
from repro.embed.registry import get_scheme


def _global_ids(cfg: EmbeddingConfig, table: int, ids: jax.Array) -> jax.Array:
    base = int(cfg.table_offsets()[table])
    return ids.astype(jnp.int32) + jnp.int32(base)


def init_embedding(key: jax.Array, cfg: EmbeddingConfig) -> dict:
    """Trainable parameters for the configured scheme."""
    return get_scheme(cfg.kind).init_params(key, cfg)


def make_buffers(cfg: EmbeddingConfig, store=None) -> dict:
    """Non-trainable device buffers (empty for schemes that need none)."""
    return get_scheme(cfg.kind).make_buffers(cfg, store)


def _memory_lookup(cfg: EmbeddingConfig, params: dict, buffers: dict,
                   gids: jax.Array) -> jax.Array:
    """[N] global ids -> [N, d] via the resolved backend (memory family).

    Under an active sparse-gradient trace (``repro.optim.sparse``) the
    lookup cooperates with the two-pass engine: the *record* pass emits the
    [N, d] location tensor (everything else dead-codes away) and the
    *provide* pass runs the real lookup with the pool behind stop_gradient
    plus an additive zero tap whose cotangent carries the sparse values —
    the dense zeros(m) pool gradient is never materialized.
    """
    from repro.optim import sparse as _sparse
    scheme = get_scheme(cfg.kind)
    st = _sparse.active()
    tiered = bke.tiered_active(buffers)
    if st is not None and st.mode == "record":
        rows = scheme.sparse_row_ids(cfg, buffers, gids)
        # row mode needs the pool to tile exactly into d-wide rows; a
        # ragged budget (m % d != 0) falls back to element-level records.
        # A tiered pool also falls back: the tier remap is element-wise
        # over the compact pool, so row/stripe structure does not survive.
        if not tiered and rows is not None and \
                scheme.memory_slots(cfg) % cfg.dim == 0:
            st.record_rows(params["memory"], rows, cfg.dim)
        else:
            loc = bke.sparse_locations(cfg, scheme, params, buffers, gids)
            # striped-layout schemes declare bucketed columns: the sparse
            # engine then builds the SparseGrad with d per-stripe sorts
            # instead of one global O(K log K) argsort
            st.record(params["memory"], loc,
                      n_buckets=0 if tiered else scheme.sparse_buckets(cfg))
        return jnp.zeros((gids.shape[0], cfg.dim), params["memory"].dtype)
    if st is not None and st.mode == "provide":
        tap = st.next_tap((gids.shape[0], cfg.dim))
        params = dict(params,
                      memory=jax.lax.stop_gradient(params["memory"]))
        backend = bke.resolve_backend(cfg, params, scheme, buffers)
        return backend.lookup(cfg, scheme, params, buffers, gids) + tap
    backend = bke.resolve_backend(cfg, params, scheme, buffers)
    return backend.lookup(cfg, scheme, params, buffers, gids)


def _table_lookup(cfg: EmbeddingConfig, scheme, params: dict,
                  ids: jax.Array) -> jax.Array:
    """ids [B, F] -> [B, F, d] for a scheme whose table rows are the
    embeddings, under the ``table_lookup`` scope.

    Like ``_memory_lookup`` it cooperates with the sparse-gradient engine:
    the *record* pass reports each table's rows (``record_rows``) and
    returns zeros, the *provide* pass reads the tables behind
    stop_gradient plus one zero tap per table, so no [V, d] table gradient
    is made.  Under a mesh the rows come from the 'model'-sharded tables
    through ``scheme.sharded_lookup`` (the tables lane-packed there, as
    ``repro.dist.sharding.row_table_shape`` lays them out); with none, one
    ``jnp.take`` per table.
    """
    from repro.optim import sparse as _sparse
    B, F = ids.shape
    keys = [f"table_{t}" for t in range(F)]
    st = _sparse.active()
    if st is not None and st.mode == "record":
        for t, key in enumerate(keys):
            # a lane-packed table holds k rows per row of its width
            k = params[key].shape[-1] // cfg.dim
            v = ids[:, t].astype(jnp.int32)
            st.record_rows(params[key], v // k, cfg.dim,
                           lanes=v % k if k > 1 else None)
        return jnp.zeros((B, F, cfg.dim), params[keys[0]].dtype)
    taps = None
    if st is not None and st.mode == "provide":
        taps = jnp.stack([st.next_tap((B, cfg.dim)) for _ in keys], axis=1)
        params = dict(params, **{k: jax.lax.stop_gradient(params[k])
                                 for k in keys})
    ctx = bke.sharded_ctx()
    with jax.named_scope("table_lookup"):
        if ctx is not None:
            out = scheme.sharded_lookup(cfg, params, {}, ids, *ctx)
        else:
            out = jnp.stack([scheme.embed_rows(cfg, params, t, ids[:, t])
                             for t in range(F)], axis=1)
    return out if taps is None else out + taps


def embed(cfg: EmbeddingConfig, params: dict, buffers: dict, table: int,
          ids: jax.Array) -> jax.Array:
    """ids [...]: int -> embeddings [..., dim]."""
    scheme = get_scheme(cfg.kind)
    shape = ids.shape
    flat = ids.reshape(-1)
    if scheme.family == "memory":
        out = _memory_lookup(cfg, params, buffers,
                             _global_ids(cfg, table, flat))
    else:
        out = scheme.embed_rows(cfg, params, table, flat)
    return out.reshape(*shape, cfg.dim)


def embed_fields(cfg: EmbeddingConfig, params: dict, buffers: dict,
                 ids: jax.Array) -> jax.Array:
    """Per-field lookup: ids [B, F] (field f's id in its own vocab) -> [B, F, d].

    Memory-family schemes take the fast path: one vectorized call over
    globalized ids — a single fused gather instead of F table gathers.
    Row-table schemes (full) take ``_table_lookup``.
    """
    B, F = ids.shape
    assert F == cfg.n_tables, (F, cfg.n_tables)
    scheme = get_scheme(cfg.kind)
    if scheme.family == "memory":
        offs = jnp.asarray(cfg.table_offsets()[:-1], jnp.int32)
        gids = (ids.astype(jnp.int32) + offs[None, :]).reshape(-1)
        out = _memory_lookup(cfg, params, buffers, gids)
        return out.reshape(B, F, cfg.dim)
    if scheme.table_rows:
        return _table_lookup(cfg, scheme, params, ids)
    cols = [embed(cfg, params, buffers, f, ids[:, f]) for f in range(F)]
    return jnp.stack(cols, axis=1)


def embed_bag(cfg: EmbeddingConfig, params: dict, buffers: dict, table: int,
              ids: jax.Array, mask: jax.Array, mode: str = "sum") -> jax.Array:
    """Multi-hot pooling: ids [B, L], mask [B, L] -> [B, dim].

    JAX has no native EmbeddingBag.  When the fused backend resolves, bags
    pool inside the Pallas engine (the [B, L, d] pre-pool tensor never leaves
    VMEM); everything else is gather + masked reduce (plus the one-hot-matmul
    kernel in repro/kernels/embedding_bag for full-table TPU bags).
    """
    from repro.optim import sparse as _sparse
    scheme = get_scheme(cfg.kind)
    backend = bke.resolve_backend(cfg, params, scheme, buffers)
    if backend is bke.FUSED and _sparse.active() is None:
        # under a sparse-grad trace bags decompose into embed + masked
        # reduce, so the per-element lookup carries the tap and the values
        # cotangent arrives pre-weighted (g[b] * w[b, l]) for free
        w = mask.astype(params["memory"].dtype)
        gids = _global_ids(cfg, table, ids.reshape(-1)).reshape(ids.shape)
        s = backend.bag(cfg, scheme, params, buffers, gids, w)
    else:
        e = embed(cfg, params, buffers, table, ids)      # [B, L, d]
        w = mask.astype(e.dtype)
        s = jnp.sum(e * w[..., None], axis=-2)
    if mode == "sum":
        return s
    if mode == "mean":
        n = jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1.0)
        return s / n
    raise ValueError(mode)


def materialize_rows(cfg: EmbeddingConfig, params: dict, buffers: dict,
                     table: int, n_rows: int | None = None) -> jax.Array:
    """Materialize [V, d] virtual table rows (LM output heads / small vocabs only)."""
    v = cfg.vocab_sizes[table] if n_rows is None else n_rows
    ids = jnp.arange(v, dtype=jnp.int32)
    return embed(cfg, params, buffers, table, ids)


@dataclasses.dataclass(frozen=True)
class EmbeddingTable:
    """Facade over (config, scheme, backend): what models hold and call.

    Frozen and hashable (wraps only the hashable config), so it is safe to
    close over in jitted functions and to rebuild per call.
    """

    config: EmbeddingConfig

    @property
    def scheme(self):
        return get_scheme(self.config.kind)

    @property
    def param_count(self) -> int:
        return self.config.param_count()

    def init(self, key: jax.Array) -> dict:
        """Trainable parameter pytree (key names are checkpoint-stable)."""
        return init_embedding(key, self.config)

    def make_buffers(self, store=None) -> dict:
        return make_buffers(self.config, store)

    def embed(self, params: dict, buffers: dict, table: int,
              ids: jax.Array) -> jax.Array:
        return embed(self.config, params, buffers, table, ids)

    def embed_fields(self, params: dict, buffers: dict,
                     ids: jax.Array) -> jax.Array:
        return embed_fields(self.config, params, buffers, ids)

    def embed_bag(self, params: dict, buffers: dict, table: int,
                  ids: jax.Array, mask: jax.Array,
                  mode: str = "sum") -> jax.Array:
        return embed_bag(self.config, params, buffers, table, ids, mask, mode)

    def materialize_rows(self, params: dict, buffers: dict, table: int,
                         n_rows: int | None = None) -> jax.Array:
        return materialize_rows(self.config, params, buffers, table, n_rows)

    def describe(self) -> dict:
        """JSON-serializable introspection (dryrun meta / bench tables)."""
        return self.scheme.describe(self.config)
