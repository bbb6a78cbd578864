"""Lookup backends for memory-family schemes, and the explicit resolver.

Three interchangeable implementations of "[N] global ids -> [N, d]":

``split``
    The bit-exact oracle: materialize the [N, d] location tensor
    (``scheme.locations``) and gather with ``jnp.take`` (transpose-of-gather
    gives the scatter-add gradient automatically).  The gather sits under
    the ``pool_gather`` named scope.

``fused``
    The Pallas engine (``repro/kernels/fused_embed``): locations + pool
    gather (+ bag-pool) in one VMEM pass with a scatter-add custom VJP.
    Eligible only when the scheme publishes a :class:`FusedSpec`, the pool
    really has the spec's ``m`` slots, and the slab fits the engine's VMEM
    budget.

``sharded``
    Pool sharded over the 'model' axis (``repro/dist/sharded_memory``),
    selected whenever a distribution mesh is installed.  Cross-device
    traffic goes through a pluggable exchange strategy (psum | ring |
    all_to_all — ``repro/dist/exchange.py``), picked per lookup by the
    ``resolve_exchange`` cost model or pinned via ``REPRO_DIST_EXCHANGE`` /
    the backend's ``exchange`` attribute.  Schemes may provide a bespoke
    sharded path (lma reconstructs D' rows first); others fall back to the
    generic location-based lookup.

``resolve_backend`` is the promoted, testable form of the old implicit
``_use_fused`` / ``_sharded_ctx`` gating chain in ``core/embedding.py``;
``repro.dist.exchange.resolve_exchange`` is its collective-level sibling.
"""
from __future__ import annotations

import jax

from repro.core.memory import lookup
from repro.embed.config import EmbeddingConfig
from repro.embed.registry import Scheme, get_scheme


def sharded_ctx():
    """(mesh, dp_axes) when a distribution mesh is installed, else None."""
    from repro.dist import context as dctx
    mesh = dctx.current_mesh()
    if mesh is None:
        return None
    return mesh, dctx.dp_axes(mesh)


def fused_eligible(cfg: EmbeddingConfig, scheme: Scheme, params: dict) -> bool:
    """Single-device fused-engine gate (bit-exact twin of the split path)."""
    spec = scheme.fused_spec(cfg)
    if spec is None:
        return False
    mem = params.get("memory")
    if mem is None or mem.ndim != 1:
        return False
    # the engine indexes mod the spec's m with no clipping: it is only the
    # split path's bit-exact twin when the pool really has m slots
    if mem.shape[0] != scheme.memory_slots(cfg):
        return False
    from repro.kernels.dispatch import pallas_allowed
    from repro.kernels.fused_embed import ops as fe
    return (pallas_allowed("fused_embed") and fe.fused_enabled()
            and fe.fused_supported(mem.shape[0], mem.dtype.itemsize))


class SplitBackend:
    name = "split"

    def lookup(self, cfg: EmbeddingConfig, scheme: Scheme, params: dict,
               buffers: dict, gids: jax.Array) -> jax.Array:
        loc = scheme.locations(cfg, buffers, gids)
        with jax.named_scope("pool_gather"):
            return lookup(params["memory"], loc)


class FusedBackend:
    name = "fused"

    def lookup(self, cfg: EmbeddingConfig, scheme: Scheme, params: dict,
               buffers: dict, gids: jax.Array) -> jax.Array:
        from repro.kernels.fused_embed import ops as fe
        spec = scheme.fused_spec(cfg)
        extra = scheme.fused_inputs(cfg, buffers, gids)
        # one kernel, its location math included
        with jax.named_scope("pool_gather"):
            return fe.fused_lookup(spec, params["memory"], gids, *extra)

    def bag(self, cfg: EmbeddingConfig, scheme: Scheme, params: dict,
            buffers: dict, gids: jax.Array, weights: jax.Array) -> jax.Array:
        """Weighted-sum bags pooled inside the kernel tile.

        ``gids``: [B, L] already-globalized ids, ``weights``: [B, L].
        """
        from repro.kernels.fused_embed import ops as fe
        B, L = gids.shape
        flat = gids.reshape(-1)
        spec = scheme.fused_spec(cfg)
        extra = scheme.fused_inputs(cfg, buffers, flat)
        extra = tuple(a.reshape(B, L, *a.shape[1:]) for a in extra)
        return fe.fused_embed_bag(spec, params["memory"], gids, weights,
                                  *extra)


class ShardedBackend:
    """Model-parallel pools: [m / n_model] slab per device, lookups routed
    through a :mod:`repro.dist.exchange` strategy.  Each scheme's
    ``sharded_lookup`` driver picks the strategy (explicit ``exchange=`` >
    env > cost model) and, for ring / all_to_all on eligible slabs, runs the
    fused-chunked Pallas engine — one call per exchange chunk fusing the
    scheme's location math with a slab-tiled masked gather — with the split
    per-chunk path as the bit-exact oracle."""

    name = "sharded"

    def __init__(self, mesh, dp_axes, exchange=None):
        self.mesh = mesh
        self.dp_axes = dp_axes
        # None -> per-lookup resolve_exchange cost model (env-overridable);
        # a name or Exchange instance pins every lookup on this backend
        self.exchange = exchange

    def lookup(self, cfg: EmbeddingConfig, scheme: Scheme, params: dict,
               buffers: dict, gids: jax.Array) -> jax.Array:
        out = scheme.sharded_lookup(cfg, params, buffers, gids, self.mesh,
                                    self.dp_axes, exchange=self.exchange)
        if out is NotImplemented:
            from repro.dist.sharded_memory import sharded_location_lookup
            out = sharded_location_lookup(
                params["memory"], gids,
                lambda g: scheme.locations(cfg, buffers, g),
                cfg.dim, self.mesh, self.dp_axes, exchange=self.exchange)
        return out


class TieredBackend:
    """Over-budget pools: compact HBM pool + host-cold tier (``repro.tier``).

    The scheme computes its *global* pool locations exactly as it would for
    the split oracle; :func:`repro.tier.store.remap_locations` then folds
    them into the compact pool the :class:`~repro.tier.store.TieredStore`
    keeps resident (hot slab + this step's staged cold rows) using the three
    remap buffers the :class:`~repro.tier.training.TierController` rides in
    each batch.  Bit-identical to the split path over the full pool whenever
    the controller staged the step's cold blocks — which it guarantees by
    planning from the same ``scheme.locations`` math.
    """
    name = "tiered"

    def lookup(self, cfg: EmbeddingConfig, scheme: Scheme, params: dict,
               buffers: dict, gids: jax.Array) -> jax.Array:
        loc = tiered_locations(cfg, scheme, buffers, gids)
        with jax.named_scope("pool_gather"):
            return lookup(params["memory"], loc)


SPLIT = SplitBackend()
FUSED = FusedBackend()
TIERED = TieredBackend()


def tiered_active(buffers: dict | None) -> bool:
    """Do these buffers carry live tier remap state (hot/stage ids)?"""
    return bool(buffers) and "tier_hot_ids" in buffers


def tiered_locations(cfg: EmbeddingConfig, scheme: Scheme, buffers: dict,
                     gids: jax.Array) -> jax.Array:
    """Scheme locations remapped into the compact tiered pool."""
    from repro.tier.store import remap_locations
    loc = scheme.locations(cfg, buffers, gids)
    return remap_locations(loc, buffers["tier_hot_ids"],
                           buffers["tier_stage_ids"], buffers["tier_block"])


def sparse_locations(cfg: EmbeddingConfig, scheme: Scheme, params: dict,
                     buffers: dict, gids: jax.Array) -> jax.Array:
    """[N] gids -> [N, d] locations for sparse-gradient recording.

    This is the per-backend form of the sparse-grads flag: when the fused
    engine is eligible its in-VMEM location kernel emits the tensor (the
    same hash math the scatter kernel would have recomputed to *consume*);
    otherwise the scheme's split oracle computes it.  Either way the result
    is bit-identical to ``scheme.locations``.  Under a tiered pool the
    gradient target is the *compact* pool, so the recorded locations are
    the remapped ones — again matching what the provide-pass lookup reads.
    """
    if tiered_active(buffers):
        return tiered_locations(cfg, scheme, buffers, gids)
    if sharded_ctx() is None and fused_eligible(cfg, scheme, params):
        from repro.kernels.fused_embed import ops as fe
        spec = scheme.fused_spec(cfg)
        extra = scheme.fused_inputs(cfg, buffers, gids)
        with jax.named_scope("lma_locations"):
            return fe.fused_locations(spec, gids, *extra)
    return scheme.locations(cfg, buffers, gids)


def resolve_backend(cfg: EmbeddingConfig, params: dict,
                    scheme: Scheme | None = None, buffers: dict | None = None):
    """The dispatch policy, in one inspectable place.

    Returns the backend for a memory-family lookup, or ``None`` for
    table-family schemes (they embed directly, no shared pool).  Priority:
    tiered (the buffers carry tier remap state — the pool exceeded the
    per-device budget and ``repro.tier`` split it) > sharded (a mesh is
    installed) > fused (engine enabled + spec + VMEM fit + the TPU
    dispatch rule of ``repro.kernels.dispatch``) > split.
    ``fused_eligible`` independently rejects tiered pools: the compact pool
    has fewer than ``memory_slots`` slots, so the slab gate fails closed
    even if a caller forgets to pass ``buffers``.
    """
    scheme = get_scheme(cfg.kind) if scheme is None else scheme
    if scheme.family != "memory":
        return None
    if tiered_active(buffers):
        return TIERED
    ctx = sharded_ctx()
    if ctx is not None:
        return ShardedBackend(*ctx)
    if fused_eligible(cfg, scheme, params):
        return FUSED
    return SPLIT
