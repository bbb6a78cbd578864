"""Sharded common-memory lookups: thin drivers over the exchange strategies.

The paper's memory pool M is a flat [m] vector; production budgets (10^8+
slots) cannot live replicated on every chip.  Here M is sharded over the
'model' axis (each device owns a contiguous [m / n_model] slab, replicated
across the dp axes) and every lookup runs as a ``shard_map`` whose
cross-device traffic is delegated to a pluggable :class:`~repro.dist.
exchange.Exchange` strategy (``repro/dist/exchange.py``):

``psum``         mask-local-gather + one global psum — the bit-exact oracle,
                 and the strategy the WHOLE-SLAB fused Pallas kernel
                 (``repro/kernels/fused_embed``) composes with: locations are
                 computed and mask-gathered per batch tile in VMEM, then one
                 psum assembles complete embeddings.
``ring``         batch chunks ppermute around the ring; each rank's slab
                 gathers overlap the neighbor transfer, and location math
                 (LMA set reconstruction + minhash) runs once per chunk —
                 1/n_model of the psum strategy's.
``all_to_all``   owner-sliced exchanges: locations all-gather, partials
                 reduce-scatter via all_to_all, finished chunks all-gather;
                 the sparse-update psum disappears entirely (owner-partial
                 update values feed the masked local scatter directly).

Ring and all_to_all get their own CHUNKED fused form via ``_chunk_engine``:
a :class:`~repro.dist.exchange.FusedChunkEngine` whose per-chunk lookup is
one Pallas call fusing the scheme's location math with a slab-masked
gather, tiled over the [m / n_model] slab so the working set fits the
``REPRO_FUSED_MAX_MEM_MB`` gate even when the whole slab would not (the
135M-slot shape).  Under the whole-slab gate the engine's gather falls back
to the XLA masked take — already one in-VMEM gather — so the Pallas tiling
only pays its per-call overhead where it is the only in-budget form.  The
split per-chunk path is kept verbatim as the bit-exact oracle.

All three are bit-identical on the forward pass (exactly one rank owns each
slot, so cross-rank sums only ever add exact zeros) and 1e-6 on gradients —
``tests/test_exchange.py`` pins ring/all_to_all against the psum oracle for
every registered scheme; ``tests/test_sharded.py`` pins psum against the
single-device lookup.  Strategy selection is ``REPRO_DIST_EXCHANGE`` or the
``resolve_exchange`` traffic model; every driver takes ``exchange=`` for an
explicit override (name or instance).

Per-device traffic is O(n_local * d) — independent of m, the property
``benchmarks/bench_kernels.py`` records per strategy and
``benchmarks/check_regression.py`` gates (``sharded_gap_failures``).

For LMA the D' store rows are sharded over 'model' the same way and each
batch row's D_v set is reconstructed through the same strategy
(``Exchange.set_lookup``; integer sums: exact) before the location hashes
run.

Dispatch here is owned by ``repro.embed.backends.ShardedBackend``: schemes
with a bespoke path (lma, hashed_*) plug in directly; any other registered
pure-location scheme rides ``sharded_location_lookup``.  The full
baseline's per-field tables, row-sharded the same way, are served by
``sharded_table_lookup`` (``FullScheme.sharded_lookup``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import allocation as alc
from repro.core.allocation import LMAParams
from repro.core.memory import lookup
from repro.core.signatures import DenseSignatureStore
from repro.dist import exchange as exl
from repro.dist.exchange import local_gather_psum  # noqa: F401  (public API)
from jax import shard_map


_model_size = exl.model_size


def _fused_slab(mem_l) -> bool:
    """Fused per-shard gather when the slab fits the engine's VMEM budget."""
    from repro.kernels.fused_embed import ops as fe
    return fe.fused_enabled() and fe.fused_supported(int(mem_l.shape[0]),
                                                     mem_l.dtype.itemsize)


def _fused_eligible(memory, n_model: int) -> bool:
    """The driver-side form of the shared fused-slab gate, used to price
    the psum strategy's location bytes before the shard_map opens: a
    fused-eligible slab hashes in-VMEM, so its location tensor is free."""
    return exl.fused_slab_eligible(int(memory.shape[0]), n_model,
                                   memory.dtype.itemsize)


def _fused_chunk_eligible(memory, n_model: int) -> bool:
    """Driver-side form of the chunk-level gate: can ring / all_to_all run
    their slab-tiled Pallas engine on this pool's per-device slab?"""
    return exl.fused_chunk_eligible(int(memory.shape[0]), n_model,
                                    memory.dtype.itemsize)


def _chunk_engine(spec, inputs_fn=None, loc_fn=None):
    """Assemble the chunked strategies' :class:`~repro.dist.exchange.
    FusedChunkEngine`.

    ``spec`` is the scheme's FusedSpec — its location math runs in-VMEM
    (``fused_chunk_lookup`` / ``fused_locations``), with ``inputs_fn(g) ->
    (sets, support)`` supplying the (possibly collective, uniform-length)
    location inputs.  ``spec=None`` is the generic form: ``loc_fn``
    computes locations on the split path and only the slab-tiled Pallas
    gather fuses — what registry schemes without a FusedSpec get."""
    from repro.kernels.fused_embed import ops as fe

    def gather(mem_l, loc):
        # The slab-tiled Pallas gather is what makes over-gate slabs
        # fusable at all — each (batch, slab-block) tile stays inside the
        # VMEM budget.  Under the whole-slab gate XLA's masked take is
        # already a single in-VMEM gather with no per-call grid overhead,
        # so dispatch on the same gate the psum strategy uses; both forms
        # are bitwise identical (one owner per location, zeros elsewhere).
        if fe.fused_supported(int(mem_l.shape[0]), mem_l.dtype.itemsize):
            return exl.local_gather(mem_l, loc)
        return fe.fused_chunk_gather(mem_l, loc, base=_slab_base(mem_l))

    if spec is None:
        def chunk_lookup(mem_l, g):
            loc = loc_fn(g)
            return gather(mem_l, loc), loc

        return exl.FusedChunkEngine(chunk_lookup, loc_fn, gather)

    def chunk_lookup(mem_l, g):
        sets, support = inputs_fn(g) if inputs_fn is not None else (None, None)
        return fe.fused_chunk_lookup(spec, mem_l, g, sets, support,
                                     base=_slab_base(mem_l))

    def locations(g):
        sets, support = inputs_fn(g) if inputs_fn is not None else (None, None)
        return fe.fused_locations(spec, g, sets, support)

    return exl.FusedChunkEngine(chunk_lookup, locations, gather)


def _slab_base(mem_l, axis_name="model") -> jax.Array:
    """Global offset of this rank's slab (for the in-kernel ownership mask)."""
    rank = jax.lax.axis_index(axis_name)
    return (rank * mem_l.shape[0]).astype(jnp.int32).reshape(1)


def _batch_axes(mesh, dp_axes, lead: int) -> tuple[str, ...]:
    """dp axes for the leading batch dim — all of them or none (replicated)."""
    axes = tuple(a for a in dp_axes if a in mesh.axis_names)
    prod = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if prod > 1 and lead % prod == 0:
        return axes
    return ()


def _bspec(batch_axes) -> tuple | None:
    if not batch_axes:
        return None
    return batch_axes if len(batch_axes) > 1 else batch_axes[0]


def _resolve(exchange, mesh, n_flat: int, d: int, m: int | None,
             alloc_row: float | None = None,
             fused: bool = False,
             fused_chunk: bool = False) -> exl.Exchange:
    """Driver-side strategy resolution: explicit arg > env > cost model,
    with an eligibility fallback to psum (odd chunking, tiny batches).
    ``fused`` prices the psum-only fused-slab discount; ``fused_chunk``
    prices the chunked strategies' slab-tiled engine discount (each clamped
    through its own gate in ``resolve_exchange``).  When a fault injector
    with an armed exchange fault is installed
    (``repro.resilience.faults``), the resolved chunked strategy is wrapped
    so the injected chunk drop/corruption reaches the assembled lookup —
    the harness behind the demotion ladder's validation tests."""
    if isinstance(exchange, str):
        exchange = exl.get_exchange(exchange)
    if exchange is None:
        exchange = exl.resolve_exchange(mesh, B=n_flat, d=d, m=m,
                                        alloc_row=alloc_row, fused=fused,
                                        fused_chunk=fused_chunk)
    n_model = _model_size(mesh)
    if not exchange.eligible(n_flat, n_model):
        exchange = exl.PSUM
    from repro.resilience import faults as _flt
    return _flt.wrap_exchange(exchange)


def _local_flat(mesh, dp_axes, gids) -> tuple[tuple, int]:
    """(resolved batch axes, per-device flat row count) for a gid batch."""
    batch = _batch_axes(mesh, dp_axes, int(gids.shape[0]))
    prod = int(np.prod([mesh.shape[a] for a in batch])) if batch else 1
    return batch, int(np.prod(gids.shape)) // prod


def sharded_location_lookup(memory: jax.Array, gids: jax.Array, loc_fn,
                            d: int, mesh, dp_axes,
                            exchange=None) -> jax.Array:
    """Generic sharded lookup for any pure-location scheme.

    ``loc_fn``: [n] flat global ids -> [n, d] int32 locations; it must be
    communication-free (pure hashing / replicated-buffer math), because the
    chunked strategies call it with per-rank batch chunks inside the
    shard_map.  This is the path registry schemes get for free
    (``repro.embed.backends.ShardedBackend``) when they don't provide a
    bespoke one.  Bit-identical to ``lookup(memory, loc_fn(gids))`` under
    every strategy.  Under a chunked strategy with a chunk-eligible slab
    the gathers run through the slab-tiled Pallas engine (generic form: the
    location math stays on the split path, so no pricing discount is
    claimed — only schemes whose hashes fuse get one).
    """
    m = int(memory.shape[0])
    n_model = _model_size(mesh)
    if n_model <= 1 or m % n_model != 0:
        return lookup(memory, loc_fn(gids.reshape(-1))).reshape(*gids.shape, d)
    batch, n_flat = _local_flat(mesh, dp_axes, gids)
    ex = _resolve(exchange, mesh, n_flat, d, m,
                  alloc_row=exl.alloc_bytes_per_row(d))
    chunk_ok = _fused_chunk_eligible(memory, n_model)
    bspec = _bspec(batch)
    gspec = P(bspec, *([None] * (gids.ndim - 1)))

    def body(mem_l, gids_l):
        fce = (_chunk_engine(None, loc_fn=loc_fn)
               if chunk_ok and ex.name in ("ring", "all_to_all") else None)
        out = ex.lookup(mem_l, gids_l.reshape(-1), loc_fn, d, n_model,
                        fused=fce)
        return out.reshape(*gids_l.shape, d)

    fn = shard_map(body, mesh=mesh, in_specs=(P("model"), gspec),
                   out_specs=P(bspec, *([None] * gids.ndim)),
                   check_vma=False)
    return fn(memory, gids)


def sharded_set_lookup(table: jax.Array, gids: jax.Array, mesh, dp_axes,
                       exchange=None) -> jax.Array:
    """Reconstruct rows of a 'model'-row-sharded integer table (the D' store
    sets/lengths) for a dp-sharded gid batch — the standalone form of the
    set exchange every LMA lookup runs.  Exact (integer sums)."""
    n_model = _model_size(mesh)
    n_rows = int(table.shape[0])
    if n_model <= 1 or n_rows % n_model != 0:
        return jnp.take(table, gids.reshape(-1), axis=0).reshape(
            gids.shape + table.shape[1:])
    batch, n_flat = _local_flat(mesh, dp_axes, gids)
    # a set lookup has no location math (idx IS the input), so its psum
    # pays no alloc term — price it honestly or auto would pick a chunked
    # strategy that does psum's full gather PLUS three collectives
    ex = _resolve(exchange, mesh, n_flat,
                  int(np.prod(table.shape[1:], initial=1)), None,
                  alloc_row=0.0)
    bspec = _bspec(batch)
    gspec = P(bspec, *([None] * (gids.ndim - 1)))
    trail = len(table.shape) - 1

    def body(tab_l, gids_l):
        flat = gids_l.reshape(-1)
        if ex.name == "psum":
            out = ex.set_lookup(tab_l, flat, n_model)
        else:
            rank = jax.lax.axis_index("model")
            mine = ex.set_lookup(tab_l, exl.chunk_for_rank(flat, rank, n_model),
                                 n_model)
            out = jax.lax.all_gather(mine, "model").reshape(
                (-1,) + tab_l.shape[1:])
        return out.reshape(gids_l.shape + tab_l.shape[1:])

    fn = shard_map(body, mesh=mesh, in_specs=(P("model"), gspec),
                   out_specs=P(bspec, *([None] * (gids.ndim - 1 + trail))),
                   check_vma=False)
    return fn(table, gids)


def _table_rows(table: jax.Array, ids: jax.Array, d: int,
                gather=None) -> jax.Array:
    """Rows ``ids`` [n] of a [V, d] table, or of one lane-packed k rows to
    a [V / k, k d] row (``repro.dist.sharding.row_table_shape``): gather
    the packed rows, keep each id's slot."""
    gather = gather or (lambda t, i: jnp.take(t, i, axis=0))
    k = int(table.shape[1]) // d
    if k == 1:
        return gather(table, ids)
    rows = gather(table, ids // k).reshape(-1, k, d)
    return jnp.take_along_axis(rows, (ids % k)[:, None, None], axis=1)[:, 0]


def sharded_table_lookup(tables, ids: jax.Array, d: int, mesh, dp_axes,
                         exchange=None) -> jax.Array:
    """Per-field rows of F 'model'-row-sharded tables, in one shard_map.

    ``tables``: F tables of width d, or lane-packed to width k d, whose
    rows 'model' divides (``repro.dist.sharding.place`` lays the full
    baseline's tables out so); ``ids`` [B, F] field-local ids, leading dim
    dp-sharded when divisible -> [B, F, d], bit-identical to one
    ``jnp.take`` per field of the unpacked tables.  Each rank gathers the
    rows its slabs own (exact zeros elsewhere) for all F fields at once,
    and the strategy's ``partial_sum_lookup`` assembles them: psum over the
    whole batch, or ring / all_to_all over each rank's batch chunk and an
    all-gather of the chunks.  A lookup has no location math, so the cost
    model prices no allocation bytes (``resolve_exchange``).  Each trace
    tallies ``table_lookup.<strategy>`` in ``repro.obs``.
    """
    from repro import obs
    n_model = _model_size(mesh)
    F = len(tables)
    ids = ids.astype(jnp.int32)
    if n_model <= 1 or any(int(t.shape[0]) % n_model for t in tables):
        return jnp.stack([_table_rows(t, ids[:, f], d)
                          for f, t in enumerate(tables)], axis=1)
    batch, n_flat = _local_flat(mesh, dp_axes, ids)
    ex = _resolve(exchange, mesh, n_flat // F, F * d, None, alloc_row=0.0)
    obs.count(f"table_lookup.{ex.name}")
    bspec = _bspec(batch)

    def body(*args):
        tabs, ids_l = args[:F], args[F]

        def local_fn(idx):
            return (jnp.stack([_table_rows(t, idx[:, f], d, exl.local_gather)
                               for f, t in enumerate(tabs)], axis=1),)

        if ex.name == "psum":
            out, = ex.partial_sum_lookup(local_fn, ids_l, n_model)
            return out
        rank = jax.lax.axis_index("model")
        mine, = ex.partial_sum_lookup(
            local_fn, exl.chunk_for_rank(ids_l, rank, n_model), n_model)
        return jax.lax.all_gather(mine, "model").reshape(
            ids_l.shape + (d,))

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P("model", None),) * F + (P(bspec, None),),
                   out_specs=P(bspec, None, None), check_vma=False)
    return fn(*tables, ids)


def sharded_hashed_lookup(memory: jax.Array, gids: jax.Array, d: int, m: int,
                          seed: int, mesh, dp_axes,
                          kind: str = "hashed_elem",
                          exchange=None) -> jax.Array:
    """Hashing-trick lookup with M sharded over 'model'.

    gids [...]: global value ids (leading dim dp-sharded when divisible)
    -> [..., d].  Bit-identical to ``lookup(memory, alloc_hashed_*(gids))``.
    """
    alloc = (alc.alloc_hashed_elem if kind == "hashed_elem"
             else alc.alloc_hashed_row)
    n_model = _model_size(mesh)
    if n_model <= 1 or m % n_model != 0:
        return lookup(memory, alloc(gids.reshape(-1), d, m, seed)).reshape(
            *gids.shape, d)
    batch, n_flat = _local_flat(mesh, dp_axes, gids)
    ex = _resolve(exchange, mesh, n_flat, d, m,
                  fused=_fused_eligible(memory, n_model),
                  fused_chunk=_fused_chunk_eligible(memory, n_model))
    chunk_ok = _fused_chunk_eligible(memory, n_model)
    bspec = _bspec(batch)
    gspec = P(bspec, *([None] * (gids.ndim - 1)))

    def body(mem_l, gids_l):
        flat = gids_l.reshape(-1)
        if ex.name == "psum" and _fused_slab(mem_l):
            from repro.kernels.fused_embed import ops as fe
            part = fe.fused_lookup(fe.hashed_spec(kind, d, m, seed), mem_l,
                                   flat, base=_slab_base(mem_l))
            out = jax.lax.psum(part, "model")
        else:
            fce = None
            if chunk_ok and ex.name in ("ring", "all_to_all"):
                from repro.kernels.fused_embed import ops as fe
                fce = _chunk_engine(fe.hashed_spec(kind, d, m, seed))
            out = ex.lookup(mem_l, flat, lambda g: alloc(g, d, m, seed), d,
                            n_model, fused=fce)
        return out.reshape(*gids_l.shape, d)

    fn = shard_map(body, mesh=mesh, in_specs=(P("model"), gspec),
                   out_specs=P(bspec, *([None] * gids.ndim)),
                   check_vma=False)
    return fn(memory, gids)


# ------------------------------------------------------- sparse slab updates
#
# The sparse-gradient pipeline (repro/optim/sparse.py) replaces the dense
# psum'd [m_local] pool gradient with one (indices, values) pair — K =
# touched slots << m.  Each device applies a *masked local* sparse update to
# its own slab: gather the in-slab subset, run the O(K) moment math, scatter
# back; out-of-slab entries route to a dropped sentinel index.  The update
# exchange is the strategy's ``reduce_update``:
#
#   psum        the [K, ...] update values psum to full replication (the
#               oracle; what the 2x4 bench shipped originally);
#   all_to_all  NO collective at all — each rank's masked update already
#               holds the exact values at its owned slots and zeros
#               elsewhere, which is the only part the masked local scatter
#               in ``sharded_sparse_apply`` reads.  The per-step update
#               exchange shrinks by ~n_model; ``exchange.sparse_worthwhile``
#               moves the sparse-vs-dense crossover accordingly.
#
# all_to_all update values are *owner-partial*: consume them ONLY through
# ``sharded_sparse_apply`` (any read outside a 'model' shard_map sees one
# rank's partial).  Untouched slots never see a write, so per-device HBM
# traffic is O(K), not O(m_local).


def _slab_mask(idx, n_local, axis_name="model"):
    """(local gather idx, drop-sentinel scatter idx, in-slab mask)."""
    rank = jax.lax.axis_index(axis_name)
    rel = idx - rank * n_local
    mine = (rel >= 0) & (rel < n_local)
    return jnp.clip(rel, 0, n_local - 1), jnp.where(mine, rel, n_local), mine


def slab_aligned(unique: bool, buckets: int, k: int, n_model: int) -> bool:
    """True when a stripe-major bucketed stream's even [K] split lands each
    rank's slice exactly on its parameter slab.

    A ``buckets=d`` stream (``from_bucketed_locations``) is stripe-major:
    slice ``[j*K/d, (j+1)*K/d)`` indexes only slots ``[j*m/d, (j+1)*m/d)``.
    With ``d % n_model == 0`` each rank's K/n_model chunk covers whole
    stripes that tile its contiguous m/n_model slab — so indices and values
    can enter the shard_map already 'model'-sharded (no K-sized
    replication) and the update needs no exchange collective at all: every
    rank's slice is complete for its slab, duplicates included.
    """
    return (not unique and buckets > 0 and buckets % n_model == 0
            and k % n_model == 0)


def sharded_sparse_update(algo: str, indices, values, states: tuple,
                          hyper: dict, mesh, exchange=None, *,
                          unique: bool = True, buckets: int = 0):
    """Run one sparse optimizer update on 'model'-sharded moment slabs.

    ``indices [K]`` / ``values [K, ...]`` follow the SparseGrad contract:
    sorted unique + sentinel-padded (``unique=True``), or sorted-with-
    duplicates from the bucketed striped layout (``unique=False``) — then
    each rank owner-masks its slice and the in-kernel fold sums every
    duplicate run *before* the moment math, so Adagrad sees the complete
    per-slot (sum g)^2, not a partial.  Duplicates of an owned slot are
    adjacent in the global sorted stream and ownership is contiguous slabs,
    so the owner always sees the whole run; off-slab entries collapse onto
    the local sentinel ``n_local`` with zeroed values and fold into dropped
    no-ops.  Returns (update_values [K, ...] — replicated under the psum
    strategy, owner-partial under all_to_all — and the new slab tree).
    Must be called OUTSIDE shard_map (it opens its own).

    When ``slab_aligned(unique, buckets, K, n_model)`` holds, indices and
    values enter (and the update leaves) 'model'-sharded instead of
    replicated: each rank holds only its K/n_model stripe-major slice —
    which is exactly its slab's complete entry stream — and the body needs
    no exchange collective.  This is the pod-scale path the bucketed layout
    buys: per-step collective bytes drop from O(K) replication to zero.
    """
    from repro.kernels.sparse_update.ops import sparse_update

    if isinstance(exchange, str):
        exchange = exl.get_exchange(exchange)
    ex = exchange if exchange is not None else exl.resolve_update_exchange(mesh)
    n_model = _model_size(mesh)
    aligned = slab_aligned(unique, buckets, int(indices.shape[0]), n_model)
    gspec = P("model") if aligned else P()

    # traced hyper-parameters (adam's step-dependent bias corrections) must
    # enter the shard_map as explicit replicated inputs, not closures
    tkeys = sorted(k for k, v in hyper.items() if isinstance(v, jax.Array))
    static = {k: v for k, v in hyper.items() if k not in tkeys}
    targs = [jnp.asarray(hyper[k]) for k in tkeys]

    def body(idx, vals, *rest):
        tvals, st_l = rest[: len(tkeys)], rest[len(tkeys):]
        n_local = st_l[0].shape[0]
        _, scat, mine = _slab_mask(idx, n_local)
        vmask = mine.reshape(mine.shape + (1,) * (vals.ndim - 1))
        lvals = jnp.where(vmask, vals, 0)
        u, new_st = sparse_update(algo, scat, lvals, st_l, unique=unique,
                                  **dict(static, **dict(zip(tkeys, tvals))))
        u = u if aligned else ex.reduce_update(u, n_model)
        return (u,) + tuple(new_st)

    nst = len(states)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(gspec, gspec) + (P(),) * len(tkeys)
                   + (P("model"),) * nst,
                   out_specs=(gspec,) + (P("model"),) * nst,
                   check_vma=False)
    out = fn(indices, values, *targs, *states)
    return out[0], tuple(out[1:])


def sharded_sparse_apply(param: jax.Array, indices, values, mesh,
                         exchange=None, *, unique: bool = True,
                         buckets: int = 0):
    """Masked local scatter-add of SparseGrad update values into the
    'model'-sharded parameter slab (the sparse ``apply_updates``).  The
    ownership mask makes this the correct consumer for BOTH replicated
    (psum) and owner-partial (all_to_all) update values.  Slab-aligned
    bucketed streams (see ``slab_aligned``) keep indices/values
    'model'-sharded end to end — the scatter is purely rank-local."""
    n_model = _model_size(mesh)
    aligned = slab_aligned(unique, buckets, int(indices.shape[0]), n_model)
    gspec = P("model") if aligned else P()

    def body(p_l, idx, vals):
        _, scat, mine = _slab_mask(idx, p_l.shape[0])
        vmask = mine.reshape(mine.shape + (1,) * (vals.ndim - 1))
        return p_l.at[scat].add(jnp.where(vmask, vals, 0), mode="drop")

    fn = shard_map(body, mesh=mesh, in_specs=(P("model"), gspec, gspec),
                   out_specs=P("model"), check_vma=False)
    return fn(param, indices, values)


# ------------------------------------------------------ sharded CSR store
#
# The CSR signature-store form (store_flat [nnz] / store_offsets [n+1])
# could not shard before this: offsets are positions into the GLOBAL flat
# array, so an even row split leaves every rank needing the whole flat
# buffer — the store replicated onto every device.  ``shard_csr`` re-bases
# once on the host (each rank's rows become a local CSR over its own slice
# of flat, padded to a uniform cap), and ``Exchange.partial_sum_lookup``
# assembles set rows across ranks exactly like the dense ``set_lookup``:
# the owning rank emits real elements, everyone else exact zeros, and the
# integer sum is exact under all three strategies.


def shard_csr(flat, offsets, n_model: int):
    """Host-side prep: global CSR -> per-rank re-based CSR, stacked.

    Returns (flat_sh [n_model, cap] uint32, offs_sh [n_model, c+1] int32)
    where ``c = n_rows / n_model`` and ``cap`` is the max per-rank nnz
    (zero-padded — uniform shapes so the stack shards over 'model' with one
    row per rank).  Must run OUTSIDE jit (the split depends on offset
    *values*); launchers do it once at buffer-build time
    (``shard_csr_buffers``).
    """
    flat = np.asarray(flat)
    offsets = np.asarray(offsets, np.int64)
    n = int(offsets.shape[0]) - 1
    assert n % n_model == 0, (n, n_model)
    c = n // n_model
    bounds = [(int(offsets[r * c]), int(offsets[(r + 1) * c]))
              for r in range(n_model)]
    cap = max(max(e - s for s, e in bounds), 1)
    flat_sh = np.zeros((n_model, cap), flat.dtype)
    offs_sh = np.zeros((n_model, c + 1), np.int32)
    for r, (s, e) in enumerate(bounds):
        flat_sh[r, : e - s] = flat[s:e]
        offs_sh[r] = (offsets[r * c: (r + 1) * c + 1] - s).astype(np.int32)
    return jnp.asarray(flat_sh), jnp.asarray(offs_sh)


def shard_csr_buffers(buffers: dict, mesh) -> dict:
    """Replace raw CSR store buffers with their 'model'-sharded form
    (``store_flat_sh`` / ``store_offsets_sh``) when a non-trivial model
    axis exists and divides the row count; otherwise pass through."""
    n_model = _model_size(mesh) if mesh is not None else 1
    if "store_flat" not in buffers or n_model <= 1:
        return buffers
    n = int(buffers["store_offsets"].shape[0]) - 1
    if n % n_model != 0:
        return buffers
    flat_sh, offs_sh = shard_csr(buffers["store_flat"],
                                 buffers["store_offsets"], n_model)
    out = {k: v for k, v in buffers.items()
           if k not in ("store_flat", "store_offsets")}
    out["store_flat_sh"] = flat_sh
    out["store_offsets_sh"] = offs_sh
    return out


def _csr_local_sets(flat_l, offs_l, v, max_len: int, axis: str = "model"):
    """This rank's contribution to the ragged-set gather for global row ids
    ``v`` [B]: (elems [B, max_len] uint32, length [B] int32), real values on
    owned rows and EXACT ZEROS elsewhere — the ``local_fn`` contract of
    ``Exchange.partial_sum_lookup``.  Owned-row output matches
    ``core.minhash.gather_ragged_sets`` masked to zeros."""
    c = int(offs_l.shape[0]) - 1
    rank = jax.lax.axis_index(axis)
    rel = v.astype(jnp.int32) - rank * c
    mine = (rel >= 0) & (rel < c)
    safe = jnp.clip(rel, 0, c - 1)
    start = jnp.take(offs_l, safe)
    length = jnp.take(offs_l, safe + 1) - start
    pos = jnp.arange(max_len, dtype=jnp.int32)[None, :]
    mask = (pos < jnp.minimum(length, max_len)[:, None]) & mine[:, None]
    idx = jnp.clip(start[:, None] + pos, 0, flat_l.shape[0] - 1)
    elems = jnp.take(flat_l, idx, axis=0).astype(jnp.uint32)
    return (jnp.where(mask, elems, jnp.uint32(0)),
            jnp.where(mine, length, 0).astype(jnp.int32))


def sharded_csr_set_lookup(flat_sh, offs_sh, lengths, value_ids, max_len: int,
                           mesh, dp_axes, exchange=None):
    """Gather D_v rows from the 'model'-sharded CSR store.

    ``flat_sh`` / ``offs_sh``: the stacked per-rank CSR from
    :func:`shard_csr`; ``lengths`` [n] row-sharded.  value_ids [...] ->
    (elems [..., max_len] uint32 zero-padded, mask, support [...]) —
    bit-identical to ``gather_ragged_sets`` + masked fill on the replicated
    store.  Integer sums: exact under every strategy.
    """
    n_model = _model_size(mesh)
    n_rows = int(lengths.shape[0])
    if n_model <= 1 or n_rows % n_model != 0:
        raise ValueError("sharded_csr_set_lookup needs a non-trivial "
                         "'model' axis dividing the store rows")
    batch, n_flat = _local_flat(mesh, dp_axes, value_ids)
    ex = _resolve(exchange, mesh, n_flat, max_len, None, alloc_row=0.0)
    bspec = _bspec(batch)
    gspec = P(bspec, *([None] * (value_ids.ndim - 1)))

    def body(flat_l, offs_l, len_l, v_l):
        flat_v = v_l.reshape(-1)

        def local_fn(g):
            elems, ln = _csr_local_sets(flat_l[0], offs_l[0], g, max_len)
            sup = exl.local_gather(len_l, g)
            return elems, ln, sup

        if ex.name == "psum":
            elems, ln, sup = ex.partial_sum_lookup(local_fn, flat_v, n_model)
        else:
            rank = jax.lax.axis_index("model")
            chunk = exl.chunk_for_rank(flat_v, rank, n_model)
            e_c, l_c, s_c = ex.partial_sum_lookup(local_fn, chunk, n_model)
            elems = jax.lax.all_gather(e_c, "model").reshape(-1, max_len)
            ln = jax.lax.all_gather(l_c, "model").reshape(-1)
            sup = jax.lax.all_gather(s_c, "model").reshape(-1)
        pos = jnp.arange(max_len, dtype=jnp.int32)[None, :]
        mask = pos < jnp.minimum(ln, max_len)[:, None]
        shape = v_l.shape
        return (elems.reshape(shape + (max_len,)),
                mask.reshape(shape + (max_len,)), sup.reshape(shape))

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P("model", None), P("model", None), P("model"), gspec),
        out_specs=(P(bspec, *([None] * value_ids.ndim)),
                   P(bspec, *([None] * value_ids.ndim)),
                   P(bspec, *([None] * (value_ids.ndim - 1)))),
        check_vma=False)
    return fn(flat_sh, offs_sh, lengths, value_ids)


def sharded_lma_lookup_csr(memory: jax.Array, flat_sh, offs_sh,
                           store_lengths, gids: jax.Array, params: LMAParams,
                           mesh, dp_axes, exchange=None) -> jax.Array:
    """LMA lookup with M and the *CSR* D' store both sharded over 'model'.

    The ragged-set reconstruction rides the strategy's
    ``partial_sum_lookup`` inside the lookup's ``loc_fn`` (chunked
    strategies run it on 1/n_model of the batch, like the dense
    ``set_lookup_many`` path), then funnels through
    ``alloc_lma_from_rows`` — bit-identical to
    ``lookup(memory, alloc_lma(params, SignatureStore(...), gids))``.
    """
    n_model = _model_size(mesh)
    n_rows = int(store_lengths.shape[0])
    if n_model <= 1 or params.m % n_model != 0 or n_rows % n_model != 0:
        raise ValueError("sharded_lma_lookup_csr needs a non-trivial "
                         "'model' axis dividing pool and store rows")
    batch, n_flat = _local_flat(mesh, dp_axes, gids)
    ex = _resolve(exchange, mesh, n_flat, params.d, params.m,
                  alloc_row=exl.alloc_bytes_per_row(
                      params.d, set_width=params.max_set),
                  fused_chunk=_fused_chunk_eligible(memory, n_model))
    chunk_ok = _fused_chunk_eligible(memory, n_model)
    bspec = _bspec(batch)
    gspec = P(bspec, *([None] * (gids.ndim - 1)))
    PAD = jnp.uint32(DenseSignatureStore.PAD)

    def body(mem_l, flat_l, offs_l, len_l, gids_l):
        flat_v = gids_l.reshape(-1)

        def _inputs(set_ex, g):
            def local_fn(q):
                elems, ln = _csr_local_sets(flat_l[0], offs_l[0], q,
                                            params.max_set)
                sup = exl.local_gather(len_l, q)
                return elems, ln, sup

            elems, ln, sup = set_ex.partial_sum_lookup(local_fn, g, n_model)
            pos = jnp.arange(params.max_set, dtype=jnp.int32)[None, :]
            mask = pos < jnp.minimum(ln, params.max_set)[:, None]
            return jnp.where(mask, elems, PAD), sup

        def loc_fn(g):
            rows, sup = _inputs(ex, g)
            return alc.alloc_lma_from_rows(params, rows, sup, g)

        def inputs_fn(g):
            # fused engine: owner-partial all_to_all set reconstruction
            # regardless of the memory-exchange strategy (fewest collective
            # hops; integer sums exact under every strategy, so bit-identity
            # against the split oracle is unaffected)
            return _inputs(exl.ALL_TO_ALL, g)

        fce = None
        if chunk_ok and ex.name in ("ring", "all_to_all"):
            from repro.kernels.fused_embed import ops as fe
            fce = _chunk_engine(fe.lma_spec(params), inputs_fn)
        out = ex.lookup(mem_l, flat_v, loc_fn, params.d, n_model, fused=fce)
        return out.reshape(*gids_l.shape, params.d)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P("model"), P("model", None), P("model", None),
                  P("model"), gspec),
        out_specs=P(bspec, *([None] * gids.ndim)),
        check_vma=False)
    return fn(memory, flat_sh, offs_sh, store_lengths, gids)


def sharded_lma_lookup(memory: jax.Array, store_sets: jax.Array,
                       store_lengths: jax.Array, gids: jax.Array,
                       params: LMAParams, mesh, dp_axes,
                       exchange=None) -> jax.Array:
    """LMA lookup with M *and* the dense D' store sharded over 'model'.

    gids [...] -> [..., d], bit-identical to
    ``lookup(memory, alloc_lma(params, store, gids))``.  Each device first
    reconstructs D_v rows from the row-sharded store through the strategy's
    ``set_lookup`` (integer sums — exact), hashes them to locations, then
    gathers from the M slabs through the same strategy.  Under ring /
    all_to_all both the set reconstruction and the minhash run on 1/n_model
    of the batch per rank — the location math that dominates this lookup.
    """
    n_model = _model_size(mesh)
    n_rows = int(store_sets.shape[0])
    if (n_model <= 1 or params.m % n_model != 0 or n_rows % n_model != 0):
        store = DenseSignatureStore(sets=store_sets, lengths=store_lengths)
        loc = alc.alloc_lma(params, store, gids.reshape(-1))
        return lookup(memory, loc).reshape(*gids.shape, params.d)
    batch, n_flat = _local_flat(mesh, dp_axes, gids)
    ex = _resolve(exchange, mesh, n_flat, params.d, params.m,
                  alloc_row=exl.alloc_bytes_per_row(
                      params.d, set_width=params.max_set),
                  fused=_fused_eligible(memory, n_model),
                  fused_chunk=_fused_chunk_eligible(memory, n_model))
    chunk_ok = _fused_chunk_eligible(memory, n_model)
    bspec = _bspec(batch)
    gspec = P(bspec, *([None] * (gids.ndim - 1)))

    def body(mem_l, sets_l, len_l, gids_l):
        flat = gids_l.reshape(-1)
        if ex.name == "psum" and _fused_slab(mem_l):
            from repro.kernels.fused_embed import ops as fe
            rows = local_gather_psum(sets_l, flat)       # [n, max_set] exact
            support = local_gather_psum(len_l, flat)     # [n] exact
            part = fe.fused_lookup(fe.lma_spec(params), mem_l, flat,
                                   rows[:, : params.max_set], support,
                                   base=_slab_base(mem_l))
            out = jax.lax.psum(part, "model")
        else:
            def loc_fn(g):
                # one exchange round reconstructs sets AND lengths (ring:
                # a single traversal with two accumulators; all_to_all: a
                # shared index all-gather)
                rows, support = ex.set_lookup_many((sets_l, len_l), g,
                                                   n_model)
                return alc.alloc_lma_from_rows(params, rows, support, g)

            def inputs_fn(g):
                # the fused engine always reconstructs sets through the
                # owner-partial all_to_all form — one shared index
                # all-gather + one all_to_all — whatever strategy carries
                # the memory exchange, with lengths riding as one extra
                # column of the set table so the pair costs a single
                # gather + collective; integer sums are exact under every
                # strategy, so bit-identity against the split oracle is
                # unaffected
                packed = jnp.concatenate(
                    [sets_l[:, : params.max_set],
                     len_l[:, None].astype(sets_l.dtype)], axis=1)
                rows, = exl.ALL_TO_ALL.set_lookup_many((packed,), g,
                                                       n_model)
                return (rows[:, : params.max_set],
                        rows[:, params.max_set].astype(len_l.dtype))

            fce = None
            if chunk_ok and ex.name in ("ring", "all_to_all"):
                from repro.kernels.fused_embed import ops as fe
                fce = _chunk_engine(fe.lma_spec(params), inputs_fn)
            out = ex.lookup(mem_l, flat, loc_fn, params.d, n_model,
                            fused=fce)
        return out.reshape(*gids_l.shape, params.d)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P("model"), P("model", None), P("model"), gspec),
        out_specs=P(bspec, *([None] * gids.ndim)),
        check_vma=False)
    return fn(memory, store_sets, store_lengths, gids)
