"""Jit'd public wrappers for the fused embed engine, with a scatter-add VJP.

``fused_lookup``  : signature sets / value ids -> [N, d] embeddings.
``fused_embed_bag``: multi-hot [B, L] inputs -> [B, d] weighted-sum bags,
                     the [B, L, d] pre-pool tensor never materialized.
``fused_locations``: the backward pass's in-tile location recomputation
                     *emitted* as a [N, d] tensor — the indices of the
                     sparse-gradient pipeline (``repro.optim.sparse``).

Batches are padded to power-of-two buckets OUTSIDE the jitted entries
(``_pad_batch``), so serving/eval batch-size jitter compiles at most
log2(B) engine variants instead of one per batch size.

Both differentiate through a custom VJP whose backward is a Pallas
scatter-add kernel into the memory gradient; locations are *recomputed* in
the backward tile instead of saved, so training steps skip one full
forward-sized HBM round-trip each way.  Non-memory inputs (sets, ids,
support) are integer-typed and get float0 cotangents; bag weights get the
exact ``<g, M[loc]>`` gradient from a third kernel.

Slab mode (``base`` != 0, memory = a 'model'-axis shard of M): out-of-slab
locations contribute 0 forward and scatter nothing backward — exactly the
mask-local-gather contract of ``repro/dist/sharded_memory.py``.

Dispatch: Pallas on TPU, interpret mode elsewhere.  ``fused_supported``
gates on the slab fitting the VMEM working-set budget.  Engine selection is
owned by ``repro.embed.backends.resolve_backend``: a registered scheme
publishes a :class:`FusedSpec` via ``Scheme.fused_spec`` and the resolver
routes to this engine when eligible, else to the split
``locations + jnp.take`` oracle (or the sharded psum path under a mesh).
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.allocation import LMAParams
from repro.core.hashing import seed_stream
from repro.core.signatures import DenseSignatureStore
from repro.kernels.fused_embed.kernel import (fused_chunk_fwd_pallas,
                                              fused_chunk_gather_pallas,
                                              fused_chunk_scatter_pallas,
                                              fused_locations_pallas,
                                              fused_lookup_fwd_pallas,
                                              fused_scatter_add_pallas,
                                              fused_weight_grad_pallas)

# runtime kill-switch (tests toggle it; REPRO_FUSED_EMBED=0 disables)
ENABLED = os.environ.get("REPRO_FUSED_EMBED", "1").lower() not in (
    "0", "false", "off", "no")

# slab bytes that may sit resident in VMEM alongside the batch tiles.  The
# default tracks the smallest real TPU VMEM (~16 MiB/core): the paper-scale
# pool (m=2^21 f32 = 8 MiB) fits with head-room for the tile working set,
# and anything larger falls back to the split path instead of failing
# Mosaic VMEM allocation at compile time.
_MAX_MEM_MB = int(os.environ.get("REPRO_FUSED_MAX_MEM_MB", "16"))
_TILE_RESERVE = 4 * 2**20   # VMEM kept free for the batch-tile working set

_BLOCK_B = 256        # flat values per tile
_BLOCK_ELEMS = 4096   # bag: bb chosen so bb * L <= this


@dataclasses.dataclass(frozen=True)
class FusedSpec:
    """Static (hashable) description of one fused lookup family."""

    scheme: str            # lma | hashed_elem | hashed_row
    d: int
    m: int
    seed: int
    n_h: int = 4
    max_set: int = 64
    min_support: int = 2
    independent: bool = True
    striped: bool = False   # striped location layout (LMAParams.striped)

    @property
    def n_raw_hashes(self) -> int:
        return self.d * self.n_h if self.independent else self.d + self.n_h - 1

    @property
    def stripe(self) -> int:
        """Stripe width when the striped layout is active, else 0 (flat)."""
        return self.m // self.d if (self.striped and self.m % self.d == 0) else 0


def lma_spec(p: LMAParams) -> FusedSpec:
    return FusedSpec("lma", p.d, p.m, p.seed, p.n_h, p.max_set,
                     p.min_support, p.independent_hashes, p.striped)


def hashed_spec(kind: str, d: int, m: int, seed: int) -> FusedSpec:
    assert kind in ("hashed_elem", "hashed_row"), kind
    return FusedSpec(kind, d, m, seed)


def fused_enabled() -> bool:
    return ENABLED


def fused_supported(m_local: int, itemsize: int = 4) -> bool:
    """Does an [m_local] slab fit the fused engine's VMEM budget, with the
    batch-tile working set (sets/locations/output blocks) reserved on top?"""
    return m_local * itemsize + _TILE_RESERVE <= _MAX_MEM_MB * 2**20


def _chunk_blocks(m_local: int, itemsize: int = 4) -> int | None:
    """Smallest power-of-two slab-block count whose [m_local / n] block fits
    the VMEM budget (None when no power-of-two factor of m_local does).
    n == 1 means the whole slab fits and the chunked engine degenerates to
    one block — the same working set as the whole-slab kernel."""
    budget = _MAX_MEM_MB * 2**20 - _TILE_RESERVE
    n = 1
    while m_local % n == 0:
        if (m_local // n) * itemsize <= budget:
            return n
        n *= 2
    return None


def fused_chunk_supported(m_local: int, itemsize: int = 4) -> bool:
    """Can the chunked engine run against an [m_local] slab — i.e. does SOME
    power-of-two slab block fit the VMEM budget?  Strictly weaker than
    ``fused_supported``: a slab over the whole-slab gate still chunk-fuses
    as long as one block fits (the 135M-slot production shape)."""
    return _chunk_blocks(m_local, itemsize) is not None


def _chunk_block_m(m_local: int, itemsize: int) -> int:
    """The slab-block length the chunked kernels tile with (whole slab when
    over-gate AND unchunkable — interpret mode still runs it; a real TPU
    caller must gate on ``fused_chunk_supported`` first)."""
    return m_local // (_chunk_blocks(m_local, itemsize) or 1)


def _default_interpret(interpret):
    from repro.kernels.dispatch import platform
    return platform() != "tpu" if interpret is None else interpret


def _loc_inputs(spec: FusedSpec, sets, gids, support):
    """Assemble the kernel's location-input arrays (seed streams included)."""
    if spec.scheme == "lma":
        return (sets, gids,
                support.astype(jnp.int32),
                seed_stream(spec.seed, spec.n_raw_hashes),
                seed_stream(spec.seed ^ 0x7F4A7C15, spec.d),
                seed_stream(spec.seed ^ 0x1234567, spec.d))
    if spec.scheme == "hashed_elem":
        return (gids, seed_stream(spec.seed, spec.d))
    return (gids, seed_stream(spec.seed, 1))


def _kern_kwargs(spec: FusedSpec, interpret: bool, block_b: int) -> dict:
    return dict(d=spec.d, n_h=spec.n_h, m=spec.m,
                min_support=spec.min_support, independent=spec.independent,
                stripe=spec.stripe, block_b=block_b, interpret=interpret)


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def _pad_batch(b_pad: int, *arrays):
    """Pad dim 0 up to exactly ``b_pad``; PAD-fill uint32 set arrays so
    padded rows hash as empty sets, 0-fill everything else.

    Batches are bucketed to the next power of two (``_pow2_ceil``) *outside*
    the jitted engine entry points, so serving/eval batch-size jitter hits at
    most log2(B) distinct shapes instead of compiling a fresh Pallas kernel
    per batch size (``tests/test_sparse_update.py`` counts compilations).
    Padded rows read 0 forward and carry a 0 cotangent backward, so results
    are bit-identical to the unpadded oracle."""
    B = arrays[0].shape[0]
    if b_pad == B:
        return arrays
    out = []
    for a in arrays:
        fill = DenseSignatureStore.PAD if a.dtype == jnp.uint32 else 0
        out.append(jnp.pad(a, ((0, b_pad - B),) + ((0, 0),) * (a.ndim - 1),
                           constant_values=fill))
    return tuple(out)


def _f0(x):
    """float0 cotangent for an integer-typed primal."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


# ----------------------------------------------------------- flat lookup VJP
#
# The VJP pair operates on the already-bucketed batch (the public wrappers
# pad to a power of two and slice, OUTSIDE the jitted entry points): the
# engine compiles once per bucket, and the slice transpose 0-pads the
# cotangent for free.

@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lookup(spec, interpret, memory, sets, gids, support, base):
    bb = min(_BLOCK_B, max(gids.shape[0], 1))
    return fused_lookup_fwd_pallas(
        spec.scheme, memory, _loc_inputs(spec, sets, gids, support),
        base, **_kern_kwargs(spec, interpret, bb))


def _lookup_fwd(spec, interpret, memory, sets, gids, support, base):
    out = _lookup(spec, interpret, memory, sets, gids, support, base)
    # memory rides along only for its (shape, dtype); it is a live parameter,
    # so this saves no extra buffer
    return out, (sets, gids, support, base, memory)


def _lookup_bwd(spec, interpret, res, g):
    sets, gids, support, base, memory = res
    m_local, mdtype = memory.shape[0], memory.dtype
    bb = min(_BLOCK_B, max(gids.shape[0], 1))
    dmem = fused_scatter_add_pallas(
        spec.scheme, g.astype(mdtype),
        _loc_inputs(spec, sets, gids, support), base, m_local, mdtype,
        **_kern_kwargs(spec, interpret, bb))
    return dmem, _f0(sets), _f0(gids), _f0(support), _f0(base)


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


# ------------------------------------------------------------ bag lookup VJP

@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bag(spec, interpret, memory, sets, gids, support, weights, base):
    B, L = gids.shape
    out = fused_lookup_fwd_pallas(
        spec.scheme, memory, _loc_inputs(spec, sets, gids, support),
        base, weights=weights, **_kern_kwargs(spec, interpret,
                                              _bag_block(B, L)))
    return out


def _bag_fwd(spec, interpret, memory, sets, gids, support, weights, base):
    out = _bag(spec, interpret, memory, sets, gids, support, weights, base)
    return out, (memory, sets, gids, support, weights, base)


def _bag_bwd(spec, interpret, res, g):
    memory, sets, gids, support, weights, base = res
    B, L = gids.shape
    loc_inputs = _loc_inputs(spec, sets, gids, support)
    kw = _kern_kwargs(spec, interpret, _bag_block(B, L))
    dmem = fused_scatter_add_pallas(
        spec.scheme, g.astype(memory.dtype), loc_inputs, base,
        memory.shape[0], memory.dtype, weights=weights, **kw)
    dw = fused_weight_grad_pallas(
        spec.scheme, memory, g, loc_inputs, base, L, **kw)
    return (dmem, _f0(sets), _f0(gids), _f0(support),
            dw.astype(weights.dtype), _f0(base))


_bag.defvjp(_bag_fwd, _bag_bwd)


def _bag_block(B: int, L: int) -> int:
    """Power-of-two bag tile (divides the pow2-bucketed batch evenly)."""
    return min(max(B, 1), _pow2_floor(max(_BLOCK_ELEMS // max(L, 1), 1)))


# --------------------------------------------------- chunked-exchange VJPs
#
# The chunked engine (ring / all_to_all strategies): per-chunk location math
# + slab-TILED masked gather, so the working set is one slab block, not the
# whole slab.  The combined step (``_chunk_lookup``) emits its locations —
# the ring circulates them, and the backward scatter consumes them directly
# instead of recomputing (they were a free primal output).  Visiting chunks
# ride the location-only gather (``_chunk_gather``), whose VJP is the same
# slab-tiled scatter.

@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunk_lookup(spec, interpret, memory, sets, gids, support, base):
    bb = min(_BLOCK_B, max(gids.shape[0], 1))
    return fused_chunk_fwd_pallas(
        spec.scheme, memory, _loc_inputs(spec, sets, gids, support), base,
        block_m=_chunk_block_m(memory.shape[0], memory.dtype.itemsize),
        **_kern_kwargs(spec, interpret, bb))


def _chunk_lookup_fwd(spec, interpret, memory, sets, gids, support, base):
    vals, loc = _chunk_lookup(spec, interpret, memory, sets, gids, support,
                              base)
    return (vals, loc), (sets, gids, support, loc, base, memory)


def _chunk_lookup_bwd(spec, interpret, res, cts):
    g = cts[0]                      # the int32 location output has no grad
    sets, gids, support, loc, base, memory = res
    dmem = fused_chunk_scatter_pallas(
        loc, g.astype(memory.dtype), base, memory.shape[0], memory.dtype,
        block_b=min(_BLOCK_B, max(loc.shape[0], 1)),
        block_m=_chunk_block_m(memory.shape[0], memory.dtype.itemsize),
        interpret=interpret)
    return dmem, _f0(sets), _f0(gids), _f0(support), _f0(base)


_chunk_lookup.defvjp(_chunk_lookup_fwd, _chunk_lookup_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunk_gather(interpret, memory, loc, base):
    return fused_chunk_gather_pallas(
        memory, loc, base, block_b=min(_BLOCK_B, max(loc.shape[0], 1)),
        block_m=_chunk_block_m(memory.shape[0], memory.dtype.itemsize),
        interpret=interpret)


def _chunk_gather_fwd(interpret, memory, loc, base):
    return _chunk_gather(interpret, memory, loc, base), (loc, base, memory)


def _chunk_gather_bwd(interpret, res, g):
    loc, base, memory = res
    dmem = fused_chunk_scatter_pallas(
        loc, g.astype(memory.dtype), base, memory.shape[0], memory.dtype,
        block_b=min(_BLOCK_B, max(loc.shape[0], 1)),
        block_m=_chunk_block_m(memory.shape[0], memory.dtype.itemsize),
        interpret=interpret)
    return dmem, _f0(loc), _f0(base)


_chunk_gather.defvjp(_chunk_gather_fwd, _chunk_gather_bwd)


# ------------------------------------------------------------- public entry

@partial(jax.jit, static_argnums=(0, 6))
def _lookup_jit(spec, memory, sets, gids, support, base, interpret):
    return _lookup(spec, interpret, memory, sets, gids, support, base)


@partial(jax.jit, static_argnums=(0, 7))
def _bag_jit(spec, memory, sets, gids, support, weights, base, interpret):
    return _bag(spec, interpret, memory, sets, gids, support, weights, base)


@partial(jax.jit, static_argnums=(0, 6))
def _chunk_lookup_jit(spec, memory, sets, gids, support, base, interpret):
    return _chunk_lookup(spec, interpret, memory, sets, gids, support, base)


@partial(jax.jit, static_argnums=(3,))
def _chunk_gather_jit(memory, loc, base, interpret):
    return _chunk_gather(interpret, memory, loc, base)


@partial(jax.jit, static_argnums=(0, 4))
def _locations_jit(spec, sets, gids, support, interpret):
    bb = min(_BLOCK_B, max(gids.shape[0], 1))
    return fused_locations_pallas(
        spec.scheme, _loc_inputs(spec, sets, gids, support),
        **_kern_kwargs(spec, interpret, bb))


def _dummy_loc_state(spec, gids):
    """hashed_* schemes carry no signature sets; feed typed placeholders so
    the VJP arity stays uniform (they get float0 cotangents regardless)."""
    if spec.scheme == "lma":
        raise ValueError("lma lookups need sets + support")
    return (jnp.zeros(gids.shape + (1,), jnp.uint32),
            jnp.zeros(gids.shape, jnp.int32))


def fused_lookup(spec: FusedSpec, memory: jax.Array, gids: jax.Array,
                 sets: jax.Array | None = None,
                 support: jax.Array | None = None,
                 base: jax.Array | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """One fused pass: gids [N] (+ sets [N, S], support [N] for lma) -> [N, d].

    ``memory`` is the full [m] pool, or an [m / n_model] slab with ``base``
    its global offset (out-of-slab positions return 0 for the psum)."""
    interpret = _default_interpret(interpret)
    gids = gids.astype(jnp.int32)
    if base is None:
        base = jnp.zeros((1,), jnp.int32)
    if sets is None:
        sets, support = _dummy_loc_state(spec, gids)
    B = gids.shape[0]
    sets, gids, support = _pad_batch(_pow2_ceil(max(B, 1)),
                                     sets.astype(jnp.uint32), gids,
                                     support.astype(jnp.int32))
    return _lookup_jit(spec, memory, sets, gids, support, base,
                       interpret)[:B]


def fused_embed_bag(spec: FusedSpec, memory: jax.Array, gids: jax.Array,
                    weights: jax.Array,
                    sets: jax.Array | None = None,
                    support: jax.Array | None = None,
                    base: jax.Array | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """gids [B, L], weights [B, L] (+ sets [B, L, S], support [B, L] for lma)
    -> [B, d] weighted-sum bags, pooled inside the kernel tile."""
    interpret = _default_interpret(interpret)
    gids = gids.astype(jnp.int32)
    if base is None:
        base = jnp.zeros((1,), jnp.int32)
    if sets is None:
        sets, support = _dummy_loc_state(spec, gids)
    B = gids.shape[0]
    sets, gids, support, weights = _pad_batch(
        _pow2_ceil(max(B, 1)), sets.astype(jnp.uint32), gids,
        support.astype(jnp.int32), weights)
    return _bag_jit(spec, memory, sets, gids, support, weights, base,
                    interpret)[:B]


def fused_locations(spec: FusedSpec, gids: jax.Array,
                    sets: jax.Array | None = None,
                    support: jax.Array | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """gids [N] (+ sets/support for lma) -> [N, d] int32 locations.

    The scatter kernel's in-tile hash recomputation, *emitted* instead of
    consumed: the sparse-gradient pipeline pairs these indices with the
    lookup-output cotangent to form a SparseGrad, skipping the dense
    zeros(m) scatter entirely.  Bit-identical to ``Scheme.locations``."""
    interpret = _default_interpret(interpret)
    gids = gids.astype(jnp.int32)
    if sets is None:
        sets, support = _dummy_loc_state(spec, gids)
    B = gids.shape[0]
    sets, gids, support = _pad_batch(_pow2_ceil(max(B, 1)),
                                     sets.astype(jnp.uint32), gids,
                                     support.astype(jnp.int32))
    return _locations_jit(spec, sets, gids, support, interpret)[:B]


def fused_chunk_lookup(spec: FusedSpec, memory: jax.Array, gids: jax.Array,
                       sets: jax.Array | None = None,
                       support: jax.Array | None = None,
                       base: jax.Array | None = None,
                       interpret: bool | None = None):
    """One engine call per exchange chunk: gids [N] (+ sets/support for lma)
    -> ([N, d] slab-masked partial, [N, d] int32 locations).

    The chunked strategies' step-0 form (``repro.dist.exchange``): location
    math runs once in VMEM and the emitted locations then circulate the ring
    / all-gather for the other ranks' slab gathers.  Unlike ``fused_lookup``
    the slab is TILED (``fused_chunk_supported``), so per-device slabs over
    the whole-slab VMEM gate still fuse; the partial is bit-identical to
    ``local_gather(memory, locations)``.  Backward scatters the cotangent by
    the emitted locations (slab-tiled as well); location inputs get float0.
    """
    interpret = _default_interpret(interpret)
    gids = gids.astype(jnp.int32)
    if base is None:
        base = jnp.zeros((1,), jnp.int32)
    if sets is None:
        sets, support = _dummy_loc_state(spec, gids)
    B = gids.shape[0]
    sets, gids, support = _pad_batch(_pow2_ceil(max(B, 1)),
                                     sets.astype(jnp.uint32), gids,
                                     support.astype(jnp.int32))
    vals, loc = _chunk_lookup_jit(spec, memory, sets, gids, support, base,
                                  interpret)
    return vals[:B], loc[:B]


def fused_chunk_gather(memory: jax.Array, loc: jax.Array,
                       base: jax.Array | None = None,
                       interpret: bool | None = None) -> jax.Array:
    """loc [N, d] int32 global locations -> [N, d] slab-masked partial.

    The chunked engine's visiting-chunk step: a slab-tiled Pallas gather by
    pre-computed locations (any scheme's — no FusedSpec needed), bit-
    identical to ``local_gather``; the VJP is the slab-tiled scatter-add.
    Padded rows carry location -1 (out of every slab) so they read and
    scatter exact zeros."""
    interpret = _default_interpret(interpret)
    loc = loc.astype(jnp.int32)
    if base is None:
        base = jnp.zeros((1,), jnp.int32)
    B = loc.shape[0]
    b_pad = _pow2_ceil(max(B, 1))
    if b_pad != B:
        loc = jnp.pad(loc, ((0, b_pad - B), (0, 0)), constant_values=-1)
    return _chunk_gather_jit(memory, loc, base, interpret)[:B]
