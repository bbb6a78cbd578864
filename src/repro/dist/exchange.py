"""Pluggable cross-device exchange strategies for the sharded memory pool.

Every collective the sharded common-memory path performs — lookup assembly,
signature-set reconstruction, and the sparse-update broadcast — goes through
one of three interchangeable :class:`Exchange` strategies:

``psum``
    The original mask-local-gather + ``psum`` over 'model' (the bit-exact
    oracle).  Every rank computes locations for the FULL local batch, gathers
    the slots in its own slab, and one all-reduce assembles the result.  The
    strategy the WHOLE-SLAB fused kernel serves (locations hashed in-VMEM
    against the entire per-device slab), and the cheapest when location math
    is free.

``ring``
    Batch shards ``ppermute`` around the 'model' ring.  Each rank computes
    locations ONCE for its 1/n_model chunk of the batch; the (locations,
    accumulator) pair then visits every slab, accumulating each rank's
    contribution, so the per-step neighbor transfer overlaps the next slab
    gather instead of waiting on a global reduction.  Location work drops by
    n_model — the win for expensive allocators (LMA's set reconstruction +
    minhash).

``all_to_all``
    Chunked locations are all-gathered, every rank gathers its slab's
    contribution for the full batch, and a single ``all_to_all`` hands each
    rank exactly the partial sums for the chunk it owns (a reduce-scatter
    spelled as all-to-all + local sum), followed by one all-gather of the
    finished chunks.  For the sparse-update exchange this strategy keeps each
    rank's owned (index, value) slices local instead of replicating the full
    K vectors via psum — the per-step update exchange shrinks by ~n_model.

Ring and all_to_all additionally accept a :class:`FusedChunkEngine` — the
CHUNKED fused form: one Pallas call per exchange chunk runs the location
math in-VMEM and gathers against the per-device slab in slab-sized tiles,
so pools whose whole slab exceeds the fused VMEM gate (the 135M-slot
production shape) still fuse.  The drivers in ``repro/dist/sharded_memory``
assemble the engine per scheme and pass it down; the split per-chunk path
stays as the bit-exact oracle for it.

All three produce *bit-identical* lookups: exactly one rank owns each slot,
so every cross-rank sum adds exact zeros in some order, and x + 0.0 is
bitwise x.  ``tests/test_exchange.py`` pins ring/all_to_all against the psum
oracle for every registered scheme, forward and through 10 training steps.

Selection is ``REPRO_DIST_EXCHANGE`` (psum | ring | all_to_all | auto) with
``auto`` resolved by the traffic model in :func:`resolve_exchange` — the
promoted, testable form of the gate that used to be hard-coded in
``launch/steps.py::_sparse_worthwhile`` (now :func:`sparse_worthwhile`,
including the O(K log K) dedup-sort term the old gate ignored).
``repro.embed.backends.ShardedBackend`` threads the strategy into the
drivers in ``repro/dist/sharded_memory.py``.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Callable, ClassVar, Optional

import jax
import jax.numpy as jnp

# Forced strategy: "psum" | "ring" | "all_to_all"; None/"auto" -> cost model.
# Tests may set FORCED directly; launchers via REPRO_DIST_EXCHANGE / --exchange.
_env = os.environ.get("REPRO_DIST_EXCHANGE", "auto").strip().lower()
FORCED: str | None = None if _env in ("", "auto") else _env


def model_size(mesh) -> int:
    return int(dict(mesh.shape).get("model", 1))


# --------------------------------------------------------- slab primitives
#
# Both run INSIDE a shard_map over ``axis_name``.  ``shard`` is this rank's
# axis-0 slab of a row-sharded array; ``idx`` holds GLOBAL indices.

def local_gather(shard: jax.Array, idx: jax.Array,
                 axis_name: str = "model") -> jax.Array:
    """Gather the indices that land in this rank's slab, exact 0 elsewhere."""
    n_local = shard.shape[0]
    rel = idx - jax.lax.axis_index(axis_name) * n_local
    mine = (rel >= 0) & (rel < n_local)
    vals = jnp.take(shard, jnp.clip(rel, 0, n_local - 1), axis=0)
    mask = mine.reshape(mine.shape + (1,) * (vals.ndim - mine.ndim))
    return jnp.where(mask, vals, jnp.zeros((), vals.dtype))


def local_gather_psum(shard: jax.Array, idx: jax.Array,
                      axis_name: str = "model") -> jax.Array:
    """Axis-0-sharded slab + replicated global indices -> full values.

    Exactly one rank owns each index, so the psum (exact for integers, x+0
    for floats) reproduces the single-device gather bitwise; its transpose is
    the sharded scatter-add (zero-filled ranks scatter 0).
    """
    return jax.lax.psum(local_gather(shard, idx, axis_name), axis_name)


def chunk_for_rank(x: jax.Array, rank, n_model: int) -> jax.Array:
    """This rank's contiguous 1/n_model slice of the leading axis (the
    batch-chunking rule every chunked strategy and driver shares)."""
    c = x.shape[0] // n_model
    return jax.lax.dynamic_slice_in_dim(x, rank * c, c, axis=0)


# ----------------------------------------------------- fused chunked engine

@dataclasses.dataclass(frozen=True)
class FusedChunkEngine:
    """The chunked strategies' Pallas engine, assembled by the drivers
    (``repro/dist/sharded_memory.py``) when the per-rank slab passes the
    chunk-level VMEM gate (``fused_chunk_eligible``).

    ``chunk_lookup(mem_l, g_chunk) -> (partial [c, d], loc [c, d])``
        The ring's step 0: ONE Pallas call does the chunk's location math
        in VMEM plus the slab-tiled masked gather against this rank's slab,
        emitting the locations for the ring to circulate.  May run uniform
        collectives first (LMA's set reconstruction).
    ``locations(g_chunk) -> loc [c, d]``
        The all_to_all form of the chunk's location math (Pallas in-VMEM
        hashing; the locations all-gather replaces the ring circulation).
    ``gather(mem_l, loc) -> partial``
        A visiting chunk's slab-tiled Pallas gather by pre-computed
        locations — bit-identical to :func:`local_gather` — used for ring
        steps 1..P-1 and the all_to_all full-batch partial.

    All three produce bit-identical results to the split callables they
    replace, so a strategy given an engine still matches the psum oracle —
    ``tests/test_exchange.py`` pins it.
    """

    chunk_lookup: Callable
    locations: Callable
    gather: Callable


# -------------------------------------------------------------- strategies

class Exchange:
    """One cross-device exchange policy; all methods run inside shard_map.

    ``lookup(mem_l, gids, loc_fn, d, n_model, fused=None)``
        Full sharded lookup: flat [n] global ids (identical on every model
        rank) -> [n, d] values, replicated over 'model'.  ``loc_fn`` maps a
        flat id chunk to [k, d] int32 locations; chunked strategies call it
        with per-rank chunks, so any collective inside it must be uniform in
        chunk length (``set_lookup``/``set_lookup_many`` are).  ``fused``
        (a :class:`FusedChunkEngine`, chunked strategies only) swaps the
        split per-chunk callables for the slab-tiled Pallas engine —
        bit-identical output, one Pallas call per exchange step.
    ``set_lookup(shard, idx, n_model)`` / ``set_lookup_many(shards, ...)``
        Row-sharded table(s) + per-rank indices -> complete rows for THOSE
        indices (exact for integers).  Unlike ``local_gather_psum`` the
        chunked strategies accept a different ``idx`` on every rank; the
        ``_many`` form reconstructs several equally-row-sharded tables in
        ONE exchange round (ring: one traversal carrying an accumulator per
        table; all_to_all: one shared index all-gather) — the LMA lookup
        uses it for (sets, lengths).
    ``reduce_update(u, n_model)``
        The sparse-update exchange: per-rank owner-masked update values ->
        what ``sharded_sparse_apply`` consumes.
    """

    name: ClassVar[str]
    # all_to_all leaves update values owner-partial (see reduce_update)
    partial_updates: ClassVar[bool] = False

    def eligible(self, n_flat: int, n_model: int) -> bool:
        """Can this strategy run a lookup of ``n_flat`` rows per device?"""
        return True

    def lookup(self, mem_l, gids, loc_fn, d: int, n_model: int,
               axis: str = "model",
               fused: Optional[FusedChunkEngine] = None) -> jax.Array:
        raise NotImplementedError

    def set_lookup(self, shard, idx, n_model: int,
                   axis: str = "model") -> jax.Array:
        return self.set_lookup_many((shard,), idx, n_model, axis)[0]

    def set_lookup_many(self, shards: tuple, idx, n_model: int,
                        axis: str = "model") -> tuple:
        raise NotImplementedError

    def partial_sum_lookup(self, local_fn, idx, n_model: int,
                           axis: str = "model") -> tuple:
        """The generalized set-gather: assemble ``sum over ranks of
        local_fn(idx)`` for per-rank ``idx``, through this strategy's
        collective pattern.

        ``local_fn(idx)`` -> tuple of arrays whose leading axis matches
        ``idx``'s; each rank contributes its owned part and EXACT ZEROS
        elsewhere (exactly one owner per element -> the cross-rank sum is
        bit-exact for floats, exact for ints).  ``local_fn`` must be
        collective-free and uniform in chunk length — chunked strategies
        apply it to permuted / concatenated index chunks.

        ``set_lookup_many`` is the special case ``local_fn = local_gather
        over row-sharded tables``; the CSR signature-store gather
        (``repro.dist.sharded_memory.sharded_csr_set_lookup``) is the case
        that needs the general form — its "table" is a ragged flat/offsets
        pair that cannot be row-gathered directly.
        """
        raise NotImplementedError

    def reduce_update(self, u, n_model: int, axis: str = "model") -> jax.Array:
        return jax.lax.psum(u, axis)


class PsumExchange(Exchange):
    """Mask-local-gather + one global psum (the bit-exact oracle)."""

    name = "psum"

    def lookup(self, mem_l, gids, loc_fn, d, n_model, axis="model",
               fused=None):
        # psum has its own whole-slab fused form (the drivers dispatch it);
        # the chunk engine is a chunked-strategy construct and is ignored
        return local_gather_psum(mem_l, loc_fn(gids), axis)

    def set_lookup_many(self, shards, idx, n_model, axis="model"):
        # requires ``idx`` replicated over 'model' (true under psum.lookup,
        # whose loc_fn sees the full batch on every rank)
        return tuple(local_gather_psum(s, idx, axis) for s in shards)

    def partial_sum_lookup(self, local_fn, idx, n_model, axis="model"):
        # replicated idx (psum.lookup's loc_fn sees the full batch)
        return tuple(jax.lax.psum(p, axis) for p in local_fn(idx))


class RingExchange(Exchange):
    """ppermute batch chunks around the 'model' ring.

    The chunk's (locations, accumulator) pair visits every slab once; each
    step's neighbor transfer overlaps the next slab gather.  Location math
    runs once per chunk — 1/n_model of the psum strategy's.
    """

    name = "ring"

    def eligible(self, n_flat, n_model):
        return n_model > 1 and n_flat % n_model == 0

    def _ring(self, shards, idx, accs, n_model, axis):
        """One ring traversal: ``idx`` and every accumulator ride together,
        each rank adding its slab's contribution per step."""
        perm = [(i, (i + 1) % n_model) for i in range(n_model)]
        for t in range(n_model):
            accs = tuple(a + local_gather(s, idx, axis)
                         for s, a in zip(shards, accs))
            if t < n_model - 1:
                idx = jax.lax.ppermute(idx, axis, perm)
                accs = tuple(jax.lax.ppermute(a, axis, perm) for a in accs)
        # after the last gather the chunk sits one hop short of home
        return tuple(jax.lax.ppermute(a, axis, perm) for a in accs)

    def lookup(self, mem_l, gids, loc_fn, d, n_model, axis="model",
               fused=None):
        rank = jax.lax.axis_index(axis)
        chunk = chunk_for_rank(gids, rank, n_model)
        if fused is None:
            loc = loc_fn(chunk)                              # [c, d] ONCE
            acc = jnp.zeros(loc.shape[:1] + (d,), mem_l.dtype)
            acc, = self._ring((mem_l,), loc, (acc,), n_model, axis)
        else:
            # fused chunked: step 0 is ONE Pallas call (location math +
            # own-slab gather, locations emitted), steps 1..P-1 gather each
            # visiting chunk by its circulated locations — the same
            # accumulation order as _ring, so the result stays bitwise
            # identical (partial-first vs zeros+partial only differs on
            # -0.0, which the other ranks' exact +0.0 contributions erase)
            acc, loc = fused.chunk_lookup(mem_l, chunk)
            perm = [(i, (i + 1) % n_model) for i in range(n_model)]
            # the (acc, loc) pair rides each hop as ONE packed buffer —
            # int32 locations bitcast into the accumulator's 4-byte lanes —
            # halving the per-step collective count; ppermute is pure data
            # movement, so the bitcast round-trip is exact
            pack = acc.dtype.itemsize == 4 and acc.ndim == loc.ndim
            d_acc = acc.shape[-1]
            for _ in range(n_model - 1):
                if pack:
                    buf = jnp.concatenate(
                        [acc, jax.lax.bitcast_convert_type(loc, acc.dtype)],
                        axis=-1)
                    buf = jax.lax.ppermute(buf, axis, perm)
                    acc = buf[..., :d_acc]
                    loc = jax.lax.bitcast_convert_type(buf[..., d_acc:],
                                                       loc.dtype)
                else:
                    loc = jax.lax.ppermute(loc, axis, perm)
                    acc = jax.lax.ppermute(acc, axis, perm)
                acc = acc + fused.gather(mem_l, loc)
            # no homing hop: rank r finishes chunk r+1, so the all-gather
            # comes out rotated by one — a local roll (pure permutation,
            # bitwise exact) re-homes it without the extra collective
            out = jax.lax.all_gather(acc, axis)
            return jnp.roll(out, 1, axis=0).reshape(-1, d)
        return jax.lax.all_gather(acc, axis).reshape(-1, d)

    def set_lookup_many(self, shards, idx, n_model, axis="model"):
        accs = tuple(jnp.zeros(idx.shape + s.shape[1:], s.dtype)
                     for s in shards)
        return self._ring(shards, idx, accs, n_model, axis)

    def partial_sum_lookup(self, local_fn, idx, n_model, axis="model"):
        # same traversal as _ring with the first application seeding the
        # accumulators (no eval_shape needed for local_fn's output shapes)
        perm = [(i, (i + 1) % n_model) for i in range(n_model)]
        accs = None
        for t in range(n_model):
            part = tuple(local_fn(idx))
            accs = part if accs is None else tuple(
                a + p for a, p in zip(accs, part))
            if t < n_model - 1:
                idx = jax.lax.ppermute(idx, axis, perm)
                accs = tuple(jax.lax.ppermute(a, axis, perm) for a in accs)
        return tuple(jax.lax.ppermute(a, axis, perm) for a in accs)


class AllToAllExchange(Exchange):
    """Owner-sliced exchanges: reduce-scatter spelled as all_to_all + sum.

    Lookup: chunked locations are all-gathered, each rank contributes its
    slab's partial for the full batch, and the all_to_all hands every rank
    only the partials for ITS chunk (summed locally), then one all-gather
    replicates the finished chunks.  Update: the psum of the [K, ...] update
    values disappears entirely — each rank's copy already holds the exact
    values at its owned slots (zeros elsewhere), which is all the masked
    local scatter in ``sharded_sparse_apply`` reads.
    """

    name = "all_to_all"
    partial_updates = True

    def eligible(self, n_flat, n_model):
        return n_model > 1 and n_flat % n_model == 0

    def lookup(self, mem_l, gids, loc_fn, d, n_model, axis="model",
               fused=None):
        rank = jax.lax.axis_index(axis)
        chunk = chunk_for_rank(gids, rank, n_model)
        if fused is not None:
            # fused chunked: Pallas in-VMEM location math for the chunk,
            # one slab-tiled gather for the full batch's partial, and ONE
            # psum assembles it — the reduce-scatter + chunk all-gather
            # tail collapses into a single all-reduce of the same bytes
            # (an all-reduce IS reduce-scatter + all-gather) because the
            # chunked location math already happened before the exchange.
            # Exactly one rank owns each slot, so the psum only ever adds
            # exact zeros — bit-identical to the split tail below.
            loc = fused.locations(chunk)                     # [c, d]
            full = jax.lax.all_gather(loc, axis).reshape(-1, d)
            return jax.lax.psum(fused.gather(mem_l, full), axis)
        loc = loc_fn(chunk)                                  # [c, d]
        c = loc.shape[0]
        full = jax.lax.all_gather(loc, axis).reshape(-1, d)  # [n, d] in order
        part = local_gather(mem_l, full, axis).reshape(n_model, c, d)
        recv = jax.lax.all_to_all(part, axis, 0, 0)          # [P, c, d]
        mine = jnp.sum(recv, axis=0)                         # my chunk, done
        return jax.lax.all_gather(mine, axis).reshape(-1, d)

    def set_lookup_many(self, shards, idx, n_model, axis="model"):
        full = jax.lax.all_gather(idx, axis).reshape(-1)   # shared: ONE round
        outs = []
        for s in shards:
            part = local_gather(s, full, axis)
            part = part.reshape((n_model,) + idx.shape + s.shape[1:])
            outs.append(jnp.sum(jax.lax.all_to_all(part, axis, 0, 0), axis=0))
        return tuple(outs)

    def partial_sum_lookup(self, local_fn, idx, n_model, axis="model"):
        full = jax.lax.all_gather(idx, axis)           # [P, ...idx]
        flat = full.reshape((-1,) + idx.shape[1:])
        outs = []
        for part in tuple(local_fn(flat)):
            part = part.reshape((n_model, idx.shape[0]) + part.shape[1:])
            outs.append(jnp.sum(jax.lax.all_to_all(part, axis, 0, 0), axis=0))
        return tuple(outs)

    def reduce_update(self, u, n_model, axis="model"):
        # Owner-partial: each rank keeps exactly its owned slices.  Valid
        # ONLY for consumption by the masked local scatter (sharded_sparse_
        # apply); anything that reads the values outside a 'model' shard_map
        # sees one rank's partial.
        return u


PSUM = PsumExchange()
RING = RingExchange()
ALL_TO_ALL = AllToAllExchange()
_STRATEGIES = {e.name: e for e in (PSUM, RING, ALL_TO_ALL)}


def get_exchange(name: str) -> Exchange:
    if name not in _STRATEGIES:
        raise KeyError(f"unknown exchange strategy {name!r}; "
                       f"known: {sorted(_STRATEGIES)}")
    return _STRATEGIES[name]


def list_exchanges() -> list[str]:
    return sorted(_STRATEGIES)


# --------------------------------------------------------- demotion ladder
#
# Degraded-mode operation: when a chunked strategy fails validation
# (``repro.resilience.exchange_guard`` — injected chunk drop/corruption, or
# any shape/finite/bitwise mismatch against the psum oracle), it is demoted
# for the rest of the process and the resolvers stop picking it.  The chain
# is all_to_all -> ring -> psum: each rung trades performance for a simpler
# collective, and psum — the bit-exact oracle — is terminal.  Explicit
# per-call strategy *instances* (tests pinning a strategy) bypass demotion;
# FORCED and the cost model honor it.

FALLBACK = {"all_to_all": "ring", "ring": "psum", "psum": None}
DEMOTED: dict[str, str] = {}   # name -> reason it was demoted


def demote(name: str, reason: str = "validation failure") -> str:
    """Demote ``name`` for the rest of the run; -> its effective successor."""
    if name not in _STRATEGIES:
        raise KeyError(f"unknown exchange strategy {name!r}")
    if name == "psum":
        raise ValueError("psum is the terminal bit-exact oracle; "
                         "there is nothing to demote it to")
    DEMOTED[name] = reason
    return effective(FALLBACK[name])


def effective(name: str) -> str:
    """Map a requested strategy through the demotion chain."""
    while name in DEMOTED and FALLBACK.get(name):
        name = FALLBACK[name]
    return name


def reset_demotions():
    DEMOTED.clear()


# -------------------------------------------------------------- cost model
#
# Modeled per-device bytes, the same accounting style as
# ``bench_kernels.modeled_lookup_bytes``: collective terms count bytes a
# device sends (ring all-reduce ~ 2(P-1)/P x buffer), allocation terms count
# the write+read round-trip of the [rows, d] int32 location tensor plus any
# per-row exchange the allocator itself needs (LMA's set reconstruction).
# The model is what ``resolve_exchange`` ranks and what the dryrun meta
# records; measured CPU rows live in BENCH_kernels.json.

def fused_slab_eligible(m: int, n_model: int, itemsize: int = 4) -> bool:
    """THE gate for "the per-device [m / n_model] slab admits the fused
    engine" — shared by ``resolve_exchange``, the sharded_memory drivers,
    and the dryrun meta so their pricing can never disagree.  ``itemsize``
    is the pool dtype's (callers with a concrete array pass it; 4 = the f32
    default)."""
    from repro.kernels.dispatch import pallas_allowed
    from repro.kernels.fused_embed import ops as fe
    return (pallas_allowed("fused_embed") and fe.fused_enabled()
            and fe.fused_supported(m // max(n_model, 1), itemsize))


def fused_chunk_eligible(m: int, n_model: int, itemsize: int = 4) -> bool:
    """The chunk-level sibling of :func:`fused_slab_eligible`: can the
    chunked strategies (ring / all_to_all) run their slab-TILED Pallas
    engine against the per-device [m / n_model] slab?  True whenever SOME
    power-of-two slab block fits the VMEM budget — strictly weaker than the
    whole-slab gate, so slabs too big to psum-fuse (the 135M-slot
    production shape) still chunk-fuse.  Shared by ``resolve_exchange``,
    the sharded_memory drivers, and the dryrun meta, exactly like the slab
    gate — modeled and runtime dispatch cannot diverge."""
    from repro.kernels.dispatch import pallas_allowed
    from repro.kernels.fused_embed import ops as fe
    return (n_model > 1 and m % n_model == 0 and pallas_allowed("fused_embed")
            and fe.fused_enabled()
            and fe.fused_chunk_supported(m // n_model, itemsize))


def alloc_bytes_per_row(d: int, set_width: int = 0):
    """Location-math bytes for ONE batch row on the split path: the [d]
    int32 location row's HBM round-trip plus the signature-set row exchange
    for set-based allocators (LMA).  The fused discounts are NOT applied
    here — they belong to ``lookup_cost``: ``fused=`` prices the psum
    whole-slab kernel and ``fused_chunk=`` the ring/all_to_all chunked
    engine, each behind its own eligibility gate."""
    return 8 * d + 8 * set_width


RING_OVERLAP = 0.5   # fraction of ring step transfers hidden behind gathers


def tier_fetch_bytes(n_cold_blocks: int, block: int, n_leaves: int = 1,
                     itemsize: int = 4) -> int:
    """Modeled host<->device bytes per step of a tiered pool
    (``repro.tier``): each cold block a step touches crosses PCIe twice —
    the staged fetch down and the post-update writeback up — for every
    pool leaf (values + optimizer moments).  The dryrun meta records this
    next to the collective terms so an over-budget config's step cost is
    priced end to end; the measured twin is the ``host_fetch_bandwidth``
    bench row."""
    return 2 * n_cold_blocks * block * itemsize * n_leaves


def lookup_cost(n_model: int, n: int, d: int,
                alloc_row: float | None = None,
                fused: bool = False,
                fused_chunk: bool = False) -> dict[str, float]:
    """Per-device modeled bytes of one sharded lookup of ``n`` flat rows.

    psum: every rank runs location math for all n rows, one [n, d]
    all-reduce.  ring: location math on n/P rows, (P-1) neighbor transfers
    of the (loc, acc) chunk pair — charged at ``RING_OVERLAP`` because each
    transfer runs concurrently with the next slab gather — plus the final
    homing permute and all-gather.  all_to_all: location math on n/P rows,
    all-gather of locations + all_to_all of partials + all-gather of
    outputs (a barrier at every stage: nothing overlaps).

    The fused discounts remove the [d] location-row round-trip (the hash
    runs in-VMEM) from the strategies whose engine form passes its VMEM
    gate: ``fused`` (the whole-slab gate, ``fused_slab_eligible``)
    discounts the PSUM entry, ``fused_chunk`` (the chunk-level gate,
    ``fused_chunk_eligible``) discounts ring and all_to_all — the chunked
    engine tiles the slab, so it admits slabs psum's cannot.  The per-row
    set-reconstruction exchange (LMA's ``alloc_row`` excess over 8d) is a
    collective and survives every discount.
    """
    P = max(n_model, 1)
    base = 8 * d if alloc_row is None else alloc_row
    a = (max(base - 8 * d, 0) if fused_chunk else base) * n
    a_psum = (max(base - 8 * d, 0) if fused else base) * n
    row = 4 * d * n                    # one [n, d] f32 / int32 pass
    frac = (P - 1) / P
    return {
        "psum": a_psum + 2 * frac * row,
        "ring": a / P + RING_OVERLAP * 2 * frac * row + frac * row + row / P,
        "all_to_all": a / P + 3 * frac * row,
    }


def resolve_exchange(mesh, B: int | None = None, d: int | None = None,
                     m: int | None = None, K: int | None = None,
                     alloc_row: float | None = None,
                     fused: bool | None = None,
                     fused_chunk: bool | None = None) -> Exchange:
    """Pick the exchange strategy for a lookup of ``B`` per-device flat rows.

    ``REPRO_DIST_EXCHANGE`` (or ``FORCED``) short-circuits the model.  With
    unknown shapes, or a batch the 'model' axis does not divide, the psum
    oracle is the safe answer.  The fused flags feed the per-strategy
    location discounts of :func:`lookup_cost`, each clamped through ITS OWN
    eligibility gate — slab-level (``fused_slab_eligible``) for the psum
    discount, chunk-level (``fused_chunk_eligible``) for the ring /
    all_to_all discount — and derived from ``m`` through the same gates
    when not given.  A caller-asserted flag cannot outrun its gate: an
    explicit over-budget pool config pays full location bytes like everyone
    else (previously the psum flag could leak through and mis-pick psum;
    the chunk flag routes through the identical clamp so modeled and
    runtime dispatch cannot diverge).  ``K`` (touched slots) is accepted
    for signature parity with the sparse gate; lookups ignore it.
    """
    n_model = model_size(mesh) if mesh is not None else 1
    if n_model <= 1:
        return PSUM
    if FORCED is not None:
        return get_exchange(effective(FORCED))
    if B is None or d is None or B % n_model != 0:
        return PSUM
    if fused is None:
        fused = m is not None and fused_slab_eligible(m, n_model)
    elif fused and m is not None:
        fused = fused_slab_eligible(m, n_model)
    if fused_chunk is None:
        fused_chunk = m is not None and fused_chunk_eligible(m, n_model)
    elif fused_chunk and m is not None:
        fused_chunk = fused_chunk_eligible(m, n_model)
    costs = lookup_cost(n_model, B, d, alloc_row, fused=fused,
                        fused_chunk=fused_chunk)
    live = {n: c for n, c in costs.items() if n not in DEMOTED}
    name = min(live, key=live.get)
    ex = _STRATEGIES[name]
    return ex if ex.eligible(B, n_model) else PSUM


# ------------------------------------------------- sparse-update gate
#
# Relocated from launch/steps.py::_sparse_worthwhile, extended with (a) the
# per-strategy exchange term (all_to_all keeps owned slices local instead of
# replicating the K vectors) and (b) a per-path dedup term — on CPU at
# near-uniform traffic the flat O(K log K) sort alone can erase the sparse
# win (``sparse_dedup_sort`` bench rows measure it).  Striped-layout schemes
# (``Scheme.sparse_buckets`` > 0) escape that tax three ways at once: the
# per-stripe sorts are log(K/d) deep instead of log(K), d batched small
# sorts run several times the byte efficiency of one giant argsort
# (``BUCKETED_SORT_SPEEDUP``, fit from the measured sweep and ratcheted by
# ``check_regression.dedup_speedup_failures``), and under a 'model' mesh
# each rank sorts only its own buckets/n_model stripes.

SORT_BYTES_PER_KEY_PASS = 4.0      # one 4-byte key pass per merge level

# Measured byte-efficiency of the bucketed path (d per-stripe packed-key
# sorts + the update kernel's in-kernel duplicate fold) over the flat
# argsort + segment-sum dedup, at matched K.  The CPU sweep in
# bench_kernels (``sparse_dedup_sort`` rows, flat vs bucketed) measures
# 7-9x at K=2^17; 5.0 is the conservative modeling constant, and
# check_regression gates the measured ratio at >= 3x so the model can
# never drift above reality unnoticed.
BUCKETED_SORT_SPEEDUP = 5.0


def dedup_sort_bytes(k: int, buckets: int = 0) -> float:
    """Modeled bytes of building one sorted SparseGrad from ``k`` locations.

    ``buckets == 0`` (flat): one O(k log k) argsort + segment-sum dedup —
    k keys x log2 k merge passes.  ``buckets == d`` (striped layout,
    ``optim.sparse.from_bucketed_locations``): d independent per-stripe
    sorts of k/d packed keys each, with dedup folded into the update kernel
    — the log factor drops to log2(k/d) and the whole construction runs at
    ``BUCKETED_SORT_SPEEDUP`` the byte efficiency of the flat path.
    """
    if k <= 1:
        return 0.0
    if buckets and k % buckets == 0 and k > buckets:
        return (SORT_BYTES_PER_KEY_PASS * k * math.log2(k // buckets)
                / BUCKETED_SORT_SPEEDUP)
    return SORT_BYTES_PER_KEY_PASS * k * math.log2(k)


def sparse_update_cost(n_model: int, n_lookups: int, d: int, m: int,
                       row_mode: bool = False,
                       buckets: int = 0) -> dict[str, float]:
    """Per-device modeled bytes of one memory-pool optimizer step.

    ``dense``: the dense path's slab tax — zeros + scatter + the O(m_local)
    optimizer read-modify-write, ~8 f32 passes over the model-sharded pool
    (bench_kernels.modeled_update_bytes).  ``sparse_psum``: the replicated
    (indices, values) pair costs its construction broadcast plus the [K]
    update-value psum — the SparseGrad must be whole on every rank, so it
    always pays the replicated dedup.  ``sparse_all_to_all``: each rank
    keeps only its owned 1/n_model slice; flat records additionally touch
    the full index vector once for routing, while the bucketed layout
    (``buckets == d``, striped schemes) routes for free — stripes coincide
    with owner slabs, so the per-rank stripe sort IS the routing — and,
    when 'model' divides the bucket count, shards the sort itself by
    n_model (the sharded segment sort).  ``dedup_sort`` reports the term
    the all_to_all entry was charged.
    """
    P = max(n_model, 1)
    k_elems = n_lookups * d
    k_idx = n_lookups if row_mode else k_elems
    idx_b, val_b = 4 * k_idx, 4 * k_elems
    sort = dedup_sort_bytes(k_idx, buckets)
    shard = P if (buckets and buckets % P == 0) else 1
    if buckets:
        a2a = (idx_b + val_b) / P + sort / shard
    else:
        a2a = (idx_b + val_b) / P + idx_b + sort
    return {
        "dense": 8 * (m // P) * 4,
        "sparse_psum": 2 * (idx_b + val_b) + sort,
        "sparse_all_to_all": a2a,
        "dedup_sort": sort / shard,
    }


def sparse_worthwhile(mesh, n_lookups: int, d: int, m: int,
                      row_mode: bool = False, buckets: int = 0) -> bool:
    """Should the training step carry SparseGrad pool gradients here?

    True when the best sparse exchange (psum, or all_to_all when a 'model'
    axis exists) models cheaper than the dense slab update.  Single-host
    training always picks sparse (K << m).  At a 16x16 pod cell with a 65k
    global batch the decision splits three ways: flat element-level records
    stay dense — the O(K log K) dedup sort on ~54M element locations erases
    the win; row-aligned records (hashed_row / freq) go sparse because the
    index vector and its sort are d times smaller and the all_to_all
    exchange keeps owned slices local; and bucketed element records
    (``buckets == d``, the striped LMA layout) go sparse too — per-stripe
    sorts sharded over 'model' plus the in-kernel fold price the
    construction below the dense slab tax.  That last flip is what the
    bucketed layout was built for.
    """
    n_model = model_size(mesh) if mesh is not None else 1
    costs = sparse_update_cost(n_model, n_lookups, d, m, row_mode, buckets)
    # ring forces fall back to psum for the update exchange
    # (resolve_update_exchange), so they are priced as psum here too
    best = costs["sparse_psum"] if (n_model <= 1
                                    or FORCED in ("psum", "ring")) \
        else min(costs["sparse_psum"], costs["sparse_all_to_all"])
    return best < costs["dense"]


def resolve_update_exchange(mesh) -> Exchange:
    """The strategy for the sparse-update exchange (moment update + apply).

    all_to_all whenever a non-trivial 'model' axis exists: its update
    exchange is free (owner-partial values feed the masked local scatter
    directly), strictly dominating the [K]-sized psum.  ``ring`` forces fall
    back to psum here — ring is a lookup strategy; it has no update form.
    """
    n_model = model_size(mesh) if mesh is not None else 1
    if n_model <= 1:
        return PSUM
    if FORCED is not None:
        ex = get_exchange(effective(FORCED))
        return PSUM if ex is RING else ex
    # demotion: all_to_all's update form has no ring rung — a demoted
    # all_to_all goes straight to the psum oracle
    return PSUM if "all_to_all" in DEMOTED else ALL_TO_ALL
