"""Allocation functions (paper Definitions 1-2) and the LMA allocation (section 4).

An allocation maps a value id to the ``d`` memory locations its embedding occupies:
``A(v)[i] in [0, m)``.  We represent allocations as functions returning a dense
``[B, d]`` int32 location matrix — the one-hot matrix of Definition 1 is never
materialized (mask-based retrieval == gather).

Implemented allocations:
  * ``alloc_full``        A_full : location = v*d + i          (m == |S|*d)
  * ``alloc_hashed_elem`` A_h    : location = h(v, i) % m      (HashedNet / naive trick)
  * ``alloc_hashed_row``  row-wise trick: row = h(v) % (m//d), location = row*d + i
  * ``alloc_lma``         A_L    : location = h_r(psi_i(minhash(D_v))) % m

``fraction_shared`` computes f_A (Definition 2) for theory validation (Thm 1).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.hashing import UINT32_MAX, combine_chain, hash_pair, hash_u32, seed_stream
from repro.core.minhash import gather_ragged_sets, minhash_dense
from repro.core.signatures import DenseSignatureStore, SignatureStore


def alloc_full(value_ids: jax.Array, d: int) -> jax.Array:
    v = value_ids.astype(jnp.int32)
    return v[:, None] * d + jnp.arange(d, dtype=jnp.int32)[None, :]


def alloc_hashed_elem(value_ids: jax.Array, d: int, m: int, seed: int,
                      stripe: int = 0) -> jax.Array:
    """Element-wise naive hashing trick (HashedNet [13]).

    ``stripe > 0`` selects the striped layout: position ``i`` maps into its own
    contiguous slot range ``[i*stripe, (i+1)*stripe)`` instead of all of
    ``[0, m)``.  Used by the LMA very-sparse fallback when
    ``LMAParams.striped`` is set, so the stripe invariant holds for every row.
    """
    seeds = seed_stream(seed, d)                      # one function per element index
    v = value_ids.astype(jnp.uint32)[:, None]
    i = jnp.arange(d, dtype=jnp.uint32)[None, :]
    h = hash_pair(v, i, seeds[None, :])
    if stripe:
        return (jnp.arange(d, dtype=jnp.int32)[None, :] * stripe
                + (h % jnp.uint32(stripe)).astype(jnp.int32))
    return (h % jnp.uint32(m)).astype(jnp.int32)


def alloc_hashed_row(value_ids: jax.Array, d: int, m: int, seed: int) -> jax.Array:
    """Row-wise (vector-wise) hashing trick: whole rows collide."""
    n_rows = max(m // d, 1)
    seeds = seed_stream(seed, 1)
    row = hash_u32(value_ids.astype(jnp.uint32), seeds[0]) % jnp.uint32(n_rows)
    return (row.astype(jnp.int32)[:, None] * d
            + jnp.arange(d, dtype=jnp.int32)[None, :])


@dataclasses.dataclass(frozen=True)
class LMAParams:
    """Static hyper-parameters of the LMA allocation (paper section 7.1)."""

    d: int                 # embedding dimension (number of LSH draws)
    m: int                 # memory budget |M|
    n_h: int = 4           # power of each LSH mapping (k of section 3.2)
    seed: int = 0x5C3A
    max_set: int = 64      # cap on |D_v| representation used per lookup
    min_support: int = 2   # |D_v| below this -> fall back to A_h (very sparse values)
    independent_hashes: bool = True
    # independent_hashes=True: d*n_h raw minhashes (paper-faithful, d independent
    # power-n_h functions).  False: sliding-window sharing, d+n_h-1 raw hashes
    # (beyond-paper perf option; each window is still a valid power-n_h minhash,
    # only cross-i covariance changes — see EXPERIMENTS.md §Perf).
    striped: bool = False
    # striped=True: position i maps into its own stripe [i*(m//d), (i+1)*(m//d))
    # instead of all of [0, m) — a beyond-paper layout option (same precedent as
    # independent_hashes) that makes the VJP's location stream bucketed by
    # construction, so the sparse-update dedup replaces a global O(K log K)
    # argsort with d independent per-stripe sorts (optim/sparse.py
    # ``from_bucketed_locations``).  Cost: the Theorem 1 collision floor rises
    # from 1/m to d/m = 1/stripe (see ``expected_gamma``); with m/d >= 2^16
    # this is negligible at production budgets.  Requires m % d == 0 (otherwise
    # the flag is inert and the flat layout is used).

    @property
    def n_raw_hashes(self) -> int:
        return self.d * self.n_h if self.independent_hashes else self.d + self.n_h - 1

    @property
    def stripe(self) -> int:
        """Stripe width when the striped layout is active, else 0 (flat)."""
        return self.m // self.d if (self.striped and self.m % self.d == 0) else 0


def _rows_signatures(params: LMAParams, rows: jax.Array) -> jax.Array:
    """Dense D' rows [B, max_set_store] -> raw minhash signatures.

    THE shared hash core: PAD-mask before truncation, truncate to
    ``params.max_set``, minhash.  Every path that must stay bit-identical
    (``lma_signatures``, ``alloc_lma_from_rows``, and through it the sharded
    lookup) funnels through here."""
    mask = rows != DenseSignatureStore.PAD
    elems = rows[:, : params.max_set]
    mask = mask[:, : params.max_set]
    return minhash_dense(elems, mask, params.n_raw_hashes, params.seed)


def lma_signatures(
    params: LMAParams, store: SignatureStore | DenseSignatureStore,
    value_ids: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Raw minhash signatures for a batch of values.

    Returns (sigs [B, n_raw_hashes] uint32, support [B] int32 = |D_v|).
    """
    if isinstance(store, DenseSignatureStore):
        sigs = _rows_signatures(params, jnp.take(store.sets, value_ids, axis=0))
    else:
        elems, mask = gather_ragged_sets(store.flat, store.offsets, value_ids,
                                         params.max_set)
        sigs = minhash_dense(elems, mask, params.n_raw_hashes, params.seed)
    support = jnp.take(store.lengths, value_ids, axis=0)
    return sigs, support


def locations_from_signatures(params: LMAParams, sigs: jax.Array) -> jax.Array:
    """psi_i composition + universal rehash into [0, m) (section 3.2 / 4).

    ``sigs``: [B, n_raw_hashes] uint32 -> locations [B, d] int32.
    """
    B = sigs.shape[0]
    if params.independent_hashes:
        grouped = sigs.reshape(B, params.d, params.n_h)
    else:
        idx = (jnp.arange(params.d)[:, None] + jnp.arange(params.n_h)[None, :])
        grouped = sigs[:, idx]                        # [B, d, n_h] sliding windows
    rehash_seeds = seed_stream(params.seed ^ 0x7F4A7C15, params.d)
    h = combine_chain(grouped, rehash_seeds[None, :], axis=-1)   # [B, d]
    stripe = params.stripe
    if stripe:
        return (jnp.arange(params.d, dtype=jnp.int32)[None, :] * stripe
                + (h % jnp.uint32(stripe)).astype(jnp.int32))
    return (h % jnp.uint32(params.m)).astype(jnp.int32)


def _lma_or_fallback(params: LMAParams, loc_lma: jax.Array,
                     support: jax.Array, value_ids: jax.Array) -> jax.Array:
    """Very-sparse fallback to A_h (paper section 5): |D_v| < min_support."""
    loc_fallback = alloc_hashed_elem(value_ids, params.d, params.m,
                                     params.seed ^ 0x1234567,
                                     stripe=params.stripe)
    sparse = (support < params.min_support)[:, None]
    return jnp.where(sparse, loc_fallback, loc_lma)


def alloc_lma_from_rows(
    params: LMAParams, rows: jax.Array, support: jax.Array,
    value_ids: jax.Array,
) -> jax.Array:
    """A_L from already-gathered dense D' rows.

    ``rows``: [B, max_set_store] uint32 (PAD-padded) — exactly
    ``store.sets[value_ids]``; ``support``: [B] int32 == |D_v|.  This is the
    shared core of ``alloc_lma`` and the sharded lookup
    (``repro.dist.sharded_memory`` reconstructs the rows by mask-local-gather
    + psum and must produce bit-identical locations).
    """
    loc_lma = locations_from_signatures(params, _rows_signatures(params, rows))
    return _lma_or_fallback(params, loc_lma, support, value_ids)


@jax.named_scope("lma_locations")
def alloc_lma(
    params: LMAParams, store: SignatureStore | DenseSignatureStore,
    value_ids: jax.Array,
) -> jax.Array:
    """Full LMA allocation A_L with very-sparse fallback to A_h (paper section 5).

    Its ops (D' row gather, minhash, rehash, fallback) sit under the
    ``lma_locations`` named scope, so a device trace groups them."""
    if isinstance(store, DenseSignatureStore):
        rows = jnp.take(store.sets, value_ids, axis=0)
        support = jnp.take(store.lengths, value_ids, axis=0)
        return alloc_lma_from_rows(params, rows, support, value_ids)
    sigs, support = lma_signatures(params, store, value_ids)
    loc_lma = locations_from_signatures(params, sigs)
    return _lma_or_fallback(params, loc_lma, support, value_ids)


def fraction_shared(loc_a: jax.Array, loc_b: jax.Array) -> jax.Array:
    """f_A(v1, v2) (Definition 2): fraction of positions mapping to the same slot."""
    return jnp.mean((loc_a == loc_b).astype(jnp.float32), axis=-1)


def expected_gamma(phi: jax.Array, m: int, stripe: int = 0) -> jax.Array:
    """Theorem 1: E[f_{A_L}] = phi + (1 - phi)/m.

    Under the striped layout (``LMAParams.striped``) position i rehashes into
    its own stripe of ``m // d`` slots, so the accidental-collision floor rises
    from 1/m to 1/stripe = d/m; pass ``stripe=params.stripe`` to model it.
    The default (``stripe=0``) is the paper's flat layout.
    """
    return phi + (1.0 - phi) / (stripe if stripe else m)
