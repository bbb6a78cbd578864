"""D' signature store: the data subsample that defines semantic similarity.

Paper section 5: for each categorical value ``v``, ``D_v`` is the set of sample ids
(rows of the data subsample D') in which ``v`` appears; the semantic similarity is
``S*[v1, v2] = J(D_v1, D_v2)`` (Jaccard), which is exactly the collision kernel of
minwise hashing.  Theorem 3 shows a small i.i.d. subsample suffices (~100-125K rows
for Criteo out of 46M).

The store is CSR over a *global* value-id space: with common memory across all
embedding tables (paper section 5, "Common Memory"), table ``t``'s value ``v`` maps
to global id ``table_offsets[t] + v``.  Storage cost is O(|D'|) integers, the only
persistent artifact LMA needs beyond the budget memory M itself.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SignatureStore:
    """CSR ragged store of D_v per global value id (device-resident)."""

    flat: jax.Array       # [nnz] uint32 sample ids, concatenated per value
    offsets: jax.Array    # [n_values + 1] int32
    lengths: jax.Array    # [n_values] int32 (== diff(offsets); kept for fast masks)

    @property
    def n_values(self) -> int:
        return self.lengths.shape[0]

    @property
    def nnz(self) -> int:
        return self.flat.shape[0]


def build_signature_store(
    rows: Sequence[np.ndarray] | np.ndarray,
    n_values: int,
    max_per_value: int = 128,
    n_samples: int | None = None,
) -> SignatureStore:
    """Build D' from a subsample of the data.

    ``rows``: iterable over data rows; each row is an int array of the *global*
    value ids present in that sample (multi-hot).  ``n_samples`` rows are used
    (all, if None) — this is the paper's ``n_s`` knob.  Per-value sets are capped
    at ``max_per_value`` sample ids (reservoir-free head cap: D' rows are already
    an i.i.d. subsample, so the head of each list is itself i.i.d.).
    """
    # one (value, sample id) pair per row entry, in row order; a stable sort
    # by value keeps each value's sample ids in arrival order, so the head
    # cap keeps the first ``max_per_value`` of them
    vals = []
    for sample_id, row in enumerate(rows):
        if n_samples is not None and sample_id >= n_samples:
            break
        vals.append(np.asarray(row, np.int64).ravel())
    counts = np.fromiter(map(len, vals), np.int64, len(vals))
    vals = np.concatenate(vals) if vals else np.zeros(0, np.int64)
    if vals.size and (vals.min() < 0 or vals.max() >= n_values):
        raise IndexError(f"value ids must lie in [0, {n_values})")
    sample_ids = np.repeat(np.arange(len(counts), dtype=np.uint32), counts)
    order = np.argsort(vals, kind="stable")
    vals, sample_ids = vals[order], sample_ids[order]
    per_value = np.bincount(vals, minlength=n_values)
    first = np.cumsum(per_value) - per_value
    keep = np.arange(vals.size) - first[vals] < max_per_value
    lengths = np.minimum(per_value, max_per_value).astype(np.int32)
    offsets = np.zeros(n_values + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    return SignatureStore(
        flat=jnp.asarray(sample_ids[keep]),
        offsets=jnp.asarray(offsets),
        lengths=jnp.asarray(lengths),
    )


def synthetic_signature_store(
    n_values: int,
    n_clusters: int,
    samples_per_value: int = 32,
    overlap: float = 0.9,
    seed: int = 0,
) -> SignatureStore:
    """A signature store with *planted* cluster structure (no data pass needed).

    Values in the same cluster draw their D_v sample ids from a shared cluster pool
    (so intra-cluster Jaccard ~= ``overlap``); values in different clusters draw
    from disjoint pools (Jaccard ~= 0).  Used by tests/benchmarks and by the
    full-scale dry-run configs, where only shapes matter.
    """
    rng = np.random.default_rng(seed)
    pool_size = max(8, int(samples_per_value / max(overlap, 1e-3)))
    lengths = np.full(n_values, samples_per_value, dtype=np.int32)
    offsets = np.zeros(n_values + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    flat = np.empty(int(offsets[-1]), dtype=np.uint32)
    for v in range(n_values):
        c = v % n_clusters
        pool_base = c * (1 << 16)
        ids = rng.choice(pool_size, size=samples_per_value, replace=False)
        flat[offsets[v] : offsets[v + 1]] = (pool_base + ids).astype(np.uint32)
    return SignatureStore(
        flat=jnp.asarray(flat),
        offsets=jnp.asarray(offsets),
        lengths=jnp.asarray(lengths),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseSignatureStore:
    """Fixed-width D_v store: [n_values, max_set] uint32, PAD-sentinel padded.

    The CSR store is the compact host/tooling form; this equal-width form is the
    *sharded production* form — it splits evenly over mesh axes (value rows over
    ('data','model')), which CSR cannot (offsets reference global flat positions,
    so an even split of ``flat`` never aligns with value boundaries).  See
    DESIGN.md section 3.  PAD = 0xFFFFFFFF (also the empty-set minhash value).
    """

    sets: jax.Array      # [n_values, max_set] uint32
    lengths: jax.Array   # [n_values] int32

    PAD = np.uint32(0xFFFFFFFF)

    @property
    def n_values(self) -> int:
        return self.sets.shape[0]

    @property
    def max_set(self) -> int:
        return self.sets.shape[1]


def densify_store(store: SignatureStore, max_set: int,
                  n_rows: int | None = None) -> DenseSignatureStore:
    """CSR -> fixed-width.  ``n_rows`` pads the row count (mesh divisibility).

    Two ``repro.obs`` spans time it: ``dprime.densify`` the host fill and
    ``dprime.put`` the copy to the device, up to the arrays being ready."""
    with obs.span("dprime.densify"):
        flat = np.asarray(store.flat)
        offsets = np.asarray(store.offsets)
        lengths = np.asarray(store.lengths)
        n = lengths.shape[0]
        rows = max(n_rows or n, n)
        sets = np.full((rows, max_set), DenseSignatureStore.PAD, np.uint32)
        k = np.minimum(lengths, max_set)
        v = np.repeat(np.arange(n), k)
        j = np.arange(v.size) - np.repeat(np.cumsum(k) - k, k)
        sets[v, j] = flat[offsets[v] + j]
        out_len = np.zeros(rows, np.int32)
        out_len[:n] = k
    with obs.span("dprime.put"):
        out = DenseSignatureStore(sets=jnp.asarray(sets),
                                  lengths=jnp.asarray(out_len))
        return jax.block_until_ready(out)


def synthetic_dense_store(
    n_values: int, n_clusters: int, max_set: int = 32, overlap: float = 0.9,
    seed: int = 0,
) -> DenseSignatureStore:
    """Vectorized planted-cluster dense store (fast path for huge |S|)."""
    rng = np.random.default_rng(seed)
    pool_size = max(8, int(max_set / max(overlap, 1e-3)))
    clusters = (np.arange(n_values, dtype=np.int64) % n_clusters)
    # per-value: max_set distinct draws from its cluster pool (argsort trick)
    keys = rng.random((n_values, pool_size))
    picks = np.argsort(keys, axis=1)[:, :max_set].astype(np.uint32)
    sets = (clusters[:, None].astype(np.uint32) << np.uint32(16)) + picks
    lengths = np.full(n_values, max_set, np.int32)
    return DenseSignatureStore(sets=jnp.asarray(sets), lengths=jnp.asarray(lengths))


def table_offsets(vocab_sizes: Sequence[int]) -> np.ndarray:
    """Global-id bases for common-memory multi-table LMA (paper sec 5)."""
    return np.concatenate([[0], np.cumsum(np.asarray(vocab_sizes))]).astype(np.int64)
