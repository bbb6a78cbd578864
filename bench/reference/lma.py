"""Plain reference of the LMA allocation (arXiv:2103.06124, sections 3-5).

Written from the configuration's statement of the method, not from the
program: the D' sets of each value (the first ``max_set`` sample ids, in
row order, of the D' rows the value occurs in), ``d * n_h`` minwise hashes
of each set, each group of ``n_h`` folded by a chained rehash into a slot of
the pool, and the very-sparse fallback (a set smaller than ``min_support``
takes the element-wise hashing trick).  The hash family is the one the
configuration names: murmur3-style mixing on uint32 (``fmix32``), seeds
derived by a splitmix step; all arithmetic wraps on Z_{2^32}.

Everything here works on a block of ids at a time and holds only the sets
of the ids it is asked for, never the whole D' store.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

U32 = jnp.uint32
C1, C2 = U32(0x85EBCA6B), U32(0xC2B2AE35)
GOLDEN = U32(0x9E3779B9)
M1, M2 = U32(0xCC9E2D51), U32(0x1B873593)
EMPTY = np.uint32(0xFFFFFFFF)


def fmix32(x):
    x = x ^ (x >> 16)
    x = x * C1
    x = x ^ (x >> 13)
    x = x * C2
    return x ^ (x >> 16)


def seeds(base: int, n: int):
    i = jnp.arange(1, n + 1, dtype=U32)
    return fmix32(U32(base & 0xFFFFFFFF) + GOLDEN * i)


def hash32(x, s):
    h = (x ^ s) * M1
    h = (h ^ (h >> 15)) * M2
    return fmix32(h ^ s)


def dprime_sets(rows: np.ndarray, ids: np.ndarray, max_set: int):
    """D_v of each id in ``ids``: [N, max_set] uint32 sample ids (EMPTY-padded)
    and [N] sizes, from the D' rows ``rows`` [n_s, F] of global value ids."""
    uniq, inv = np.unique(ids, return_inverse=True)
    flat = rows.reshape(-1)
    sample = np.repeat(np.arange(rows.shape[0], dtype=np.uint32), rows.shape[1])
    hit = np.isin(flat, uniq)
    vals, sample = flat[hit], sample[hit]
    order = np.argsort(vals, kind="stable")      # row order inside a value
    vals, sample = vals[order], sample[order]
    where = np.searchsorted(uniq, vals)
    first = np.searchsorted(vals, uniq)
    rank = np.arange(vals.size) - first[where]
    keep = rank < max_set
    sets = np.full((uniq.size, max_set), EMPTY, np.uint32)
    sets[where[keep], rank[keep]] = sample[keep]
    size = np.minimum(np.bincount(where, minlength=uniq.size), max_set)
    return sets[inv], size[inv].astype(np.int32)


def locations(lma: dict, sets, size, ids):
    """[N, d] pool slots of ``ids`` with D' sets ``sets`` [N, max_set] and
    sizes ``size`` [N] (jnp arrays).  ``lma``: the configuration's LMA keys."""
    d, n_h, m = lma["d"], lma["n_h"], lma["m"]
    stripe = m // d if lma["striped"] else 0
    col = jnp.arange(d, dtype=jnp.int32)[None, :]
    # minhash: min over the set of each raw hash
    raw = seeds(lma["seed"], d * n_h)
    h = hash32(sets[:, :, None], raw[None, None, :])
    h = jnp.where((sets == EMPTY)[:, :, None], EMPTY, h)
    sig = jnp.min(h, axis=1).reshape(-1, d, n_h)
    # chained rehash of each group of n_h minhashes
    acc = jnp.broadcast_to(seeds(lma["seed"] ^ 0x7F4A7C15, d)[None, :],
                           sig.shape[:2])
    for k in range(n_h):
        acc = (acc ^ fmix32(sig[:, :, k])) * M1 + GOLDEN
    acc = fmix32(acc)
    # element-wise hashing trick for values seen fewer than min_support times
    fs = seeds(lma["seed"] ^ 0x1234567, d)[None, :]
    hv = hash32(jnp.asarray(ids).astype(U32)[:, None], fs)
    fb = hash32(jnp.arange(d, dtype=U32)[None, :] ^ hv, fs ^ GOLDEN)
    if stripe:
        slot = lambda x: col * stripe + (x % U32(stripe)).astype(jnp.int32)
    else:
        slot = lambda x: (x % U32(m)).astype(jnp.int32)
    return jnp.where((size < lma["min_support"])[:, None], slot(fb), slot(acc))


@functools.partial(jax.jit, static_argnums=0)
def _locations(lma_items: tuple, sets, size, ids):
    return locations(dict(lma_items), sets, size, ids)


def lookup_slots(lma: dict, rows: np.ndarray, gids: np.ndarray,
                 block: int = 8192) -> np.ndarray:
    """[N] global ids -> [N, d] int32 pool slots, ``block`` ids at a time."""
    sets, size = dprime_sets(rows, gids, lma["max_set"])
    items = tuple(sorted(lma.items()))
    out = []
    for lo in range(0, gids.size, block):
        n = min(block, gids.size - lo)
        pad = block - n
        s = np.pad(sets[lo:lo + n], ((0, pad), (0, 0)), constant_values=EMPTY)
        z = np.pad(size[lo:lo + n], (0, pad))
        g = np.pad(gids[lo:lo + n].astype(np.uint32), (0, pad))
        out.append(np.asarray(_locations(items, s, z, g))[:n])
    return np.concatenate(out)
