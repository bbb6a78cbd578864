"""Chip micro-bench of the two Adagrad pool-update paths at dlrm-rm2's size.

Times, on a 135,053,312-slot striped pool (64 stripes of 2,110,208):

* one stripe's stream slice written into zeros, in the forms XLA offers
  (``.at[].add`` sorted and unsorted, ``segment_sum``, and the set of
  folded run sums at unique indices that ``stripe_blocked_adagrad`` uses);
* the gather/scatter pass (``_leaf_sparse_update`` then ``sparse_apply``)
  and ``stripe_blocked_adagrad``, each at several stream sizes K, so that
  the break-even K/m fits ``STREAM_C``.

Streams are bucketed like the program's: N entries per stripe, sorted in
the stripe, drawn from 0.4 N distinct slots (a batch's 41% distinct ids).
A device time needs the chip, so the script refuses any other platform.

    python benchmarks/bench_pool_update.py [--out chiprun_out/pool_update.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.sparse_update.ref import fold_duplicates
from repro.optim import sparse as sp

M, D = 135_053_312, 64
STRIPE = M // D
LR, EPS = 0.01, 1e-10


def stream(rng, n: int) -> sp.SparseGrad:
    """A bucketed stream: n sorted entries in each of the D stripes."""
    distinct = max(1, int(0.4 * n))
    idx = np.empty((D, n), np.int32)
    for j in range(D):
        slots = rng.integers(0, STRIPE, distinct)
        idx[j] = np.sort(rng.choice(slots, n)) + j * STRIPE
    vals = rng.normal(0, 1e-3, (D, n)).astype(np.float32)
    return sp.SparseGrad(jnp.asarray(idx.reshape(-1)),
                         jnp.asarray(vals.reshape(-1)), (M,),
                         unique=False, buckets=D)


def ms_per_call(fn, state, g, reps: int) -> tuple[float, tuple]:
    """Milliseconds per call of a donating ``fn(state, g) -> state``."""
    state = jax.block_until_ready(fn(state, g))          # compiles
    t0 = time.perf_counter()
    for _ in range(reps):
        state = fn(state, g)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / reps * 1e3, state


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="chiprun_out/pool_update.json")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU; found {dev.platform}")
    rng = np.random.default_rng(20261018)
    out = {"device_kind": dev.device_kind, "m": M, "d": D, "scatter": {},
           "paths": []}

    n_real = 106_496                      # dlrm-rm2's lookups per stripe
    g = stream(rng, n_real)
    i, v = g.indices[:n_real], g.values[:n_real]
    zeros = lambda: jnp.zeros((STRIPE,), jnp.float32)

    def folded_set(i, v):
        head, f = fold_duplicates(i, v)
        j = jnp.where(head, i, STRIPE + jnp.arange(n_real, dtype=i.dtype))
        return zeros().at[j].set(f, unique_indices=True, mode="drop")

    forms = {
        "add_sorted": lambda i, v: zeros().at[i].add(
            v, indices_are_sorted=True, mode="promise_in_bounds"),
        "add_unsorted": lambda i, v: zeros().at[i].add(
            v, mode="promise_in_bounds"),
        "segment_sum_sorted": lambda i, v: jax.ops.segment_sum(
            v, i, num_segments=STRIPE, indices_are_sorted=True),
        "fold_then_unique_set": folded_set,
    }
    for name, f in forms.items():
        f = jax.jit(f)
        jax.block_until_ready(f(i, v))
        t0 = time.perf_counter()
        for _ in range(args.reps * 5):
            r = f(i, v)
        jax.block_until_ready(r)
        ms = (time.perf_counter() - t0) / (args.reps * 5) * 1e3
        out["scatter"][name] = {"ms": ms, "entries": n_real,
                                "ns_per_entry": ms * 1e6 / n_real}
        print(f"one stripe, {name}: {ms:.3f} ms, "
              f"{ms * 1e6 / n_real:.2f} ns/entry", flush=True)

    def gather_scatter(state, g):
        acc, p = state
        u, (acc,) = sp._leaf_sparse_update("adagrad", g, (acc,), lr=LR,
                                           eps=EPS)
        return acc, sp.sparse_apply(p, u)

    def blocked(state, g):
        acc, p = state
        nv, acc = sp.stripe_blocked_adagrad(g, acc, p, lr=LR, eps=EPS)
        return acc, nv.value

    fns = {"gather_scatter": gather_scatter, "stripe_blocked": blocked}
    fns = {k: jax.jit(f, donate_argnums=(0,)) for k, f in fns.items()}
    state = (jnp.zeros((M,), jnp.float32), jnp.zeros((M,), jnp.float32))
    for n in (n_real, 26_624, 10_650, 5_325, 3_200, 2_110, 1):
        g = g if n == n_real else stream(rng, n)
        row = {"n_per_stripe": n, "k": n * D, "k_over_m": n * D / M}
        for k, f in fns.items():
            row[k + "_ms"], state = ms_per_call(f, state, g, args.reps)
        out["paths"].append(row)
        print(json.dumps(row), flush=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
