"""Online serving demo: request batching over an LMA-compressed DCN-v2.

Spins up the BatchingScorer (pad-bucketed dynamic batching), feeds it a
Poisson-ish trickle of single requests, and reports latency/batching stats —
the serve_p99 pattern of the assigned recsys shapes.

Run: PYTHONPATH=src python examples/serve_recsys.py
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.embedding import make_buffers
from repro.core.signatures import synthetic_dense_store
from repro.models import recsys
from repro.serve import BatchingScorer

cfg = get_config("dcn-v2").make_smoke()
store = synthetic_dense_store(cfg.embedding.total_vocab, 16,
                              max_set=cfg.embedding.lma.max_set)
bufs = make_buffers(cfg.embedding, store)
params = recsys.init(jax.random.key(0), cfg)
fwd = jax.jit(lambda p, b, bufs: recsys.forward(p, cfg, b, bufs))


def score_fn(batch):
    return np.asarray(fwd(params, {k: jnp.asarray(v) for k, v in batch.items()},
                          bufs))


def main():
    rng = np.random.default_rng(0)
    scorer = BatchingScorer(score_fn, max_batch=64, max_delay_ms=2.0)
    lat = []
    n = 400
    try:
        pending = []
        for i in range(n):
            feats = {
                "sparse": np.asarray(
                    [rng.integers(0, v) for v in cfg.embedding.vocab_sizes],
                    np.int32),
                "dense": rng.normal(0, 1, cfg.n_dense).astype(np.float32),
            }
            t0 = time.perf_counter()
            p = scorer.submit(feats)
            pending.append((t0, p))
            if rng.random() < 0.3:
                time.sleep(0.001)        # bursty arrivals
        for t0, p in pending:
            p.event.wait(30)
            lat.append((time.perf_counter() - t0) * 1e3)
    finally:
        scorer.close()
    lat = np.asarray(lat)
    bs = np.asarray(scorer.batch_sizes)
    print(f"served {scorer.n_requests} requests in {scorer.n_batches} device "
          f"calls (mean batch {bs.mean():.1f}, max {bs.max()})")
    print(f"latency ms: p50={np.percentile(lat,50):.1f} "
          f"p95={np.percentile(lat,95):.1f} p99={np.percentile(lat,99):.1f}")


if __name__ == "__main__":
    main()
