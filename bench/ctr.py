"""The benchmark's CTR traffic generator: a vectorised copy of the program's
``repro.data.synthetic_ctr.CTRGenerator``.

It lives with the benchmark so that no later change to the program's copy
moves the yardstick.  For the same spec and seed it yields the same batches,
bit for bit, as the program's generator (``tests/bench`` checks this): the
random draws are made in the same order from the same streams, only grouped
into array calls.

Ids follow Criteo's schema (its fields and vocabularies), not a measured
Criteo access statistic: every field's vocabulary is split into
``n_clusters`` clusters; a sample picks a latent intent, and with
probability ``p_signal`` each field's value comes from that cluster, drawn
with a geometric rank inside it, of success probability
min(8 / cluster size, 0.9): in a large cluster the mean rank is about an
eighth of the cluster, so a large field repeats few ids in a batch.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SIGNATURE_BATCH = 4096        # rows per generator call for D' rows
SIGNATURE_FIRST_BATCH = 10_000_000   # D' rows come from a disjoint stream


@dataclasses.dataclass(frozen=True)
class Spec:
    vocab_sizes: tuple[int, ...]
    n_dense: int
    seed: int
    n_clusters: int = 24
    p_signal: float = 0.8
    label_noise: float = 0.15
    value_dist: str = "geometric"

    @property
    def n_fields(self) -> int:
        return len(self.vocab_sizes)


class CTR:
    """Seeded, seekable batches: ``batch(size, index)``."""

    def __init__(self, spec: Spec):
        self.spec = spec
        rng = np.random.default_rng(spec.seed)
        K = spec.n_clusters
        self.value_cluster = []
        for v in spec.vocab_sizes:
            assign = (np.arange(v) % K).astype(np.uint8 if K < 256 else np.int32)
            rng.shuffle(assign)
            self.value_cluster.append(assign)
        self.w_fc = rng.normal(0, 1.0, (spec.n_fields, K))
        self.w_dense = rng.normal(0, 0.5, spec.n_dense)
        self.dense_mu = rng.normal(0, 1.0, (K, spec.n_dense))
        # per field: values sorted by cluster (ascending value inside one),
        # with each cluster's start and size; an empty cluster (a field with
        # fewer values than clusters) draws from the whole field
        self.by_cluster, self.c_start, self.c_len = [], [], []
        for f, v in enumerate(spec.vocab_sizes):
            a = self.value_cluster[f]
            self.by_cluster.append(np.argsort(a, kind="stable"))
            n = np.bincount(a, minlength=K)
            self.c_start.append(np.cumsum(n) - n)
            self.c_len.append(n)
        self.offsets = np.concatenate(
            [[0], np.cumsum(np.asarray(spec.vocab_sizes, np.int64))])

    def _values(self, rng, f: int, clusters: np.ndarray) -> np.ndarray:
        """Field ``f``'s values for samples in ``clusters``: the same draws,
        in the same order, as one call per cluster in ascending order."""
        spec = self.spec
        v = spec.vocab_sizes[f]
        n = self.c_len[f]
        pool_len = np.where(n > 0, n, v)
        order = np.argsort(clusters, kind="stable")
        cs = clusters[order]
        plen = pool_len[cs]
        if spec.value_dist == "uniform":
            ranks = np.empty(cs.size, np.int64)
            bounds = np.flatnonzero(np.diff(cs)) + 1
            for lo, hi in zip(np.r_[0, bounds], np.r_[bounds, cs.size]):
                ranks[lo:hi] = rng.integers(0, plen[lo], hi - lo)
        else:
            p = np.minimum(8.0 / np.maximum(plen, 1), 0.9)
            ranks = np.minimum(rng.geometric(p) - 1, plen - 1)
        vals = np.where(n[cs] > 0,
                        self.by_cluster[f][np.minimum(
                            self.c_start[f][cs] + ranks, v - 1)],
                        ranks)
        out = np.empty(clusters.size, np.int64)
        out[order] = vals
        return out

    def batch(self, batch_size: int, batch_idx: int) -> dict:
        """{dense [B, n_dense] f32, sparse [B, F] i32 (field-local), label [B]}."""
        spec = self.spec
        rng = np.random.default_rng((spec.seed, batch_idx, 0xC7))
        K = spec.n_clusters
        z = rng.integers(0, K, batch_size)
        sparse = np.empty((batch_size, spec.n_fields), np.int32)
        logits = np.zeros(batch_size)
        for f in range(spec.n_fields):
            signal = rng.random(batch_size) < spec.p_signal
            clusters = np.where(signal, z, rng.integers(0, K, batch_size))
            vals = self._values(rng, f, clusters)
            sparse[:, f] = vals
            logits += self.w_fc[f, self.value_cluster[f][vals]]
        dense = (self.dense_mu[z] + rng.normal(
            0, 1.0, (batch_size, spec.n_dense))).astype(np.float32)
        logits = logits / np.sqrt(spec.n_fields) + dense @ self.w_dense
        logits = (logits - logits.mean()) / max(logits.std(), 1e-6) * 2.0
        prob = 1.0 / (1.0 + np.exp(-logits))
        label = (rng.random(batch_size) < np.where(
            rng.random(batch_size) < spec.label_noise,
            0.5, prob)).astype(np.float32)
        return {"dense": dense, "sparse": sparse, "label": label}

    def signature_rows(self, n_rows: int) -> np.ndarray:
        """[n_rows, F] global value ids: the D' subsample (the rows the
        program's ``rows_for_signatures`` yields)."""
        out, done, bidx = [], 0, SIGNATURE_FIRST_BATCH
        while done < n_rows:
            b = self.batch(min(SIGNATURE_BATCH, n_rows - done), bidx)
            out.append(b["sparse"].astype(np.int64) + self.offsets[:-1][None, :])
            done += b["sparse"].shape[0]
            bidx += 1
        return np.concatenate(out)
