"""Data pipeline: generators (determinism, planted semantics), signature store,
metrics (exact AUC), neighbor sampler."""
from __future__ import annotations

import numpy as np
import pytest

try:  # optional dev dep: only the property-based tests need it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.minhash import jaccard_from_sets
from repro.core.signatures import (DenseSignatureStore, build_signature_store,
                                   densify_store, synthetic_dense_store,
                                   synthetic_signature_store)
from repro.data.graph import NeighborSampler, molecule_batch, pad_block, sbm_graph
from repro.data.lm_data import LMGenerator
from repro.data.metrics import StreamingEval, accuracy, logloss, roc_auc
from repro.data.synthetic_ctr import CTRGenerator, CTRSpec, DINGenerator, DINSpec


# ------------------------------------------------------------------ CTR data

def test_ctr_batches_deterministic_and_seekable():
    gen = CTRGenerator(CTRSpec(n_fields=6, n_dense=3, seed=1))
    a = gen.batch(64, 5)
    b = gen.batch(64, 5)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    c = gen.batch(64, 6)
    assert (a["sparse"] != c["sparse"]).any()


def test_ctr_schema_and_ranges():
    spec = CTRSpec(n_fields=6, n_dense=3, seed=2)
    gen = CTRGenerator(spec)
    b = gen.batch(128, 0)
    assert b["dense"].shape == (128, 3) and b["dense"].dtype == np.float32
    assert b["sparse"].shape == (128, 6) and b["sparse"].dtype == np.int32
    for f, v in enumerate(spec.vocab_sizes):
        assert b["sparse"][:, f].min() >= 0
        assert b["sparse"][:, f].max() < v
    rate = b["label"].mean()
    assert 0.1 < rate < 0.9


def test_ctr_planted_jaccard_structure():
    """Cross-field same-cluster values co-occur -> higher Jaccard.

    With single-valued fields, two values of the SAME field never share a
    sample (disjoint D_v) — the paper's common-memory sharing materializes
    across fields: a sample of intent z picks cluster-z values in every field,
    so field-0/cluster-c values co-occur with field-1/cluster-c values.
    """
    spec = CTRSpec(n_fields=4, n_dense=2, n_clusters=4, p_signal=0.9, seed=3)
    gen = CTRGenerator(spec)
    store = build_signature_store(gen.rows_for_signatures(4000),
                                  spec.total_vocab, max_per_value=256)
    flat = np.asarray(store.flat)
    offs = np.asarray(store.offsets)
    lens = np.asarray(store.lengths)

    def value_set(gid):
        return set(flat[offs[gid]: offs[gid + 1]].tolist())

    v0, v1 = spec.vocab_sizes[0], spec.vocab_sizes[1]
    # most frequent value of each field
    top_f0 = int(np.argmax(lens[:v0]))
    top_f1_local = int(np.argmax(lens[v0: v0 + v1]))
    c0 = gen.value_cluster[0][top_f0]
    same, diff = [], []
    # compare field-0 top value against frequent field-1 values by cluster
    freq_f1 = np.argsort(-lens[v0: v0 + v1])[:40]
    for w in freq_f1:
        j = jaccard_from_sets(value_set(top_f0), value_set(v0 + int(w)))
        (same if gen.value_cluster[1][int(w)] == c0 else diff).append(j)
    assert same and diff
    # head values appear in 1000s of rows but D_v is capped at 256 sample ids,
    # so absolute Jaccard is diluted — the planted structure shows as a strong
    # RATIO between same- and cross-cluster pairs
    assert np.mean(same) > 3.0 * max(np.mean(diff), 1e-4), (
        np.mean(same), np.mean(diff))
    assert np.mean(same) > 0.004
    # same-field values are sample-disjoint by construction
    second_f0 = int(np.argsort(-lens[:v0])[1])
    assert jaccard_from_sets(value_set(top_f0), value_set(second_f0)) == 0.0


def test_din_batches():
    gen = DINGenerator(DINSpec(n_items=500, n_clusters=10, hist_len=20, seed=0))
    b = gen.batch(64, 0)
    assert b["hist"].shape == (64, 20)
    assert b["hist_mask"].dtype == bool
    assert set(np.unique(b["label"])) <= {0.0, 1.0}
    # labels carry signal: same-cluster candidates mostly positive
    assert 0.2 < b["label"].mean() < 0.8


# ------------------------------------------------------------ signature store

def test_build_signature_store_counts():
    rows = [np.array([0, 1]), np.array([1, 2]), np.array([0, 1, 2])]
    store = build_signature_store(rows, n_values=4)
    np.testing.assert_array_equal(np.asarray(store.lengths), [2, 3, 2, 0])
    flat = np.asarray(store.flat)
    offs = np.asarray(store.offsets)
    assert set(flat[offs[1]: offs[2]].tolist()) == {0, 1, 2}  # value 1's rows


def test_build_store_respects_n_samples_and_cap():
    rows = [np.array([0])] * 100
    store = build_signature_store(rows, n_values=1, max_per_value=8,
                                  n_samples=50)
    assert int(store.lengths[0]) == 8   # capped
    store2 = build_signature_store(rows, n_values=1, max_per_value=128,
                                   n_samples=50)
    assert int(store2.lengths[0]) == 50  # n_samples honored


def test_densify_matches_csr():
    store = synthetic_signature_store(n_values=50, n_clusters=5,
                                      samples_per_value=16, seed=0)
    dense = densify_store(store, max_set=16)
    flat, offs = np.asarray(store.flat), np.asarray(store.offsets)
    sets_np = np.asarray(dense.sets)
    for v in range(50):
        want = flat[offs[v]: offs[v] + 16]
        np.testing.assert_array_equal(sets_np[v, : len(want)], want)


def _loop_build(rows, n_values, max_per_value, n_samples):
    """The per-value loop build_signature_store replaced (the reference)."""
    buckets = [[] for _ in range(n_values)]
    for sample_id, row in enumerate(rows):
        if n_samples is not None and sample_id >= n_samples:
            break
        for v in np.asarray(row).ravel():
            b = buckets[int(v)]
            if len(b) < max_per_value:
                b.append(sample_id)
    lengths = np.array([len(b) for b in buckets], dtype=np.int32)
    offsets = np.zeros(n_values + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    flat = np.empty(int(offsets[-1]), dtype=np.uint32)
    for v, b in enumerate(buckets):
        flat[offsets[v]: offsets[v + 1]] = b
    return flat, offsets, lengths


def _loop_densify(flat, offsets, lengths, max_set, n_rows):
    """The per-value loop densify_store replaced (the reference)."""
    n = lengths.shape[0]
    rows = max(n_rows or n, n)
    sets = np.full((rows, max_set), DenseSignatureStore.PAD, np.uint32)
    for v in range(n):
        k = min(int(lengths[v]), max_set)
        sets[v, :k] = flat[offsets[v]: offsets[v] + k]
    out_len = np.zeros(rows, np.int32)
    out_len[:n] = np.minimum(lengths, max_set)
    return sets, out_len


@pytest.mark.parametrize("seed", range(4))
def test_vectorized_store_build_matches_loop(seed):
    """build_signature_store / densify_store are byte-identical to the
    per-value loops, on ragged multi-hot rows with repeats, empty values,
    head caps, an n_samples cut and row padding."""
    rng = np.random.default_rng(seed)
    n_values = int(rng.integers(1, 60))
    rows = [rng.integers(0, n_values, int(rng.integers(0, 9)))
            for _ in range(int(rng.integers(0, 80)))]
    cap, max_set = int(rng.integers(1, 12)), int(rng.integers(1, 10))
    n_samples = None if seed % 2 else int(rng.integers(0, 60))
    n_rows = n_values + int(rng.integers(0, 5))
    store = build_signature_store(iter(rows), n_values, max_per_value=cap,
                                  n_samples=n_samples)
    want = _loop_build(rows, n_values, cap, n_samples)
    for got, ref in zip((store.flat, store.offsets, store.lengths), want):
        got = np.asarray(got)
        assert got.dtype == ref.dtype and got.tobytes() == ref.tobytes()
    dense = densify_store(store, max_set, n_rows=n_rows)
    for got, ref in zip((dense.sets, dense.lengths),
                        _loop_densify(*want, max_set, n_rows)):
        got = np.asarray(got)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()


def test_densify_row_padding():
    store = synthetic_signature_store(n_values=10, n_clusters=2,
                                      samples_per_value=4, seed=1)
    dense = densify_store(store, max_set=8, n_rows=16)
    assert dense.sets.shape == (16, 8)
    assert int(dense.lengths[12]) == 0  # padded rows are empty


def test_synthetic_dense_store_cluster_structure():
    d = synthetic_dense_store(n_values=40, n_clusters=4, max_set=16, seed=0)
    sets_np = np.asarray(d.sets)
    same = jaccard_from_sets(set(sets_np[0]), set(sets_np[4]))    # cluster 0
    diff = jaccard_from_sets(set(sets_np[0]), set(sets_np[1]))    # 0 vs 1
    assert same > 0.3 > diff == 0.0


# ---------------------------------------------------------------------- LM

def test_lm_generator_learnable_bigrams():
    gen = LMGenerator(vocab_size=256, seed=0)
    b = gen.batch(16, 32, 0)
    assert b["tokens"].shape == (16, 32)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    # patterned successors appear: P(label == successor(token)) well above 1/V
    toks, labs = b["tokens"].ravel(), b["labels"].ravel()
    hit = (labs == gen.successor[toks]).mean()
    assert hit > 0.3


# ------------------------------------------------------------------- metrics

def _auc_brute(y, s):
    pos = s[y == 1]
    neg = s[y == 0]
    if len(pos) == 0 or len(neg) == 0:
        return 0.5
    cmp = (pos[:, None] > neg[None, :]).sum() + 0.5 * (
        pos[:, None] == neg[None, :]).sum()
    return cmp / (len(pos) * len(neg))


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(st.lists(st.tuples(st.booleans(),
                              st.integers(0, 20)), min_size=2, max_size=60))
    def test_property_auc_matches_brute_force(pairs):
        y = np.asarray([int(a) for a, _ in pairs], np.float64)
        s = np.asarray([b for _, b in pairs], np.float64) / 7.0  # force ties
        assert roc_auc(y, s) == pytest.approx(_auc_brute(y, s), abs=1e-9)
else:
    def test_property_auc_matches_brute_force():
        pytest.importorskip("hypothesis")


def test_auc_perfect_and_inverted():
    y = np.asarray([0, 0, 1, 1])
    assert roc_auc(y, np.asarray([0.1, 0.2, 0.8, 0.9])) == 1.0
    assert roc_auc(y, np.asarray([0.9, 0.8, 0.2, 0.1])) == 0.0
    assert roc_auc(y, np.asarray([0.5, 0.5, 0.5, 0.5])) == 0.5


def test_streaming_eval():
    ev = StreamingEval()
    rng = np.random.default_rng(0)
    all_y, all_s = [], []
    for _ in range(5):
        y = (rng.random(100) < 0.4).astype(np.float64)
        s = y * 1.5 + rng.normal(0, 1, 100)
        ev.add(y, s)
        all_y.append(y)
        all_s.append(s)
    out = ev.compute()
    want = roc_auc(np.concatenate(all_y), np.concatenate(all_s))
    assert out["auc"] == pytest.approx(want)
    assert out["n"] == 500
    assert 0 < out["logloss"] < 2


# ------------------------------------------------------------------- graphs

def test_sbm_graph_homophily():
    g = sbm_graph(n_nodes=400, n_edges=2000, d_feat=16, n_classes=4, seed=0,
                  homophily=0.9)
    same = (g.labels[g.src] == g.labels[g.dst]).mean()
    assert same > 0.6  # way above the 1/4 chance rate


def test_neighbor_sampler_block_validity():
    g = sbm_graph(n_nodes=300, n_edges=1500, d_feat=8, n_classes=3, seed=1)
    sampler = NeighborSampler(g, fanouts=(4, 3), seed=0)
    batch_nodes = np.arange(10)
    block = sampler.sample(batch_nodes)
    n = block["n_nodes"]
    assert block["src"].max() < n and block["dst"].max() < n
    assert block["features"].shape == (n, 8)
    # every batch node is present and labeled
    assert block["label_mask"].sum() == len(batch_nodes)
    # fanout respected: each hop adds at most fan * frontier edges
    assert len(block["src"]) <= 10 * 4 + 10 * 4 * 3 + n  # + self loops


def test_pad_block_shapes_stable():
    g = sbm_graph(n_nodes=200, n_edges=900, d_feat=8, n_classes=3, seed=2)
    sampler = NeighborSampler(g, fanouts=(3,), seed=0)
    shapes = set()
    for i in range(3):
        block = sampler.sample(np.arange(i * 5, i * 5 + 5))
        padded = pad_block(block, max_nodes=64, max_edges=128)
        shapes.add((padded["src"].shape, padded["features"].shape))
    assert len(shapes) == 1  # stable jit signature


def test_molecule_batch_block_diagonal():
    mb = molecule_batch(batch_size=4, n_nodes=6, n_edges=10, d_feat=8,
                        n_classes=3, seed=0)
    # edges never cross graph boundaries
    gid_src = mb["graph_ids"][mb["src"]]
    gid_dst = mb["graph_ids"][mb["dst"]]
    np.testing.assert_array_equal(gid_src, gid_dst)
    assert mb["labels"].shape == (4,)
