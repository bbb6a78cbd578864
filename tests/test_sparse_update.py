"""Sparse-gradient update engine (repro/optim/sparse.py + kernels/sparse_update).

Covers the PR-4 contract:
  * sparse-vs-dense training parity to 1e-6 after 10 steps for every
    registered scheme (+ freq), single-device here and 2x4-sharded in the
    subprocess test;
  * duplicate-location dedup correctness (sort + segment-sum);
  * untouched-slot moment invariance for sparse_adagrad (bit-equal);
  * the shared adagrad / sparse_adagrad ``initial_acc``/``eps`` contract;
  * Pallas kernel (interpret) vs jnp reference parity for all three algos,
    in both slab layouts (flat [m] and row-mode [rows, d] incl. rowwise nu);
  * power-of-two batch bucketing keeps the fused engine at one compilation
    across batch-size jitter;
  * the check_regression sparse-update gate logic.

And the bucketed-layout contract that replaced the flat dedup sort: the
per-stripe ``from_bucketed_locations`` construction against the
``from_locations`` parity oracle, the in-kernel duplicate fold
(``fold_duplicates`` + ``unique=False`` through ref and Pallas), the K=1 /
all-duplicate / sentinel-only / ragged-budget edge cases, and the striped
LMA config actually taking the bucketed path end-to-end (the 10-step
parity sweep above runs lma on the striped layout already — its
``build_config`` auto-stripes whenever budget % dim == 0).
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.signatures import synthetic_dense_store
from repro.embed import EmbeddingTable, get_scheme, list_schemes
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sp

ALL_KINDS = sorted(set(list_schemes()))   # six built-ins + freq


# ------------------------------------------------------------------- dedup

def test_dedup_duplicate_locations():
    m = 64
    loc = jnp.asarray([3, 9, 3, 3, 60, 9], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 10.0, 100.0, 5.0, 7.0], jnp.float32)
    sg = sp.from_locations(loc, vals, (m,))
    dense = np.zeros(m, np.float32)
    np.add.at(dense, np.asarray(loc), np.asarray(vals))
    np.testing.assert_allclose(np.asarray(sg.densify()), dense, rtol=1e-7)
    idx = np.asarray(sg.indices)
    live = idx[idx < m]
    assert list(live) == [3, 9, 60]                   # sorted unique, compact
    assert (idx[len(live):] == m).all()               # sentinel-padded tail
    assert np.asarray(sg.values)[len(live):].sum() == 0.0


def test_dedup_row_mode_trailing_dims():
    rows = jnp.asarray([5, 1, 5], jnp.int32)
    vals = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    sg = sp.from_locations(rows, vals, (8, 4))
    dense = np.zeros((8, 4), np.float32)
    np.add.at(dense, np.asarray(rows), np.asarray(vals))
    np.testing.assert_allclose(np.asarray(sg.densify()), dense, rtol=1e-7)
    assert sg.values.shape == (3, 4)


def test_dedup_under_jit():
    f = jax.jit(lambda l, v: sp.from_locations(l, v, (32,)).densify())
    loc = jnp.asarray([0, 0, 31], jnp.int32)
    out = f(loc, jnp.asarray([1.0, 2.0, 4.0]))
    assert float(out[0]) == 3.0 and float(out[31]) == 4.0


# -------------------------------------------- bucketed layout (striped LMA)

def _striped_loc(rng, n: int, d: int, stripe: int) -> jnp.ndarray:
    return jnp.asarray(np.arange(d)[None, :] * stripe
                       + rng.integers(0, stripe, (n, d)), jnp.int32)


def test_bucketed_locations_matches_flat_oracle():
    """from_bucketed_locations: d per-stripe sorts, no dedup, no sentinels —
    same dense gradient as the from_locations oracle, with the layout the
    unique=False contract promises (sorted non-decreasing, duplicates
    kept, every entry live)."""
    m, d, n = 4096, 8, 128
    rng = np.random.default_rng(5)
    loc = _striped_loc(rng, n, d, m // d)
    vals = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    gb = sp.from_bucketed_locations(loc, vals, (m,))
    assert not gb.unique
    assert gb.indices.shape == (n * d,)               # duplicates kept
    idx = np.asarray(gb.indices)
    assert (np.diff(idx) >= 0).all() and idx.max() < m
    np.testing.assert_allclose(
        np.asarray(gb.densify()),
        np.asarray(sp.from_locations(loc, vals, (m,)).densify()),
        atol=1e-6, rtol=1e-6)


def test_bucketed_edge_cases_k1_and_all_duplicate():
    m, d = 256, 4
    # K = 1 row: position bits degenerate to zero width
    loc1 = _striped_loc(np.random.default_rng(0), 1, d, m // d)
    v1 = jnp.ones((1, d), jnp.float32)
    g1 = sp.from_bucketed_locations(loc1, v1, (m,))
    np.testing.assert_allclose(
        np.asarray(g1.densify()),
        np.asarray(sp.from_locations(loc1, v1, (m,)).densify()), atol=1e-6)
    # every row hits the SAME slot in every stripe: one maximal duplicate
    # run per bucket, the worst case for the in-kernel fold
    rng = np.random.default_rng(1)
    loc = jnp.tile(_striped_loc(rng, 1, d, m // d), (64, 1))
    vals = jnp.asarray(rng.normal(size=(64, d)).astype(np.float32))
    gb = sp.from_bucketed_locations(loc, vals, (m,))
    np.testing.assert_allclose(
        np.asarray(gb.densify()),
        np.asarray(sp.from_locations(loc, vals, (m,)).densify()),
        atol=1e-6, rtol=1e-6)
    # ... and through the unique=False adagrad update (ref backend)
    from repro.kernels.sparse_update import ops as su
    acc = jnp.full((m,), 0.1, jnp.float32)
    u, (acc1,) = su.sparse_update("adagrad", gb.indices, gb.values, (acc,),
                                  unique=False, lr=0.05)
    gsum = np.asarray(gb.densify())
    np.testing.assert_allclose(np.asarray(acc1), 0.1 + gsum ** 2,
                               atol=1e-6, rtol=1e-6)
    applied = np.zeros(m, np.float32)
    np.add.at(applied, np.asarray(gb.indices), np.asarray(u))
    expect = np.where(gsum != 0, -0.05 * gsum / np.sqrt(0.1 + gsum ** 2), 0)
    np.testing.assert_allclose(applied, expect, atol=1e-6, rtol=1e-6)


def test_sentinel_only_sparse_grad_is_a_no_op():
    """An empty SparseGrad (all-sentinel unique layout — e.g. a batch that
    touched nothing after masking) must leave moments bit-identical and
    emit all-zero updates; the unique=False layout has no sentinels, so its
    degenerate form is the zero-value stream."""
    from repro.kernels.sparse_update import ops as su
    m = 64
    acc = jnp.asarray(np.random.default_rng(2).uniform(0.5, 2, m)
                      .astype(np.float32))
    idx = jnp.full((8,), m, jnp.int32)
    u, (acc1,) = su.sparse_update("adagrad", idx, jnp.zeros(8), (acc,),
                                  unique=True, lr=0.1)
    assert np.asarray(u).sum() == 0.0
    np.testing.assert_array_equal(np.asarray(acc1), np.asarray(acc))
    g = sp.SparseGrad(idx, jnp.zeros(8), (m,))
    assert np.asarray(g.densify()).sum() == 0.0


def test_fold_duplicates_matches_oracle():
    from repro.kernels.sparse_update import ref as r
    rng = np.random.default_rng(3)
    for ii in (np.sort(rng.integers(0, 16, 64)), np.full(64, 7),
               np.array([3]), np.arange(16)):
        vv = rng.normal(size=ii.shape).astype(np.float32)
        head, s = r.fold_duplicates(jnp.asarray(ii, jnp.int32),
                                    jnp.asarray(vv))
        dense_o = np.zeros(16, np.float64)
        np.add.at(dense_o, ii, vv.astype(np.float64))
        dense_f = np.zeros(16, np.float64)
        hm = np.asarray(head)
        np.add.at(dense_f, ii[hm], np.asarray(s)[hm].astype(np.float64))
        np.testing.assert_allclose(dense_f, dense_o, atol=1e-6)
        if (~hm).any():                     # non-heads carry exact zeros
            assert np.abs(np.asarray(s)[~hm]).max() == 0.0


@pytest.mark.parametrize("algo", ["sgd", "adagrad", "adam"])
def test_pallas_kernel_matches_ref_unique_false(algo):
    """Pallas (interpret) vs jnp reference on the duplicate stream — the
    in-kernel fold path — checked against the unique=True result on the
    pre-deduped twin of the same gradient."""
    from repro.kernels.sparse_update import ops as su
    m = 512
    rng = np.random.default_rng(4)
    idx = jnp.asarray(np.sort(rng.integers(0, m, 96)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=96).astype(np.float32))
    uni = sp.from_locations(idx[:, None], vals[:, None], (m,))
    states = {"sgd": (jnp.zeros(m),),
              "adagrad": (jnp.full((m,), 0.2, jnp.float32),),
              "adam": (jnp.zeros(m), jnp.zeros(m))}[algo]
    hyper = {"sgd": dict(lr=0.1, momentum=0.9),
             "adagrad": dict(lr=0.1, eps=1e-8),
             "adam": dict(lr=1e-3, b1=0.9, b2=0.999, bc1=0.9, bc2=0.99,
                          eps=1e-8)}[algo]
    u_k, s_k = su.sparse_update(algo, idx, vals, states, unique=False,
                                interpret=True, **hyper)
    u_r, s_r = su.sparse_update(algo, idx, vals, states, unique=False,
                                **hyper)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_r),
                               atol=1e-6, rtol=1e-6)
    for a, b in zip(s_k, s_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)
    # applied result == the unique=True path on the deduped twin
    u_u, s_u = su.sparse_update(algo, uni.indices, uni.values, states,
                                unique=True, **hyper)
    keep = np.asarray(uni.indices) < m
    a_dup = np.zeros(m, np.float32)
    np.add.at(a_dup, np.asarray(idx), np.asarray(u_r))
    a_uni = np.zeros(m, np.float32)
    np.add.at(a_uni, np.asarray(uni.indices)[keep], np.asarray(u_u)[keep])
    np.testing.assert_allclose(a_dup, a_uni, atol=1e-6, rtol=1e-6)
    for a, b in zip(s_r, s_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


def test_lma_striped_grad_takes_bucketed_path():
    """The end-to-end wiring: a striped lma config records bucketed
    locations and the engine emits the unique=False duplicate stream; a
    ragged budget (m % d != 0) keeps striping inert and falls back to the
    flat sorted-unique layout — bit-compatible, just slower."""
    table, bufs, params = _make_setup("lma")
    assert table.config.lma.striped and table.scheme.sparse_buckets(
        table.config) == table.config.dim

    def loss_fn(p, b):
        e = table.embed_fields(p["embedding"], bufs, b["ids"])
        return jnp.mean(e ** 2), {}

    (_, _m), g = sp.sparse_value_and_grad(loss_fn)(params, _batch(0))
    sg = g["embedding"]["memory"]
    assert isinstance(sg, sp.SparseGrad) and not sg.unique
    idx = np.asarray(sg.indices)
    assert (np.diff(idx) >= 0).all() and idx.max() < 4096

    scheme = get_scheme("lma")
    ragged = EmbeddingTable(scheme.build_config((512,), 8, 4094, seed=3))
    assert not ragged.config.lma.striped
    assert scheme.sparse_buckets(ragged.config) == 0
    store = synthetic_dense_store(512, 8, max_set=32, seed=2)
    rbufs = ragged.make_buffers(store)
    rparams = {"embedding": ragged.init(jax.random.key(1))}

    def loss_r(p, ids):
        return jnp.mean(ragged.embed(p["embedding"], rbufs, 0, ids) ** 2), {}

    (_, _m), gr = sp.sparse_value_and_grad(loss_r)(
        rparams, jnp.arange(16, dtype=jnp.int32))
    sgr = gr["embedding"]["memory"]
    assert isinstance(sgr, sp.SparseGrad) and sgr.unique


# ------------------------------------------------- optimizer leaf semantics

def test_untouched_slot_moments_bit_invariant():
    m = 256
    rng = np.random.default_rng(0)
    acc0 = jnp.asarray(rng.uniform(0.5, 2.0, m).astype(np.float32))
    touched = np.asarray([7, 8, 100])
    sg = sp.from_locations(jnp.asarray(touched, jnp.int32),
                           jnp.asarray([1.0, -2.0, 3.0]), (m,))
    opt = sp.sparse_adagrad(0.1)
    upd, acc1 = opt.update({"memory": sg}, {"memory": acc0})
    acc1 = np.asarray(acc1["memory"])
    untouched = np.setdiff1d(np.arange(m), touched)
    # bit-equal, not just close: untouched slots never see a write
    assert (acc1[untouched] == np.asarray(acc0)[untouched]).all()
    np.testing.assert_allclose(acc1[touched],
                               np.asarray(acc0)[touched] + [1.0, 4.0, 9.0],
                               rtol=1e-6)
    u = upd["memory"]
    assert isinstance(u, sp.SparseGrad)
    assert float(jnp.sum(jnp.abs(u.densify()[untouched]))) == 0.0


@pytest.mark.parametrize("initial_acc,eps", [(0.0, 1e-10), (0.1, 1e-6)])
def test_adagrad_initial_acc_contract_shared(initial_acc, eps):
    """adagrad and sparse_adagrad must honor the same initial_acc/eps
    contract — same init state, same first-step update values."""
    m = 32
    rng = np.random.default_rng(1)
    params = {"memory": jnp.asarray(rng.normal(size=m).astype(np.float32))}
    g = jnp.asarray(rng.normal(size=m).astype(np.float32))
    gs = sp.from_locations(jnp.arange(m, dtype=jnp.int32), g, (m,))

    dense = opt_lib.adagrad(0.3, eps=eps, initial_acc=initial_acc)
    sparse = sp.sparse_adagrad(0.3, eps=eps, initial_acc=initial_acc)
    sd, ss = dense.init(params), sparse.init(params)
    np.testing.assert_array_equal(np.asarray(sd["memory"]),
                                  np.asarray(ss["memory"]))
    ud, sd = dense.update({"memory": g}, sd, params)
    us, ss = sparse.update({"memory": gs}, ss, params)
    np.testing.assert_allclose(np.asarray(us["memory"].densify()),
                               np.asarray(ud["memory"]), atol=1e-7)
    np.testing.assert_allclose(np.asarray(ss["memory"]),
                               np.asarray(sd["memory"]), atol=1e-7)


def test_sparse_rowwise_adam_matches_lazy_reference():
    """10 steps of sparse_rowwise_adam == a numpy lazy-Adam oracle."""
    m, lr, b1, b2, eps = 16, 0.1, 0.9, 0.999, 1e-8
    rng = np.random.default_rng(2)
    p = {"w": jnp.asarray(rng.normal(size=m).astype(np.float32))}
    opt = sp.sparse_rowwise_adam(lr, b1=b1, b2=b2, eps=eps)
    state = opt.init(p)

    p_ref = np.asarray(p["w"]).copy()
    mu_ref = np.zeros(m, np.float32)
    nu_ref = np.zeros(m, np.float32)
    for t in range(1, 11):
        touched = rng.choice(m, 5, replace=False).astype(np.int32)
        vals = rng.normal(size=5).astype(np.float32)
        sg = sp.from_locations(jnp.asarray(touched), jnp.asarray(vals), (m,))
        upd, state = opt.update({"w": sg}, state, p)
        p = opt_lib.apply_updates(p, upd)
        # lazy oracle: only touched slots decay/update; global-step bias corr
        mu_ref[touched] = b1 * mu_ref[touched] + (1 - b1) * vals
        nu_ref[touched] = b2 * nu_ref[touched] + (1 - b2) * vals ** 2
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p_ref[touched] += -lr * (mu_ref[touched] / bc1) / (
            np.sqrt(nu_ref[touched] / bc2) + eps)
    np.testing.assert_allclose(np.asarray(p["w"]), p_ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state.mu["w"]), mu_ref, atol=1e-5)


def test_adamw_sparse_leaf_keeps_weight_decay():
    """Full-coverage sparse grads through adamw == dense adamw exactly
    (lazy == dense when every slot is touched, including decoupled decay)."""
    m = 24
    rng = np.random.default_rng(4)
    params = {"memory": jnp.asarray(rng.normal(size=m).astype(np.float32))}
    g = jnp.asarray(rng.normal(size=m).astype(np.float32))
    gs = sp.from_locations(jnp.arange(m, dtype=jnp.int32), g, (m,))
    opt = opt_lib.adamw(0.1, weight_decay=0.05)
    sd, ss = opt.init(params), opt.init(params)
    for _ in range(3):
        ud, sd = opt.update({"memory": g}, sd, params)
        us, ss = opt.update({"memory": gs}, ss, params)
        np.testing.assert_allclose(np.asarray(us["memory"].densify()),
                                   np.asarray(ud["memory"]), atol=1e-6)


def test_sgd_momentum_sparse_leaf_lazy():
    m = 8
    p = {"w": jnp.zeros(m, jnp.float32)}
    opt = opt_lib.sgd(1.0, momentum=0.5)
    state = opt.init(p)
    sg = sp.from_locations(jnp.asarray([2], jnp.int32),
                           jnp.asarray([1.0]), (m,))
    for _ in range(2):
        upd, state = opt.update({"w": sg}, state, p)
        p = opt_lib.apply_updates(p, upd)
    # lazy momentum on slot 2: u1 = -1.0, u2 = -(0.5*1+1) = -1.5
    np.testing.assert_allclose(float(p["w"][2]), -2.5, atol=1e-6)
    assert float(jnp.sum(jnp.abs(p["w"]))) == pytest.approx(2.5, abs=1e-6)


# ------------------------------------------------ kernel-vs-reference parity

@pytest.mark.parametrize("algo", ["sgd", "adagrad", "adam"])
def test_pallas_kernel_matches_ref(algo):
    from repro.kernels.sparse_update import ops as su
    m, k = 512, 64
    rng = np.random.default_rng(3)
    live = np.sort(rng.choice(m, 40, replace=False)).astype(np.int32)
    idx = jnp.asarray(np.concatenate([live, np.full(k - 40, m, np.int32)]))
    vals = jnp.asarray(rng.normal(size=k).astype(np.float32)).at[40:].set(0.0)
    if algo == "sgd":
        states = (jnp.asarray(rng.normal(size=m).astype(np.float32)),)
        hyper = dict(lr=0.1, momentum=0.9)
    elif algo == "adagrad":
        states = (jnp.asarray(rng.uniform(0.1, 1, m).astype(np.float32)),)
        hyper = dict(lr=0.1, eps=1e-8)
    else:
        states = (jnp.asarray(rng.normal(size=m).astype(np.float32)),
                  jnp.asarray(rng.uniform(0, 1, m).astype(np.float32)))
        hyper = dict(lr=0.1, b1=0.9, b2=0.99, bc1=0.5, bc2=0.2, eps=1e-8)
    u_r, st_r = su.sparse_update(algo, idx, vals, states, **hyper)
    u_p, st_p = su.sparse_update(algo, idx, vals, states, interpret=True,
                                 **hyper)
    np.testing.assert_allclose(np.asarray(u_p), np.asarray(u_r), atol=1e-6)
    for a, b in zip(st_p, st_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("algo,rowwise_nu", [("sgd", False),
                                             ("adagrad", False),
                                             ("adam", False),
                                             ("adam", True)])
def test_pallas_kernel_matches_ref_row_mode(algo, rowwise_nu):
    """[rows, d] slab layout (row-mode SparseGrad: hashed_row / freq) through
    the Pallas kernels, incl. rowwise-Adam's 1-D second moment — row schemes
    on TPU no longer round-trip through the flat [m] reshape.  Untouched
    rows must stay bit-identical (add-of-delta scatters)."""
    from repro.kernels.sparse_update import ops as su
    rows, d, k = 128, 8, 32
    rng = np.random.default_rng(5)
    live = np.sort(rng.choice(rows, 20, replace=False)).astype(np.int32)
    idx = jnp.asarray(np.concatenate([live, np.full(k - 20, rows, np.int32)]))
    vals = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    vals = vals.at[20:].set(0.0)
    if algo == "sgd":
        states = (jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32)),)
        hyper = dict(lr=0.1, momentum=0.9)
    elif algo == "adagrad":
        states = (jnp.asarray(rng.uniform(0.1, 1, (rows, d))
                              .astype(np.float32)),)
        hyper = dict(lr=0.1, eps=1e-8)
    else:
        nu_shape = (rows,) if rowwise_nu else (rows, d)
        states = (jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32)),
                  jnp.asarray(rng.uniform(0, 1, nu_shape).astype(np.float32)))
        hyper = dict(lr=0.1, b1=0.9, b2=0.99, bc1=0.5, bc2=0.2, eps=1e-8)
    u_r, st_r = su.sparse_update(algo, idx, vals, states, **hyper)
    u_p, st_p = su.sparse_update(algo, idx, vals, states, interpret=True,
                                 **hyper)
    np.testing.assert_allclose(np.asarray(u_p), np.asarray(u_r), atol=1e-6)
    untouched = np.setdiff1d(np.arange(rows), live)
    for a, b, s0 in zip(st_p, st_r, states):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(a)[untouched],
                                      np.asarray(s0)[untouched])


def test_pallas_dispatch_accepts_row_layout():
    """The TPU auto-dispatch gate admits [rows, d] working sets, rejects
    >2-D shapes, and only allows a rank-dropped state for Adam's rowwise
    nu — a 1-D sgd/adagrad state against 2-D values routes to the jnp
    reference instead of crashing in the kernel."""
    from repro.kernels.sparse_update.ops import _pallas_ok, _shapes_ok
    idx = jnp.zeros((8,), jnp.int32)
    v2 = jnp.zeros((8, 4), jnp.float32)
    assert _shapes_ok("adagrad", v2, (jnp.zeros((16, 4)),))
    assert _shapes_ok("adam", v2, (jnp.zeros((16, 4)), jnp.zeros((16,))))
    assert not _shapes_ok("sgd", v2, (jnp.zeros((16,)),))
    assert not _shapes_ok("adagrad", v2, (jnp.zeros((16,)),))
    assert not _shapes_ok("adam", v2, (jnp.zeros((16,)), jnp.zeros((16,))))
    assert not _shapes_ok("adagrad", jnp.zeros((8, 4, 2)),
                          (jnp.zeros((16, 4, 2)),))
    assert _pallas_ok("adagrad", idx, v2, (jnp.zeros((16, 4)),))


# ------------------------------------------------- training parity (oracle)

def _make_setup(kind: str):
    scheme = get_scheme(kind)
    table = EmbeddingTable(scheme.build_config((512, 256), 8, 4096, seed=3))
    store = synthetic_dense_store(table.config.total_vocab, 8, max_set=32,
                                  seed=2) if scheme.needs_signature_store \
        else None
    bufs = table.make_buffers(store)
    params = {"embedding": table.init(jax.random.key(1)),
              "w": jnp.full((8,), 0.1, jnp.float32)}
    return table, bufs, params


def _batch(step: int):
    r = np.random.default_rng(step)
    ids = r.integers(0, 512, (48, 2)).astype(np.int32) % np.array([512, 256])
    return {"ids": jnp.asarray(ids),
            "y": jnp.asarray(r.normal(size=(48,)).astype(np.float32))}


def _train(table, bufs, params, sparse: bool, steps: int = 10):
    def loss_fn(p, b):
        e = table.embed_fields(p["embedding"], bufs, b["ids"])
        pred = jnp.einsum("bfd,d->b", e, p["w"])
        loss = jnp.mean((pred - b["y"]) ** 2)
        return loss, {"loss": loss}

    opt = opt_lib.adagrad(0.1, eps=1e-8)
    state = opt.init(params)
    vg = sp.sparse_value_and_grad(loss_fn) if sparse else \
        jax.value_and_grad(loss_fn, has_aux=True)

    @jax.jit
    def step(params, state, b):
        (_, _m), g = vg(params, b)
        u, state = opt.update(g, state, params)
        return opt_lib.apply_updates(params, u), state

    for s in range(steps):
        params, state = step(params, state, _batch(s))
    return params, state


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_sparse_vs_dense_training_parity(kind):
    """10 steps, adagrad: the sparse pipeline must match the dense oracle to
    1e-6 on every parameter (for memory-family schemes the pool gradient
    travels as a SparseGrad; table-family schemes are pass-through)."""
    table, bufs, params = _make_setup(kind)
    p0 = jax.tree_util.tree_map(lambda x: x, params)
    pd, sd = _train(table, bufs, params, sparse=False)
    ps, ss = _train(table, bufs, p0, sparse=True)
    for (kp, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(pd)[0],
                               jax.tree_util.tree_flatten_with_path(ps)[0]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6,
            err_msg=f"{kind}: param {kp} diverged sparse-vs-dense")
    for a, b in zip(jax.tree_util.tree_leaves(sd),
                    jax.tree_util.tree_leaves(ss)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=1e-6)


def test_memory_grad_is_sparse_leaf():
    """The pool gradient really is a SparseGrad (not a densified twin)."""
    table, bufs, params = _make_setup("lma")

    def loss_fn(p, b):
        e = table.embed_fields(p["embedding"], bufs, b["ids"])
        return jnp.mean(e ** 2), {}

    (_, _m), g = sp.sparse_value_and_grad(loss_fn)(params, _batch(0))
    assert isinstance(g["embedding"]["memory"], sp.SparseGrad)
    assert g["embedding"]["memory"].dense_shape == (4096,)
    # row-aligned scheme -> row-mode SparseGrad with [K, d] values
    table_r, bufs_r, params_r = _make_setup("freq")

    def loss_r(p, b):
        e = table_r.embed_fields(p["embedding"], bufs_r, b["ids"])
        return jnp.mean(e ** 2), {}

    (_, _m), gr = sp.sparse_value_and_grad(loss_r)(params_r, _batch(0))
    sg = gr["embedding"]["memory"]
    assert isinstance(sg, sp.SparseGrad)
    assert sg.dense_shape == (512, 8) and sg.values.ndim == 2


def test_ragged_budget_falls_back_to_element_mode():
    """m % d != 0 cannot tile into rows: the row-aligned scheme must fall
    back to element-level records (and still train/apply cleanly)."""
    scheme = get_scheme("hashed_row")
    table = EmbeddingTable(scheme.build_config((128,), 4, 66, seed=1))
    params = {"embedding": table.init(jax.random.key(0))}

    def loss(p, ids):
        return jnp.mean(table.embed(p["embedding"], {}, 0, ids) ** 2), {}

    (_, _m), g = sp.sparse_value_and_grad(loss)(
        params, jnp.arange(8, dtype=jnp.int32))
    sg = g["embedding"]["memory"]
    assert sg.dense_shape == (66,) and sg.values.ndim == 1
    p2 = opt_lib.apply_updates(
        params, {"embedding": {"memory": sg.map_values(lambda v: -v)}})
    assert p2["embedding"]["memory"].shape == (66,)
    # adafactor's densify fallback reshapes a row-mode grad to the flat
    # param layout (the other review-found crash)
    opt = opt_lib.adafactor(0.01)
    st = opt.init({"w": jnp.zeros(64, jnp.float32)})
    rg = sp.from_locations(jnp.asarray([1, 3], jnp.int32),
                           jnp.ones((2, 4), jnp.float32), (16, 4))
    u, st = opt.update({"w": rg}, st, {"w": jnp.zeros(64, jnp.float32)})
    assert u["w"].shape == (64,)


def test_trainer_auto_sparse_and_throughput():
    from repro.train.trainer import Trainer, TrainerConfig
    table, bufs, params = _make_setup("hashed_elem")

    def loss_fn(p, b):
        e = table.embed_fields(p["embedding"], bufs, b["ids"])
        pred = jnp.einsum("bfd,d->b", e, p["w"])
        loss = jnp.mean((pred - b["y"]) ** 2)
        return loss, {"loss": loss}

    t = Trainer(TrainerConfig(total_steps=4, log_every=0,
                              lookups_per_step=96),
                loss_fn, params, opt_lib.adagrad(0.1), _batch)
    assert t.sparse_grads        # gate on + memory pool present -> auto
    out = t.fit(log=lambda *_: None)
    assert out["step"] == 4
    assert out["steps_per_sec"] > 0
    assert out["lookups_per_sec"] == pytest.approx(
        96 * out["steps_per_sec"])
    t2 = Trainer(TrainerConfig(total_steps=1), loss_fn, params,
                 opt_lib.adagrad(0.1), _batch, sparse_grads=False)
    assert not t2.sparse_grads   # explicit dense oracle


def test_multi_transform_routes_memory_to_sparse_optimizer():
    table, bufs, params = _make_setup("hashed_row")
    opt = opt_lib.multi_transform(
        [(r"(^|/)memory$", sp.sparse_adagrad(0.1))],
        default=opt_lib.adagrad(0.1))
    state = opt.init(params)

    def loss_fn(p, b):
        e = table.embed_fields(p["embedding"], bufs, b["ids"])
        return jnp.mean(e ** 2), {}

    (_, _m), g = sp.sparse_value_and_grad(loss_fn)(params, _batch(0))
    upd, state = opt.update(g, state, params)
    assert isinstance(upd["embedding"]["memory"], sp.SparseGrad)
    p2 = opt_lib.apply_updates(params, upd)
    assert p2["embedding"]["memory"].shape == \
        params["embedding"]["memory"].shape


# ----------------------------------------------- compile-churn (pow2 pad)

def test_pad_batch_pow2_one_compilation_across_jitter():
    from repro.kernels.fused_embed import ops as fe
    rng = np.random.default_rng(5)
    spec = fe.hashed_spec("hashed_elem", 8, 1024, seed=0)
    mem = jnp.asarray(rng.normal(size=1024).astype(np.float32))
    gids = jnp.asarray(rng.integers(0, 512, 512, np.int32))
    fe.fused_lookup(spec, mem, gids[:260])            # warm the 512 bucket
    n0 = fe._lookup_jit._cache_size()
    for b in (300, 301, 333, 400, 511, 512):          # serving-style jitter
        out = fe.fused_lookup(spec, mem, gids[:b])
        assert out.shape == (b, 8)
    assert fe._lookup_jit._cache_size() == n0, (
        "batch-size jitter inside one pow2 bucket must not recompile")
    # crossing a bucket boundary compiles exactly once more
    fe.fused_lookup(spec, mem, jnp.concatenate([gids, gids])[:600])
    assert fe._lookup_jit._cache_size() == n0 + 1


def test_fused_locations_matches_scheme_oracle():
    from repro.kernels.fused_embed import ops as fe
    table, bufs, params = _make_setup("lma")
    cfg = table.config
    scheme = table.scheme
    gids = jnp.asarray(np.random.default_rng(6).integers(
        0, cfg.total_vocab, 300, np.int32))
    want = scheme.locations(cfg, bufs, gids)
    got = fe.fused_locations(scheme.fused_spec(cfg), gids,
                             *scheme.fused_inputs(cfg, bufs, gids))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------------- check_regression gate

def test_check_regression_sparse_gate():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.check_regression import sparse_speedup_failures
    rows = {("sparse_update_adagrad", "s"): 100.0,
            ("dense_update_adagrad", "s"): 130.0}
    doc_ok = {"modeled_update_bytes_per_step":
              {"dense": 900, "sparse": 100, "speedup": 9.0}}
    assert sparse_speedup_failures(rows, doc_ok) == []
    doc_slow = {"modeled_update_bytes_per_step":
                {"dense": 200, "sparse": 100, "speedup": 2.0}}
    assert any("modeled speedup" in f
               for f in sparse_speedup_failures(rows, doc_slow))
    rows_wall = {("sparse_update_adagrad", "s"): 130.0,
                 ("dense_update_adagrad", "s"): 100.0}
    assert any("wall gate" in f
               for f in sparse_speedup_failures(rows_wall, doc_ok))
    assert any("missing" in f for f in sparse_speedup_failures({}, doc_ok))


# ------------------------------------------------------- 2x4 sharded parity

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.signatures import synthetic_dense_store
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.embed import EmbeddingTable, get_scheme
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sp

assert len(jax.devices()) == 8
mesh = make_mesh((2, 4), ("data", "model"))

for kind in ("lma", "hashed_row", "freq"):
    scheme = get_scheme(kind)
    table = EmbeddingTable(scheme.build_config((512,), 16, 4096, seed=3))
    store = synthetic_dense_store(512, 8, max_set=32, seed=2) \
        if scheme.needs_signature_store else None
    bufs = table.make_buffers(store)
    params0 = {"embedding": table.init(jax.random.key(1))}

    def batch(step):
        r = np.random.default_rng(step)
        return (jnp.asarray(r.integers(0, 512, 64, np.int32)),
                jnp.asarray(r.normal(size=(64, 16)).astype(np.float32)))

    def loss_fn(p, ids, y):
        e = table.embed(p["embedding"], bufs, 0, ids)
        l = jnp.mean((e - y) ** 2)
        return l, {"l": l}

    def train(sparse, mesh_ctx):
        params = jax.tree_util.tree_map(lambda x: x, params0)
        opt = opt_lib.adagrad(0.1, eps=1e-8)
        state = opt.init(params)
        vg = sp.sparse_value_and_grad(loss_fn) if sparse else \
            jax.value_and_grad(loss_fn, has_aux=True)
        def step(params, state, ids, y):
            (_, _m), g = vg(params, ids, y)
            u, state = opt.update(g, state, params)
            return opt_lib.apply_updates(params, u), state
        for s in range(10):
            ids, y = batch(s)
            if mesh_ctx is None:
                params, state = step(params, state, ids, y)
            else:
                with use_mesh(mesh_ctx):
                    params, state = step(params, state, ids, y)
        return params

    p_oracle = train(False, None)                 # single-device dense
    p_sharded = train(True, mesh)                 # 2x4 sharded sparse
    a = np.asarray(p_oracle["embedding"]["memory"])
    b = np.asarray(p_sharded["embedding"]["memory"])
    np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    print(kind, "sharded sparse parity OK")

# rowwise adam (traced bias corrections enter the shard_map as explicit
# inputs): meshed sparse must match unmeshed sparse exactly
scheme = get_scheme("hashed_row")
table = EmbeddingTable(scheme.build_config((512,), 16, 4096, seed=3))
bufs = table.make_buffers(None)
params0 = {"embedding": table.init(jax.random.key(1))}

def loss_fn(p, ids, y):
    e = table.embed(p["embedding"], bufs, 0, ids)
    return jnp.mean((e - y) ** 2), {}

def train_adam(mesh_ctx):
    params = jax.tree_util.tree_map(lambda x: x, params0)
    opt = sp.sparse_rowwise_adam(0.05)
    state = opt.init(params)
    vg = sp.sparse_value_and_grad(loss_fn)
    for s in range(5):
        r = np.random.default_rng(s)
        ids = jnp.asarray(r.integers(0, 512, 64, np.int32))
        y = jnp.asarray(r.normal(size=(64, 16)).astype(np.float32))
        def one(params, state):
            (_, _m), g = vg(params, ids, y)
            u, state = opt.update(g, state, params)
            return opt_lib.apply_updates(params, u), state
        if mesh_ctx is None:
            params, state = one(params, state)
        else:
            with use_mesh(mesh_ctx):
                params, state = jax.jit(one)(params, state)
    return params

pa = np.asarray(train_adam(None)["embedding"]["memory"])
pb = np.asarray(train_adam(mesh)["embedding"]["memory"])
np.testing.assert_allclose(pa, pb, atol=1e-6, rtol=1e-6)
print("rowwise adam sharded parity OK")
print("ALL OK")
"""


@pytest.mark.slow
def test_sharded_sparse_parity_2x4():
    """Sparse updates on a (2, 4) mesh (masked local slab apply) match the
    single-device dense oracle to 1e-6 after 10 steps."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "ALL OK" in r.stdout
