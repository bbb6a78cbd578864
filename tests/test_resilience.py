"""Self-healing training: injector, step guard, integrity, exchange fallback.

Every resilience path is driven by the deterministic fault injector
(``repro.resilience.faults``), so outcomes are exact: a skipped step leaves
state bit-identical, a rolled-back run converges to the clean run's bits,
quarantined pool chunks zero out and the model keeps training.
"""
from __future__ import annotations

import os
import signal as signal_mod
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.dist import exchange as exl
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sparse_lib
from repro.resilience import faults as flt
from repro.resilience import guard as guard_lib
from repro.resilience import integrity as integ
from repro.resilience.exchange_guard import ExchangeGuard
from repro.resilience.health import Health
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(autouse=True)
def _clean_global_state():
    yield
    flt.install(None)
    exl.reset_demotions()


def _problem(noise=0.0):
    """Noise-free by default: clean and faulted runs both converge to ~0,
    making the <= 1e-6 loss-parity assertion exact."""
    rng = np.random.default_rng(0)
    w_true = rng.normal(0, 1, (8, 1)).astype(np.float32)

    def batch_fn(step):
        r = np.random.default_rng(step)
        x = r.normal(0, 1, (32, 8)).astype(np.float32)
        y = x @ w_true + noise * r.normal(0, 1, (32, 1)).astype(np.float32)
        return {"x": jnp.asarray(x), "y": jnp.asarray(y)}

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"mse": loss}

    return loss_fn, batch_fn


def _trainer(total_steps, faults=None, ckpt_dir=None, **cfg_kw):
    loss_fn, batch_fn = _problem()
    cfg = TrainerConfig(total_steps=total_steps, log_every=0,
                        ckpt_dir=ckpt_dir, **cfg_kw)
    inj = flt.FaultInjector(faults) if faults else None
    return Trainer(cfg, loss_fn, {"w": jnp.zeros((8, 1), jnp.float32)},
                   opt_lib.adam(5e-2), batch_fn, faults=inj)


def _pool_problem(kind, m=32768, d=16, vocab=512):
    """Memory-pool regression problem exercising the sparse-grad path."""
    from repro.core.signatures import synthetic_dense_store
    from repro.embed import EmbeddingTable, get_scheme

    scheme = get_scheme(kind)
    table = EmbeddingTable(scheme.build_config((vocab,), d, m, seed=3))
    store = (synthetic_dense_store(vocab, 64, max_set=16, seed=2)
             if scheme.buffer_source == "signatures" else None)
    bufs = table.make_buffers(store)
    rng = np.random.default_rng(1)
    Y = rng.normal(size=(vocab, d)).astype(np.float32)

    def batch_fn(step):
        r = np.random.default_rng(step)
        ids = r.integers(0, vocab, (64,), np.int32)
        return {"ids": jnp.asarray(ids), "y": jnp.asarray(Y[ids])}

    def loss_fn(params, batch):
        e = table.embed(params["embedding"], bufs, 0, batch["ids"])
        loss = jnp.mean((e - batch["y"]) ** 2)
        return loss, {}

    params = {"embedding": table.init(jax.random.key(0))}
    return loss_fn, batch_fn, params


def _tree_bit_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------ fault grammar

def test_fault_grammar():
    fs = flt.parse_faults("nan_grad@17, rot_row@40:8 ,slow_rank@55:0.5")
    assert [(f.kind, f.step, f.arg) for f in fs] == [
        ("nan_grad", 17, None), ("rot_row", 40, 8.0), ("slow_rank", 55, 0.5)]
    assert flt.parse_faults("") == []
    with pytest.raises(ValueError, match="unknown fault kind"):
        flt.parse_faults("bad_kind@3")
    with pytest.raises(ValueError, match="malformed"):
        flt.parse_faults("nan_grad")
    with pytest.raises(ValueError, match="malformed"):
        flt.parse_faults("nan_grad@x")


def test_grad_fault_fires_once():
    inj = flt.FaultInjector("inf_grad@2")
    assert inj.grad_fault(1) == 1.0
    assert inj.grad_fault(2) == float("inf")
    assert inj.grad_fault(2) == 1.0     # transient: consumed
    inj.reset()
    assert inj.grad_fault(2) == float("inf")


def test_from_env(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "nan_grad@5")
    inj = flt.from_env()
    assert inj is not None and inj.faults[0].kind == "nan_grad"
    assert flt.active_injector() is inj
    monkeypatch.setenv("REPRO_FAULTS", "")
    assert flt.from_env() is None


# ------------------------------------------------------------- guarded step

@pytest.mark.parametrize("fault", ["nan_grad", "inf_grad", "huge_grad"])
def test_skipped_step_is_bit_exact_noop(fault):
    """The acceptance-criterion core: a poisoned step leaves params,
    opt_state and every Adam moment bit-identical to the pre-step state."""
    t_clean = _trainer(total_steps=2)
    t_clean.fit(log=lambda *_: None)

    t_fault = _trainer(total_steps=3, faults=f"{fault}@2")
    out = t_fault.fit(log=lambda *_: None)
    assert out["step"] == 3
    assert out["skipped_steps"] == 1 and out["nonfinite_grads"] == 1
    # state after (clean 0, clean 1, skipped 2) == state after (clean 0, 1)
    _tree_bit_equal(t_clean.params, t_fault.params)
    _tree_bit_equal(t_clean.opt_state, t_fault.opt_state)


def test_skipped_step_sparse_pool_bit_exact():
    """Same bit-exactness through the SparseGrad path (lma striped: bucketed
    ``unique=False`` streams) — the donated pool and adagrad moments come
    back untouched from the skip branch."""
    loss_fn, batch_fn, params = _pool_problem("lma")
    opt = opt_lib.adagrad(0.1)

    def run(steps, faults=None):
        _, _, p = _pool_problem("lma")
        inj = flt.FaultInjector(faults) if faults else None
        t = Trainer(TrainerConfig(total_steps=steps, log_every=0),
                    loss_fn, p, opt, batch_fn, faults=inj)
        assert t.sparse_grads, "pool problem must exercise the sparse path"
        t.fit(log=lambda *_: None)
        return t

    t_clean = run(3)
    t_fault = run(4, faults="nan_grad@3")
    assert t_fault.health.skipped_steps == 1
    _tree_bit_equal(t_clean.params, t_fault.params)
    _tree_bit_equal(t_clean.opt_state, t_fault.opt_state)


def test_huge_grad_caught_by_magnitude_bound():
    """1e30-scaled gradients are *finite* — only the |g| <= max_abs_grad
    bound catches them before the optimizer squares them into inf."""
    t = _trainer(total_steps=3, faults="huge_grad@1")
    t.fit(log=lambda *_: None)
    assert t.health.skipped_steps == 1
    assert np.isfinite(np.asarray(t.params["w"])).all()


def test_recovery_to_loss_parity():
    """After the skip, training recovers: final loss within 1e-6 of the
    un-faulted run (noise-free problem; both converge to ~0)."""
    r_clean = _trainer(total_steps=150).fit(log=lambda *_: None)
    r_fault = _trainer(total_steps=150, faults="nan_grad@3").fit(
        log=lambda *_: None)
    assert r_fault["skipped_steps"] == 1
    assert abs(r_clean["loss"] - r_fault["loss"]) <= 1e-6


def test_skip_is_independent_of_poison_value():
    """NaN and inf poison at the same step must leave identical bits — the
    cond's skip branch never reads the poisoned update."""
    t_a = _trainer(total_steps=10, faults="nan_grad@4")
    t_b = _trainer(total_steps=10, faults="inf_grad@4")
    t_a.fit(log=lambda *_: None)
    t_b.fit(log=lambda *_: None)
    _tree_bit_equal(t_a.params, t_b.params)
    _tree_bit_equal(t_a.opt_state, t_b.opt_state)


def test_unguarded_step_applies_poison():
    """guard_step=False restores the fast path: the NaN lands in params
    (and the checkpoint manager then refuses to persist it)."""
    t = _trainer(total_steps=3, faults="nan_grad@1", guard_step=False)
    t.fit(log=lambda *_: None)
    assert t.health.skipped_steps == 0
    assert not np.isfinite(np.asarray(t.params["w"])).all()


def test_guard_env_gate(monkeypatch):
    monkeypatch.setenv("REPRO_GUARD_STEP", "0")
    assert not guard_lib.guard_enabled()
    t = _trainer(total_steps=1)
    assert t.guard is False
    monkeypatch.setenv("REPRO_GUARD_STEP", "1")
    assert guard_lib.guard_enabled()


# ------------------------------------------------------------------ rollback

def test_rollback_restores_and_recovers_bit_exact(tmp_path):
    """Two skips in a row roll back to the last checkpoint; the transient
    faults are consumed, the replayed steps apply cleanly, and the final
    state is bit-identical to a never-faulted run."""
    t_fault = _trainer(total_steps=10, faults="nan_grad@4,nan_grad@5",
                       ckpt_dir=str(tmp_path / "a"), ckpt_every=2,
                       max_consecutive_skips=2, rollback_backoff=0.01)
    out = t_fault.fit(log=lambda *_: None)
    assert out["rollbacks"] == 1 and out["retries"] >= 1
    assert out["skipped_steps"] == 2
    assert out["step"] == 10 and not out["preempted"]

    t_clean = _trainer(total_steps=10, ckpt_dir=str(tmp_path / "b"),
                       ckpt_every=2)
    t_clean.fit(log=lambda *_: None)
    _tree_bit_equal(t_clean.params, t_fault.params)
    _tree_bit_equal(t_clean.opt_state, t_fault.opt_state)


def test_rollback_gives_up_loudly():
    """Bounded: persistent non-finite steps end in RuntimeError, not an
    infinite rollback loop."""
    t = _trainer(total_steps=10, faults="nan_grad@1,nan_grad@2",
                 max_consecutive_skips=1, max_rollbacks=1,
                 rollback_backoff=0.0)
    with pytest.raises(RuntimeError, match="giving up"):
        t.fit(log=lambda *_: None)
    assert t.health.rollbacks == 2


def test_rollback_backoff_is_bounded():
    t = _trainer(total_steps=1, rollback_backoff=0.05,
                 rollback_backoff_max=0.2, max_rollbacks=100)
    delays = [min(t.cfg.rollback_backoff * (2 ** k), t.cfg.rollback_backoff_max)
              for k in range(10)]
    assert delays[0] == 0.05 and max(delays) == 0.2


# ------------------------------------------------- stragglers and preemption

def test_slow_rank_fault_counts_straggler():
    t = _trainer(total_steps=24, faults="slow_rank@20:0.3")
    t.fit(log=lambda *_: None)
    assert t.health.straggler_steps >= 1


def test_preempt_fault_and_unified_result(tmp_path):
    """The preempted exit path returns the SAME result keys as normal
    completion (the old dict silently dropped straggler_steps)."""
    t = _trainer(total_steps=50, faults="preempt@3",
                 ckpt_dir=str(tmp_path), ckpt_every=5)
    out = t.fit(log=lambda *_: None)
    assert out["preempted"] and out["step"] == 3
    normal = _trainer(total_steps=2).fit(log=lambda *_: None)
    assert set(out) == set(normal)
    for key in ("straggler_steps", "skipped_steps", "rollbacks",
                "quarantined_chunks", "exchange_demotions"):
        assert key in out


def test_second_sigint_restores_default_handler():
    t = _trainer(total_steps=1)
    orig_int = signal_mod.getsignal(signal_mod.SIGINT)
    orig_term = signal_mod.getsignal(signal_mod.SIGTERM)
    try:
        t.install_signal_handlers()
        handler = signal_mod.getsignal(signal_mod.SIGINT)
        assert handler not in (orig_int, signal_mod.SIG_DFL)
        handler(signal_mod.SIGINT, None)          # graceful: flag + keep going
        assert t._preempted
        assert signal_mod.getsignal(signal_mod.SIGINT) is handler
        handler(signal_mod.SIGINT, None)          # hung save: make us killable
        assert signal_mod.getsignal(signal_mod.SIGINT) is signal_mod.SIG_DFL
    finally:
        signal_mod.signal(signal_mod.SIGINT, orig_int)
        signal_mod.signal(signal_mod.SIGTERM, orig_term)


def test_try_resume_waits_for_inflight_async_save(tmp_path):
    """An async save still writing must not race the restore."""
    t = _trainer(total_steps=5, ckpt_dir=str(tmp_path))
    t.fit(log=lambda *_: None)
    t.step = 7
    real_write = t.mgr._write

    def slow_write(step, host, *a):
        time.sleep(0.3)
        real_write(step, host, *a)

    t.mgr._write = slow_write
    t.save(blocking=False)               # in flight for >= 0.3 s
    t2 = _trainer(total_steps=9, ckpt_dir=str(tmp_path))
    t2.mgr = t.mgr                       # same manager: the rollback path
    assert t2.try_resume()
    assert t2.step == 7                  # saw the in-flight save, not step 5


# ------------------------------------------------------------ pool integrity

def test_integrity_checksum_device_host_parity():
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(40000,)).astype(np.float32))
    dev = np.asarray(integ.chunk_checksums(x))
    host = integ.np_chunk_checksums(np.asarray(x))
    np.testing.assert_array_equal(dev, host)


def test_integrity_sanitize_quarantines_only_bad_chunks():
    x = jnp.arange(3 * integ.CHUNK, dtype=jnp.float32)
    bad = x.at[integ.CHUNK + 5].set(jnp.inf).at[7].set(1e38)
    clean, n_bad = integ.sanitize(bad)
    assert int(n_bad) == 2
    c = np.asarray(clean)
    assert (c[:integ.CHUNK] == 0).all()                     # chunk 0 zeroed
    assert (c[integ.CHUNK:2 * integ.CHUNK] == 0).all()      # chunk 1 zeroed
    np.testing.assert_array_equal(c[2 * integ.CHUNK:],
                                  np.asarray(x[2 * integ.CHUNK:]))


def test_integrity_sanitize_clean_is_bitwise_noop():
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(2 * integ.CHUNK + 17,)).astype(np.float32))
    clean, n_bad = integ.sanitize(x)
    assert int(n_bad) == 0
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(x))


def test_rot_row_detected_quarantined_run_completes():
    """Injected slab bit-rot: the poisoned steps are skipped by the guard,
    the ckpt-boundary integrity scan quarantines the rotten chunks, and the
    run completes with a finite pool."""
    loss_fn, batch_fn, params = _pool_problem("lma")
    t = Trainer(
        TrainerConfig(total_steps=10, log_every=0, ckpt_every=4,
                      max_consecutive_skips=50),   # heal via scan, not rollback
        loss_fn, params, opt_lib.adagrad(0.1), batch_fn,
        faults=flt.FaultInjector("rot_row@5:4"))
    out = t.fit(log=lambda *_: None)
    assert out["step"] == 10
    assert out["quarantined_chunks"] >= 1
    mem = np.asarray(t.params["embedding"]["memory"])
    assert np.isfinite(mem).all() and np.abs(mem).max() <= integ.MAX_ABS


def test_restore_sanitizes_pool(tmp_path):
    """A restored checkpoint that somehow carries corruption (verify=False
    path, legacy ckpt) is scanned on resume."""
    loss_fn, batch_fn, params = _pool_problem("hashed_row")
    cfg = TrainerConfig(total_steps=4, log_every=0, ckpt_dir=str(tmp_path),
                        ckpt_every=2)
    t = Trainer(cfg, loss_fn, params, opt_lib.adagrad(0.1), batch_fn)
    t.fit(log=lambda *_: None)
    # corrupt BOTH saved pool leaves (params and the adagrad accumulator)
    # *and* their recorded integrity, so restore's manifest verification
    # passes and only the trainer-side scan can catch it
    import json
    step_dir = os.path.join(str(tmp_path), "step_0000000004")
    p = os.path.join(step_dir, "arrays.npz")
    with np.load(p) as z:
        host = {k: z[k].copy() for k in z.files}
    keys = [k for k in host if k.endswith("memory")]
    assert len(keys) == 2          # params/.../memory + opt_state/.../memory
    for key in keys:
        host[key][3] = np.float32("nan")
    np.savez(p, **host)
    from repro.checkpoint.manager import _leaf_sha, _tree_digest
    man_path = os.path.join(step_dir, "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    man["checksum"] = _tree_digest(host)
    for key in keys:
        man["leaves"][key]["sha256"] = _leaf_sha(host[key])
        man["integrity"][key]["checksums"] = [
            int(c) for c in integ.np_chunk_checksums(host[key])]
    with open(man_path, "w") as f:
        json.dump(man, f)

    loss_fn2, batch_fn2, params2 = _pool_problem("hashed_row")
    t2 = Trainer(cfg, loss_fn2, params2, opt_lib.adagrad(0.1), batch_fn2)
    assert t2.try_resume()
    assert t2.health.quarantined_chunks >= 2
    assert np.isfinite(np.asarray(t2.params["embedding"]["memory"])).all()
    for leaf in jax.tree_util.tree_leaves(t2.opt_state):
        assert np.isfinite(np.asarray(leaf)).all()


def test_save_refuses_nonfinite_state(tmp_path):
    """With the guard off, poison reaches params — and the checkpoint
    manager must refuse to persist it."""
    from repro.checkpoint.manager import CheckpointManager
    t = _trainer(total_steps=3, faults="nan_grad@1", guard_step=False)
    t.fit(log=lambda *_: None)
    assert not np.isfinite(np.asarray(t.params["w"])).all()
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(ValueError, match="non-finite"):
        mgr.save(3, {"params": t.params})
    assert mgr.latest_step() is None     # nothing was persisted
    mgr.save(3, {"params": t.params}, check_finite=False)  # debug escape
    assert mgr.latest_step() == 3


def test_ctr_smoke_survives_bit_rot_with_bounded_auc_dent():
    """The tentpole's graceful-degradation claim on the CTR smoke model:
    bit-rot mid-training is quarantined (zeroed LMA chunks) and the run
    finishes with a measured — bounded — AUC dent instead of crashing."""
    import dataclasses as dc

    from repro.configs._recsys_common import embedding_of_kind
    from repro.configs.lma_dlrm_criteo import make_model
    from repro.core.embedding import make_buffers as core_make_buffers
    from repro.core.signatures import build_signature_store, densify_store
    from repro.data.metrics import StreamingEval
    from repro.data.synthetic_ctr import CTRGenerator, CTRSpec
    from repro.models import recsys

    # expansion=1.0 -> m=32768 = 4 integrity chunks, so quarantining the one
    # rotten chunk zeroes 1/4 of the pool (expansion=8 would leave a
    # single-chunk pool, where quarantine == losing everything)
    vocabs = tuple(150 + (i * 37) % 250 for i in range(8))
    cfg = make_model(embedding_kind="lma", expansion=1.0)
    emb = embedding_of_kind("lma", vocabs, 16, expansion=1.0, max_set=32)
    cfg = dc.replace(cfg, embedding=emb, n_dense=4, bot_mlp=(32, 16),
                     top_mlp=(64, 1))
    spec = CTRSpec(n_fields=8, n_dense=4, vocab_sizes=vocabs, n_clusters=8,
                   p_signal=0.85, seed=0)
    gen = CTRGenerator(spec)
    store = build_signature_store(gen.rows_for_signatures(6000), sum(vocabs),
                                  max_per_value=32)
    bufs = core_make_buffers(cfg.embedding, densify_store(store, 32))

    def batch_fn(step):
        return {k: jnp.asarray(v) for k, v in gen.batch(256, step).items()}

    def loss_fn(p, b):
        return recsys.loss_fn(p, cfg, b, bufs)

    def auc_of(params):
        ev = StreamingEval()
        fwd = jax.jit(lambda p, b: recsys.forward(p, cfg, b, bufs))
        for i in range(6):
            b = gen.batch(512, 100_000 + i)
            jb = {k: jnp.asarray(v) for k, v in b.items() if k != "label"}
            ev.add(b["label"], np.asarray(fwd(params, jb)))
        return ev.compute()["auc"]

    def run(faults=None):
        params = recsys.init(jax.random.key(0), cfg)
        t = Trainer(
            TrainerConfig(total_steps=100, log_every=0, ckpt_every=10,
                          max_consecutive_skips=50),
            loss_fn, params, opt_lib.adagrad(0.05), batch_fn,
            faults=flt.FaultInjector(faults) if faults else None)
        t.fit(log=lambda *_: None)
        return t

    t_clean = run()
    t_rot = run(faults="rot_row@55:1")   # 1 element -> exactly 1 bad chunk
    assert t_rot.health.quarantined_chunks >= 1
    auc_clean, auc_rot = auc_of(t_clean.params), auc_of(t_rot.params)
    dent = auc_clean - auc_rot
    print(f"[resilience] CTR smoke AUC clean {auc_clean:.4f} vs bit-rot "
          f"{auc_rot:.4f} (dent {dent:+.4f}, "
          f"{t_rot.health.quarantined_chunks} chunk(s) quarantined)")
    assert auc_rot > 0.60          # still far above chance
    assert dent < 0.10             # graceful, not catastrophic


# -------------------------------------------------------- exchange demotion

def fake_mesh(**axes):
    from types import SimpleNamespace
    return SimpleNamespace(shape=axes)


def test_demote_effective_and_reset():
    assert exl.effective("all_to_all") == "all_to_all"
    assert exl.demote("all_to_all", "test") == "ring"
    assert exl.effective("all_to_all") == "ring"
    assert exl.demote("ring", "test") == "psum"
    assert exl.effective("all_to_all") == "psum"
    assert exl.effective("psum") == "psum"
    with pytest.raises(ValueError):
        exl.demote("psum")
    with pytest.raises(KeyError):
        exl.demote("nope")
    exl.reset_demotions()
    assert exl.effective("all_to_all") == "all_to_all"


def test_resolver_honors_demotions():
    mesh = fake_mesh(data=2, model=4)
    # big batch, fused discount off: a chunked strategy wins the cost model
    picked = exl.resolve_exchange(mesh, B=4096, d=32, fused=False)
    assert picked.name in ("ring", "all_to_all")
    exl.demote("all_to_all", "test")
    assert exl.resolve_exchange(mesh, B=4096, d=32, fused=False).name in (
        "ring", "psum")
    exl.demote("ring", "test")
    assert exl.resolve_exchange(mesh, B=4096, d=32, fused=False).name == "psum"
    # the update exchange follows: demoted all_to_all -> psum oracle
    assert exl.resolve_update_exchange(mesh) is exl.PSUM


def test_forced_strategy_maps_through_demotion():
    mesh = fake_mesh(data=2, model=4)
    old = exl.FORCED
    try:
        exl.FORCED = "all_to_all"
        assert exl.resolve_exchange(mesh, B=4096, d=32).name == "all_to_all"
        exl.demote("all_to_all", "test")
        assert exl.resolve_exchange(mesh, B=4096, d=32).name == "ring"
    finally:
        exl.FORCED = old


def test_exchange_guard_demotes_after_retry():
    oracle = np.arange(12, dtype=np.float32).reshape(4, 3)
    calls = []

    def probe(name):
        calls.append(name)
        if name == "all_to_all":
            return np.zeros_like(oracle)     # dropped chunk: wrong bits
        return oracle                        # psum oracle and ring agree

    h = Health()
    g = ExchangeGuard(probe, health=h, log=lambda *_: None)
    assert g.validate() == "ring"
    assert "all_to_all" in exl.DEMOTED and "ring" not in exl.DEMOTED
    assert h.exchange_demotions == 1 and h.retries == 1
    assert calls.count("all_to_all") == 2    # failed, retried, then demoted


def test_exchange_guard_transient_failure_recovers():
    oracle = np.ones((4,), np.float32)
    state = {"n": 0}

    def probe(name):
        if name == "all_to_all":
            state["n"] += 1
            if state["n"] == 1:
                return np.zeros_like(oracle)  # one transient glitch
        return oracle

    h = Health()
    g = ExchangeGuard(probe, health=h, log=lambda *_: None)
    assert g.validate() == "all_to_all"
    assert not exl.DEMOTED and h.exchange_demotions == 0 and h.retries == 1


def test_exchange_guard_finite_check_without_oracle():
    def probe(name):
        if name == "all_to_all":
            return np.asarray([1.0, np.nan], np.float32)
        return np.asarray([1.0, 2.0], np.float32)

    g = ExchangeGuard(probe, log=lambda *_: None, use_oracle=False)
    assert g.validate() == "ring"
    assert exl.DEMOTED["all_to_all"].startswith("non-finite")


def test_exchange_guard_all_chunked_fail():
    def probe(name):
        if name == "psum":
            return np.ones((4,), np.float32)
        return np.zeros((4,), np.float32)

    h = Health()
    g = ExchangeGuard(probe, health=h, log=lambda *_: None)
    assert g.validate() == "psum"
    assert set(exl.DEMOTED) == {"all_to_all", "ring"}
    assert h.exchange_demotions == 2


def test_faulty_exchange_wrapper_mangles_lookup_name_preserved():
    inj = flt.FaultInjector("drop_chunk@0")
    wrapped = flt.FaultyExchange(exl.ALL_TO_ALL, inj)
    assert wrapped.name == "all_to_all" and wrapped.partial_updates
    out = wrapped._mangle(jnp.ones((8, 4)), n_model=4)
    np.testing.assert_array_equal(np.asarray(out[:2]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[2:]), 1.0)
    # corrupt variant NaNs the chunk instead
    inj2 = flt.FaultInjector("corrupt_chunk@0")
    out2 = flt.FaultyExchange(exl.RING, inj2)._mangle(jnp.ones((8, 4)), 4)
    assert np.isnan(np.asarray(out2[:2])).all()


def test_wrap_exchange_only_when_armed_and_not_psum():
    assert flt.wrap_exchange(exl.RING) is exl.RING        # no injector
    flt.install(flt.FaultInjector("drop_chunk@0"))
    assert isinstance(flt.wrap_exchange(exl.RING), flt.FaultyExchange)
    assert flt.wrap_exchange(exl.PSUM) is exl.PSUM        # oracle exempt
    flt.install(flt.FaultInjector("nan_grad@0"))          # no chunk fault
    assert flt.wrap_exchange(exl.RING) is exl.RING


# --------------------------------------- end-to-end demotion on a real mesh

_DEMOTION_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import numpy as np
import jax, jax.numpy as jnp
from repro.core.allocation import alloc_hashed_elem
from repro.core.memory import init_memory, lookup
from repro.dist import exchange as exl
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.dist.sharded_memory import sharded_hashed_lookup
from repro.resilience import faults as flt
from repro.resilience.exchange_guard import ExchangeGuard
from repro.resilience.health import Health

m, d, B = 1 << 15, 16, 256
mem = init_memory(jax.random.key(0), m, "normal", 0.1)
gids = jnp.asarray(np.random.default_rng(1).integers(0, 4096, (B,), np.int32))
mesh = make_mesh((2, 4), ("data", "model"))

# the injected chunk drop reaches every chunked strategy via _resolve's wrap
flt.install(flt.FaultInjector("drop_chunk@0"))

def probe(name):
    with use_mesh(mesh):
        out = sharded_hashed_lookup(mem, gids, d, m, 7, mesh, ("data",),
                                    exchange=name)
    return np.asarray(out)

h = Health()
guard = ExchangeGuard(probe, health=h, log=lambda s: print(s))
final = guard.validate()
assert final == "psum", final
assert set(exl.DEMOTED) == {"all_to_all", "ring"}, exl.DEMOTED
assert h.exchange_demotions == 2 and h.retries == 2, h

# after demotion the auto-resolver lands on psum, whose lookup is
# bit-identical to the replicated oracle even with the injector still armed
with use_mesh(mesh):
    auto = sharded_hashed_lookup(mem, gids, d, m, 7, mesh, ("data",))
oracle = lookup(mem, alloc_hashed_elem(gids, d, m, 7))
np.testing.assert_array_equal(np.asarray(auto), np.asarray(oracle))
print("OK demotion ladder -> psum, lookups bit-identical")
"""


@pytest.mark.slow
def test_chunk_drop_demotes_to_psum_bit_identical(tmp_path):
    """Acceptance criterion (d): injected all_to_all chunk drop demotes to
    ring then psum, and the surviving lookups are bit-identical to the
    replicated oracle."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("REPRO_DIST_EXCHANGE", None)
    env.pop("REPRO_FAULTS", None)
    r = subprocess.run([sys.executable, "-c", _DEMOTION_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK demotion ladder" in r.stdout
