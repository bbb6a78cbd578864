"""One-batch-ahead input prefetch in ``Trainer.fit``: the same batches feed
the same step in the same order, whether ``fit`` runs N steps at once or
one step a call, and a step whose batch was not made ahead makes it
inline.  CPU only; no wall-clock assertion."""
from __future__ import annotations

import gc
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.optim import optimizers as opt_lib
from repro.resilience import faults as faults_lib
from repro.resilience import guard as guard_lib
from repro.train.trainer import Trainer, TrainerConfig

N = 6


def _loss_fn(p, b):
    return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), {}


def _batch_fn(step):
    r = np.random.default_rng(step)
    return {"x": jnp.asarray(r.normal(size=(16, 4)).astype(np.float32)),
            "y": jnp.asarray(r.normal(size=(16, 1)).astype(np.float32)),
            "s": jnp.int32(step)}


def _params():
    return {"w": jnp.zeros((4, 1), jnp.float32)}


def _trainer(total=N, batch_fn=_batch_fn, **kw) -> Trainer:
    cfg_kw = {k: kw.pop(k) for k in list(kw) if hasattr(TrainerConfig, k)}
    cfg = TrainerConfig(**{"total_steps": total, "log_every": 0,
                           "guard_step": True, **cfg_kw})
    return Trainer(cfg, _loss_fn, _params(), opt_lib.adam(5e-2), batch_fn,
                   **kw)


def _record(tr: Trainer) -> list:
    """(trainer step, batch step, loss) of each step the trainer runs."""
    seen, step = [], tr._jit_step

    def wrapped(params, opt_state, batch, *rest):
        out = step(params, opt_state, batch, *rest)
        seen.append((tr.step, int(batch["s"]), float(out[2])))
        return out

    tr._jit_step = wrapped
    return seen


def _calls(name: str) -> int:
    return obs.totals().get(name, {}).get("calls", 0)


def _hand_loop(n: int):
    """The guarded step over ``_batch_fn(0..n-1)``, by hand."""
    opt = opt_lib.adam(5e-2)
    step = guard_lib.make_step(_loss_fn, opt, guard=True)
    params = _params()
    state = opt.init(params)
    losses = []
    for s in range(n):
        params, state, loss = step(params, state, _batch_fn(s),
                                   np.float32(1.0))[:3]
        losses.append(float(loss))
    return params, state, losses


@pytest.mark.parametrize("calls", ["one_fit", "one_step_per_fit"])
def test_prefetch_is_bit_equal_to_a_hand_loop(calls):
    want_p, want_s, want_losses = _hand_loop(N)
    tr = _trainer()
    seen = _record(tr)
    obs.reset()
    if calls == "one_fit":
        out = tr.fit(log=lambda _: None)
    else:
        for s in range(N):
            tr.cfg.total_steps = s + 1
            out = tr.fit(log=lambda _: None)
    assert out["step"] == N
    assert [(a, b) for a, b, _ in seen] == [(s, s) for s in range(N)]
    assert [x for _, _, x in seen] == want_losses
    for got, want in ((tr.params, want_p), (tr.opt_state, want_s)):
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _calls("input.prefetch.hit") == N - 1
    assert _calls("input.prefetch.miss") == 1
    assert tr._ahead.step == N                  # one made past the end
    tr._ahead.future.result()
    assert _calls("input.prefetch") == N


def test_step_after_a_resume_makes_its_batch_inline(tmp_path):
    tr = _trainer(total=4, ckpt_dir=str(tmp_path), ckpt_every=1000)
    seen = _record(tr)
    tr.fit(log=lambda _: None)
    tr.cfg.total_steps = 8
    obs.reset()
    out = tr.fit(log=lambda _: None)       # restores 4: 4's batch dropped
    assert out["resumed_step"] == 4 and out["step"] == 8
    assert [(a, b) for a, b, _ in seen] == [(s, s) for s in range(8)]
    assert _calls("input.prefetch.miss") == 1
    assert _calls("input.prefetch.hit") == 3


def test_each_step_takes_its_own_batch_after_a_rollback(tmp_path):
    """Steps 5 and 6 are poisoned once: two skips in a row roll back to
    the step-4 checkpoint, and the replay takes batch_fn(step) again."""
    poisoned = set()

    def batch_fn(step):
        b = _batch_fn(step)
        if step in (5, 6) and step not in poisoned:
            poisoned.add(step)
            b["y"] = b["y"] * jnp.nan
        return b

    tr = _trainer(total=10, batch_fn=batch_fn, ckpt_dir=str(tmp_path),
                  ckpt_every=4, max_consecutive_skips=2,
                  rollback_backoff=0.0)
    seen = _record(tr)
    obs.reset()
    out = tr.fit(log=lambda _: None)
    assert out["rollbacks"] == 1 and out["skipped_steps"] == 2
    order = [a for a, _, _ in seen]
    assert order == list(range(7)) + list(range(4, 10))
    assert all(a == b for a, b, _ in seen)
    assert _calls("input.prefetch.miss") == 2    # step 0 and the replayed 4
    assert _calls("input.prefetch.hit") == len(seen) - 2


def test_a_step_moved_back_by_hand_takes_its_own_batch():
    tr = _trainer()
    seen = _record(tr)
    tr.fit(log=lambda _: None)                 # leaves batch N made ahead
    tr.step, tr.cfg.total_steps = 3, 5
    obs.reset()
    tr.fit(log=lambda _: None)
    assert [(a, b) for a, b, _ in seen[N:]] == [(3, 3), (4, 4)]
    assert _calls("input.prefetch.miss") == 1


def test_a_failing_batch_past_the_end_raises_only_when_run():
    def batch_fn(step):
        if step >= N:
            raise IndexError(f"no batch {step}")
        return _batch_fn(step)

    tr = _trainer(batch_fn=batch_fn)
    out = tr.fit(log=lambda _: None)           # batch N failed, unused
    assert out["step"] == N
    tr.cfg.total_steps = N + 1
    with pytest.raises(IndexError, match=f"no batch {N}"):
        tr.fit(log=lambda _: None)
    assert tr.step == N


class _Tier:
    """The tier controller's hooks the trainer calls, recording their
    order."""

    def __init__(self, log):
        self.log = log

    def batch_fn(self, step):
        self.log.append(("batch", step, threading.current_thread().name))
        return _batch_fn(step)

    def pre_step(self, step, params, opt_state):
        self.log.append(("pre", step))
        return params, opt_state, {}

    def stats(self):
        return {}


def test_tiered_trainer_draws_inline():
    log = []
    tier = _Tier(log)
    tr = _trainer(tier=tier)
    obs.reset()
    tr.fit(log=lambda _: None)
    main = threading.main_thread().name
    assert log == [x for s in range(N)
                   for x in (("pre", s), ("batch", s, main))]
    assert _calls("input.prefetch.miss") == N
    assert _calls("input.prefetch.hit") == 0
    assert "input.prefetch" not in obs.totals()


def test_fault_injected_trainer_prefetches_and_stops_where_inline_did():
    """The injector's hooks run at each step boundary on the prefetch
    path too: a preempt it raises at step 3 checkpoints at 3, as the
    inline order did, and the batch made ahead for 3 is dropped."""
    threads = []

    def batch_fn(step):
        threads.append((step, threading.current_thread().name))
        return _batch_fn(step)

    inj = faults_lib.FaultInjector("slow_rank@1:0.0,preempt@3")
    tr = _trainer(batch_fn=batch_fn, faults=inj)
    seen = _record(tr)
    obs.reset()
    try:
        out = tr.fit(log=lambda _: None)
    finally:
        faults_lib.install(None)
    tr._input_pool.shutdown(wait=True)      # the dropped batch 3 is made
    assert out["preempted"] and out["step"] == 3
    assert [(a, b) for a, b, _ in seen] == [(s, s) for s in range(3)]
    main = threading.main_thread().name
    assert threads[0] == (0, main)
    assert all(t != main for _, t in threads[1:])
    assert [s for s, _ in threads] == [0, 1, 2, 3]
    assert _calls("train.tier") == 4
    assert _calls("input.prefetch.hit") == 2
    assert _calls("input.prefetch.miss") == 1


def test_log_line_counts_the_prefetched_steps():
    lines = []
    _trainer(total=4, log_every=2).fit(log=lines.append)
    steps = [x for x in lines if x.startswith("[trainer] step")]
    assert [x.split("prefetched ")[1][:3] for x in steps] == ["1/2", "2/2"]


def test_the_input_worker_ends_with_its_trainer():
    tr = _trainer()
    tr.fit(log=lambda _: None)
    workers = list(tr._input_pool._threads)
    assert len(workers) == 1 and workers[0].is_alive()
    del tr
    gc.collect()
    workers[0].join(timeout=30)
    assert not workers[0].is_alive()


def test_span_totals_lose_no_update_across_threads():
    """Spans close on the input worker and the main thread at once: the
    totals are updated under a lock."""
    n_threads, n = 2 * (os.cpu_count() or 1) + 1, 2000
    n_threads = min(n_threads, 64)
    obs.reset()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        for _ in range(n):
            with obs.span("x.span"):
                pass
            obs.count("x.count")

    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    tot = obs.totals()
    assert tot["x.span"]["calls"] == tot["x.count"]["calls"] == n_threads * n


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from repro import obs
from repro.launch.mesh import parse_mesh
from repro.optim import optimizers as opt_lib
from repro.train.trainer import Trainer, TrainerConfig

assert len(jax.devices()) == 4
mesh = parse_mesh("2x2")

def loss_fn(p, b):
    return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), {}

def batch_fn(step):
    r = np.random.default_rng(step)
    return {"x": r.normal(size=(8, 4)).astype(np.float32),
            "y": r.normal(size=(8, 1)).astype(np.float32)}

tr = Trainer(TrainerConfig(total_steps=3, log_every=0), loss_fn,
             {"w": jnp.zeros((4, 1), jnp.float32)}, opt_lib.adagrad(0.1),
             batch_fn, mesh=mesh)
obs.reset()
tr.fit(log=lambda _: None)
assert obs.totals()["input.prefetch.hit"]["calls"] == 2
ahead = tr._ahead.future.result()
want = tr.put_batch(batch_fn(3))
for k in want:
    assert isinstance(ahead[k].sharding, NamedSharding), ahead[k].sharding
    assert ahead[k].sharding == want[k].sharding, (k, ahead[k].sharding)
    assert ahead[k].sharding.spec[0] is not None      # rows over 'data'
    np.testing.assert_array_equal(np.asarray(ahead[k]), np.asarray(want[k]))
print("PREFETCH_MESH_OK")
"""


def test_mesh_prefetch_carries_put_batch_sharding():
    """On a (2, 2) mesh of forced host devices the batch made ahead is
    put as ``put_batch`` puts it: rows over 'data'."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "PREFETCH_MESH_OK" in r.stdout
