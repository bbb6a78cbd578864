"""``correct`` at smoke size on the CPU: true for the program as it is,
false for the control (the reference at three bfloat16 passes in the
program's place) and for each planted fault of the timed path."""
import numpy as np
import pytest

import jax.numpy as jnp

from smoke import LIMITS, make_root, run_cell
from bench import calibrate, checks, harness

CELLS = [("dlrm-smoke.train", "dlrm-rm2-smoke", "train"),
         ("xdeepfm-smoke.train", "xdeepfm-smoke", "train")]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        # the CPU would run the fused engine in interpret mode; the chip
        # never dispatches it (repro.kernels.dispatch)
        from repro.kernels.fused_embed import ops
        mp.setattr(ops, "ENABLED", False)
        yield make_root(tmp_path_factory.mktemp("bench"), CELLS)


@pytest.mark.parametrize("name", [c[0] for c in CELLS])
def test_sound_run_is_correct(root, name):
    r = run_cell(root, name, seconds=0.5)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("name", ["dlrm-smoke.train", "xdeepfm-smoke.train"])
def test_control_fails_a_train_limit(root, name):
    cell = harness.load_cell(name, root)
    r = calibrate.train_readings(cell, 4, with_program=False)
    assert not checks.verdict(r["control"], {"limits": LIMITS["train"]})[0]
    assert not checks.verdict(r["half"], {"limits": LIMITS["train"]})[0]


def test_verdict_needs_every_limit_and_a_finite_number():
    limits = {"limits": {"loss1": 1e-5, "grad": 1e-5}}
    assert checks.verdict({"loss1": 0.0, "grad": 1e-6}, limits)[0]
    assert not checks.verdict({"loss1": 0.0, "grad": 1e-6}, None)[0]
    assert not checks.verdict({"loss1": 0.0, "change": 0.0}, limits)[0]
    assert not checks.verdict({"loss1": float("nan"), "grad": 0.0}, limits)[0]


def _state_unchanged(mp):
    from repro.resilience import guard
    mp.setattr(guard, "apply_updates", lambda p, u: p)


def _half_batch(mp):
    from repro.models import recsys
    loss_fn = recsys.loss_fn

    def half(p, cfg, batch, buffers=None):
        n = batch["label"].shape[0] // 2
        return loss_fn(p, cfg, {k: v[:n] for k, v in batch.items()}, buffers)
    mp.setattr(recsys, "loss_fn", half)


def _answer_altered(mp):
    from repro.models import recsys
    forward = recsys.forward
    mp.setattr(recsys, "forward",
               lambda *a, **k: forward(*a, **k).at[0].add(jnp.float32(1.0)))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault,name", [
    ("state_unchanged", "dlrm-smoke.train"),
    ("half_batch", "dlrm-smoke.train"),
    ("answer_altered", "xdeepfm-smoke.train")])
def test_planted_fault_is_not_correct(root, fault, name):
    with pytest.MonkeyPatch.context() as mp:
        FAULTS[fault](mp)
        r = run_cell(root, name, seed=5, seconds=0.3)
    assert not r["correct"], r["checks"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
    assert np.isfinite([c["value"] for c in r["checks"].values()]).all()
