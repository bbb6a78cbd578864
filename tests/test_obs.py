"""The program's host spans (``repro.obs``, ``Trainer.fit``) and the named
scopes of the jitted step."""
import glob
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.optim import optimizers as opt_lib
from repro.resilience import faults as faults_lib
from repro.train.trainer import Trainer, TrainerConfig

DATA = Path(__file__).resolve().parent / "bench" / "data"
SCOPES = ("lma_locations", "pool_gather", "dense_net", "sparse_grad",
          "pool_update", "dense_update", "guard_check", "record", "provide")
STEP_SPANS = ["train.batch", "train.dispatch", "train.wait", "train.sync",
              "train.bookkeeping"]


def _toy_trainer(steps: int, log_every: int = 0, faults=None) -> Trainer:
    def loss_fn(p, b):
        loss = jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)
        return loss, {}

    def batch_fn(step):
        rng = np.random.default_rng(step)
        return {"x": rng.normal(size=(8, 4)).astype(np.float32),
                "y": rng.normal(size=(8,)).astype(np.float32)}

    return Trainer(TrainerConfig(total_steps=steps, log_every=log_every),
                   loss_fn, {"w": jnp.zeros((4,))}, opt_lib.sgd(0.1),
                   batch_fn, faults=faults)


def test_span_totals_count_calls_and_time():
    obs.reset()
    for _ in range(3):
        with obs.span("x.a") as s:
            pass
    with obs.step_span("x.step", 7):
        with obs.span("x.b"):
            pass
    tot = obs.totals()
    assert tot["x.a"]["calls"] == 3 and tot["x.b"]["calls"] == 1
    assert tot["x.step"]["calls"] == 1
    assert s.t1 >= s.t0 and tot["x.a"]["s"] >= 0
    obs.reset()
    assert obs.totals() == {}


def test_fit_counts_each_span_once_per_step_and_one_step_time_each():
    obs.reset()
    tr = _toy_trainer(3)
    tr.fit(log=lambda _: None)
    tot = obs.totals()
    for name in ["train.step"] + STEP_SPANS:
        assert tot[name]["calls"] == 3, name
    assert tot["train.resume"]["calls"] == 1
    assert tot["train.result"]["calls"] == 1
    assert "train.tier" not in tot          # no fault or tier hook
    assert len(tr._step_times) == 3


def test_step_time_spans_dispatch_to_wait_with_an_injected_delay():
    obs.reset()
    tr = _toy_trainer(3, faults=faults_lib.FaultInjector("slow_rank@2:0.3"))
    tr.fit(log=lambda _: None)
    assert obs.totals()["train.tier"]["calls"] == 3
    assert len(tr._step_times) == 3
    assert tr._step_times[2] >= 0.3 > tr._step_times[1]


def test_log_line_carries_the_host_split():
    lines = []
    _toy_trainer(4, log_every=2).fit(log=lines.append)
    steps = [x for x in lines if x.startswith("[trainer] step")]
    assert len(steps) == 2
    assert all(re.search(r"batch [\d.]+ ms, host [\d.]+ ms per step", x)
               for x in steps)


def _host_events(trace_dir: str):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("train."):
                    out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return out


def test_fit_spans_nest_under_the_step_annotation_in_order(tmp_path):
    tr = _toy_trainer(3)
    tr.cfg.total_steps = 1
    tr.fit(log=lambda _: None)              # compile outside the trace
    tr.cfg.total_steps = 4
    jax.profiler.start_trace(str(tmp_path))
    tr.fit(log=lambda _: None)
    jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    steps = sorted((s, e, st) for n, s, e, st in evs if n == "train.step")
    assert [int(st["step_num"]) for _, _, st in steps] == [1, 2, 3]
    for s0, s1, _ in steps:
        inside = sorted((s, n) for n, s, e, _ in evs
                        if n != "train.step" and s0 <= s and e <= s1)
        assert [n for _, n in inside] == STEP_SPANS
    names = [n for n, *_ in sorted(evs, key=lambda x: x[1])]
    assert names[0] == "train.resume" and names[-1] == "train.result"


def _smoke_step_hlo(config: str) -> str:
    """The lowered (pre-optimisation) HLO of the guarded train step of a
    smoke configuration on the split lookup path."""
    import sys
    sys.path.insert(0, str(DATA.parents[2]))
    from bench import program
    from repro.core.embedding import make_buffers
    from repro.core.signatures import build_signature_store, densify_store
    from repro.launch.train import make_optimizer
    from repro.models import recsys
    from repro.resilience import guard
    c = json.loads((DATA / f"{config}.json").read_text())
    arch, cfg = program.model_config(c)
    gen = program.generator(c, {"generator": {
        "n_clusters": 4, "p_signal": 0.8, "label_noise": 0.15,
        "value_dist": "geometric"}}, 1)
    e = cfg.embedding
    store = build_signature_store(gen.signature_rows(c["n_s"]), e.total_vocab,
                                  max_per_value=e.lma.max_set)
    bufs = make_buffers(e, densify_store(store, e.lma.max_set))
    params = jax.eval_shape(lambda k: recsys.init(k, cfg), jax.random.key(0))
    opt = make_optimizer(arch)
    step = guard.make_step(lambda p, b, bf: recsys.loss_fn(p, cfg, b, bf),
                           opt, sparse_grads=True)
    batch = program.device_batch(gen.batch(16, 0))
    return step.lower(params, jax.eval_shape(opt.init, params), batch,
                      np.float32(1.0), bufs).as_text(dialect="hlo", debug_info=True)


@pytest.mark.parametrize("config", ["dlrm-rm2-smoke", "xdeepfm-smoke"])
def test_step_ops_carry_every_scope(config, monkeypatch):
    from repro.kernels.fused_embed import ops as fe
    monkeypatch.setattr(fe, "ENABLED", False)     # the split path, as on a TPU
    names = re.findall(r'op_name="([^"]*)"', _smoke_step_hlo(config))
    comps = {re.sub(r"^(?:[\w.-]+\()+|\)+$", "", c)
             for n in names for c in n.split("/")}
    assert set(SCOPES) <= comps, set(SCOPES) - comps


def test_launcher_profile_holds_the_spans_and_scopes(tmp_path):
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=str(DATA.parents[2] / "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "lma-dlrm-criteo", "--smoke", "--steps", "3", "--batch", "32",
         "--n-signatures", "300", "--eval-batches", "1", "--profile-dir",
         str(tmp_path / "prof")], env=env, cwd=tmp_path, capture_output=True,
        text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    evs = _host_events(str(tmp_path / "prof"))
    assert sum(n == "train.step" for n, *_ in evs) == 3
    assert re.search(r"batch [\d.]+ ms, host [\d.]+ ms per step", r.stdout)
