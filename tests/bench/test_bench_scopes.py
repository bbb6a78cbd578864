"""The scope reduction: device self time by named scope, idle time charged
per instant to the program's host spans, and the host/device clock offset
from each step's constraints."""
import random

import pytest

from smoke import DATA  # noqa: F401  (puts the repository on sys.path)
from bench import scopes, trace

DEV = "/device:TPU:0"
HOST = "/host:CPU"
US, MS = 1_000, 1_000_000
P = "jit(step)/provide/"


def _host(name, a, b, info=""):
    return (HOST, "python", name, a, b - a, info)


def _op(name, a, b, op_name):
    return (DEV, "XLA Ops", name, a, b - a, op_name)


def _run(a, b):
    """One run of the step program on the device's ``XLA Modules`` line."""
    return (DEV, scopes.MODULES_LINE, "jit_step(1)", a, b - a, "")


def _one_step():
    """One 100 ms step: batch [0, 30), dispatch [30, 32), wait [32, 90),
    sync [90, 95); the device busy [31, 89) ms."""
    return [
        _host("bench.window", 0, 100 * MS),
        _host("train.step", 0, 100 * MS, "0"),
        _host("train.batch", 0, 30 * MS),
        _host("train.dispatch", 30 * MS, 32 * MS),
        _host("train.wait", 32 * MS, 90 * MS),
        _host("train.sync", 90 * MS, 95 * MS),
        _run(31 * MS, 89 * MS),
        _op("fusion.1", 31 * MS, 50 * MS, P + "jvp(lma_locations)/gather"),
        _op("fusion.2", 50 * MS, 70 * MS, P + "jvp(pool_gather)/gather"),
        _op("cond.1", 70 * MS, 89 * MS, "jit(step)/cond"),
        _op("fusion.3", 72 * MS, 88 * MS,
            "jit(step)/cond/branch_1_fun/dense_update/pool_update/scatter"),
    ]


def test_idle_is_charged_instant_by_instant_not_gap_by_gap():
    r = scopes.reduce(_one_step())
    assert r["clock"]["offset_us"] == [0.0]
    idle = r["idle_s"]
    # [0, 31): 30 ms of batch, 1 of dispatch; [89, 100): 1 ms of wait,
    # 5 of sync, 5 of the step itself after the sync
    assert idle == pytest.approx({"train.batch": 0.030, "train.dispatch": 0.001,
                                  "train.wait": 0.001, "train.sync": 0.005,
                                  "train.step": 0.005})
    m = scopes.metrics(r)
    assert m["idle_batch_ms.train"] == pytest.approx(30.0)
    assert m["idle_host_ms.train"] == pytest.approx(12.0)
    # the whole-gap rule gives [0, 31) to the span overlapping it most,
    # the step, and nothing to the input span inside it
    gaps = dict(trace.reduce([x[:5] for x in _one_step()
                              if not x[2].startswith("train.")]
                             + [(HOST, "python", "bench.step", 0, 100 * MS),
                                (HOST, "python", "bench.input", 0, 30 * MS)])
                ["idle_gaps"])
    assert gaps == pytest.approx({"bench.step": 0.042})


def test_scope_self_times_sum_to_busy_time():
    rows = _one_step()
    r = scopes.reduce(rows)
    busy = trace.reduce([x[:5] for x in rows])["busy_s"]
    assert sum(r["scope_s"].values()) == pytest.approx(busy)
    assert r["scope_s"] == pytest.approx({"lma_locations": 0.019,
                                          "pool_gather": 0.020,
                                          "other": 0.003,      # the cond's own
                                          "pool_update": 0.016})
    m = scopes.metrics(r)
    assert sum(m[k] for k in scopes.DEV_METRICS) == pytest.approx(busy * 1e3)
    assert r["pass_scope_s"]["provide/lma_locations"] == pytest.approx(0.019)


def _steps(shift_ns: int, n: int = 12, seed: int = 0):
    """``n`` steps of 10 ms whose device runs start 10-40 us after their
    dispatch and end 10-40 us before their wait ends, on a device clock
    ``shift_ns`` behind the host's."""
    rng = random.Random(seed)
    rows = [_host("bench.window", 0, n * 10 * MS)]
    for k in range(n):
        t = k * 10 * MS
        a, b = rng.randint(10, 40) * US, rng.randint(10, 40) * US
        rows += [_host("train.step", t, t + 10 * MS, str(k)),
                 _host("train.batch", t, t + 2 * MS),
                 _host("train.dispatch", t + 2 * MS, t + 3 * MS),
                 _host("train.wait", t + 3 * MS, t + 9 * MS),
                 _run(t + 2 * MS + a - shift_ns, t + 9 * MS - b - shift_ns),
                 _op(f"fusion.{k}", t + 2 * MS + a - shift_ns,
                     t + 9 * MS - b - shift_ns, P + "jvp(dense_net)/dot")]
    return rows


def test_a_planted_clock_shift_is_recovered():
    r = scopes.reduce(_steps(300 * US))
    off, = r["clock"]["offset_us"]
    assert off == pytest.approx(300, abs=20)
    lo, = r["clock"]["lo_us"]
    hi, = r["clock"]["hi_us"]
    assert lo <= 300 <= hi and r["clock"]["steps"] == [12]
    # shifted back, the runs leave 1 ms of batch idle per step in view
    m = scopes.metrics(r)
    assert m["idle_batch_ms.train"] == pytest.approx(2.0, abs=0.05)


def test_contradictory_constraints_read_no_idle_metric():
    rows = _steps(0, n=2)
    # the second step's run starts 1 ms before its own dispatch and ends
    # 1 ms after its wait: no single offset satisfies it and the first
    rows = [r for r in rows if r[1] != scopes.MODULES_LINE or r[3] < 10 * MS]
    rows += [_run(11 * MS, 20 * MS)]
    r = scopes.reduce(rows)
    assert r["clock"] is None and r["idle_s"] is None
    m = scopes.metrics(r)
    assert "idle_batch_ms.train" not in m and "dense_dev_ms.train" in m
    assert scopes.clock_offset([(0, 10)], [(5, 20)]) is None


def test_scope_is_the_innermost_named_one():
    assert scopes.scope_of(P + "transpose(jvp(dense_net))/dot_general") \
        == "dense_net"
    assert scopes.scope_of("jit(step)/cond/branch_1_fun/dense_update/"
                           "pool_update/add") == "pool_update"
    assert scopes.scope_of("jit(step)/cond") == scopes.OTHER
    assert scopes.scope_of("") == scopes.OTHER
    assert scopes.pass_of("jit(step)/record/jvp(lma_locations)/x") == "record"
    assert scopes.scopes_of(P + "jvp(dense_net)/mul;" + P
                            + "jvp(pool_gather)/gather") == {"dense_net",
                                                             "pool_gather"}


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %a = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/provide/jvp(dense_net)/add"}
  ROOT %g = f32[8]{0} gather(%a), metadata={op_name="jit(step)/provide/jvp(pool_gather)/gather"}
}

ENTRY %main.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %sort.3 = f32[8]{0} sort(%p), metadata={op_name="jit(step)/sparse_grad/sort"}
  ROOT %fusion.1 = f32[8]{0} fusion(%sort.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/provide/jvp(pool_gather)/gather"}
}
"""


def test_compiled_hlo_names_each_instruction_and_flags_spanning_fusions():
    names = scopes.hlo_op_names(HLO)
    assert names["sort.3"] == ("jit(step)/sparse_grad/sort", False)
    assert names["fusion.1"] == ("jit(step)/provide/jvp(pool_gather)/gather",
                                 True)
    rows = [_host("bench.window", 0, 10 * MS),
            _host("train.step", 0, 10 * MS, "0"),
            _host("train.dispatch", 0, 1 * MS),
            _host("train.wait", 1 * MS, 10 * MS),
            _run(1 * MS, 10 * MS),
            _op("%sort.3 = f32[8]{0} sort(f32[8]{0} %p)", 1 * MS, 4 * MS, ""),
            _op("fusion.1 f32[8] fusion", 4 * MS, 10 * MS, "")]
    r = scopes.reduce(rows, HLO)
    assert r["scope_s"] == pytest.approx({"sparse_grad": 0.003,
                                          "pool_gather": 0.006})
    assert r["spanning_share"] == pytest.approx(6 / 9)
    assert r["op_name_source"] == {"hlo": 2}


def test_no_window_no_device_or_no_step_reads_nothing():
    rows = _one_step()
    assert scopes.reduce(rows[1:]) is None
    assert scopes.reduce([r for r in rows if r[0] != DEV]) is None
    assert scopes.reduce([r for r in rows if r[2] != "train.step"]) is None
    assert scopes.metrics(None) == {}


@pytest.mark.parametrize("metric,span", [("dprime_densify_s.train",
                                          "dprime.densify"),
                                         ("dprime_put_s.train", "dprime.put")])
def test_set_up_span_readers(metric, span, monkeypatch):
    import sys
    from bench import harness
    from repro import obs
    reader = harness.load_reader(metric)
    obs.reset()
    assert reader.read({"examples_per_s": 1.0}) is None     # span not run
    with obs.span(span):
        pass
    assert reader.read({"examples_per_s": 1.0}) > 0
    assert reader.read({}) is None
    import repro                                            # an older program
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reader.read({"examples_per_s": 1.0}) is None


def test_scope_run_off_the_chip_keeps_spans_and_reads_no_device(tmp_path):
    import gzip
    import json
    from smoke import make_root
    from bench import harness, scope_run
    root = make_root(tmp_path, [("dummy.train", "dlrm-rm2-smoke", "train")])
    out = tmp_path / "out"
    r = scope_run.run(harness.load_cell("dummy.train", root), 3, 0.5,
                      out=str(out))
    assert r["steps"] > 0 and r["dprime_put_s"] > 0 and r["span_off_us"] > 0
    assert r["scopes"] is None and r["metrics"] == {}     # no TPU plane
    with gzip.open(out / "scope_rows.json.gz", "rt") as f:
        rows = json.load(f)
    assert sum(x[2] == "train.step" for x in rows) == r["steps"]
    assert "dense_net" in (out / "step.hlo.txt").read_text()


def _scope_slice():
    """65 ms of a dlrm-rm2.train trace recorded on a TPU v5 lite with the
    program's spans and scopes, across the boundary of two steps (op
    names shortened, each op's op_name resolved from the step's compiled
    HLO)."""
    import gzip
    import json
    with gzip.open(DATA / "dlrm_scope_slice.json.gz", "rt") as f:
        return [tuple(r) for r in json.load(f)]


def test_chip_scope_slice_self_times_match_the_busy_union():
    rows = _scope_slice()
    r = scopes.reduce(rows)
    old = trace.reduce([x[:5] for x in rows])
    assert r["window_s"] == pytest.approx(0.0652552)
    assert sum(r["scope_s"].values()) == pytest.approx(old["busy_s"], abs=1e-5)
    top = max(r["scope_s"], key=r["scope_s"].get)
    assert top == "pool_update"                     # the 135M-slot scatter
    assert r["scope_s"]["pool_update"] == pytest.approx(
        dict(old["device_ops"])["fusion.6 f32[135053312] fusion"])
    assert 0.015 < r["scope_s"]["pool_gather"] < 0.016
    assert 0.010 < r["scope_s"]["lma_locations"] < 0.011
    # the location math runs once, in the provide pass (CSE merged the
    # record pass's copy into it)
    assert not any(k.startswith("record/") for k in r["pass_scope_s"])


def test_chip_scope_slice_clock_and_idle_by_span():
    r = scopes.reduce(_scope_slice())
    off, = r["clock"]["offset_us"]
    lo, = r["clock"]["lo_us"]
    hi, = r["clock"]["hi_us"]
    assert lo < off < hi and r["clock"]["steps"] == [2]
    assert off == pytest.approx(218.266, abs=0.01)
    idle = r["idle_s"]
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["shifted_busy_s"])
    # most of the idle between two steps is batch making; the whole-gap
    # rule of bench.trace gives all of it to one bench span instead
    assert 0.013 < idle["train.batch"] < 0.0135
    assert 0.001 < idle["train.sync"] < 0.0015
    m = scopes.metrics(r)
    assert m["idle_batch_ms.train"] + m["idle_host_ms.train"] == pytest.approx(
        sum(idle.values()) * 1e3)
    old = dict(trace.reduce([x[:5] for x in _scope_slice()])["idle_gaps"])
    assert list(old) == ["bench.input"]
