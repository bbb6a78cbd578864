"""The trace reduction: busy union, idle share, top device ops and the
idle gaps charged to host spans."""
import gzip
import json

import pytest

from smoke import DATA
from bench import trace

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _rows():
    ms = 1_000_000
    return [
        (HOST, "python", "bench.window", 0, 100 * ms),
        (HOST, "python", "bench.step", 0, 60 * ms),
        (HOST, "python", "bench.input", 0, 20 * ms),
        (HOST, "python", "bench.step", 60 * ms, 40 * ms),
        (DEV, "XLA Ops", "fusion.1", 20 * ms, 30 * ms),
        (DEV, "XLA Ops", "fusion.2", 40 * ms, 20 * ms),     # overlaps fusion.1
        (DEV, "XLA Ops", "scatter", 70 * ms, 10 * ms),
        (DEV, "XLA Ops", "late", 95 * ms, 10 * ms),         # runs past the window
    ]


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce(_rows())
    # busy: [20, 60) + [70, 80) + [95, 100) ms
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["idle_share"] == pytest.approx(0.45)


def test_top_ops_sum_their_self_time_in_the_window():
    ops = dict(trace.reduce(_rows())["device_ops"])
    # fusion.2 starts inside fusion.1: fusion.1 keeps 30 - 10 ms
    assert ops == pytest.approx({"fusion.1": 0.02, "fusion.2": 0.02,
                                 "scatter": 0.01, "late": 0.005})


def test_short_name_keeps_instruction_shape_and_opcode():
    hlo = ("%fusion.2 = f32[6815744]{0:T(1024)} fusion(f32[135053312]{0} "
           "%p, s32[6815744]{0} %i), kind=kCustom, calls=%fused_computation.2")
    assert trace.short_name(hlo) == "fusion.2 f32[6815744] fusion"
    assert trace.short_name("copy.3") == "copy.3"


def test_idle_gaps_go_to_the_innermost_overlapping_span():
    gaps = dict(trace.reduce(_rows())["idle_gaps"])
    # [0, 20): input (inside the first step); [60, 70) and [80, 95): step
    assert gaps == pytest.approx({"bench.input": 0.02, "bench.step": 0.025})


def test_no_window_or_no_device_reads_nothing():
    rows = _rows()
    assert trace.reduce(rows[1:]) is None
    assert trace.reduce([r for r in rows if r[0] != DEV]) is None


def test_devices_are_averaged():
    rows = _rows() + [("/device:TPU:1", "XLA Ops", "fusion.1", 0, 100_000_000)]
    r = trace.reduce(rows)
    assert r["busy_s"] == pytest.approx((0.055 + 0.1) / 2)


def _chip_slice():
    """65 ms of a dlrm-rm2.train trace recorded on a TPU v5 lite, across
    the boundary of two steps (device op names shortened)."""
    with gzip.open(DATA / "dlrm_trace_slice.json.gz", "rt") as f:
        return [tuple(r) for r in json.load(f)]


def test_chip_slice_busy_matches_a_painted_timeline():
    import numpy as np
    rows = _chip_slice()
    r = trace.reduce(rows)
    (w0, w1), = [(s, s + d) for _, _, n, s, d in rows if n == trace.WINDOW]
    painted = np.zeros((w1 - w0) // 1000 + 1, bool)       # 1 us cells
    for p, _, _, s, d in rows:
        if p.startswith(DEV):
            painted[max(s - w0, 0) // 1000:max(min(s + d, w1) - w0, 0) // 1000] = True
    assert r["window_s"] == pytest.approx(0.065)
    assert r["busy_s"] == pytest.approx(painted.sum() / 1e6, abs=2e-4)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_chip_slice_top_op_is_the_pool_scatter():
    r = trace.reduce(_chip_slice())
    name, seconds = r["device_ops"][0]
    assert name == "fusion.6 f32[135053312] fusion"     # writes the 135M pool
    assert 0.03 < seconds < 0.04
    assert len(r["device_ops"]) == 10
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.step", "bench.input",
                                              trace.NO_SPAN}
