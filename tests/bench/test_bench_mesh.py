"""The four-chip mesh cell at smoke size on the CPU, and its readers.

A smoke copy of the cell (``dlrm-rm2-full-smoke`` under ``train_mesh``
traffic, batch 64) runs in a subprocess with 4 forced host devices, laid
out and driven as ``smoke.make_root`` / ``smoke.run_cell`` do: it reads
``correct``, and the half-batch fault and the control read not correct.
The scope reduction and the seven per-layer readers run on a synthetic
trace slice of two chips.
"""
import json
import os
import subprocess
import sys

import pytest

from smoke import DATA, REPO
from bench import harness, mesh_scopes, table_cost

CELL = "dlrm-rm2-full.train.4chip"
# Limits of the smoke copy on the CPU, set from readings on seeds 1-6
# (the program reads 0 on every loss, at most 3.0e-7 on grad and 9.7e-7
# on change; the control at least 1.8e-6 on grad; half a batch left out
# at least 2.2e-3 on a loss)
LIMITS = {"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5, "grad": 1e-6,
          "change": 3e-6}

_SMOKE = r"""
import argparse, contextlib, io, json, os, shutil, sys, tempfile, time
from pathlib import Path
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
REPO, DATA, CELL = Path(sys.argv[1]), Path(sys.argv[2]), sys.argv[3]
LIMITS = json.loads(sys.argv[4])
sys.path.insert(0, str(REPO))
import jax
from bench import checks, harness, ici, peaks
from bench.cells import train_mesh

root = Path(tempfile.mkdtemp()) / "checkout"
shutil.copytree(REPO / "bench", root / "bench",
                ignore=shutil.ignore_patterns("__pycache__"))
(root / "src").symlink_to(REPO / "src")
bench = json.loads((REPO / "BENCHMARK.json").read_text())
shutil.copy(DATA / "dlrm-rm2-full-smoke.json", root / "bench" / "configs")
traffic = json.loads((REPO / "bench/traffic/train_mesh.json").read_text())
traffic["batch"] = 64
(root / "bench/traffic/smoke_train_mesh.json").write_text(
    json.dumps(traffic))
name = "full-smoke.train_mesh"
bench["configs"].append({"name": "dlrm-rm2-full-smoke",
                         "source": "https://arxiv.org/abs/1906.00091",
                         "file": "bench/configs/dlrm-rm2-full-smoke.json",
                         "reduced": [], "why": "smoke preset"})
bench["workloads"].append({"name": name, "config": "dlrm-rm2-full-smoke",
                           "traffic": "smoke_train_mesh", "chips": 4,
                           "why": "smoke cell"})
for m in bench["end_to_end"] + bench["per_layer"]:
    if CELL in m.get("workloads", []):
        m["workloads"].append(name)
(root / "BENCHMARK.json").write_text(json.dumps(bench))
(root / "bench/limits" / f"{name}.json").write_text(
    json.dumps({"limits": LIMITS}))

cell = harness.load_cell(name, root)
devs = jax.devices()[:4]
kind = devs[0].device_kind
peaks.PEAKS.setdefault(kind, peaks.PEAKS["TPU v5e"])
ici.ICI_BYTES_PER_S.setdefault(kind, ici.ICI_BYTES_PER_S["TPU v5e"])
args = argparse.Namespace(workload=name, seed=7, seconds=0.5, trace=1,
                          trace_dir=None)
out = io.StringIO()
with contextlib.redirect_stdout(out):
    harness.runner(cell["traffic"]["kind"]).run(
        cell, args, devs, harness.Phases(), harness.CompileCounter(),
        time.perf_counter())
result = json.loads(out.getvalue().strip().splitlines()[-1])
r = train_mesh.readings(cell, 8, devs, with_program=False)
faults = {k: checks.verdict(r[k], {"limits": LIMITS})[0]
          for k in ("control", "half")}
print("SMOKE " + json.dumps({"result": result, "faults": faults}))
"""


@pytest.fixture(scope="module")
def smoke():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("REPRO_DIST_EXCHANGE", None)
    r = subprocess.run(
        [sys.executable, "-c", _SMOKE, str(REPO), str(DATA), CELL,
         json.dumps(LIMITS)], capture_output=True, text=True, env=env,
        timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    line = [x for x in r.stdout.splitlines() if x.startswith("SMOKE ")][-1]
    return json.loads(line[len("SMOKE "):])


@pytest.mark.slow
def test_smoke_mesh_cell_is_correct(smoke):
    r = smoke["result"]
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["control", "half"])
def test_smoke_mesh_control_and_half_batch_fail(smoke, fault):
    assert smoke["faults"][fault] is False


def test_cell_entries_resolve():
    """The cell, its configuration and traffic resolve from BENCHMARK.json,
    with the seven new per-layer metrics and no accepted one that lists
    other cells."""
    cell = harness.load_cell(CELL)
    assert cell["workload"]["chips"] == 4
    assert cell["traffic"]["kind"] == "train_mesh"
    assert cell["config"]["reduced"] == []
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "train_examples_per_s", "peak_hbm_gib"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "table_lookup_dev_ms.train_mesh", "table_update_dev_ms.train_mesh",
        "table_exchange_dev_ms.train_mesh",
        "table_lookup_roofline.train_mesh",
        "table_update_roofline.train_mesh",
        "table_exchange_roofline.train_mesh", "train_mfu.train_mesh"}


def test_full_config_sizes():
    c = json.loads((REPO / "bench/configs/dlrm-rm2-full.json").read_text())
    rows = sum(c["vocab_sizes"])
    assert rows == 33_762_577
    assert rows * c["d"] * 4 == 8_643_219_712
    # the heaviest chip's tables and accumulators, lane-packed and padded
    from repro.dist.sharding import row_table_shape
    per_chip = sum(2 * 4 * a * b // 4 for a, b in (
        row_table_shape((v, c["d"]), 4) for v in c["vocab_sizes"]))
    assert 4.0 < per_chip / 2**30 < 4.05


def test_flops_per_example_dlrm():
    c = json.loads((REPO / "bench/configs/dlrm-rm2-full.json").read_text())
    # bottom 13-512-256-64 (no input gradient), 351 dot pairs of d=64,
    # top 415-512-512-256-1; forward + 2x backward
    bot = 2 * (13 * 512 + 512 * 256 + 256 * 64)
    top = 2 * (415 * 512 + 512 * 512 + 512 * 256 + 256)
    inter = 2 * 351 * 64
    want = bot + (2 * bot - 2 * 13 * 512) + 3 * inter + 3 * top
    assert table_cost.train_flops_per_example(c) == want


def test_rows_per_chip_and_bytes():
    import numpy as np
    c = {"vocab_sizes": [8, 3], "d": 64}
    sparse = np.array([[0, 0], [1, 2], [7, 2], [7, 1]])
    # slabs of 2 rows (8 over 4 chips) and 1 row (3 padded to 4)
    r = table_cost.rows_per_chip(c, sparse, 4)
    assert r["lookups"] == [2 + 1, 0 + 1, 0 + 2, 2 + 0]
    assert r["distinct"] == [2 + 1, 0 + 1, 0 + 1, 1 + 0]
    assert table_cost.lookup_bytes(c, 4, 3) == 4 * (8 + 3 * 64 + 8 * 64)
    assert table_cost.update_bytes(c, 3) == 4 * 3 * (1 + 5 * 64)
    out = 4 * 4 * 2 * 64
    assert table_cost.exchange_bytes(c, 4, 4, "psum", "all_to_all") == \
        1.5 * out
    assert table_cost.exchange_bytes(c, 4, 4, "psum", "psum") == 3 * out
    ids = 4 * 1 * 2
    assert table_cost.exchange_bytes(c, 4, 4, "ring", "all_to_all") == \
        3 * ids + out + 0.75 * out
    assert table_cost.exchange_bytes(c, 4, 4, "all_to_all", "all_to_all") \
        == 3 * ids + 1.5 * out


_HLO = """HloModule jit_step

%fused_computation.1 (p: f32[8,128]) -> f32[8,128] {
  %p = f32[8,128]{1,0} parameter(0)
  ROOT %g = f32[8,128]{1,0} gather(%p), metadata={op_name="jit(step)/provide/jvp(table_lookup)/shard_map/gather"}
}

ENTRY %main (a: f32[8,128]) -> f32[8,128] {
  %a = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,128]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %all-reduce.3 = f32[8,128]{1,0} all-reduce(%fusion.1), replica_groups={}, metadata={op_name="jit(step)/provide/table_lookup/shard_map/psum"}
  %scatter.2 = f32[8,128]{1,0} scatter(%all-reduce.3), metadata={op_name="jit(step)/cond/dense_update/table_update/shard_map/scatter-add"}
  ROOT %fusion.9 = f32[8,128]{1,0} add(%scatter.2, %scatter.2), metadata={op_name="jit(step)/dense_net/add"}
}
"""


def _rows():
    """Two chips, two window steps: chip 1 is slower in every scope."""
    host = "/host:CPU"
    rows = [(host, "t", "bench.window", 0, 100_000, ""),
            (host, "t", "train.step", 1_000, 40_000, "0"),
            (host, "t", "train.step", 50_000, 40_000, "1")]
    for chip, k in (("/device:TPU:0", 1), ("/device:TPU:1", 2)):
        for s0 in (2_000, 52_000):
            rows += [(chip, "XLA Ops", "fusion.1", s0, 1_000 * k, ""),
                     (chip, "XLA Ops", "all-reduce.3", s0 + 3_000,
                      2_000 * k, ""),
                     (chip, "XLA Ops", "scatter.2", s0 + 8_000, 4_000 * k, ""),
                     (chip, "XLA Ops", "fusion.9", s0 + 20_000, 500, "")]
    return rows


def test_mesh_scopes_reduce_synthetic_slice():
    assert mesh_scopes.collective_instructions(_HLO) == {"all-reduce.3"}
    r = mesh_scopes.reduce(_rows(), _HLO)
    assert r["steps"] == 2
    c1 = r["chips"]["/device:TPU:1"]
    assert c1["table_lookup"] == pytest.approx(2 * (2_000 + 4_000) / 1e9)
    assert c1["table_lookup_exchange"] == pytest.approx(2 * 4_000 / 1e9)
    assert c1["table_update"] == pytest.approx(2 * 8_000 / 1e9)
    assert c1["table_update_exchange"] == 0.0
    assert mesh_scopes.slowest(r, "table_lookup") == (
        "/device:TPU:1", pytest.approx(6_000 / 1e9))


def test_mesh_readers_on_synthetic_slice():
    """The seven readers on the slice: ms per window step on the slowest
    chip, HBM shares over each scope's time less its collectives', the
    ICI share over the collectives' time, and the whole step's share of
    the chips' summed peak."""
    r = mesh_scopes.reduce(_rows(), _HLO)
    planes = ["/device:TPU:0", "/device:TPU:1"]
    ctx = {"table_scopes": r, "examples_per_s": 1000.0, "chips": 2,
           "mesh_flops_per_example": 1e9,
           "peak": {"hbm_bytes_per_s": 1e12, "bf16_flops": 1e14},
           "ici_bytes_per_s": 1e11,
           "table_bytes": {"table_lookup": dict.fromkeys(planes, 1000.0),
                           "table_update": dict.fromkeys(planes, 4000.0),
                           "exchange": dict.fromkeys(planes, 200.0)}}
    read = {m["name"]: harness.load_reader(m["name"]).read(ctx)
            for m in harness.load_cell(CELL)["per_layer"]}
    assert read["table_lookup_dev_ms.train_mesh"] == pytest.approx(6e-3)
    assert read["table_update_dev_ms.train_mesh"] == pytest.approx(8e-3)
    assert read["table_exchange_dev_ms.train_mesh"] == pytest.approx(4e-3)
    assert read["table_lookup_roofline.train_mesh"] == pytest.approx(
        100 * 1000 / (2e-6 * 1e12))
    assert read["table_update_roofline.train_mesh"] == pytest.approx(
        100 * 4000 / (8e-6 * 1e12))
    assert read["table_exchange_roofline.train_mesh"] == pytest.approx(
        100 * 200 / (4e-6 * 1e11))
    assert read["train_mfu.train_mesh"] == pytest.approx(
        100 * 1e9 * 1000 / (2 * 1e14))


def test_mesh_readers_read_nothing_without_a_trace():
    for m in harness.load_cell(CELL)["per_layer"]:
        assert harness.load_reader(m["name"]).read({}) is None
