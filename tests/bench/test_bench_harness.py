"""The harness finds every file by name, refuses a run without a TPU, and
loads a cell that is added as data alone."""
import json
import os
import re
import subprocess
import sys

import pytest

from smoke import REPO, make_root
from bench import harness, peaks, program

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_loads_by_name(workload):
    cell = harness.load_cell(workload, REPO)
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert cell["traffic"]["kind"] == "train"
    assert harness.runner(cell["traffic"]["kind"]).run
    assert cell["limits"] is not None, "every cell's limits are a file"
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s",
                                                       "peak_hbm_gib"}
    assert cell["per_layer"], "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_reader_loads_by_name(metric):
    reader = harness.load_reader(metric, REPO)
    assert reader.read({}) is None      # nothing to read: no number


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_config_is_the_programs(config):
    c = json.loads((REPO / "bench" / "configs" / f"{config}.json").read_text())
    arch, cfg = program.model_config(c)
    assert cfg.embedding.lma.m == c["lma"]["m"]


def test_names_and_keys_keep_to_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert BENCH["end_to_end"][0]["name"] == "setup_s"


def test_program_config_mismatch_is_refused():
    c = json.loads((REPO / "bench" / "configs" / "dlrm-rm2.json").read_text())
    c["top_mlp"] = [256, 1]
    with pytest.raises(SystemExit):
        program.model_config(c)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_dummy_cell_added_as_data_loads(tmp_path):
    root = make_root(tmp_path, [("dummy.train", "dlrm-rm2-smoke", "train")])
    cell = harness.load_cell("dummy.train", root)
    assert cell["config"]["variant"] == "smoke"
    assert cell["traffic"]["batch"] == 64
    assert cell["limits"]["limits"]["loss1"] > 0
    assert "train_examples_per_s" in {m["name"] for m in cell["end_to_end"]}
    assert "step_ms.train" in {m["name"] for m in cell["per_layer"]}


def test_compile_cache_is_the_programs_and_keeps_every_program(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=f"{REPO}:{REPO / 'src'}")
    code = ("import jax; from bench import harness; "
            "from repro.launch import compile_cache as c; "
            "p = harness.setup_compile_cache(); "
            "assert p == str(c.DEFAULT_DIR), p; "
            "assert jax.config.jax_compilation_cache_dir == p; "
            "assert jax.config.jax_persistent_cache_min_compile_time_secs == 0")
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def _run(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dlrm-rm2.train",
         "--seed", "1", "--seconds", "1", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_run_refuses_a_non_tpu_backend():
    r = _run(REPO)
    assert r.returncode != 0
    assert "TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    import shutil
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
