"""A smoke-sized copy of the benchmark for CPU tests.

Importing it puts the repository root on ``sys.path``, so the test
modules import it before ``bench``.  ``make_root`` lays out a checkout in a temporary directory: the
benchmark's own files, the program's ``src``, and a ``BENCHMARK.json``
whose cells are added as data alone (a configuration file, a traffic
file and a limits file each).  ``run_cell`` drives a cell there on the
CPU, past the harness's look for a chip, and returns its result line.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(REPO) not in sys.path:          # the benchmark imports as ``bench``
    sys.path.insert(0, str(REPO))

TRAIN = {"kind": "train", "loop": "closed", "batch": 64, "check_steps": 3,
         "generator": {"n_clusters": 24, "p_signal": 0.8, "label_noise": 0.15,
                       "value_dist": "geometric"}}
# Limits for the smoke cells on the CPU, set from readings on seeds 1-3
# (the program reads at most 8.8e-8 on a loss, 8.1e-8 on grad and 1.1e-7
# on change; the control at least 2.0e-6 on grad; half a batch left out
# at least 1.1e-4 on a loss)
LIMITS = {"train": {"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5,
                    "grad": 6e-7, "change": 3e-6}}


def make_root(tmp: Path, cells: list[tuple[str, str, str]]) -> Path:
    """A checkout under ``tmp`` with the cells (workload, config, traffic
    kind) added as data: ``config`` names a file in ``tests/bench/data``."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, config, kind in cells:
        shutil.copy(DATA / f"{config}.json", root / "bench" / "configs")
        traffic = f"smoke_{kind}"
        (root / "bench" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(TRAIN))
        (root / "bench" / "limits" / f"{name}.json").write_text(
            json.dumps({"limits": LIMITS[kind]}))
        if config not in {c["name"] for c in bench["configs"]}:
            bench["configs"].append(
                {"name": config, "source": "https://arxiv.org/abs/1906.00091",
                 "file": f"bench/configs/{config}.json", "reduced": [],
                 "why": "smoke preset"})
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "smoke cell"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m and any(w.endswith("." + kind)
                                        for w in m["workloads"]):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_cell(root: Path, name: str, seed: int = 3, seconds: float = 1.0,
             trace: int = 0) -> dict:
    """Run cell ``name`` of ``root`` on the CPU; the parsed result line."""
    import jax

    from bench import harness, peaks
    cell = harness.load_cell(name, root)
    devs = jax.devices()[:1]
    kind = devs[0].device_kind
    peaks.PEAKS.setdefault(kind, peaks.PEAKS["TPU v5e"])
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace, trace_dir=None)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        harness.runner(cell["traffic"]["kind"]).run(
            cell, args, devs, harness.Phases(), harness.CompileCounter(),
            time.perf_counter())
    return json.loads(out.getvalue().strip().splitlines()[-1])
