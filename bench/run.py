"""Run one cell of the on-chip benchmark.

    python bench/run.py --workload dlrm-rm2.train --seed 7 --seconds 20 --trace 0

The cell (configuration, traffic, limits, metrics) is found by name from
``BENCHMARK.json``.  The run needs a TPU with the chips the cell asks for
and fails on anything else.  It prints set-up phases and progress, then,
as its last line, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device`` and, last, ``checks``: each number compared with the
reference beside its limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the trace here (default: a temporary "
                         "directory, removed after it is read)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    sys.stdout.reconfigure(line_buffering=True)
    from bench import harness
    cell = harness.load_cell(args.workload)
    cache = harness.setup_compile_cache()
    import jax
    devs = harness.require_chips(cell["workload"]["chips"])
    jax.config.update("jax_default_matmul_precision",
                      cell["config"]["matmul_precision"])
    print(f"device {devs[0].platform} {devs[0].device_kind} x {len(devs)}; "
          f"compile cache {cache}", flush=True)
    harness.runner(cell["traffic"]["kind"]).run(
        cell, args, devs, harness.Phases(), harness.CompileCounter(),
        T_PROCESS)


if __name__ == "__main__":
    main()
