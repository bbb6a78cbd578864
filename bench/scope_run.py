"""Run one training cell's window under the profiler and read it by the
program's named scopes and host spans (``bench.scopes``).

    python3 bench/scope_run.py --workload dlrm-rm2.train --seed 7 --seconds 20

Set-up is the cell's own (``bench.cells.train.build`` and its check steps,
without the reference); the window calls ``Trainer.fit`` once per step
inside ``bench.window`` / ``bench.step`` spans, as the cell's runner does.
The last line is one JSON object: the scope metrics (``bench.scopes
.metrics``), the set-up spans (``dprime.densify``, ``dprime.put``), the
reduction's clock offset, per-pass times and spanning share, and
``bench.trace.reduce``'s busy time of the same trace to compare with.
With ``--out`` the trace's rows (``scope_rows.json.gz``) and the step's
compiled HLO (``step.hlo.txt``) are kept there.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def span_cost_us(n: int = 100_000) -> float:
    """Microseconds per ``repro.obs`` span with no profiler running."""
    from repro import obs
    t = time.perf_counter()
    for _ in range(n):
        with obs.span("bench.span_cost"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def step_hlo(trainer, batch) -> str:
    """The compiled HLO text of the trainer's jitted step for ``batch``."""
    import numpy as np
    return trainer._jit_step.lower(
        trainer.params, trainer.opt_state, batch, np.float32(1.0),
        *trainer.loss_args).compile().as_text()


def run(cell: dict, seed: int, seconds: float, out: str | None = None,
        t_process: float = T_PROCESS) -> dict:
    """Set up ``cell``, trace a window of ``seconds`` and reduce it."""
    import jax
    from bench import harness, program, scopes, trace
    from bench.cells import train
    from repro import obs
    phases = harness.Phases()
    t = cell["traffic"]
    cfg, gen, rows, bufs, trainer, input_ms = train.build(cell, seed, phases)
    for i in range(t["check_steps"]):
        with phases(f"step_{i + 1}"):
            train.step(trainer)
    setup_s = time.perf_counter() - t_process
    tdir = tempfile.mkdtemp(prefix="bench-scopes-")
    jax.profiler.start_trace(tdir)
    n_steps, t0 = 0, time.perf_counter()
    with harness.span(trace.WINDOW):
        while time.perf_counter() < t0 + seconds:
            with harness.span("bench.step"):
                last = train.step(trainer)
            n_steps += 1
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    srows, trows = scopes.rows(tdir), trace.events(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    hlo = step_hlo(trainer, program.device_batch(gen.batch(t["batch"], 0)))
    red = scopes.reduce(srows, hlo)
    old = trace.reduce(trows)
    if out:
        os.makedirs(out, exist_ok=True)
        with gzip.open(os.path.join(out, "scope_rows.json.gz"), "wt") as f:
            json.dump(srows, f)
        with open(os.path.join(out, "step.hlo.txt"), "w") as f:
            f.write(hlo)
    tot = obs.totals()
    return {"workload": cell["workload"]["name"], "seed": seed,
            "setup_s": setup_s, "window_s": window_s, "steps": n_steps,
            "loss": last["loss"],
            "traced_examples_per_s": n_steps * t["batch"] / window_s,
            "metrics": scopes.metrics(red),
            "dprime_densify_s": tot.get("dprime.densify", {}).get("s"),
            "dprime_put_s": tot.get("dprime.put", {}).get("s"),
            "span_off_us": span_cost_us(),
            "trace_busy_s": old and old["busy_s"],
            "trace_window_s": old and old["window_s"],
            "scopes": red}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    import jax
    # the cache's key leaves op metadata out by default, so a step compiled
    # before the program had its scopes would be served with its old names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    devs = harness.require_chips(cell["workload"]["chips"])
    jax.config.update("jax_default_matmul_precision",
                      cell["config"]["matmul_precision"])
    out = run(cell, args.seed, args.seconds, args.out)
    out["device"] = devs[0].device_kind
    print(json.dumps(out))


if __name__ == "__main__":
    main()
