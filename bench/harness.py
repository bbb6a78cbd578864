"""What every cell shares: finding a cell's files by name, the chip check,
the compile cache, phase timing, host spans and the result line.

Everything a cell is made of is found by name from ``BENCHMARK.json``: the
configuration file it names, ``bench/traffic/<traffic>.json``,
``bench/limits/<workload>.json`` (the limits of its correctness check),
``bench/metrics/<metric>.py`` (one reader per per-layer metric) and
``bench/cells/<kind>.py`` (the runner the traffic's ``kind`` names).
"""
from __future__ import annotations

import contextlib
import gzip
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from bench import trace

ROOT = Path(__file__).resolve().parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The workload ``name`` with its configuration, traffic, limits and
    metric entries, all resolved from ``root``'s ``BENCHMARK.json``."""
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    limits = root / "bench" / "limits" / f"{name}.json"

    def applies(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in names]
    return {"root": root, "workload": w,
            "config": load_json(root / conf["file"]),
            "traffic": load_json(root / "bench" / "traffic"
                                 / f"{w['traffic']}.json"),
            "limits": load_json(limits) if limits.exists() else None,
            "end_to_end": e2e, "per_layer": per_layer}


def load_reader(name: str, root: Path = ROOT):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(kind: str):
    return importlib.import_module(f"bench.cells.{kind}")


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR``, else a
    fixed directory in the checkout).  The program caches only compiles of
    a second or more; the benchmark caches every program, so that a warm
    set-up compiles nothing and its time stays steady."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_chips(n: int):
    """The devices to run on; exits non-zero when JAX finds no TPU or
    fewer than ``n`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"benchmark needs {n} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(1)
    return devs[:n]


def device_info(devs) -> dict:
    d = devs[0]
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devs)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class Phases:
    """Set-up phase times, printed as each phase ends."""

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        yield
        print(f"set-up phase {name}: {time.perf_counter() - t:.3f} s",
              flush=True)


def span(name: str):
    """A host span that the profiler's trace records (a no-op cost when no
    trace is running); the idle-gap attribution reads these names."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts backend compiles while ``active`` (none may run in a window)."""

    def __init__(self):
        import jax
        self.active, self.count = False, 0

        def listener(event, duration, **_):
            if self.active and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listener)


class Tracer:
    """The profiler over a window; ``stop`` returns the trace's reduction.
    The trace goes to a temporary directory that is removed after reading;
    with ``keep_dir`` its rows (``rows.json.gz``) and the list of its
    planes and lines (``layout.txt``) are kept there."""

    def __init__(self, keep_dir: str | None = None):
        import jax
        self.keep = keep_dir
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)

    def stop(self) -> dict | None:
        import jax
        jax.profiler.stop_trace()
        rows = trace.events(self.dir)
        if self.keep:
            os.makedirs(self.keep, exist_ok=True)
            with open(os.path.join(self.keep, "layout.txt"), "w") as f:
                f.write("\n".join(trace.layout(self.dir)) + "\n")
            with gzip.open(os.path.join(self.keep, "rows.json.gz"), "wt") as f:
                json.dump(rows, f)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace.reduce(rows)


def report(cell: dict, trace_on: bool, correct: bool, attempted: int,
           failed: int, values: dict, ctx: dict, device: dict,
           reduced: dict | None, checks: dict):
    """The result line: the end-to-end metrics, or with a trace the
    per-layer metrics that their readers find, the trace's busy time and
    its breakdown."""
    breakdown = None
    if trace_on:
        metrics = {}
        for m in cell["per_layer"]:
            v = load_reader(m["name"], cell["root"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device = {**device, "busy_s": reduced["busy_s"],
                      "window_s": reduced["window_s"]}
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    print_result(correct, attempted, failed, metrics, device, checks,
                 breakdown)


def print_result(correct: bool, attempted: int, failed: int, metrics: dict,
                 device: dict, checks: dict, breakdown: dict | None = None):
    """The checks on the last lines of stderr, then the result line."""
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)
