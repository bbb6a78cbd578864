"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, print memory/cost analysis, and persist roofline inputs.

The first two statements MUST set XLA_FLAGS before any other import (jax locks
the device count at first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all          # every cell, both meshes

Each cell writes experiments/dryrun/<arch>__<shape>__<mesh>.json with:
  memory_analysis (per-device bytes), cost_analysis (FLOPs/bytes),
  per-collective byte totals parsed from the partitioned HLO.
"""
import os
# The LICM disables are measurement methodology, not a perf tweak: XLA:CPU has
# no native bf16, so float-normalization inserts bf16->f32 converts which LICM
# then hoists out of the layer scan — materializing an f32 SHADOW COPY of every
# stacked bf16 weight/cache (2x its true size) that no TPU compilation creates.
# With hoisting off, converts stay per-layer-slice (transient), matching the
# TPU working set.  See EXPERIMENTS.md §Dry-run "CPU-measurement caveats".
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion,"
    "while-loop-expensive-invariant-code-motion")

import argparse
import json
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs.base import get_config, list_archs
from repro.dist.context import use_mesh
from repro.launch.mesh import make_production_mesh, n_chips
from repro.launch.steps import build_cell

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Sum byte sizes of all array shapes in an HLO result-type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-collective-kind output-byte totals from partitioned HLO (per device).

    Methodology: the bytes of each collective's *result* shape are a per-device
    traffic proxy (all-gather result = bytes received; all-reduce in a ring
    moves ~2x its buffer — we report buffer bytes and note the factor in
    EXPERIMENTS.md).  Async '-start' ops carry an (operand, result) tuple: the
    largest member is counted once; '-done' ops are skipped.
    """
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        eq = line.find(" = ")
        if eq < 0:
            continue
        rhs = line[eq + 3:]
        for coll in _COLLECTIVES:
            pos = rhs.find(coll + "(")
            if pos < 0:
                pos = rhs.find(coll + "-start(")
            if pos < 0:
                continue
            shape_str = rhs[:pos]
            shapes = [_shape_bytes(s + "]") for s in shape_str.split("]")
                      if "[" in s]
            if not shapes:
                break
            is_tuple_async = shape_str.lstrip().startswith("(")
            nbytes = max(shapes) if (is_tuple_async and coll != "all-to-all") \
                else sum(shapes)
            out[coll]["count"] += 1
            out[coll]["bytes"] += nbytes
            break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def run_cell(arch_id: str, shape_id: str, multi_pod: bool,
             save: bool = True, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    t0 = time.time()
    with use_mesh(mesh):
        bundle = build_cell(arch_id, shape_id, mesh)
        jitted = jax.jit(bundle.fn,
                         in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings,
                         donate_argnums=bundle.donate)
        lowered = jitted.lower(*bundle.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = dict(compiled.cost_analysis())
    colls = parse_collectives(compiled.as_text())
    result = {
        "arch": arch_id, "shape": shape_id, "mesh": mesh_name,
        "chips": n_chips(mesh),
        "kind": bundle.meta.get("kind"),
        "meta": bundle.meta,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_device_bytes": int(mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     - mem.alias_size_in_bytes),
        },
        "cost": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        },
        "collectives": colls,
    }
    if verbose:
        print(f"[dryrun] {arch_id} x {shape_id} @ {mesh_name}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory/device: args {result['memory']['argument_bytes']/2**30:.3f} GiB, "
              f"temp {result['memory']['temp_bytes']/2**30:.3f} GiB, "
              f"out {result['memory']['output_bytes']/2**30:.3f} GiB "
              f"(alias {result['memory']['alias_bytes']/2**30:.3f})")
        print(f"  cost: {result['cost']['flops']:.3e} flops, "
              f"{result['cost']['bytes_accessed']:.3e} bytes")
        print(f"  collectives/device: {colls['total_bytes']/2**20:.1f} MiB over "
              + ", ".join(f"{k}:{v['count']}" for k, v in colls.items()
                          if isinstance(v, dict) and v["count"]))
        emb = bundle.meta.get("embedding")
        if emb:   # registry describe(): honest alpha from param_count()
            print(f"  embedding: {emb['kind']} ({emb['family']}) "
                  f"params {emb['param_count']:,} "
                  f"alpha {emb['expansion_rate']:.1f}")
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        fname = f"{arch_id}__{shape_id}__{mesh_name}.json"
        with open(os.path.join(ARTIFACT_DIR, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def all_cells() -> list[tuple[str, str]]:
    cells = []
    for arch_id in list_archs():
        if arch_id.startswith("lma-dlrm"):
            continue  # the paper's bench-scale config; not part of the 40 cells
        cfg = get_config(arch_id)
        for shape in cfg.shapes:
            cells.append((arch_id, shape))
    return cells


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args(argv)

    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    failures = []
    for arch_id, shape_id in cells:
        for mp in meshes:
            try:
                run_cell(arch_id, shape_id, mp)
            except Exception:
                failures.append((arch_id, shape_id, mp))
                traceback.print_exc()
    if failures:
        print(f"FAILED cells: {failures}")
        sys.exit(1)
    print(f"dry-run OK: {len(cells)} cell(s) x {len(meshes)} mesh(es)")


if __name__ == "__main__":
    main()
