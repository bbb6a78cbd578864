"""Readings for the limits of a cell's correctness check, over many seeds in
one process (the set-up is long; the compiled programs are shared).

For each seed it prints one JSON line with the numbers that decide
``correct`` for:

- ``program``: the program's check steps (set-up and the first steps
  exactly as a benchmark run makes them, no window);
- ``control``: the reference at the precision below the configuration's
  (three bfloat16 passes for float32 at ``highest``) in the program's place;
- ``half``: the reference with half of each batch left out and the mean
  taken over the rest, in the program's place.

    python bench/calibrate.py --workload dlrm-rm2.train --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def train_readings(cell, seed, with_program: bool) -> dict:
    from bench import checks, harness, program
    from bench.cells import train
    phases = harness.Phases()
    out = {"seed": seed}
    if with_program:
        cfg, gen, rows, bufs, trainer, _ = train.build(cell, seed, phases)
        losses, grad, change, _ = train.check_steps(cell, seed, trainer,
                                                    phases)
        del trainer, bufs
        gc.collect()
    else:
        gen = program.generator(cell["config"], cell["traffic"], seed)
        rows = gen.signature_rows(cell["config"]["n_s"])
    ref = train.reference(cell, gen, rows, seed)
    if with_program:
        out["program"] = checks.train_numbers(losses, grad, change, *ref)
    out["control"] = checks.train_numbers(
        *train.reference(cell, gen, rows, seed, prec="high"), *ref)
    out["half"] = checks.train_numbers(
        *train.reference(cell, gen, rows, seed, half=True), *ref)
    out["ref_losses"] = ref[0]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-program", action="store_true",
                    help="control and fault readings only")
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    import jax
    harness.require_chips(cell["workload"]["chips"])
    jax.config.update("jax_default_matmul_precision",
                      cell["config"]["matmul_precision"])
    for seed in args.seeds:
        r = train_readings(cell, seed, not args.no_program)
        print("READING " + json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
