"""The numbers that decide ``correct``, and the verdict against the limits.

Training: each check step's loss, the first gradient's norm as the
optimizer holds it (from its Adagrad accumulator after one step), and the
norm of each parameter's change after the check steps.  Norms are compared
leaf by leaf: the gap between the program's norm and the reference's,
over the larger of the reference's norm of that leaf and the median leaf's
norm (some gradients are all but zero), taken at the worst leaf.  Leaves
whose reference gradient is under a thousandth of the median leaf's move by
round-off alone and are left out of the change.
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE_GRAD = 1e-3


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys)


def train_numbers(prog_losses, prog_grad, prog_change,
                  ref_losses, ref_grad, ref_change) -> dict:
    med = float(np.median(list(ref_grad.values())))
    moving = {k for k, v in ref_grad.items() if v >= NEGLIGIBLE_GRAD * med}
    out = {f"loss{i + 1}": rel(p, r)
           for i, (p, r) in enumerate(zip(prog_losses, ref_losses))}
    out["grad"] = worst_leaf(prog_grad, ref_grad)
    out["change"] = worst_leaf(prog_change, ref_change, keep=moving)
    return out


def verdict(numbers: dict, limits: dict | None) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}): correct when every number is at
    or under its limit.  With no limits file every limit reads None and the
    run is not correct."""
    lim = (limits or {}).get("limits", {})
    checks = {k: {"value": v, "limit": lim.get(k)} for k, v in numbers.items()}
    ok = bool(lim) and all(c["limit"] is not None and
                           np.isfinite(c["value"]) and c["value"] <= c["limit"]
                           for c in checks.values())
    return ok, checks
