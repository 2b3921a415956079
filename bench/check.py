"""The comparison that decides `correct` for a training cell.

Numbers, each held to its cell's limit (bench/limits/<cell>.json):
  loss_gap    the largest |program loss - reference loss| over the checked
              steps, in nats;
  grad_gap    over the leaves kept, the largest gap between the program's
              and the reference's norm of the first gradient (as the
              optimizer holds it after step 1), over the larger of that
              leaf's reference norm and the median leaf's;
  change_gap  the same for the norm of the weights' change after the
              checked steps.
A leaf is kept unless its reference gradient norm is under a thousandth of
the median leaf's: such a gradient is nought to rounding, and Adam moves it
by round-off alone.
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
DROP_BELOW = 1e-3


def kept_leaves(ref_grad: dict) -> list:
    med = statistics.median(ref_grad.values())
    return sorted(k for k, v in ref_grad.items() if v >= DROP_BELOW * med)


def _worst(prog: dict, ref: dict, keep: list) -> tuple:
    med = statistics.median(ref[k] for k in keep)
    worst, leaf = 0.0, None
    for k in keep:
        p = prog.get(k, float("nan"))
        gap = abs(p - ref[k]) / max(ref[k], med)
        if not math.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, leaf = gap, k
    return worst, leaf


def readings(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [...], "grad_norms": {leaf: n},
    "change_norms": {leaf: n}} -> {number: value} plus the worst leaves."""
    n = len(ref["losses"])
    if len(prog["losses"]) < n:
        loss_gap = float("inf")
    else:
        loss_gap = max(abs(a - b) for a, b in zip(prog["losses"][:n],
                                                   ref["losses"]))
        if not math.isfinite(loss_gap):
            loss_gap = float("inf")
    keep = kept_leaves(ref["grad_norms"])
    grad_gap, grad_leaf = _worst(prog["grad_norms"], ref["grad_norms"], keep)
    change_gap, change_leaf = _worst(prog["change_norms"],
                                     ref["change_norms"], keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap, "grad_leaf": grad_leaf,
            "change_leaf": change_leaf,
            "dropped_leaves": sorted(set(ref["grad_norms"]) - set(keep))}


def judge(numbers: dict, limits: dict, names=NUMBERS) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit; a number with no limit fails."""
    out, ok = {}, True
    for name in names:
        value, limit = numbers[name], limits.get(name)
        out[name] = {"value": value, "limit": limit}
        if limit is None or not value <= limit:
            ok = False
    return ok, out
