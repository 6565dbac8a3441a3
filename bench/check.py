"""The comparison that decides ``correct``.

Three numbers, each the worst case over its items, each with a limit of
its own (``bench/workloads/<cell>.json``):

  loss_gap    over the checked steps, |loss - ref loss| / |ref loss|
  grad_gap    over leaves, the gap between the norm of the first gradient
              as the optimizer takes it and the reference's, over the
              larger of the reference's norm of that leaf and of the
              median leaf
  change_gap  the same for each leaf's change after the checked steps;
              leaves whose reference gradient is under a thousandth of
              the median leaf's move by round-off alone and are left out

A number that is not finite fails.  The check prints each number beside
its limit.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
STILL = 1e-3     # a leaf whose reference gradient is under this share of
#                  the median leaf's is left out of change_gap


def _norm_gap(prog: Dict[str, float], ref: Dict[str, float],
              leaves) -> Tuple[float, str]:
    rn = {k: math.sqrt(max(ref[k], 0.0)) for k in leaves}
    med = float(np.median(list(rn.values())))
    worst, where = 0.0, ""
    for k in leaves:
        p = prog.get(k, float("nan"))
        gap = abs(math.sqrt(p) - rn[k]) / max(rn[k], med) \
            if p >= 0 else float("nan")
        if not math.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def numbers(prog, ref) -> Tuple[Dict[str, float], Dict[str, str]]:
    """The three numbers of a program run (or a control) against the
    reference, and the item each was taken at."""
    losses = [abs(p - r) / abs(r) if math.isfinite(p) else float("inf")
              for p, r in zip(prog.losses, ref.losses)]
    if len(prog.losses) != len(ref.losses):
        losses.append(float("inf"))
    step = int(np.argmax(losses))
    out = {"loss_gap": float(losses[step])}
    where = {"loss_gap": f"step {step}"}
    out["grad_gap"], where["grad_gap"] = _norm_gap(
        prog.grad_sumsq, ref.grad_sumsq, list(ref.grad_sumsq))
    gn = {k: math.sqrt(v) for k, v in ref.grad_sumsq.items()}
    med = float(np.median(list(gn.values())))
    moving = [k for k in ref.change_sumsq if gn.get(k, 0.0) >= STILL * med]
    out["change_gap"], where["change_gap"] = _norm_gap(
        prog.change_sumsq, ref.change_sumsq, moving)
    return out, where


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {number: {"value", "limit"}}) for the numbers that have
    a limit; a number without one is printed and not held."""
    checks, ok = {}, True
    for k in NUMBERS:
        v = values[k]
        if k in limits:
            checks[k] = {"value": v, "limit": limits[k]}
            ok = ok and math.isfinite(v) and v <= limits[k]
        else:
            checks[k] = {"value": v, "limit": None}
    return ok, checks
