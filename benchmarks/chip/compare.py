"""The comparison that decides `correct`: the program's readings against
the reference's, each number held to its limit.

Numbers (each a relative gap, 0 when the two agree):
  loss    worst step of |loss_prog - loss_ref| / loss_ref over the first
          rounds;
  grad    the first round's direction as the optimizer gets it, per leaf
          norm: the program's from its mean shift after one round
          (H_1 = beta * direction_1), the reference's from its own wire;
  shift   the shift each client writes back after its first round (the
          fleet's store rows, or the step's shift table);
  change  per leaf norm of the parameters' change after the first rounds.
The leaf gaps are |norm_prog - norm_ref| over the larger of norm_ref and
the median leaf's norm_ref, worst leaf. Leaves whose reference gradient is
under a thousandth of the median leaf's (a key bias under softmax) only
move by round-off and are left out.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss", "grad", "shift", "change")


def kept_leaves(gnorm) -> np.ndarray:
    g = np.asarray(gnorm, np.float64)
    return g >= 1e-3 * np.median(g)


def leaf_gap(prog, ref, keep) -> float:
    p = np.asarray(prog, np.float64)[keep]
    r = np.asarray(ref, np.float64)[keep]
    scale = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / scale))


def gaps(prog: dict, ref: dict) -> dict:
    keep = kept_leaves(ref["gnorm"])
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]):
        loss = math.inf
    shift = (max(leaf_gap(p, r, keep)
                 for p, r in zip(prog["shift"], ref["shift"]))
             if len(prog["shift"]) == len(ref["shift"]) else math.inf)
    return {"loss": loss,
            "grad": leaf_gap(prog["grad"], ref["grad"], keep),
            "shift": shift,
            "change": leaf_gap(prog["change"], ref["change"], keep)}


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a missing or NaN number fails."""
    checks, ok = {}, True
    for name in NUMBERS:
        value = found.get(name, math.nan)
        checks[name] = {"value": value, "limit": limits[name]}
        ok &= bool(value <= limits[name])
    return ok, checks
