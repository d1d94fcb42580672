"""The numbers that decide ``correct``, each held against its limit.

``loss_gap``: the widest relative gap between the program's and the
reference's mean loss, over every local step of the first calls.
``momentum_gap``: the worst leaf's gap between the norms of the
optimizer's momentum after the first call (for one local step a call, the
first gradient as the optimizer got it).  ``change_gap``: the worst leaf's
gap between the norms of the parameters' change over the first calls.  A
leaf's gap is measured against the reference's norm of that leaf or of the
median leaf, whichever is larger.  Leaves whose reference momentum is under
``QUIET`` of the median leaf's (a gradient that is nought to rounding) are
left out of the change.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "momentum_gap", "change_gap")
QUIET = 1e-3


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> float:
    median = float(np.median(list(ref.values())))
    return max(
        abs(prog[k] - ref[k]) / max(ref[k], median) for k in (leaves or list(ref))
    )


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` (calls, L), ``momentum``
    and ``change`` (leaf -> norm)."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    median = float(np.median(list(ref["momentum"].values())))
    moving = [k for k, v in ref["momentum"].items() if v >= QUIET * median]
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "momentum_gap": worst_leaf_gap(prog["momentum"], ref["momentum"]),
        "change_gap": worst_leaf_gap(prog["change"], ref["change"], moving),
    }


def judge(values: dict[str, float], limits: dict) -> bool:
    """True where every number is finite and within its limit."""
    return all(math.isfinite(values[k]) and values[k] <= limits[k] for k in NUMBERS)
