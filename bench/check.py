"""The numbers that decide ``correct``, from the program's and the
reference's readings of a cell's first steps.

Each reading holds the losses of the first steps, the per-leaf norms of the
first clipped gradient (the program's worked out from AdamW's first moment
after one step) and the per-leaf norms of the parameters' change over the
steps.  Norms are compared leaf by leaf: the gap between the two norms, over
the reference's norm of that leaf or of the median leaf, whichever is larger.
"""

from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
# a leaf whose reference gradient is below this share of the median leaf's
# moves under AdamW by rounding alone, and its change is not compared
STILL_LEAF = 1e-3


def worst_leaf(prog, ref, keep=None) -> float:
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    gaps = np.abs(p - r) / np.maximum(r, np.median(r))
    return float(np.max(gaps)) if np.all(np.isfinite(p)) else math.inf


def numbers(prog: dict, ref: dict) -> dict:
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.all(np.isfinite(lp)):
        loss_gap = math.inf
    g = np.asarray(ref["grad_norms"], np.float64)
    keep = g >= STILL_LEAF * np.median(g)
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf(prog["grad_norms"], ref["grad_norms"]),
            "change_gap": worst_leaf(prog["change_norms"],
                                     ref["change_norms"], keep)}


def verdict(values: dict, limits: dict) -> tuple:
    """(all within their limits, {name: {"value", "limit"}})."""
    checks = {k: {"value": values[k], "limit": limits[k]["limit"]}
              for k in NUMBERS}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
