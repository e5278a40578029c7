"""The comparison that decides a training cell's ``correct``.

Both sides give, for the first steps from the same weights and batches: the
loss of each step, each leaf's norm of the first gradient as the optimizer
got it (after clipping), and each leaf's norm of the weights' change after
the last step.  A leaf is one parameter array, each layer's slice of a
stacked array apart.  A norm gap is taken by the worst leaf: the gap
between the two norms, over the reference's norm of that leaf or of the
median leaf, whichever is larger.  Leaves whose reference gradient is under
a thousandth of the median leaf's move under Adam by rounding alone and are
left out of the change.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

NEGLIGIBLE_GRAD = 1e-3


def _worst(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        return math.inf
    denom = np.maximum(ref, np.median(ref[keep]))
    gap = np.abs(prog - ref) / denom
    if not np.all(np.isfinite(gap)):
        return math.inf
    return float(np.max(gap[keep]))


def gaps(prog: Dict[str, np.ndarray],
         ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """``loss_gap`` (nats, the largest over the steps), ``grad_gap`` and
    ``delta_gap`` (shares, the worst leaf)."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    loss_gap = (float(np.max(np.abs(lp - lr)))
                if lp.shape == lr.shape and np.all(np.isfinite(lp))
                else math.inf)
    g_ref = np.asarray(ref["grad"], np.float64)
    every = np.ones(g_ref.shape, bool)
    moved = g_ref >= NEGLIGIBLE_GRAD * np.median(g_ref)
    return {"loss_gap": loss_gap,
            "grad_gap": _worst(prog["grad"], g_ref, every),
            "delta_gap": _worst(prog["delta"], ref["delta"], moved)}


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """Each number beside its limit; ``correct`` only when every number is
    finite and within its limit."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return {"correct": ok, "checks": checks}
