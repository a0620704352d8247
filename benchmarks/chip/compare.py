"""The comparison that decides ``correct``.

Both sides give the same readings (see ``refcore.train``) of the first
training steps (``harness.CHECK_STEPS``) from the same weights on the same
batches: each step's loss, per leaf the sum of squares of the first
gradient (from adagrad's accumulator after one step) and the norm of the
parameters' change over the steps. Three numbers are compared:

* ``loss_gap``   - the largest relative gap between the two sides' losses;
* ``grad_gap``   - over leaves, the largest gap between the two first
  gradient norms, against the reference's norm of that leaf or of the
  median leaf, whichever is larger;
* ``change_gap`` - the same for the norms of the change. Leaves whose
  reference gradient is under a thousandth of the median leaf's move by
  round-off alone (adagrad divides by the root of their accumulator), so
  they are left out of it.

Each limit sits between the largest reading of sound runs of the program
over a dozen seeds or more and the smallest reading of the control (the
reference in bfloat16) and of the faults; ``PERF.md`` gives the readings.
A number that is not finite is over any limit.
"""
from __future__ import annotations

import math
from typing import Dict, Mapping, Tuple

import numpy as np

#: leaves whose first reference gradient is under this share of the median
#: leaf's are left out of ``change_gap``
STILL_LEAF = 1e-3


def _worst(prog: Mapping[str, float], ref: Mapping[str, float],
           keys) -> Tuple[float, str]:
    keys = list(keys)
    if set(prog) != set(ref):
        return math.inf, f"leaf sets differ: {sorted(set(prog) ^ set(ref))}"
    med = float(np.median([ref[k] for k in keys]))
    worst, leaf = 0.0, ""
    for k in keys:
        p, r = prog[k], ref[k]
        gap = abs(p - r) / max(r, med) if max(r, med) > 0 else math.inf
        if not math.isfinite(p) or not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, leaf = gap, k
    return worst, leaf


def gaps(prog: Mapping, ref: Mapping) -> Dict[str, float]:
    """The compared numbers of one side's readings against the reference's,
    with the leaf or step that sets each."""
    losses = []
    for lp, lr in zip(prog["losses"], ref["losses"]):
        g = abs(lp - lr) / max(abs(lr), 1e-30)
        losses.append(g if math.isfinite(g) else math.inf)
    g_prog = {k: math.sqrt(max(v, 0.0)) for k, v in prog["grad_sq"].items()}
    g_ref = {k: math.sqrt(max(v, 0.0)) for k, v in ref["grad_sq"].items()}
    grad_gap, grad_leaf = _worst(g_prog, g_ref, g_ref)
    med = float(np.median(list(g_ref.values())))
    moving = [k for k, v in g_ref.items() if v >= STILL_LEAF * med]
    change_gap, change_leaf = _worst(prog["change"], ref["change"], moving)
    return {"loss_gap": max(losses), "loss_step": int(np.argmax(losses)) + 1,
            "grad_gap": grad_gap, "grad_leaf": grad_leaf,
            "change_gap": change_gap, "change_leaf": change_leaf,
            "still_leaves": sorted(set(g_ref) - set(moving))}


def judge(found: Mapping, limits: Mapping[str, float]) -> Tuple[bool, Dict]:
    """``correct`` and each compared number beside its limit."""
    checks = {k: {"value": found[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
