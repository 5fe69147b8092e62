"""The comparison that decides `correct`: the program's first steps against
the plain reference's, from the same weights and batches.

Three numbers, each held to the cell's limit:

  loss_gap    largest relative gap between a step's loss and the
              reference's, over the checked steps;
  grad_gap    worst leaf of the first clipped gradient: the gap between
              the program's leaf norm and the reference's, over the larger
              of the reference's norm of that leaf and of the median leaf;
  update_gap  the same for each leaf's change after the checked steps,
              leaving out leaves whose reference gradient is under a
              thousandth of the median leaf's (they move by weight decay
              and round-off alone).

A reading that is not finite counts as BIG, which no limit admits.
"""
from __future__ import annotations

import math
import statistics

BIG = 1e30
NEGLIGIBLE_GRAD = 1e-3


def _finite(x):
    return x if math.isfinite(x) else BIG


def worst_leaf(prog, ref, keys):
    med = statistics.median(ref[k] for k in keys)
    return max(_finite(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30))
               for k in keys)


def gaps(prog, ref):
    """{name: reading} for program and reference readings, each a dict of
    `losses` (per step), `grad` and `delta` ({leaf path: norm})."""
    loss_gap = max(_finite(abs(a - b) / max(abs(b), 1e-30))
                   for a, b in zip(prog["losses"], ref["losses"]))
    keys = sorted(ref["grad"])
    med = statistics.median(ref["grad"][k] for k in keys)
    moved = [k for k in keys if ref["grad"][k] >= NEGLIGIBLE_GRAD * med]
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf(prog["grad"], ref["grad"], keys),
            "update_gap": worst_leaf(prog["delta"], ref["delta"], moved)}


def judge(readings, limits):
    """(correct, {name: {"value", "limit"}}) against the cell's limits."""
    checks = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
