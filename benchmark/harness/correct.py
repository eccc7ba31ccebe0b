"""The comparison that decides `correct`: numbers read from what the timed
path produced, each beside a limit of its own from limits/<workload>.json.
Every limit was set between two readings on the chip (PERF.md gives them)."""
from __future__ import annotations

import json
import math
import os
import sys

from . import manifest, stats


def limits_for(workload: str) -> dict:
    path = os.path.join(manifest.BENCH_DIR, "limits", workload + ".json")
    with open(path) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def norm_gaps(prog: dict, ref: dict, skip=()):
    """Worst leaf of |program's norm - reference's norm| over the larger of
    the reference's norm of that leaf and of the median leaf (some gradients
    are all but zero). Returns (gap, leaf)."""
    med = stats.median(list(ref.values()))
    worst, where = 0.0, None
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        g = abs(prog[leaf] - r) / max(r, med)
        if not math.isfinite(g):
            return math.inf, leaf
        if g > worst:
            worst, where = g, leaf
    return worst, where


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses", "grad_norm", "change_norm"} of the first steps.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move under Adam by round-off alone: they are left out of the
    change (by that rule, not by name)."""
    med_g = stats.median(list(ref["grad_norm"].values()))
    dead = {k for k, g in ref["grad_norm"].items() if g < 1e-3 * med_g}
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_leaf = norm_gaps(prog["grad_norm"], ref["grad_norm"])
    chg_gap, chg_leaf = norm_gaps(prog["change_norm"], ref["change_norm"],
                                  skip=dead)
    return {"numbers": {"loss_gap": loss_gap, "grad_gap": grad_gap,
                        "change_gap": chg_gap},
            "where": {"grad_gap": grad_leaf, "change_gap": chg_leaf,
                      "leaves_left_out": sorted(dead)}}


def decide(numbers: dict, limits: dict):
    """(correct, {name: [number, limit]}). A number with no limit, a limit
    with no number, or a number that is not finite, is not correct."""
    checks, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name)
        good = v is not None and math.isfinite(v) and v <= lim
        ok = ok and good
        checks[name] = [v, lim]
    for name in numbers:
        if name not in limits:
            ok = False
            checks[name] = [numbers[name], None]
    return ok, checks


def report(checks: dict, correct: bool):
    """Each number compared beside its limit, as the last lines on stderr."""
    for name, (v, lim) in checks.items():
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
