"""The end-to-end arithmetic and the comparison that decides ``correct``.

Kept apart from the drivers so that it is tested on synthetic logs: a
stall must move the rate, a lower precision must fail the comparison.
"""

from __future__ import annotations

import math
import statistics

__all__ = ["steps_per_s", "leaf_gaps", "worst_leaf_gap", "training_numbers", "judge"]


def steps_per_s(periods) -> float:
    """All steps over all wall time: ``periods`` is [(start, end, steps)]
    of every ``run_period`` call of the window, fence included, from the
    first call's start to the last call's end.  No medians, no slopes: a
    stall anywhere in the window moves it."""
    if not periods:
        raise ValueError("an empty window has no rate")
    elapsed = periods[-1][1] - periods[0][0]
    if elapsed <= 0:
        raise ValueError("a window of no length has no rate")
    return sum(p[2] for p in periods) / elapsed


def leaf_gaps(prog: dict, ref: dict, skip=()) -> dict:
    """``{leaf: gap}``: |program's norm - reference's norm| against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  The gap of norms, not the norm of the gap."""
    if set(prog) != set(ref):
        missing = sorted(set(ref) ^ set(prog))[:5]
        raise KeyError(f"program and reference name different leaves: {missing}")
    med = statistics.median(ref.values())
    out = {}
    for k, r in ref.items():
        if k in skip:
            continue
        p = prog[k]
        ok = math.isfinite(p) and math.isfinite(r)
        out[k] = abs(p - r) / max(r, med, 1e-30) if ok else float("inf")
    return out


def worst_leaf_gap(prog: dict, ref: dict, skip=()):
    """``(gap, leaf)`` of the leaf that reads worst."""
    gaps = leaf_gaps(prog, ref, skip)
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def _spread(gaps: dict, stem: str) -> dict:
    """Worst, 90th-percentile and median leaf of one set of gaps.  The
    worst leaf is what a cell compares where its readings separate; where
    one noisy leaf owns it on every seed the steadier two stand beside it
    (a cell's limits say which are compared)."""
    v = sorted(gaps.values())
    return {
        stem: v[-1],
        f"{stem}_p90": v[min(len(v) - 1, int(0.9 * len(v)))],
        f"{stem}_median": statistics.median(v),
    }


def _global_gap(prog: dict, ref: dict, skip=()) -> float:
    """The gap of the norms over all leaves together: the whole gradient's
    (or change's) norm, one steady number beside the per-leaf ones."""
    p = math.sqrt(sum(v * v for k, v in prog.items() if k not in skip))
    r = math.sqrt(sum(v * v for k, v in ref.items() if k not in skip))
    ok = math.isfinite(p) and math.isfinite(r) and r > 0
    return abs(p - r) / r if ok else float("inf")


def training_numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared for a training cell, each a relative gap.

    ``prog``/``ref``: ``{"losses": [3], "grad_norms": {}, "delta_norms":
    {}}``.  Leaves whose reference gradient is under a thousandth of the
    median leaf's move under Adam by round-off alone and are left out of
    the change (by that rule, never by name)."""
    out, notes = {}, {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        ok = math.isfinite(lp) and math.isfinite(lr) and lr != 0
        out[f"loss_gap_step{i + 1}"] = abs(lp - lr) / abs(lr) if ok else float("inf")
    grad = leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    out.update(_spread(grad, "grad_norm_gap"))
    out["grad_norm_gap_global"] = _global_gap(prog["grad_norms"], ref["grad_norms"])
    notes["grad_norm_leaf"] = max(grad, key=grad.get)
    med = statistics.median(ref["grad_norms"].values())
    still = {k for k, g in ref["grad_norms"].items() if g < 1e-3 * med}
    delta = leaf_gaps(prog["delta_norms"], ref["delta_norms"], skip=still)
    out.update(_spread(delta, "delta_norm_gap"))
    out["delta_norm_gap_global"] = _global_gap(prog["delta_norms"], ref["delta_norms"], still)
    notes["delta_norm_leaf"] = max(delta, key=delta.get)
    notes["leaves_left_out_of_delta"] = sorted(still)
    return {"numbers": out, "notes": notes}


def judge(numbers: dict, limits: dict):
    """``(correct, {name: {"value", "limit"}})``: every limit in the cell's
    file is held against the number of that name.  A limit whose number is
    missing cannot pass, and neither can a file with no limits: limits come
    from readings, and a cell without them is not proven.  A number the
    file gives no limit for is a reading (PERF.md names those that had no
    upper end): the harness prints it on an earlier line."""
    table = {name: {"value": numbers.get(name), "limit": limit}
             for name, limit in limits.items()}
    correct = bool(table) and all(
        row["value"] is not None and row["value"] <= row["limit"] for row in table.values()
    )
    return correct, table
