"""The comparison that decides ``correct`` for a training cell.

``got`` is what the timed path's own first steps produced, ``want`` what the
plain reference produced over the same batches from the same seed: each
step's loss, each leaf's gradient norm at the first step, each leaf's change
over the steps. Five numbers come out; each that is compared has a limit of
its own in the cell's file (``check.limits``; PERF.md gives the readings each
was set from).

The norms are compared by the worst leaf: the gap between the program's norm
and the reference's (not the norm of their difference), against the
reference's norm of that leaf or of the median leaf, whichever is larger,
since some gradients are all but zero. A leaf whose reference gradient is under
a thousandth of the median leaf's moves under Adam by round-off alone (a key's
bias under softmax): it is left out of the change, by that rule and not by
name.
"""
from __future__ import annotations

import statistics

DEAD_GRADIENT = 1e-3


def worst_leaf(got: dict, want: dict, leaves=None):
    """(gap, leaf) of the leaf whose norm is farthest from the reference's."""
    leaves = list(want) if leaves is None else leaves
    floor = statistics.median(want[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
        if gap != gap:              # a NaN is the worst there can be
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def live_leaves(want_grad: dict) -> list:
    cut = DEAD_GRADIENT * statistics.median(want_grad.values())
    return [k for k, v in want_grad.items() if v >= cut]


def training(got: dict, want: dict, limits: dict) -> list:
    """Every number read, each beside its limit. A number that the cell's
    file gives no limit (``null`` or absent) is read and not compared: its
    control and its faults gave it no upper reading (PERF.md names it)."""
    out = []
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"]), start=1):
        out.append({"name": f"loss_gap_step{i}",
                    "value": abs(a - b) / abs(b)})
    g, leaf = worst_leaf(got["grad_norm"], want["grad_norm"])
    out.append({"name": "grad_norm_gap", "value": g, "leaf": leaf})
    c, leaf = worst_leaf(got["change_norm"], want["change_norm"],
                         live_leaves(want["grad_norm"]))
    out.append({"name": "change_norm_gap", "value": c, "leaf": leaf})
    for row in out:
        row["limit"] = limits.get(row["name"])
        if not row["value"] == row["value"]:    # NaN never passes
            row["value"] = float("inf")
    return out


def passes(rows: list) -> bool:
    return all(r["value"] <= r["limit"] for r in rows
               if r["limit"] is not None)
