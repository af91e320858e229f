"""The comparisons that decide ``correct``, and how their numbers print.

Each number compared is held to a limit set in the cell's traffic file from
two readings on the chip (the largest that sound runs gave, and the
smallest that the control or a planted fault gave; see PERF.md).
"""
from __future__ import annotations

import statistics
import sys
from typing import Dict, Iterable, Optional


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keys: Optional[Iterable[str]] = None) -> tuple:
    """Largest gap between the program's and the reference's norm of a
    leaf, each over the larger of the reference's norm of that leaf and of
    the median leaf.  Returns (gap, leaf)."""
    keys = sorted(keys if keys is not None else ref)
    if set(keys) - set(prog):
        raise KeyError(f"leaves missing from the program: "
                       f"{sorted(set(keys) - set(prog))}")
    med = statistics.median(ref[k] for k in keys)
    worst, leaf = -1.0, None
    for k in keys:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, leaf = gap, k
    return worst, leaf


def moving_leaves(ref_grad: Dict[str, float], floor: float = 1e-3):
    """Leaves whose reference gradient is at least ``floor`` of the median
    leaf's: a leaf under it moves under Adam by round-off alone."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= floor * med]


class Checks:
    """Named numbers beside their limits; ``ok`` when each is at or under
    its limit."""

    def __init__(self):
        self.items: Dict[str, dict] = {}

    def add(self, name: str, value: float, limit: float):
        self.items[name] = {"value": float(value), "limit": float(limit)}

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(
            v["value"] == v["value"] and v["value"] <= v["limit"]
            for v in self.items.values())

    def print_stderr(self):
        for k, v in self.items.items():
            print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
                  file=sys.stderr, flush=True)
