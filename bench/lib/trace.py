"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

What a TPU trace holds, as read here: one plane per chip named
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per run of a
compiled program (``jit_train_step(<hash>)``) and whose line ``XLA Ops``
has one event per HLO operation, nested (a ``while`` op contains the ops
of its body).  Host threads are lines of the plane ``/host:CPU``; spans
that the benchmark opens with ``jax.profiler.TraceAnnotation`` appear
there under their own names.  Device and host events share one clock.

* busy time of a chip: the union of its ``XLA Ops`` intervals inside the
  traced window; idle share: one minus busy over the window;
* an idle gap is attributed to the innermost host span (of those that
  ``host_span`` admits) that covers the gap's midpoint (``no host span``
  when none does);
* an op's self time is its duration less that of the ops nested in it;
  ops are named ``<program>/<opcode>:<result type>``
  (``jit_train_step/fusion:f32[30689,2304]``), since HLO instruction
  numbers change with every compile.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Tuple

_OPCODE = re.compile(r"[\)\}\]] ([a-z][a-z0-9\-]*)\(")
_HASH = re.compile(r"\(\d+\)$")
_SHAPE = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\])")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def opcode(text: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``fusion``, ``while``,
    ``custom-call``, ...)."""
    head, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    return m.group(1) if m else head.lstrip("%").split(".")[0]


def op_name(text: str) -> str:
    """``fusion:f32[30689,2304]``: the opcode and the first result's type,
    both stable from one compile to the next."""
    m = _SHAPE.search(text)
    return f"{opcode(text)}:{m.group(1)}" if m else opcode(text)


def program(text: str) -> str:
    """``jit_train_step(1803...)`` -> ``jit_train_step``."""
    return _HASH.sub("", text)


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, w0: float, w1: float):
    return [(max(a, w0), min(b, w1)) for a, b in intervals
            if b > w0 and a < w1]


def self_times(events: List[Tuple[float, float, str]]) -> List[float]:
    """Self time of each (start, end, name) event of one line, whose
    events nest but never partly overlap."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_t = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= e - s
        stack.append(i)
    return [max(t, 0.0) for t in self_t]


class Trace:
    """The device and host events of one trace, in seconds."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        self.devices: Dict[str, dict] = {}
        self.host: List[Tuple[float, float, str]] = []
        data = ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {"XLA Ops": "ops",
                           "XLA Modules": "modules"}.get(line.name)
                    if key is None:
                        continue
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dev[key].append((s, s + ev.duration_ns * 1e-9,
                                         ev.name))
                self.devices[plane.name] = dev
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        self.host.append((s, s + ev.duration_ns * 1e-9,
                                          ev.name))

    def span(self, name: str) -> Optional[Tuple[float, float]]:
        """The first host span called ``name``."""
        for s, e, n in self.host:
            if n == name:
                return s, e
        return None


def reduce(tr: Trace, window: Tuple[float, float], chips: int,
           host_span: Callable[[str], bool], top: int = 10) -> dict:
    """Busy and idle seconds per chip in ``window``, the top device ops by
    self time, the longest idle gaps by host span, and, for kernel
    readers, the runs of each program that lie wholly in the window and
    their ops."""
    w0, w1 = window
    spans = sorted((s, e, n) for s, e, n in tr.host if host_span(n))
    starts = [s for s, _, _ in spans]
    devs = sorted(tr.devices)[:chips]
    if len(devs) < chips:
        raise ValueError(f"trace holds {len(devs)} TPU planes, "
                         f"{chips} chips asked")
    busy, gaps = [], []
    op_self: Dict[str, float] = defaultdict(float)
    program_ops: Dict[str, List[Tuple[float, float, str]]] = defaultdict(
        list)
    program_runs: Dict[str, int] = defaultdict(int)
    for d in devs:
        dev = tr.devices[d]
        ops = [(s, e, n) for s, e, n in dev["ops"] if e > w0 and s < w1]
        mods = sorted((s, e, program(n)) for s, e, n in dev["modules"])
        mstarts = [m[0] for m in mods]
        merged = union(clip([(s, e) for s, e, _ in ops], w0, w1))
        busy.append(sum(b - a for a, b in merged))
        cursor = w0
        for a, b in merged + [[w1, w1]]:
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
        for ms, me, prog in mods:
            if ms >= w0 and me <= w1:
                program_runs[prog] += 1
        for (s, e, n), st in zip(ops, self_times(ops)):
            i = bisect.bisect_right(mstarts, s) - 1
            inside = i >= 0 and mods[i][1] >= s
            prog = mods[i][2] if inside else "?"
            op_self[f"{prog}/{op_name(n)}"] += st
            if inside and mods[i][0] >= w0 and mods[i][1] <= w1:
                program_ops[prog].append((s, e, n))
    by_host: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = 0.5 * (a + b)
        best = None
        for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            s, e, n = spans[j]
            if s <= mid < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        by_host[best[2] if best else "no host span"] += b - a
    n = len(devs)
    return {
        "busy_s": sum(busy) / n, "window_s": w1 - w0,
        "idle_share": 1.0 - sum(busy) / n / (w1 - w0),
        "device_ops": sorted(([k, v / n] for k, v in op_self.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n] for k, v in by_host.items()),
                            key=lambda kv: -kv[1])[:top],
        "program_ops": dict(program_ops),
        "program_runs": dict(program_runs),
        "chips": n,
    }
