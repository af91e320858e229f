"""Flight-recorder span tracing for the pilot runtime.

A :class:`Tracer` is handed to ``PilotRuntime(tracer=...)`` (or set as
``Fleet.tracer``) and the executor/AppManager/federation hook points call
``task_begin``/``task_end``/``begin``/``end``/``instant`` on it — every
call site guards with ``if tracer is not None``, so an untraced run pays a
single attribute read per hook.  Spans are begin/end pairs keyed to
(pod, slot, pipeline, task, attempt) on the run's authoritative clock:
the virtual clock in sim mode, wall seconds since drain start in real
mode.  A truncated attempt (preemption, pod loss, supersession) ENDS its
span at the truncation time with that outcome — spans never overlap on a
slot, which is what keeps the TTC decomposition (repro.obs.report)
disjoint.

The task-attempt path is the hot one (a 100k-task sim opens and closes
100k spans inside the DES loop), so it records raw tuples and defers
EVERYTHING derivable — dict materialization (the :attr:`spans` read),
outcome counters and span/data/exec histograms (:meth:`_fold`) — to read
time.  The per-attempt cost inside the DES loop is two dict ops and one
tuple append.  The generic ``begin``/``end`` path (parks, transfers)
stays dict-based; it fires orders of magnitude less often.

The tracer owns a :class:`~repro.obs.metrics.MetricsTimeline` — per-attempt
spans fold into histograms (``t_data_attempt``/``t_exec_attempt`` are
recorded for attempts that staged data; for the rest ``attempt_span`` IS
the exec histogram) and ``attempts_<outcome>`` counters, and the drain
loops sample the registered gauges on clock ticks.  Read the timeline
through :meth:`timeseries` (it folds first); ``metrics.series()`` alone
misses attempts recorded since the last fold.

Program spans (:meth:`span`, :meth:`open`/:meth:`close`, :meth:`record`)
are the layers' own phases inside a task attempt: ``engine.decode``,
``member.wait``, ``runtime.launch`` and so on.  Each gets an ``id`` and the
``parent`` id of the innermost span open on its thread; a kernel's
outermost span has the ``(task, attempt)`` its worker thread runs as
parent.  While a span is open a ``jax.profiler.TraceAnnotation`` named
``"<cat>.<name>"`` is open too, so an operator finds the program's phases
in the profiler's trace beside the device ops, on the device trace's
clock.  In real mode the tracer owns the run's clock: :meth:`now` is
seconds since the origin :attr:`t0` the executor fixes at drain start.
While a real-mode drain runs, :meth:`compile_spans` records JAX's trace,
lowering and backend-compile events as ``compile`` spans and counts
``compiles``.  Program spans never take the categories ``task`` or
``park``, which the TTC decomposition reads.
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from repro.obs.metrics import MetricsTimeline

#: span category for task attempts (other cats: "park", "transfer", ...)
TASK = "task"

#: JAX's compile events (``jax._src.dispatch``) -> ``compile`` span names;
#: ``backend_compile`` includes a load from the persistent compile cache
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}

#: interned ``attempts_<outcome>`` counter names (hot-path cache)
_COUNTER_KEY: Dict[str, str] = {}


class _Open:
    """An open program span: its record, and the profiler annotation that
    mirrors it."""
    __slots__ = ("span", "ann", "stacked")

    def __init__(self, span, ann, stacked):
        self.span, self.ann, self.stacked = span, ann, stacked


class Tracer:
    def __init__(self, *, metrics: Optional[MetricsTimeline] = None):
        self.metrics = metrics if metrics is not None else MetricsTimeline()
        #: "virtual" | "wall" — stamped by the session at first use
        self.clock: Optional[str] = None
        #: real-mode origin on ``time.perf_counter`` (first drain start)
        self.t0: Optional[float] = None
        self._wall0 = 0.0         # time.time() at the origin
        self._annotation = None   # jax.profiler.TraceAnnotation, lazily
        self._ids = itertools.count(1)
        self._local = threading.local()   # per thread: span stack, attempt
        self._lock = threading.Lock()
        self._listening = 0
        self.events: List[Dict[str, Any]] = []    # instants (pod loss, ...)
        self._open: Dict[Tuple, Any] = {}
        # task spans: raw (task, attempt, t0, t1, outcome, extras) tuples,
        # materialized to dicts lazily by the .spans property
        self._raw: List[Tuple] = []
        self._span_cache: List[Dict[str, Any]] = []
        self._closed: List[Dict[str, Any]] = []   # generic (non-task) spans
        self._folded = 0          # prefix of _raw already in the metrics

    # ------------------------------------------------------------ generic
    def begin(self, key: Tuple, cat: str, name: str, now: float, **args):
        """Open a span under ``key`` (re-begin on an open key replaces the
        stale span — defensively; the runtime never does)."""
        span = {"cat": cat, "name": name, "t0": float(now), "t1": None,
                "outcome": None}
        if args:
            span.update(args)
        self._open[key] = span

    def end(self, key: Tuple, now: float, outcome: str = "done", **args):
        """Close the span under ``key`` (no-op when the key is unknown —
        e.g. a supersession record for a task that never launched)."""
        span = self._open.pop(key, None)
        if span is None:
            return None
        span["t1"] = float(now)
        span["outcome"] = outcome
        if args:
            span.update(args)
        self._closed.append(span)
        return span

    def instant(self, cat: str, name: str, now: float, **args):
        ev = {"cat": cat, "name": name, "t": float(now)}
        if args:
            ev.update(args)
        self.events.append(ev)

    # ------------------------------------------------------------ clock
    def start_clock(self, t0: Optional[float] = None) -> float:
        """Fix the real-mode origin at ``t0`` (default: now) unless it is
        already fixed — one origin per tracer; returns it."""
        if self.t0 is None:
            pc, wall = time.perf_counter(), time.time()
            self.t0 = pc if t0 is None else t0
            self._wall0 = wall - (pc - self.t0)
        return self.t0

    def now(self) -> float:
        """Seconds since the origin (fixed here on first use if no drain
        has fixed it)."""
        t0 = self.t0
        if t0 is None:
            t0 = self.start_clock()
        return time.perf_counter() - t0

    # ------------------------------------------------------ program spans
    def bind_attempt(self, task: str, attempt: int):
        """Run the calling thread as task attempt ``(task, attempt)``: its
        outermost program spans take the attempt as parent (the executor
        calls this first thing on each attempt's worker thread)."""
        self._local.attempt = (task, attempt)
        self._local.stack = []

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _parent(self, stack):
        return stack[-1].span["id"] if stack \
            else getattr(self._local, "attempt", None)

    def open(self, cat: str, name: str, *, t0: Optional[float] = None,
             parent: Any = None, stacked: bool = True, **attrs) -> _Open:
        """Open a program span now (or back-dated to ``t0``); pair with
        :meth:`close`.  A stacked span parents the spans its thread opens
        until it closes; ``stacked=False`` makes one that another thread
        may close, with the given ``parent``."""
        if stacked:
            stack = self._stack()
            parent = self._parent(stack)
        span = {"cat": cat, "name": name, "id": next(self._ids),
                "parent": parent, "t0": self.now() if t0 is None else t0,
                "t1": None, "outcome": None}
        if attrs:
            span.update(attrs)
        if self._annotation is None:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        ann = self._annotation(f"{cat}.{name}")
        ann.__enter__()
        h = _Open(span, ann, stacked)
        if stacked:
            stack.append(h)
        return h

    def close(self, h: _Open, outcome: str = "done", **attrs):
        """Close ``h`` and keep its span.  Stacked spans close on the
        thread that opened them; spans left open above ``h`` (a raise
        skipped their close) end with it, unkept."""
        if h.stacked:
            stack = self._stack()
            while stack:
                top = stack.pop()
                if top is h:
                    break
                top.ann.__exit__(None, None, None)
        h.ann.__exit__(None, None, None)
        span = h.span
        span["t1"] = self.now()
        span["outcome"] = outcome
        if attrs:
            span.update(attrs)
        self._closed.append(span)
        return span

    @contextmanager
    def span(self, cat: str, name: str, **attrs):
        """``with tracer.span("engine", "decode", slots=3) as s:`` — a
        stacked program span around the block (outcome ``failed`` when
        the block raises); yields the span's record."""
        h = self.open(cat, name, **attrs)
        try:
            yield h.span
        except BaseException:
            self.close(h, "failed")
            raise
        self.close(h)

    def record(self, cat: str, name: str, t0: float, t1: float,
               **attrs) -> Dict[str, Any]:
        """Keep a program span that has already ended (not mirrored),
        parented like a span opened now on the calling thread."""
        span = {"cat": cat, "name": name, "id": next(self._ids),
                "parent": self._parent(getattr(self._local, "stack", None)),
                "t0": float(t0), "t1": float(t1), "outcome": "done"}
        if attrs:
            span.update(attrs)
        self._closed.append(span)
        return span

    # ------------------------------------------------------------ compiles
    def _on_time_span(self, event: str, start: float, end: float, **kw):
        name = _COMPILE_EVENTS.get(event)
        if name is None:
            return
        self.record("compile", name, start - self._wall0,
                    end - self._wall0, fun_name=kw.get("fun_name"))
        if name == "backend_compile":
            with self._lock:
                self.metrics.inc("compiles")

    @contextmanager
    def compile_spans(self):
        """While the block runs, record JAX's trace, lowering and
        backend-compile events (``time.time()`` moved onto this tracer's
        clock) as ``compile`` spans, parented to the span open on the
        compiling thread, and count ``backend_compile`` events as
        ``compiles``.  Not mirrored: JAX's own profiler events cover
        compiles.  Reentrant; the listener is removed on exit."""
        import jax
        self.start_clock()
        with self._lock:
            first = self._listening == 0
            self._listening += 1
        if first:
            jax.monitoring.register_event_time_span_listener(
                self._on_time_span)
        try:
            yield self
        finally:
            with self._lock:
                self._listening -= 1
                last = self._listening == 0
            if last:
                jax.monitoring.unregister_event_time_span_listener(
                    self._on_time_span)

    # ------------------------------------------------------------ tasks
    def task_begin(self, t, now: float, pod: Optional[str] = None,
                   t_data: float = 0.0):
        """Open a task-attempt span (hot path: no dict until the span is
        read back; ``extras`` only materializes for annotated tasks)."""
        extras = None
        meta = t.meta
        if pod is not None:
            extras = {"pod": pod}
        if t_data:
            extras = extras or {}
            extras["t_data"] = t_data
        if t.slots != 1:
            extras = extras or {}
            extras["width"] = t.slots
        if meta:
            pilot = meta.get("pilot")
            pipeline = meta.get("pipeline")
            ids = meta.get("slot_ids")
            if pilot is not None or pipeline is not None or ids:
                extras = extras or {}
                if pilot is not None:
                    extras["pilot"] = pilot
                if pipeline is not None:
                    extras["pipeline"] = pipeline
                if ids:
                    extras["slots"] = list(ids)
        self._open[(t.name, t.attempts)] = (now, extras)

    def task_end(self, t, now: float, outcome: str):
        opened = self._open.pop((t.name, t.attempts), None)
        if opened is None:
            return None
        self._raw.append(
            (t.name, t.attempts, opened[0], now, outcome, opened[1]))
        return True

    def _fold(self):
        """Fold raw attempt records into the metrics registry (counters
        and histograms) — deferred off the DES hot path; idempotent over
        the already-folded prefix."""
        raw = self._raw
        if self._folded == len(raw):
            return
        m = self.metrics
        h_span = m.hist("attempt_span")
        h_data = m.hist("t_data_attempt")
        h_exec = m.hist("t_exec_attempt")
        cnt = m.counters
        for rec in raw[self._folded:]:
            _name, _attempt, t0, t1, outcome, extras = rec
            key = _COUNTER_KEY.get(outcome)
            if key is None:
                key = _COUNTER_KEY[outcome] = "attempts_" + outcome
            cnt[key] = cnt.get(key, 0.0) + 1.0
            dur = t1 - t0
            h_span.add(dur)
            if extras is not None and outcome == "done":
                t_data = extras.get("t_data")
                if t_data:
                    h_data.add(t_data)
                    h_exec.add(dur - t_data if dur > t_data else 0.0)
        self._folded = len(raw)

    def timeseries(self) -> dict:
        """The metrics timeline with all recorded attempts folded in —
        this is what lands in ``prof.results["timeseries"]``."""
        self._fold()
        return self.metrics.series()

    # ------------------------------------------------------------ results
    @staticmethod
    def _materialize(raw: Tuple) -> Dict[str, Any]:
        name, attempt, t0, t1, outcome, extras = raw
        span = {"cat": TASK, "task": name, "attempt": attempt,
                "t0": t0, "t1": t1, "outcome": outcome}
        if extras:
            span.update(extras)
        return span

    @property
    def spans(self) -> List[Dict[str, Any]]:
        """All closed spans as dicts, end order: task-attempt spans
        (materialized from the raw hot-path records) then generic spans
        (parks, transfers, program spans)."""
        if len(self._span_cache) != len(self._raw) + len(self._closed):
            self._span_cache = [self._materialize(r) for r in self._raw]
            self._span_cache.extend(self._closed)
        return self._span_cache

    def unpaired(self) -> List[Dict[str, Any]]:
        """Spans still open (a clean run ends with none; pipelines parked
        at drain end legitimately remain — the caller filters by cat)."""
        out = []
        for key, val in self._open.items():
            if isinstance(val, dict):
                out.append(val)
            else:
                t0, extras = val
                span = {"cat": TASK, "task": key[0], "attempt": key[1],
                        "t0": t0, "t1": None, "outcome": None}
                if extras:
                    span.update(extras)
                out.append(span)
        return out

    def summary(self) -> dict:
        self._fold()
        return {"n_spans": len(self._raw) + len(self._closed),
                "n_events": len(self.events),
                "n_open": len(self._open), "clock": self.clock}
