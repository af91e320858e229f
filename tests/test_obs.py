"""Flight recorder (repro.obs): span tracing, TTC decomposition, exports.

Covers the observability acceptance surface:

  - span pairing: every task attempt opens and closes exactly one span on
    the virtual clock; a clean drain ends with zero open task spans
  - the decomposition identity: per-slot TTC = t_exec + t_data + t_sched
    + t_block + t_idle (+ t_exec_lost) exactly, residual < 1e-6, as a
    property over random DAGs
  - fault/preemption runs: truncated attempts (pod_lost, preempted) end
    their span at the truncation time, never overlap the retry's span,
    and the lost exec time is attributed (t_exec_lost)
  - Chrome trace_event export is deterministic (byte-identical across
    loads) and schema-valid
  - critical path on a hand-built diamond journal, with per-link slack
  - journal sim-fidelity: every sim record carries wall ``t`` AND ``vt``;
    a hand-built same-slot overlap on vt trips the sanitizer's S306
  - metrics timelines land in prof.results["timeseries"] and stay
    bounded by adaptive decimation
"""
import json
import os
import tempfile

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.obs import (MetricsTimeline, Tracer, critical_path, decompose,
                       load_segments, to_chrome)
from repro.obs.tracer import TASK
from repro.runtime.executor import PilotRuntime
from repro.runtime.faults import FaultInjector
from repro.runtime.journal import Journal
from repro.runtime.states import Task, TaskGraph


def _bag(n, duration=1.0):
    g = TaskGraph()
    for i in range(n):
        g.add(Task(name=f"t{i:03d}", duration=duration, stage="s"))
    return g


# ------------------------------------------------------------ span pairing
def test_every_attempt_is_one_paired_span():
    tr = Tracer()
    g = _bag(40)
    prof = PilotRuntime(slots=4, mode="sim", tracer=tr).run(g)
    assert prof.n_tasks == 40 and prof.n_failed == 0
    assert tr.clock == "virtual"
    spans = [s for s in tr.spans if s["cat"] == TASK]
    assert len(spans) == 40
    assert {s["task"] for s in spans} == set(g.tasks)
    for s in spans:
        assert s["outcome"] == "done"
        assert s["attempt"] == 1
        assert s["t1"] - s["t0"] == pytest.approx(1.0)
    assert not [s for s in tr.unpaired() if s["cat"] == TASK]
    ts = tr.timeseries()
    assert ts["counters"]["attempts_done"] == 40
    assert ts["histograms"]["attempt_span"]["n"] == 40
    assert ts["n_samples"] > 0
    assert "frontier_depth" in ts["gauges"]
    assert "busy_slots" in ts["gauges"]


def test_unpaired_spans_are_reported():
    tr = Tracer()
    t = Task(name="orphan", duration=1.0, stage="s")
    t.attempts = 1
    tr.task_begin(t, 0.0)
    open_spans = tr.unpaired()
    assert len(open_spans) == 1
    assert open_spans[0]["task"] == "orphan" and open_spans[0]["t1"] is None
    assert tr.summary()["n_open"] == 1


# --------------------------------------------------- decomposition identity
def _random_dag(rng_deps, durations):
    g = TaskGraph()
    names = [f"t{i:03d}" for i in range(len(durations))]
    for i, (dur, dep_draw) in enumerate(zip(durations, rng_deps)):
        deps = [names[d % i] for d in dep_draw] if i else []
        g.add(Task(name=names[i], duration=dur, stage="s",
                   deps=sorted(set(deps))))
    return g


@settings(max_examples=15, deadline=None)
@given(st.lists(st.floats(min_value=0.1, max_value=9.0),
                min_size=2, max_size=25),
       st.data())
def test_decomposition_identity_random_dags(durations, data):
    """TTC = t_exec + t_data + t_sched + t_block + t_idle per slot,
    exactly, for arbitrary DAG shapes."""
    deps = [data.draw(st.lists(st.integers(0, 1000), max_size=2),
                      label=f"deps{i}") for i in range(len(durations))]
    g = _random_dag(deps, durations)
    path = os.path.join(tempfile.mkdtemp(prefix="repro_obs_"), "run.jsonl")
    prof = PilotRuntime(slots=3, mode="sim", journal=Journal(path)).run(g)
    assert prof.n_failed == 0

    seg = load_segments(path)[-1]
    rep = decompose(seg)
    assert rep["n_open"] == 0
    assert rep["residual_max"] < 1e-6
    tot = rep["totals"]
    assert tot["t_exec"] == pytest.approx(sum(durations), abs=1e-6)
    span = seg.w1 - seg.w0
    budget = span * len(rep["slots"])
    spent = sum(tot[k] for k in
                ("t_exec", "t_data", "t_sched", "t_block", "t_idle",
                 "t_exec_lost"))
    assert spent == pytest.approx(budget, abs=1e-6)


# ------------------------------------------------ truncated spans: faults
def test_fault_run_truncated_spans_and_lost_time(tmp_path):
    """Pod loss truncates the running attempts' spans at the kill time;
    the decomposition attributes their exec time to t_exec_lost and the
    journal still balances to residual ~0."""
    tr = Tracer()
    path = str(tmp_path / "faults.jsonl")
    g = _bag(24, duration=2.0)
    faults = FaultInjector(kill_every=7.0, pods=["pod0", "pod1"],
                           max_kills=2, respawn_after=3.0)
    prof = PilotRuntime(slots=4, mode="sim", journal=Journal(path),
                        faults=faults, tracer=tr).run(g)
    assert prof.n_tasks == 24 and prof.n_failed == 0

    lost = [s for s in tr.spans if s["outcome"] == "pod_lost"]
    assert lost, "fault injection produced no truncated spans"
    by_task = {}
    for s in tr.spans:
        by_task.setdefault(s["task"], []).append(s)
    for s in lost:
        assert s["t1"] is not None and s["t1"] >= s["t0"]
        retries = [r for r in by_task[s["task"]]
                   if r["attempt"] > s["attempt"]]
        assert retries, f"{s['task']} lost its pod but never retried"
        # truncation keeps attempt spans disjoint per task
        assert all(r["t0"] >= s["t1"] - 1e-9 for r in retries)
    assert not [s for s in tr.unpaired() if s["cat"] == TASK]
    assert [e for e in tr.events if e["name"].startswith("pod_lost:")]

    rep = decompose(load_segments(path)[-1])
    assert rep["residual_max"] < 1e-6 and rep["n_open"] == 0
    assert rep["totals"]["t_exec_lost"] > 0
    assert tr.timeseries()["counters"]["attempts_pod_lost"] == len(lost)


def test_preempted_attempt_is_truncated_span():
    tr = Tracer()
    g = TaskGraph()
    g.add(Task(name="starter", duration=1.0, stage="s"))
    g.add(Task(name="lowA", duration=50.0, stage="s"))
    g.add(Task(name="lowB", duration=50.0, stage="s"))
    g.add(Task(name="hi", duration=5.0, slots=2, priority=10,
               deps=["starter"], stage="s"))
    prof = PilotRuntime(slots=2, mode="sim", preempt=True,
                        tracer=tr).run(g)
    assert prof.n_preempted >= 1 and prof.n_failed == 0

    evicted = [s for s in tr.spans if s["outcome"] == "preempted"]
    assert evicted
    for s in evicted:
        assert s["t1"] == pytest.approx(1.0)     # truncated when hi arrived
        rerun = [r for r in tr.spans if r["task"] == s["task"]
                 and r["outcome"] == "done"]
        assert len(rerun) == 1 and rerun[0]["t0"] >= s["t1"]
    assert not [s for s in tr.unpaired() if s["cat"] == TASK]


# --------------------------------------------------------- chrome export
def test_chrome_export_is_byte_identical_and_schema_valid(tmp_path):
    path = str(tmp_path / "run.jsonl")
    g = _random_dag([[], [0], [0], [1, 2]], [3.0, 1.0, 2.0, 1.0])
    PilotRuntime(slots=2, mode="sim", journal=Journal(path)).run(g)

    one = to_chrome([("run", s) for s in load_segments(path)])
    two = to_chrome([("run", s) for s in load_segments(path)])
    assert one == two                       # deterministic, byte for byte

    doc = json.loads(one)
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"M", "X", "i"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    assert any(e["name"] == "process_name" for e in evs if e["ph"] == "M")
    cats = {e["cat"] for e in xs}
    assert "exec" in cats and ("idle" in cats or "sched" in cats)


# --------------------------------------------------------- critical path
def _write_diamond(path):
    """A -> (B: 2s, C: 5s) -> D on two slots, by hand: C is critical."""
    recs = [
        {"t": 0.0, "event": "session_start", "vt": 0.0, "mode": "sim"},
        _sched("A", 1, 0.0), _fin("A", 1, 0.0, 1.0),
        _sched("B", 1, 1.0), _sched("C", 1, 1.0),
        _fin("B", 1, 1.0, 3.0), _fin("C", 1, 1.0, 6.0),
        _sched("D", 1, 6.0), _fin("D", 1, 6.0, 7.0),
    ]
    deps = {"B": ["A"], "C": ["A"], "D": ["B", "C"]}
    for r in recs:
        if r.get("event") == "scheduled":
            r["deps"] = deps.get(r["task"], [])
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def _sched(name, attempt, vt, **kw):
    return {"t": vt, "vt": vt, "task": name, "event": "scheduled",
            "state": "SCHEDULED", "attempts": attempt, **kw}


def _fin(name, attempt, v0, v1, **kw):
    return {"t": v1, "vt": v1, "task": name, "event": "finished",
            "state": "DONE", "attempts": attempt, "t_exec": v1 - v0,
            "t_data": 0.0, "v_started": v0, "v_finished": v1, **kw}


def test_critical_path_on_diamond(tmp_path):
    path = str(tmp_path / "diamond.jsonl")
    _write_diamond(path)
    seg = load_segments(path)[-1]

    chains = critical_path(seg, k=3)
    assert chains
    top = chains[0]
    assert [ln["task"] for ln in top["links"]] == ["A", "C", "D"]
    assert top["ttc"] == pytest.approx(7.0)
    # D starts the instant C finishes: zero slack on the critical edge
    assert top["links"][-1]["slack"] == pytest.approx(0.0)

    rep = decompose(seg)
    assert rep["residual_max"] < 1e-6
    assert rep["totals"]["t_exec"] == pytest.approx(1 + 2 + 5 + 1)


# ------------------------------------------------- journal sim fidelity
def test_sim_journal_records_carry_wall_and_virtual_time(tmp_path):
    path = str(tmp_path / "vt.jsonl")
    PilotRuntime(slots=2, mode="sim",
                 journal=Journal(path)).run(_bag(6, duration=2.0))
    recs = [json.loads(ln) for ln in open(path)]
    assert recs
    for r in recs:
        assert "t" in r and "vt" in r, f"record missing clocks: {r}"
    done = [r for r in recs if r["event"] == "finished"]
    assert done and all(r["vt"] == r["v_finished"] for r in done)


def test_sanitizer_s306_rejects_same_slot_overlap_on_vt(tmp_path):
    """Two attempts granted the same slot id with overlapping [v_started,
    v_finished) is a sim-fidelity violation the sanitizer must flag."""
    from repro.analysis.sanitizer import sanitize_file
    path = str(tmp_path / "overlap.jsonl")
    recs = [
        {"t": 0.0, "event": "session_start", "vt": 0.0, "mode": "sim"},
        _sched("a", 1, 0.0, slot_ids=[0]),
        _sched("b", 1, 1.0, slot_ids=[0]),       # slot 0 still held by a
        _fin("a", 1, 0.0, 3.0), _fin("b", 1, 1.0, 4.0),
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    report = sanitize_file(path)
    assert "S306" in report.codes(), report.format()


# ------------------------------------------------------- metrics timeline
def test_timeseries_lands_in_prof_results():
    from repro.core import AppManager, Kernel, PipelineSpec, Stage, TaskSpec
    k = Kernel("synthetic.noop")
    k.sim_duration = 1.0
    spec = PipelineSpec([Stage([TaskSpec(k, name=f"s.t{i}")
                                for i in range(4)], name="only")],
                        name="p")
    rt = PilotRuntime(slots=2, mode="sim", tracer=Tracer())
    prof = AppManager(rt).run([spec])
    ts = prof.results["timeseries"]
    assert ts["n_samples"] > 0
    assert ts["counters"]["attempts_done"] == 4
    assert len(ts["t"]) == ts["n_samples"]
    for series in ts["gauges"].values():
        assert len(series) == ts["n_samples"]
    assert prof.results["trace"]["n_open"] == 0


def test_metrics_decimation_keeps_timeline_bounded():
    m = MetricsTimeline(max_samples=16)
    m.gauge("x", lambda: 1.0)
    for i in range(10_000):
        m.maybe_sample(float(i))
    assert len(m.t) <= 16
    s = m.series()
    assert s["n_samples"] == len(s["t"]) == len(s["gauges"]["x"])
    # decimation keeps the earliest and tracks the latest region
    assert s["t"][0] == 0.0 and s["t"][-1] > 5_000


# ------------------------------------------------------- program spans
def _kernel_spans(cat):
    """Register (once) a kernel that opens a ``<cat>.outer`` span with a
    ``<cat>.inner`` child and jits a fresh function inside it."""
    from repro.core.kernel_plugin import _KERNEL_REGISTRY, register_kernel
    name = "test.obs.spans"
    if name not in _KERNEL_REGISTRY:
        @register_kernel(name, description="program spans + a fresh jit")
        def spans(args, ctx):
            import jax
            import jax.numpy as jnp
            tr = ctx["runtime"].tracer
            if tr is None:
                return float(jax.jit(lambda x: x + args["k"])(
                    jnp.ones(2)).sum())
            with tr.span(args["cat"], "outer", k=args["k"]):
                with tr.span(args["cat"], "inner"):
                    return float(jax.jit(lambda x: x + args["k"])(
                        jnp.ones(2)).sum())
    return name


def _span_pipes(stages=2, width=2, cat="probe"):
    from repro.core import Kernel, PipelineSpec, Stage, TaskSpec
    name = _kernel_spans(cat)
    out = []
    for s in range(stages):
        specs = []
        for j in range(width):
            k = Kernel(name)
            k.arguments = {"cat": cat, "k": 10 * s + j}
            specs.append(TaskSpec(k, name=f"p.s{s}.t{j}"))
        out.append(Stage(specs, name=f"s{s}"))
    return [PipelineSpec(out, name="p")]


def test_span_parents_on_concurrent_threads():
    """Each thread's spans nest under that thread's open span, whatever
    the others do meanwhile; a thread bound to a task attempt parents its
    outermost span to the attempt."""
    import threading
    tr = Tracer()
    n = 8
    gate = threading.Barrier(n)
    done = []

    def work(i):
        if i % 2:
            tr.bind_attempt(f"t{i}", 1)
        with tr.span("probe", "outer", i=i) as outer:
            gate.wait(timeout=10)
            with tr.span("probe", "inner", i=i) as inner:
                gate.wait(timeout=10)
                tr.record("probe", "mark", 0.0, 0.0, i=i)
        done.append((outer, inner))

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=20)
    assert not any(th.is_alive() for th in threads) and len(done) == n
    by = {(s["name"], s["i"]): s for s in tr.spans}
    assert len({s["id"] for s in tr.spans}) == 3 * n
    for i in range(n):
        outer, inner = by[("outer", i)], by[("inner", i)]
        assert outer["parent"] == ((f"t{i}", 1) if i % 2 else None)
        assert inner["parent"] == outer["id"]
        assert by[("mark", i)]["parent"] == inner["id"]
        assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]


def test_close_ends_spans_left_open_above_it():
    tr = Tracer()
    outer = tr.open("probe", "outer")
    tr.open("probe", "leaked")          # a raise skipped its close
    tr.close(outer)
    after = tr.open("probe", "next")
    tr.close(after)
    names = [s["name"] for s in tr.spans]
    assert names == ["outer", "next"]
    assert tr.spans[1]["parent"] is None


def test_span_outcome_when_the_block_raises():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("probe", "body"):
            raise ValueError("x")
    assert tr.spans[0]["outcome"] == "failed"


def test_real_drain_program_spans_share_the_runtime_clock():
    """A kernel's outermost span is parented to its task attempt and lies
    inside the attempt's span; runtime.launch runs from the last
    dependency's settling to the kernel's first line; one runtime.settle
    per attempt; the executor fixed the tracer's origin."""
    from repro.core import AppManager
    tr = Tracer()
    am = AppManager(PilotRuntime(slots=2, mode="real", tracer=tr))
    prof = am.run(_span_pipes())
    assert prof.n_failed == 0
    assert tr.t0 == am.session._t0
    tasks = {s["task"]: s for s in tr.spans if s["cat"] == TASK}
    prog = [s for s in tr.spans if s["cat"] not in (TASK, "park")]
    by_id = {s["id"]: s for s in prog}
    outer = {s["parent"][0]: s for s in prog if s["name"] == "outer"}
    assert set(outer) == set(tasks)
    for name, s in outer.items():
        assert s["parent"] == (name, 1)
        assert tasks[name]["t0"] <= s["t0"] <= s["t1"] <= tasks[name]["t1"]
    for s in prog:
        if s["name"] == "inner":
            assert by_id[s["parent"]]["name"] == "outer"
    settle = [s for s in prog if s["cat"] == "runtime"
              and s["name"] == "settle"]
    assert sorted(s["parent"][0] for s in settle) == sorted(tasks)
    launch = {s["task"]: s for s in prog if s["cat"] == "runtime"
              and s["name"] == "launch"}
    # the first stage has neither a dependency nor an input: no launch
    assert set(launch) == {"p.s1.t0", "p.s1.t1"}
    ready = max(tasks[f"p.s0.t{j}"]["t1"] for j in range(2))
    for name, s in launch.items():
        assert s["parent"] == (name, 1)
        assert s["t0"] == ready
        assert s["t1"] <= outer[name]["t0"]


def test_launch_of_a_channel_consumer_starts_at_the_put():
    from repro.core import AppManager, Channel, Kernel, PipelineSpec, \
        Stage, TaskSpec
    ch = Channel("obs.data")
    k = Kernel("synthetic.echo")
    prod = PipelineSpec([Stage([TaskSpec(k, name="prod")], name="p",
                               outputs=[ch])], name="P")
    cons = PipelineSpec([Stage([TaskSpec(k, name="cons")], name="c",
                               inputs={"x": ch})], name="C")
    tr = Tracer()
    prof = AppManager(PilotRuntime(slots=2, mode="real", tracer=tr)).run(
        [prod, cons])
    assert prof.n_failed == 0
    tasks = {s["task"]: s for s in tr.spans if s["cat"] == TASK}
    launch = [s for s in tr.spans if s["cat"] == "runtime"
              and s["name"] == "launch"]
    assert [s["task"] for s in launch] == ["cons"]
    # the put follows the producer's settling, on the same clock
    assert tasks["prod"]["t1"] <= launch[0]["t0"] <= launch[0]["t1"]
    assert launch[0]["t1"] <= tasks["cons"]["t1"]


def test_compile_listener_records_compiles_in_a_real_drain(monkeypatch):
    """A fresh jax.jit inside a task yields compile spans parented to the
    span open on the compiling thread, and counts compiles; the listener
    is registered for the drain alone."""
    import jax
    from repro.core import AppManager
    calls = []
    reg = jax.monitoring.register_event_time_span_listener
    unreg = jax.monitoring.unregister_event_time_span_listener
    monkeypatch.setattr(jax.monitoring, "register_event_time_span_listener",
                        lambda cb: (calls.append("reg"), reg(cb)))
    monkeypatch.setattr(jax.monitoring,
                        "unregister_event_time_span_listener",
                        lambda cb: (calls.append("unreg"), unreg(cb)))
    tr = Tracer()
    prof = AppManager(PilotRuntime(slots=2, mode="real", tracer=tr)).run(
        _span_pipes(stages=1))
    assert prof.n_failed == 0
    assert calls == ["reg", "unreg"]
    comp = [s for s in tr.spans if s["cat"] == "compile"]
    names = {s["name"] for s in comp}
    assert {"jaxpr_trace", "backend_compile"} <= names
    assert tr.metrics.counters["compiles"] >= 1
    assert tr.metrics.counters["compiles"] == sum(
        1 for s in comp if s["name"] == "backend_compile")
    inner = {s["id"] for s in tr.spans if s.get("name") == "inner"}
    assert all(s["parent"] in inner for s in comp)
    assert all(0.0 <= s["t0"] <= s["t1"] for s in comp)
    # after the drain, JAX's events no longer reach the tracer
    n = len(tr.spans)
    jax.monitoring.record_event_time_span(
        "/jax/core/compile/backend_compile_duration", 0.0, 1.0,
        fun_name="late")
    assert len(tr.spans) == n


def test_untraced_drain_records_nothing_and_registers_no_listener(
        monkeypatch):
    import jax
    from repro.core import AppManager
    calls = []
    monkeypatch.setattr(jax.monitoring, "register_event_time_span_listener",
                        lambda cb: calls.append(cb))
    am = AppManager(PilotRuntime(slots=2, mode="real"))
    prof = am.run(_span_pipes())
    assert prof.n_failed == 0 and calls == []
    assert "trace" not in prof.results
    assert not any("t_settled" in t.meta or "t_input" in t.meta
                   for t in am.session.graph.tasks.values())


def test_decompose_totals_ignore_program_spans():
    """The TTC decomposition reads task and park spans only: program
    spans, even ones reaching outside the attempts, leave it as it is."""
    from repro.obs.report import segment_from_tracer
    tr = Tracer()
    g = _random_dag([[0], [0, 1], [1], [], [2, 3]],
                    [1.0, 2.0, 0.5, 3.0, 1.5, 2.5])
    PilotRuntime(slots=2, mode="sim", tracer=tr).run(g)
    before = decompose(segment_from_tracer(tr))
    tr.record("compile", "backend_compile", -5.0, 1.0, fun_name="f")
    tr.record("runtime", "launch", -1.0, 0.5, task="t001", attempt=1)
    tr.record("runtime", "settle", 100.0, 101.0)
    with tr.span("engine", "decode", slots=1):
        with tr.span("engine", "wait"):
            pass
    tr.record("engine", "request", 0.0, 200.0, rid=3, first_token_at=1.0)
    after = decompose(segment_from_tracer(tr))
    assert after["totals"] == before["totals"]
    assert after["slots"] == before["slots"]


def test_member_spans_and_losses_do_not_depend_on_the_tracer():
    """lm.train under a tracer: one member.train per task, parented to
    its attempt, with a member.data child per step and a member.wait
    child per blocking read-back (three) — and the same losses as the
    untraced run of the same member."""
    from repro.core import AppManager, Kernel, PipelineSpec, Stage, TaskSpec
    from repro.plugins.lm import STATE_STORE

    def losses(ensemble, tracer):
        k = Kernel("lm.train")
        k.arguments = {"arch": "reduced:gemma2-2b", "steps": 2,
                       "member": 0, "ensemble": ensemble, "batch": 2,
                       "seq": 32, "seed": 3}
        am = AppManager(PilotRuntime(slots=1, mode="real", tracer=tracer))
        prof = am.run([PipelineSpec([Stage([TaskSpec(k, name="m0")])],
                                    name="p")])
        assert prof.n_failed == 0
        STATE_STORE.pop((ensemble, 0), None)
        return am.session.graph.tasks["m0"].result["losses"]

    tr = Tracer()
    assert losses("obs_traced", tr) == losses("obs_plain", None)
    body = [s for s in tr.spans if s["cat"] == "member"
            and s["name"] == "train"]
    assert len(body) == 1 and body[0]["parent"] == ("m0", 1)
    kids = [s["name"] for s in tr.spans if s["cat"] == "member"
            and s["parent"] == body[0]["id"]]
    assert sorted(kids) == ["data", "data", "wait", "wait", "wait"]
