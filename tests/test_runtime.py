"""Runtime/scheduler invariants: unit tests + hypothesis property tests."""
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.runtime.executor import PilotRuntime
from repro.runtime.journal import Journal
from repro.runtime.states import Task, TaskGraph, TaskState


def _graph(durations, deps=None, slots=None):
    g = TaskGraph()
    for i, d in enumerate(durations):
        g.add(Task(name=f"t{i}", duration=float(d),
                   deps=[f"t{j}" for j in (deps or {}).get(i, [])],
                   slots=(slots or {}).get(i, 1), stage="s"))
    return g


# ---------------------------------------------------------------- property

@settings(max_examples=60, deadline=None)
@given(st.data())
def test_sim_scheduler_invariants(data):
    n = data.draw(st.integers(1, 24))
    slots = data.draw(st.integers(1, 8))
    durations = data.draw(st.lists(
        st.floats(0.0, 100.0, allow_nan=False), min_size=n, max_size=n))
    # random acyclic deps: edges only to earlier tasks
    deps = {}
    for i in range(n):
        if i and data.draw(st.booleans()):
            k = data.draw(st.integers(1, min(i, 3)))
            deps[i] = list(np.random.default_rng(i).choice(i, k, False))
    g = _graph(durations, deps)
    rt = PilotRuntime(slots=slots, mode="sim")
    prof = rt.run(g)

    # all tasks reach a terminal state, none failed (no failure injection)
    assert all(t.state == TaskState.DONE for t in g.tasks.values())
    # makespan bounds: >= critical path, <= serial sum (+eps)
    total = sum(durations)
    # critical path lower bound
    cp = {}
    for i in range(n):
        cp[i] = durations[i] + max((cp[j] for j in deps.get(i, [])),
                                   default=0.0)
    assert prof.ttc >= max(cp.values()) - 1e-6
    assert prof.ttc <= total + 1e-6
    # never oversubscribed: with 1-slot tasks, ttc >= total/slots
    assert prof.ttc >= total / slots - 1e-6
    # dependency order respected
    for i, t in enumerate(g.tasks.values()):
        for d in t.deps:
            assert g.tasks[d].v_finished <= t.v_started + 1e-9


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 30), st.integers(1, 6))
def test_sim_bag_exact_makespan(n, slots):
    g = _graph([10.0] * n)
    prof = PilotRuntime(slots=slots, mode="sim").run(g)
    assert abs(prof.ttc - 10.0 * int(np.ceil(n / slots))) < 1e-9


# ---------------------------------------------------------------- unit

def test_real_mode_runs_callables():
    calls = []
    g = TaskGraph()
    g.add(Task(name="a", run=lambda t: calls.append("a") or 1))
    g.add(Task(name="b", deps=["a"], run=lambda t: calls.append("b") or 2))
    prof = PilotRuntime(slots=2, mode="real").run(g)
    assert calls == ["a", "b"]
    assert g.tasks["b"].result == 2
    assert prof.n_failed == 0


def test_retry_then_fail_cancels_dependents():
    attempts = {"n": 0}

    def boom(t):
        attempts["n"] += 1
        raise RuntimeError("nope")

    g = TaskGraph()
    g.add(Task(name="a", run=boom))
    g.add(Task(name="b", deps=["a"], run=lambda t: 1))
    prof = PilotRuntime(slots=1, mode="real", max_retries=2).run(g)
    assert attempts["n"] == 3                        # 1 + 2 retries
    assert g.tasks["a"].state == TaskState.FAILED
    assert g.tasks["b"].state == TaskState.CANCELED
    assert prof.n_failed == 1


def test_straggler_speculation_cuts_makespan():
    g = _graph([10.0] * 15 + [100.0])
    rt = PilotRuntime(slots=8, mode="sim", straggler_factor=2.0)
    prof = rt.run(g)
    assert prof.n_speculative >= 1
    assert prof.ttc < 100.0
    assert all(t.state == TaskState.DONE for t in g.tasks.values())


def test_elastic_grow_and_shrink():
    rt = PilotRuntime(slots=2, mode="sim")
    rt.resize(4)
    prof = rt.run(_graph([10.0] * 8))
    assert prof.ttc == 20.0
    rt = PilotRuntime(slots=8, mode="sim")
    rt.resize(2)
    prof = rt.run(_graph([10.0] * 8))
    assert prof.ttc == 40.0


def test_journal_restart_skips_done():
    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/j.jsonl"
        g1 = _graph([1.0] * 5)
        PilotRuntime(slots=2, mode="sim", journal=Journal(path)).run(g1)
        g2 = _graph([1.0] * 5)
        prof = PilotRuntime(slots=2, mode="sim",
                            journal=Journal(path)).run(g2)
        assert prof.ttc == 0.0          # everything replayed as DONE
        assert all(t.state == TaskState.DONE for t in g2.tasks.values())


def test_graph_cycle_detection():
    g = TaskGraph()
    g.add(Task(name="a", deps=["b"]))
    g.add(Task(name="b", deps=["a"]))
    with pytest.raises(ValueError, match="cycle"):
        g.validate()


def test_multislot_tasks_respect_capacity():
    g = _graph([10.0, 10.0, 10.0], slots={0: 2, 1: 2, 2: 2})
    prof = PilotRuntime(slots=4, mode="sim").run(g)
    assert prof.ttc == 20.0             # two fit concurrently, third waits


# ------------------------------------------------------- sim-mode edges

def _slot_topology(n):
    from repro.dist.topology import SlotTopology
    # slot accounting needs no real devices; any object array works
    return SlotTopology(np.arange(n).reshape(n, 1), ("model",))


def test_speculative_supersession_frees_slot_exactly_once():
    """Duplicate wins: the straggling original's slot is freed at
    supersession and must NOT be freed again when its stale finish event
    pops off the heap."""
    topo = _slot_topology(8)
    g = _graph([10.0] * 15 + [200.0])
    rt = PilotRuntime(mode="sim", straggler_factor=2.0, topology=topo)
    prof = rt.run(g)
    orig = g.tasks["t15"]
    assert prof.n_speculative == 1
    assert orig.state == TaskState.DONE
    assert orig.meta.get("slot_freed") is True          # superseded
    # duplicate launched at trigger=30 (2x median after start=10), runs the
    # median 10s: makespan 40, far below the 200s straggler
    assert prof.ttc == 40.0
    assert all(t.state == TaskState.DONE for t in g.tasks.values())
    # slot-id pool intact: a double free (or leak) would change its size
    assert sorted(rt._free_ids) == list(range(8))
    assert prof.slot_busy <= prof.ttc * 8 + 1e-9


def test_canceled_twin_bookkeeping():
    """Original wins: the speculative twin is CANCELED and contributes
    nothing to t_exec/slot_busy; its heap pop releases its slot."""
    topo = _slot_topology(8)
    g = _graph([10.0] * 15 + [25.0])
    rt = PilotRuntime(mode="sim", straggler_factor=2.0, topology=topo)
    prof = rt.run(g)
    # trigger 30 + median 10 = 40 > the original's finish at 35: orig wins
    assert prof.n_speculative == 1
    assert g.tasks["t15"].state == TaskState.DONE
    assert not g.tasks["t15"].meta.get("slot_freed")    # not superseded
    assert prof.ttc == 35.0
    assert prof.t_exec == 15 * 10.0 + 25.0              # twin excluded
    assert prof.slot_busy == prof.t_exec                # 1-slot tasks
    assert sorted(rt._free_ids) == list(range(8))       # twin's id returned


def _max_overlap(intervals):
    """Most intervals open at once (an end at x closes before a start at
    x opens)."""
    edges = sorted([(a, 1) for a, _ in intervals]
                   + [(b, -1) for _, b in intervals])
    now = peak = 0
    for _, d in edges:
        now += d
        peak = max(peak, now)
    return peak


@pytest.mark.parametrize("mode", ["sim", "real"])
def test_resize_takes_effect_mid_run(mode):
    """Elastic grow DURING a run (not between runs): the on_schedule hook
    fires resize() once the first task finished; later waves run at the
    new width, which free capacity (slots minus busy) admits at once on
    both clocks.  A real attempt sleeps its duration."""
    fired = []

    def grow(rt, graph, vnow):
        if not fired and any(t.state == TaskState.DONE
                             for t in graph.tasks.values()):
            fired.append(vnow)
            rt.resize(4)

    rt = PilotRuntime(slots=2, mode=mode, on_schedule=grow)
    dur = {"sim": 10.0, "real": 0.2}[mode]
    sess = rt.session()
    sess.submit([Task(name=f"t{i}", duration=dur) for i in range(8)])
    prof = sess.drain()
    assert rt.slots == 4
    assert all(t.state == TaskState.DONE for t in sess.graph.tasks.values())
    assert sess.busy_slots == 0
    tasks = sess.graph.tasks.values()
    if mode == "sim":
        # wave1: 2 tasks @[0,10); resize at v=10; then 4-wide: 4 @[10,20),
        # 2 @[20,30) -> makespan 30 (serial 2-wide would be 40)
        assert fired == [10.0]
        assert prof.ttc == 30.0
        spans = [(t.v_started, t.v_finished) for t in tasks]
    else:
        assert fired == [None]
        spans = [(t.t_started, t.t_finished) for t in tasks]
    assert _max_overlap(spans) == 4


def test_resize_takes_effect_mid_run_real_mode():
    """Real-mode grow while a task is in flight: the freed capacity must
    reach the scheduler (two tasks rendezvous on a barrier that only
    passes if both run concurrently)."""
    import threading

    barrier = threading.Barrier(2, timeout=10)
    g = TaskGraph()
    g.add(Task(name="a", run=lambda t: barrier.wait()))
    g.add(Task(name="b", run=lambda t: barrier.wait()))
    grown = []

    def grow(rt, graph, vnow):
        if not grown and graph.tasks["a"].state == TaskState.RUNNING:
            grown.append(1)
            rt.resize(2)

    prof = PilotRuntime(slots=1, mode="real", on_schedule=grow).run(g)
    assert grown
    assert prof.n_failed == 0
    assert all(t.state == TaskState.DONE for t in g.tasks.values())


def test_real_mode_never_oversubscribes():
    """Regression: one scheduling pass admits several ready tasks and must
    re-check capacity per task (a stale snapshot launched 2 tasks on a
    1-slot pilot)."""
    import threading
    import time as _time

    lock = threading.Lock()
    concurrency = {"now": 0, "max": 0}

    def work(t):
        with lock:
            concurrency["now"] += 1
            concurrency["max"] = max(concurrency["max"], concurrency["now"])
        _time.sleep(0.05)
        with lock:
            concurrency["now"] -= 1

    g = TaskGraph()
    for i in range(4):
        g.add(Task(name=f"t{i}", run=work))
    prof = PilotRuntime(slots=2, mode="real").run(g)
    assert prof.n_failed == 0
    assert concurrency["max"] <= 2


def test_real_busy_account_balances_under_thread_churn():
    """Stress: the busy account is written by the drain thread (launch)
    and, under the same condition variable, by every worker (settle).
    Many short attempts on more worker threads than cores, with a short
    switch interval: a lost update would leave busy slots above zero
    after the drain or let more attempts run than the pilot holds."""
    import sys
    import threading
    import time as _time

    lock = threading.Lock()
    running = {"now": 0, "max": 0}

    def work(t):
        with lock:
            running["now"] += 1
            running["max"] = max(running["max"], running["now"])
        _time.sleep(0.002)
        with lock:
            running["now"] -= 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        rt = PilotRuntime(slots=16, mode="real")
        sess = rt.session()
        sess.submit([Task(name=f"t{i}", run=work, slots=1 + i % 3)
                     for i in range(300)])
        t0 = _time.perf_counter()
        prof = sess.drain()
        assert _time.perf_counter() - t0 < 60.0
    finally:
        sys.setswitchinterval(interval)
    assert prof.n_failed == 0
    assert all(t.state == TaskState.DONE for t in sess.graph.tasks.values())
    assert sess.busy_slots == 0
    assert running["max"] <= 16


def test_multislot_with_topology_grants_disjoint_submeshes():
    topo = _slot_topology(4)
    g = _graph([10.0, 10.0, 10.0], slots={0: 2, 1: 2, 2: 2})
    rt = PilotRuntime(mode="sim", topology=topo)
    prof = rt.run(g)
    assert prof.ttc == 20.0
    for t in g.tasks.values():
        assert len(t.meta["slot_ids"]) == 2
    # the two concurrent tasks held disjoint ids
    first_wave = [t for t in g.tasks.values() if t.v_started == 0.0]
    held = sum((t.meta["slot_ids"] for t in first_wave), [])
    assert len(held) == len(set(held)) == 4
    assert sorted(rt._free_ids) == list(range(4))


# ------------------------------------------------------- journal replay

def test_journal_partial_replay_skips_done():
    """Restart from a PARTIAL journal: only unjournaled tasks re-run, and
    the restarted profile still accounts for the full graph."""
    import json
    import os

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "j.jsonl")
        g1 = _graph([1.0] * 6)
        prof1 = PilotRuntime(slots=2, mode="sim",
                             journal=Journal(path)).run(g1)
        # crash simulation: keep the records of 3 tasks + one torn line
        keep = [ln for ln in open(path).read().splitlines()
                if json.loads(ln).get("task") in ("t0", "t1", "t2")]
        with open(path, "w") as f:
            f.write("\n".join(keep) + '\n{"task": "t3", "ev')
        g2 = _graph([1.0] * 6)
        prof2 = PilotRuntime(slots=2, mode="sim",
                             journal=Journal(path)).run(g2)
        assert prof2.n_tasks == prof1.n_tasks == 6
        assert {"event": "journal_skip", "n": 3} in \
            [{k: e[k] for k in ("event", "n")} for e in prof2.events
             if e.get("event") == "journal_skip"]
        assert prof2.t_exec == 3.0          # only t3..t5 executed
        assert all(t.state == TaskState.DONE for t in g2.tasks.values())


def test_metropolis_host_vs_device():
    import jax
    import jax.numpy as jnp
    from repro.core.ensemble import metropolis_swap_device
    from repro.plugins.re_exchange import metropolis_swaps
    losses = np.array([3.0, 2.5, 2.0, 1.5])
    temps = np.array([1.0, 2.0, 4.0, 8.0])
    # deterministic acceptance: huge energy gap -> always swap pair (0,1)
    newt, acc = metropolis_swaps([10.0, 0.0, 0.0, 0.0],
                                 [1.0, 10.0, 10.0, 10.0], cycle=0)
    assert (newt[0], newt[1]) == (10.0, 1.0)
    nt_dev, nacc = metropolis_swap_device(
        jnp.array([10.0, 0.0, 0.0, 0.0]), jnp.array([1.0, 10.0, 10.0, 10.0]),
        0, jax.random.PRNGKey(0))
    assert float(nt_dev[0]) == 10.0 and float(nt_dev[1]) == 1.0


# ---------------------------------------------------------------- preemption

@pytest.mark.parametrize("mode", ["sim", "real"])
def test_preemption_requeues_victim_and_discards_zombie(mode):
    """A ready high-priority task that cannot fit evicts a running
    priority-0 attempt: eviction is the abandon path (epoch nulled, state
    NEW), not a failure (no pod blame, no retry spent), and the victim
    reruns to completion afterwards.  The evicted attempt's completion
    still arrives (a stale heap entry on the virtual clock, a worker
    thread that cannot be stopped on the wall clock) and is discarded:
    the rerun's result is the one that lands.  Sim runs the durations,
    real the callables."""
    import threading

    release = threading.Event()
    calls = []

    def low_run(t):
        n = len(calls)
        calls.append(n)
        release.wait(10.0)
        return f"low{n}"

    g = TaskGraph()
    g.add(Task(name="starter", duration=1.0,
               run=lambda t: __import__("time").sleep(0.05), stage="s"))
    g.add(Task(name="lowA", duration=50.0, run=low_run, stage="s"))
    g.add(Task(name="hi", duration=5.0, slots=2, priority=10,
               deps=["starter"], run=lambda t: release.set() or "hi",
               stage="s"))
    rt = PilotRuntime(slots=2, mode=mode, preempt=True)
    recs = []
    rt.journal.observer = recs.append
    prof = rt.run(g)

    assert prof.n_preempted == 1 and prof.n_failed == 0
    assert prof.n_retries == 0              # no retry budget spent
    assert all(t.state == TaskState.DONE for t in g.tasks.values())
    hi, lowA = g.tasks["hi"], g.tasks["lowA"]
    assert lowA.attempts == 2               # evicted attempt + rerun
    assert [h["outcome"] for h in lowA.history] == ["preempted", "done"]
    assert not lowA.excluded_pods()         # preemption never blames a pod
    assert [r["event"] for r in recs if r.get("task") == "lowA"] == [
        "scheduled", "preempted", "scheduled", "finished"]
    if mode == "sim":
        # hi launched the moment it became ready, not after a 50s task;
        # the evicted attempt's entry (v=50) did not finish lowA
        assert hi.v_started == 1.0 and hi.v_finished == 6.0
        assert lowA.v_finished == 56.0
    else:
        assert len(calls) == 2              # zombie attempt + rerun
        assert lowA.result == "low1"        # zombie's "low0" was discarded
        assert hi.result == "hi"


def test_preempted_attempt_history_replays_from_journal():
    """The journal reconstructs a preempted task's attempt history, and
    the sanitizer accepts the preempt/requeue record stream."""
    from repro.analysis import sanitize_file

    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/j.jsonl"
        g = TaskGraph()
        g.add(Task(name="starter", duration=1.0, stage="s"))
        g.add(Task(name="lowA", duration=50.0, stage="s"))
        g.add(Task(name="hi", duration=5.0, slots=2, priority=10,
                   deps=["starter"], stage="s"))
        prof = PilotRuntime(slots=2, mode="sim", preempt=True,
                            journal=Journal(path)).run(g)
        assert prof.n_preempted == 1
        _, _, history = Journal(path).load_state()
        assert [h["outcome"] for h in history["lowA"]] == ["preempted"]
        assert history["lowA"][0]["attempt"] == 1
        assert sanitize_file(path).ok
