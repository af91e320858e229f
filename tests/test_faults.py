"""Fault-tolerant fleet: pod death, retry exclusion, epochs, spill GC.

Covers the failure half of the pilot runtime: sim-mode pod kills with
history-driven retries placed off the dead pod, capacity shrink vs respawn
vs topology shrink-recarve, real-mode worker-thread death and heartbeat
staleness, deterministic DES ordering under speculation, speculative twins
charging t_data through shared staging manifests, canceled twins settling
journal/staging state, spill-file GC at close, and journal replay of a run
crashed mid-retry.
"""
import json
import threading

import numpy as np
import pytest

from repro.core import AppManager, Kernel, PipelineSpec, Stage, TaskSpec
from repro.dist.topology import SlotTopology
from repro.runtime.executor import PilotRuntime
from repro.runtime.faults import FaultInjector
from repro.runtime.journal import Journal
from repro.runtime.states import Task, TaskGraph, TaskState
from repro.staging import LocalityMap, StagingLayer
from repro.staging.store import ObjectStore


def bag(n, dur=10.0, stage="w"):
    g = TaskGraph()
    for i in range(n):
        g.add(Task(name=f"t{i}", duration=dur, stage=stage))
    return g


def _journal_records(rt):
    """Collect every record the runtime's journal emits (no file)."""
    recs = []
    rt.journal.observer = recs.append
    return recs


def _kinds(recs, task=None):
    """Event kinds of one task's records, or of the taskless ones."""
    return [r["event"] for r in recs if r.get("task") == task]


# ------------------------------------------------------- kills, both clocks
# The pod-kill, abandon and retry policies are one code path on both
# clocks: each case runs on the virtual clock and on the wall clock and
# checks the same attempt outcomes and journal event kinds.  A real
# attempt sleeps its duration, so the wall-clock case runs shorter.

@pytest.mark.parametrize("mode", ["sim", "real"])
def test_pod_kill_retries_off_dead_pod(mode):
    dur, kill_at = {"sim": (10.0, 5.0), "real": (0.5, 0.1)}[mode]
    faults = FaultInjector(kill_at=[(kill_at, "pod2")])
    rt = PilotRuntime(slots=8, mode=mode, faults=faults, max_retries=2)
    recs = _journal_records(rt)
    g = bag(8, dur=dur)
    prof = rt.run(g)

    assert prof.n_failed == 0
    assert prof.n_pod_lost == 1 and prof.n_retries == 1
    victims = [t for t in g.tasks.values()
               if any(h["outcome"] == "pod_lost" for h in t.history)]
    assert len(victims) == 1
    t = victims[0]
    assert t.state == TaskState.DONE
    assert t.error is None                  # stale error cleared on retry
    assert t.attempts == 2
    hist = {h["attempt"]: h for h in t.history}
    assert hist[1]["outcome"] == "pod_lost" and hist[1]["pod"] == "pod2"
    assert hist[2]["outcome"] == "done" and hist[2]["pod"] != "pod2"
    assert _kinds(recs, t.name) == ["scheduled", "pod_lost", "scheduled",
                                     "finished"]
    assert _kinds(recs) == ["session_start", "pod_lost"]
    # kept per clock: the virtual clock stamps its kill time on the
    # taskless record and in the attempt's error
    lost = next(r for r in recs if r["event"] == "pod_lost"
                and "task" not in r)
    if mode == "sim":
        assert lost["v"] == 5.0
        assert hist[1]["error"] == "pod_lost: pod pod2 died at v=5"
        # retry waited for a completion (v=10), then ran 10s on a live pod
        assert prof.ttc == 20.0
    else:
        assert "v" not in lost
        assert hist[1]["error"] == "pod_lost: pod pod2"
    # the dead pod's id is retired; every surviving id returned exactly once
    assert rt.slots == 7
    assert sorted(rt._free_ids) == [0, 1, 3, 4, 5, 6, 7]
    assert rt.dead_pods == {"pod2"}


@pytest.mark.parametrize("mode", ["sim", "real"])
def test_pod_loss_failure_cancels_dependents(mode):
    """Two kills exhaust a one-retry budget: the task fails on the second
    pod, and its dependent is canceled, not left waiting."""
    faults = FaultInjector(kill_at=[(0.1, "pod0"), (0.6, "pod1")])
    rt = PilotRuntime(slots=3, mode=mode, faults=faults, max_retries=1)
    recs = _journal_records(rt)
    g = TaskGraph()
    g.add(Task(name="a", duration=1.5))
    g.add(Task(name="b", duration=1.5, deps=["a"]))
    prof = rt.run(g)

    a, b = g.tasks["a"], g.tasks["b"]
    assert a.state == TaskState.FAILED and b.state == TaskState.CANCELED
    assert [(h["outcome"], h["pod"]) for h in a.history] == [
        ("pod_lost", "pod0"), ("pod_lost", "pod1")]
    assert _kinds(recs, "a") == ["scheduled", "pod_lost", "scheduled",
                                 "pod_lost", "failed"]
    assert _kinds(recs, "b") == ["canceled"]
    assert _kinds(recs) == ["session_start", "pod_lost", "pod_lost"]
    assert (prof.n_failed, prof.n_canceled) == (1, 1)
    assert (prof.n_pod_lost, prof.n_retries) == (2, 1)
    assert rt.slots == 1


# ---------------------------------------------------------------- sim kills

def test_sim_pod_respawn_restores_capacity():
    faults = FaultInjector(kill_at=[(5.0, "pod2")], respawn_after=3.0)
    rt = PilotRuntime(slots=8, mode="sim", faults=faults)
    g = bag(8)
    prof = rt.run(g)

    assert prof.n_failed == 0
    # replacement pod joined: full capacity and id pool restored
    assert rt.slots == 8
    assert sorted(rt._free_ids) == list(range(8))
    assert not rt.dead_pods and not rt._dead_ids
    # retry launched the moment the replacement arrived (v=8), on the
    # revived pod — exclusion is a preference, availability wins
    t = next(t for t in g.tasks.values() if t.attempts == 2)
    assert t.history[-1]["outcome"] == "done"
    assert prof.ttc == 18.0
    events = [e["event"] for e in prof.events]
    assert "pod_lost" in events and "pod_revived" in events


def test_topology_shrink_recarve_after_pod_loss():
    topo = SlotTopology.even(np.arange(8), 8)
    faults = FaultInjector(kill_at=[(5.0, "pod3")])
    rt = PilotRuntime(topology=topo, mode="sim", faults=faults)
    g = bag(8)
    prof = rt.run(g)

    assert prof.n_failed == 0
    # the dead slot's devices left the fleet; ids renumbered compactly
    assert rt.topology.n_slots == 7
    assert rt.slots == 7
    assert not rt._dead_ids and not rt.dead_pods and not rt._drop_pending
    assert sorted(rt._free_ids) == list(range(7))
    # device 3 is gone from the compacted topology
    assert 3 not in rt.topology.devices.ravel().tolist()


# ---------------------------------------------------------------- real mode

@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_real_worker_thread_death_retries():
    calls = {"n": 0}

    def run(task):
        calls["n"] += 1
        if calls["n"] == 1:
            raise SystemExit("oom killed")   # escapes the except Exception
        return "ok"

    rt = PilotRuntime(slots=2, mode="real", max_retries=2)
    g = TaskGraph()
    g.add(Task(name="t0", run=run))
    prof = rt.run(g)

    t = g.tasks["t0"]
    assert t.state == TaskState.DONE and t.result == "ok"
    assert t.error is None
    assert prof.n_failed == 0 and prof.n_pod_lost == 1
    assert [h["outcome"] for h in t.history] == ["worker_died", "done"]


def test_real_heartbeat_timeout_retries_and_ignores_zombie():
    release = threading.Event()
    calls = {"n": 0}

    def run(task):
        calls["n"] += 1
        if calls["n"] == 1:
            release.wait(5.0)          # hung attempt: never beats again
            return "late"
        return "ok"

    rt = PilotRuntime(slots=2, mode="real", heartbeat_timeout=0.15,
                      max_retries=2)
    g = TaskGraph()
    g.add(Task(name="h0", run=run))
    prof = rt.run(g)
    release.set()

    t = g.tasks["h0"]
    assert t.state == TaskState.DONE and t.result == "ok"
    assert "heartbeat_timeout" in [h["outcome"] for h in t.history]
    assert prof.n_pod_lost >= 1 and prof.n_failed == 0
    # abandoned attempt's slot id credited exactly once
    assert sorted(rt._free_ids) == [0, 1]


def test_real_pod_kill_mid_run():
    started = threading.Event()

    def slow(task):
        started.set()
        import time as _t
        _t.sleep(0.3)
        return "v"

    faults = FaultInjector()
    rt = PilotRuntime(slots=4, mode="real", faults=faults, max_retries=2)
    g = TaskGraph()
    g.add(Task(name="r0", run=slow))

    def killer():
        started.wait(5.0)
        rt.inject_pod_failure()        # kills the busiest pod

    th = threading.Thread(target=killer)
    th.start()
    prof = rt.run(g)
    th.join()

    t = g.tasks["r0"]
    assert t.state == TaskState.DONE and t.result == "v"
    assert prof.n_failed == 0
    assert any(h["outcome"] == "pod_lost" for h in t.history)
    # the killed pod stays retired (no respawn configured)
    assert len(rt.dead_pods) == 1
    dead = next(iter(rt.dead_pods))
    assert t.history[-1]["pod"] != dead


# ---------------------------------------------------------------- DES order

def test_sim_speculation_is_deterministic():
    def run_once():
        tasks = [Task(name=f"t{i}",
                      duration=50.0 if i >= 10 else 10.0, stage="s")
                 for i in range(12)]
        rt = PilotRuntime(slots=6, mode="sim", straggler_factor=2.0)
        order = []
        sess = rt.session(
            on_task_done=lambda t, s: order.append((t.name, s.vnow)))
        sess.submit(tasks)
        prof = sess.drain()
        return order, prof.ttc, prof.n_speculative

    o1, ttc1, ns1 = run_once()
    o2, ttc2, ns2 = run_once()
    assert ns1 == ns2 and ns1 >= 1     # duplicates actually launched
    assert o1 == o2                    # identical completion sequence
    assert ttc1 == ttc2


# ------------------------------------------------------------ clone staging

COPY_COST = 1e-4 + 250_000_000 / (25.0 * 1e9)    # latency + nbytes/copy_gbps
# first consumer pulls the blob over the slow host link (tiered planner);
# later consumers copy pod->pod off the replica it left behind
HOST_COST = 1e-4 + 250_000_000 / (8.0 * 1e9)


def _staged_straggler(straggler_dur, tmp_path):
    layer = StagingLayer(locality=LocalityMap(8, slots_per_pod=1),
                         threshold_bytes=1024)
    jpath = str(tmp_path / "j.jsonl")
    rt = PilotRuntime(slots=8, mode="sim", staging=layer,
                      straggler_factor=2.0, journal=Journal(jpath))
    g = TaskGraph()
    for i in range(6):
        g.add(Task(name=f"w{i}", duration=10.0, stage="s"))
    s = Task(name="s0", duration=straggler_dur, stage="s")
    ref = layer.stage_virtual("blob", 250_000_000, [])   # lives at host
    layer.manifest_input(s, "x", ref)
    g.add(s)
    return layer, rt, g, ref, jpath


def test_speculative_clone_charges_t_data(tmp_path):
    layer, rt, g, ref, _ = _staged_straggler(100.0, tmp_path)
    prof = rt.run(g)

    assert prof.n_speculative == 1 and prof.n_failed == 0
    # the clone copied host -> its pod through the SHARED manifest; the
    # superseded original's charge is dropped, so the profile carries
    # exactly the winning clone's transfer — terms stay disjoint
    assert prof.t_data == pytest.approx(COPY_COST, rel=1e-6)
    assert layer.planner.stats["copy"] == 2      # original AND clone moved
    assert layer.store.refcount(ref.digest) == 0  # all holds released
    assert g.tasks["s0"].state == TaskState.DONE


def test_canceled_twin_settles_journal_staging_and_t_data(tmp_path):
    # original (25s) beats the clone (starts at 20, runs the 10s median)
    layer, rt, g, ref, jpath = _staged_straggler(25.0, tmp_path)
    prof = rt.run(g)

    assert prof.n_speculative == 1 and prof.n_failed == 0
    assert g.tasks["s0"].state == TaskState.DONE
    # both twins moved the blob; the canceled clone's t_data still counts
    # (host -> pod for the original, pod -> pod for the clone)
    assert prof.t_data == pytest.approx(HOST_COST + COPY_COST, rel=1e-6)
    assert layer.store.refcount(ref.digest) == 0  # clone's hold released
    recs = [json.loads(line) for line in open(jpath)]
    cancels = [r for r in recs
               if r.get("event") == "canceled" and r.get("by") == "original"]
    assert len(cancels) == 1 and cancels[0]["task"].startswith("s0.spec")
    # full slot pool back: no twin leaked its ids
    assert sorted(rt._free_ids) == list(range(8))


# ---------------------------------------------------------------- spill GC

def test_spill_gc_keeps_journaled_refs(tmp_path):
    spill = tmp_path / "spill"
    layer = StagingLayer(store=ObjectStore(spill_dir=str(spill)),
                         threshold_bytes=16)
    ta, tb = Task(name="a"), Task(name="b")
    keep_val = {"x": list(range(100))}
    r_keep = layer.acquire_stage_in(ta, keep_val)
    r_drop = layer.acquire_stage_in(tb, {"y": list(range(200))})
    j = Journal(str(tmp_path / "j.jsonl"))
    j.record_flow("channel_put", "ch", "p",
                  digest=r_keep.digest, nbytes=r_keep.nbytes)
    layer.finish(ta)
    layer.finish(tb)                    # both refcounts now 0

    assert layer.gc_spill(j, keep_durable=True) == 1
    names = {p.name for p in spill.glob("*.blob")}
    assert names == {f"{r_keep.digest}.blob"}

    # restartability: a fresh store re-materializes the journaled ref
    store2 = ObjectStore(spill_dir=str(spill))
    assert store2.get(r_keep.digest) == keep_val
    with pytest.raises(KeyError):
        store2.get(r_drop.digest)

    # keep_durable=False drops the journal keep-set too
    assert layer.gc_spill(j, keep_durable=False) == 1
    assert not list(spill.glob("*.blob"))
    j.close()


def test_runtime_close_runs_spill_gc(tmp_path):
    spill = tmp_path / "spill"
    layer = StagingLayer(store=ObjectStore(spill_dir=str(spill)),
                         threshold_bytes=16)
    rt = PilotRuntime(slots=2, mode="real", staging=layer,
                      journal=Journal(str(tmp_path / "j.jsonl")))
    t = Task(name="a")
    layer.acquire_stage_in(t, {"z": list(range(50))})
    layer.finish(t)
    assert len(list(spill.glob("*.blob"))) == 1
    assert rt.close() == 1              # unreferenced spill file reclaimed
    assert not list(spill.glob("*.blob"))
    assert rt.journal._fh is None       # journal closed too


# ------------------------------------------------------------ replay/retry

def test_journal_replay_resumes_mid_retry(tmp_path):
    jpath = str(tmp_path / "j.jsonl")
    j = Journal(jpath)
    crashed = Task(name="t0")
    crashed.attempts = 1
    crashed.meta["slot_ids"] = [2]
    j.record(crashed, "pod_lost", pod="pod2")
    crashed.attempts = 2
    crashed.meta["slot_ids"] = [1]
    j.record(crashed, "worker_died", pod="pod1")
    j.close()

    # restart: same journal; FaultInjector() turns on slot-id tracking so
    # the pod exclusion is observable
    rt = PilotRuntime(slots=4, mode="sim", journal=Journal(jpath),
                      faults=FaultInjector(), max_retries=3)
    g = TaskGraph()
    g.add(Task(name="t0", duration=5.0))
    g.add(Task(name="t1", duration=5.0))
    prof = rt.run(g)

    t = g.tasks["t0"]
    assert t.state == TaskState.DONE
    assert t.attempts == 3              # resumed at attempt 3, not 1
    assert prof.n_failed == 0
    blamed = {h["pod"] for h in t.history if h["outcome"] != "done"}
    assert blamed == {"pod1", "pod2"}
    done = [h for h in t.history if h["outcome"] == "done"]
    assert len(done) == 1 and done[0]["attempt"] == 3
    assert done[0]["pod"] not in blamed    # re-grant excluded both pods


def test_journal_replay_exhausted_retries_fail_fast(tmp_path):
    jpath = str(tmp_path / "j.jsonl")
    j = Journal(jpath)
    crashed = Task(name="t0")
    for i, pod in enumerate(("pod0", "pod1", "pod2"), start=1):
        crashed.attempts = i
        j.record(crashed, "pod_lost", pod=pod)
    j.close()

    rt = PilotRuntime(slots=4, mode="sim", journal=Journal(jpath),
                      faults=FaultInjector(), max_retries=3)
    g = TaskGraph()
    g.add(Task(name="t0", duration=5.0))
    prof = rt.run(g)
    # attempts resumed at 3: exactly one more try within the budget
    assert g.tasks["t0"].attempts == 4
    assert g.tasks["t0"].state == TaskState.DONE
    assert prof.n_failed == 0


# ------------------------------------------------------------ PST profiles

def test_pipeline_profile_reports_failure_counts():
    def member(dur):
        k = Kernel("synthetic.noop")
        k.sim_duration = dur
        return k

    staging = StagingLayer(locality=LocalityMap(4, slots_per_pod=1),
                           threshold_bytes=1 << 30)
    faults = FaultInjector(kill_at=[(5.0, "pod1")], respawn_after=2.0)
    rt = PilotRuntime(slots=4, mode="sim", staging=staging, faults=faults,
                      max_retries=2)
    am = AppManager(rt)
    pipes = [PipelineSpec(
        [Stage([TaskSpec(member(10.0), name=f"p{p}.m{m}")
                for m in range(2)], name="s0")], name=f"p{p}")
        for p in range(2)]
    prof = am.run(pipes)

    assert prof.n_failed == 0
    assert prof.n_pod_lost == 1
    rows = prof.results["pipelines"]
    assert set(rows) == {"p0", "p1"}
    assert sum(r["n_pod_lost"] for r in rows.values()) == 1
    assert sum(r["n_retries"] for r in rows.values()) == 1
    assert all(r["n_failed"] == 0 for r in rows.values())
