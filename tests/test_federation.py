"""repro.federation: fleet dispatch, recruiter, whole-pilot loss, replay.

Covers the federated runtime end to end: late-binding dispatch spreading a
bag over heterogeneous pilots, locality-aware pilot choice over one shared
store (cross-pilot fetches only when no local replica exists), whole-pilot
death mid-run with retries landing on survivors and the dead pilot's
replicas dropped, per-pilot journal replay reconstructing the fleet at the
right attempt counts, the recruiter's grow/shrink/hysteresis behavior, the
journal name-collision guard, sanitizer pilot-scoping, the AppManager
surface (same PST app, federated by swapping the runtime object), and the
static diagnostics E114/W205 with their clean twins.
"""
import json

import pytest

from repro.analysis import sanitize_file, validate_app
from repro.core import AppManager, Channel, Kernel, PipelineSpec, Stage, \
    TaskSpec
from repro.federation import Fleet, Recruiter, build_fleet, make_pilot
from repro.runtime.journal import Journal, journal_from_env
from repro.runtime.states import Task, TaskGraph, TaskState


def bag(n, dur=10.0, slots=1):
    g = TaskGraph()
    for i in range(n):
        g.add(Task(name=f"t{i}", duration=dur, slots=slots))
    return g


def _member(dur=1.0, nbytes=None, **attrs):
    k = Kernel("synthetic.noop")
    k.sim_duration = dur
    if nbytes is not None:
        k.output_nbytes = nbytes
    for name, v in attrs.items():
        setattr(k, name, v)
    return k


def _coupled(pipelines=2, cycles=4, members=4, nbytes=64 << 20):
    pipes = []
    for p in range(pipelines):
        ch = Channel(f"traj{p}")
        pipes.append(PipelineSpec(
            [Stage([TaskSpec(_member(nbytes=nbytes), name=f"p{p}.c{c}.m{m}")
                    for m in range(members)], name=f"cycle{c}", outputs=[ch])
             for c in range(cycles)], name=f"producer{p}"))
        pipes.append(PipelineSpec(
            [Stage([TaskSpec(_member(dur=0.5), name=f"a{p}.r{c}")],
                   name=f"round{c}", inputs={"traj": ch})
             for c in range(cycles)], name=f"analysis{p}"))
    return pipes


# ------------------------------------------------------------- dispatch

def test_fleet_spreads_bag_across_pilots():
    fleet = build_fleet(2, slots=4, staging=False)
    g = bag(40, dur=1.0)
    prof = fleet.run(g)
    assert prof.n_failed == 0
    assert prof.ttc == pytest.approx(5.0)      # 40 tasks / 8 slots, 1s each
    by = {}
    for t in g.tasks.values():
        by[t.meta["pilot"]] = by.get(t.meta["pilot"], 0) + 1
    assert by == {"p1": 20, "p2": 20}
    assert fleet.slots == 8 and fleet.summary()["n_active"] == 2


def test_fleet_respects_per_pilot_width():
    # 3 slots free fleet-wide is NOT 3 slots on one pilot: a 3-wide task
    # must wait for a single pilot that can host it
    fleet = Fleet({"a": make_pilot("a", slots=2), "b": make_pilot("b", slots=4)})
    g = TaskGraph()
    g.add(Task(name="wide", duration=1.0, slots=3))
    prof = fleet.run(g)
    assert prof.n_failed == 0
    assert g.tasks["wide"].meta["pilot"] == "b"


def test_task_wider_than_every_pilot_cancels_not_hangs():
    fleet = build_fleet(2, slots=4, staging=False)    # fleet sum = 8
    g = bag(1, dur=1.0, slots=6)                      # no single pilot fits
    prof = fleet.run(g)
    assert prof.n_canceled == 1
    assert g.tasks["t0"].state == TaskState.CANCELED


def test_locality_dispatch_avoids_cross_pilot_copies():
    fleet = build_fleet(2, slots=8, slots_per_pod=2, journal_base=None)
    am = AppManager(fleet)
    prof = am.run(_coupled())
    assert prof.n_failed == 0
    stats = fleet.staging.planner.stats
    # every analysis round late-binds to the pilot holding its inputs
    assert stats["cross_pilot"] == 0 and stats["bytes_cross_pilot"] == 0
    assert fleet.staging.planner.summary()["locality_hit_rate"] == 1.0
    fed = prof.results["federation"]
    assert fed["n_pilots"] == 2 and sum(fed["dispatch"].values()) == prof.n_tasks
    fleet.close()


def test_cross_pilot_fetch_when_only_remote_replica():
    # force the consumer onto the pilot WITHOUT the replica: the producer's
    # pilot is retired between runs, so stage-in must fetch pilot-to-pilot
    fleet = build_fleet(2, slots=4, slots_per_pod=2)
    am = AppManager(fleet)
    ch = Channel("traj")
    prod = PipelineSpec([Stage([TaskSpec(_member(nbytes=64 << 20),
                                         name="prod")],
                               name="s0", outputs=[ch])], name="P")
    assert am.run([prod]).n_failed == 0
    src = am.session.graph.tasks["prod"].meta["pilot"]
    fleet.retire_pilot(src)

    cons = PipelineSpec([Stage([TaskSpec(_member(), name="cons")],
                               name="r0", inputs={"traj": ch})], name="C")
    assert am.run([cons]).n_failed == 0
    t = am.session.graph.tasks["cons"]
    assert t.meta["pilot"] != src
    stats = fleet.staging.planner.stats
    assert stats["cross_pilot"] >= 1 and stats["bytes_cross_pilot"] > 0
    assert t.t_data > 0                    # the fetch was charged
    fleet.close()


# ---------------------------------------------------------- pilot failure

def test_whole_pilot_loss_mid_run():
    # staging on => slot-id tracking on, so pods are addressable to kill
    fleet = build_fleet(2, slots=4, max_retries=2)
    g = bag(16, dur=2.0)
    killed = {}

    def chaos(rt, graph, now):
        if now >= 2.0 and not killed:
            killed["t"] = now
            fleet.inject_pilot_failure("p2")
    for rt in fleet.pilots.values():
        rt.on_schedule = chaos
    prof = fleet.run(g)

    assert prof.n_failed == 0
    assert prof.n_pod_lost == 4 and prof.n_retries == 4
    assert fleet.pilots["p2"].slots == 0
    assert fleet.dead_pods == {f"p2:pod{i}" for i in range(4)}
    # every retry and every post-kill launch landed on the survivor
    for t in g.tasks.values():
        assert t.state == TaskState.DONE
        if any(h["outcome"] == "pod_lost" for h in t.history):
            assert t.history[-1]["pod"].startswith("p1:")


def test_pilot_loss_drops_its_replicas():
    fleet = build_fleet(2, slots=4, slots_per_pod=2)
    ch = Channel("out")
    prod = PipelineSpec(
        [Stage([TaskSpec(_member(dur=2.0, nbytes=16 << 20), name=f"w{c}.{m}")
                for m in range(4)], name=f"c{c}", outputs=[ch])
         for c in range(2)], name="P")
    killed = {}

    def chaos(rt, graph, now):
        if now >= 2.0 and not killed:
            killed["t"] = now
            fleet.inject_pilot_failure("p2")
    for rt in fleet.pilots.values():
        rt.on_schedule = chaos
    prof = AppManager(fleet).run([prod])
    assert prof.n_failed == 0 and killed
    store = fleet.staging.store
    locs = {loc for d in list(store._blobs) for loc in store.locations(d)}
    assert not any(loc.startswith("p2:") for loc in locs)
    fleet.close()


# ------------------------------------------------------------- journals

def test_journal_replay_resumes_fleet(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_JOURNAL_DIR", str(tmp_path))
    fleet = build_fleet(2, slots=4, staging=False, journal_base="run")
    prof = fleet.run(bag(8, dur=1.0))
    assert prof.n_failed == 0
    fleet.close()
    assert {p.name for p in tmp_path.glob("*.jsonl")} == \
        {"run-fleet.jsonl", "run-p1.jsonl", "run-p2.jsonl"}

    fleet2 = build_fleet(2, slots=4, staging=False, journal_base="run")
    g = bag(8, dur=1.0)
    g.add(Task(name="fresh", duration=1.0))
    prof2 = fleet2.run(g)
    assert {"event": "journal_skip", "n": 8} in prof2.events
    assert prof2.ttc == pytest.approx(1.0)     # only the fresh task ran
    assert g.tasks["fresh"].state == TaskState.DONE
    # replayed tasks are DONE without re-running (attempts untouched)
    assert all(g.tasks[f"t{i}"].state == TaskState.DONE
               and g.tasks[f"t{i}"].attempts == 0 for i in range(8))
    fleet2.close()


def test_journal_replay_resumes_mid_retry_on_any_pilot(tmp_path,
                                                       monkeypatch):
    # a crash recorded in PILOT journals (prefixed pods) must replay into
    # the fleet session: attempts resume, the dead pilot's pod stays blamed
    monkeypatch.setenv("REPRO_JOURNAL_DIR", str(tmp_path))
    j = Journal(str(tmp_path / "rep-p2.jsonl"), tag="p2")
    crashed = Task(name="t0")
    crashed.attempts = 1
    j.record(crashed, "pod_lost", pod="p2:pod0")
    j.close()

    fleet = build_fleet(2, slots=4, journal_base="rep")
    g = TaskGraph()
    g.add(Task(name="t0", duration=5.0))
    prof = fleet.run(g)
    t = g.tasks["t0"]
    assert prof.n_failed == 0 and t.state == TaskState.DONE
    assert t.attempts == 2                     # resumed, not restarted
    done = [h for h in t.history if h["outcome"] == "done"]
    assert done[-1]["pod"] != "p2:pod0"
    fleet.close()


def test_journal_name_collision_gets_suffix(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_JOURNAL_DIR", str(tmp_path))
    a = journal_from_env("twin", tag="p1")
    b = journal_from_env("twin", tag="p2")
    assert a.path != b.path and b.path.endswith("twin-2.jsonl")
    a.close(), b.close()
    # name freed at close: a fresh claim reuses the base name
    c = journal_from_env("twin")
    assert c.path.endswith("twin.jsonl")
    c.close()


def test_sanitizer_accepts_per_pilot_journals(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_JOURNAL_DIR", str(tmp_path))
    fleet = build_fleet(2, slots=4, journal_base="san")
    killed = {}

    def chaos(rt, graph, now):
        if now >= 1.0 and not killed:
            killed["t"] = now
            fleet.inject_pilot_failure("p2")
    for rt in fleet.pilots.values():
        rt.on_schedule = chaos
    fleet.run(bag(8, dur=2.0))
    fleet.close()
    for path in sorted(tmp_path.glob("*.jsonl")):
        report = sanitize_file(str(path))
        assert report.ok, f"{path.name}: {report.format()}"


def test_tagged_records_carry_pilot_field(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_JOURNAL_DIR", str(tmp_path))
    fleet = build_fleet(2, slots=4, staging=False, journal_base="tag")
    fleet.run(bag(4, dur=1.0))
    fleet.close()
    recs = [json.loads(line) for line in
            (tmp_path / "tag-p1.jsonl").read_text().splitlines()]
    assert recs and all(r.get("pilot") == "p1" for r in recs)


# ------------------------------------------------------------- recruiter

def test_recruiter_grows_fleet_to_backlog():
    rec = Recruiter(min_pilots=1, max_pilots=4, slots_per_pilot=4,
                    budget_slots=16, hysteresis_s=6.0, spinup_s=2.0)
    fleet = build_fleet(1, slots=4, staging=False, recruiter=rec)
    prof = fleet.run(bag(200, dur=1.0))
    assert prof.n_failed == 0
    s = rec.summary()
    assert s["n_spawned"] == 3 and s["n_joined"] == 3
    assert s["direction_flips"] == 0           # converged, no oscillation
    assert len(fleet.active()) == 4
    # static 4 slots would take 50s; elasticity lands well under that
    assert prof.ttc < 30.0


def test_recruiter_respects_slot_budget():
    rec = Recruiter(min_pilots=1, max_pilots=8, slots_per_pilot=4,
                    budget_slots=8, hysteresis_s=4.0, spinup_s=1.0)
    fleet = build_fleet(1, slots=4, staging=False, recruiter=rec)
    prof = fleet.run(bag(100, dur=1.0))
    assert prof.n_failed == 0
    assert fleet.slots <= 8                    # never exceeded the budget
    assert rec.summary()["n_spawned"] <= 1


def test_recruiter_shrinks_idle_fleet():
    rec = Recruiter(min_pilots=1, max_pilots=4, slots_per_pilot=4,
                    budget_slots=16, hysteresis_s=1.0, spinup_s=0.5)
    fleet = build_fleet(3, slots=4, staging=False, recruiter=rec)
    sess = fleet.session()
    g = sess.graph
    # a long straggler keeps the session alive after the bag drains
    g.add(Task(name="long", duration=40.0))
    for i in range(8):
        g.add(Task(name=f"s{i}", duration=1.0))
    prof = sess.drain()
    assert prof.n_failed == 0
    assert rec.summary()["n_retired"] >= 1
    assert len(fleet.active()) >= rec.min_pilots


def test_recruiter_hysteresis_spaces_decisions():
    rec = Recruiter(min_pilots=1, max_pilots=4, slots_per_pilot=4,
                    budget_slots=16, hysteresis_s=6.0, spinup_s=2.0)
    fleet = build_fleet(1, slots=4, staging=False, recruiter=rec)
    fleet.run(bag(200, dur=1.0))
    decisions = [e["t"] for e in rec.events
                 if e["action"] in ("spawn", "retire")]
    assert all(b - a >= rec.hysteresis_s
               for a, b in zip(decisions, decisions[1:]))


# ------------------------------------------------------------- real mode

def test_real_mode_federated_smoke():
    fleet = build_fleet(2, slots=2, mode="real", staging=False)
    g = TaskGraph()
    for i in range(6):
        g.add(Task(name=f"r{i}", run=lambda task: "ok"))
    prof = fleet.run(g)
    assert prof.n_failed == 0
    assert all(t.result == "ok" for t in g.tasks.values())
    assert {t.meta["pilot"] for t in g.tasks.values()} <= {"p1", "p2"}


def test_real_mode_federated_pod_kill_keeps_pilot_busy_within_slots():
    """A wall-clock pod kill on one pilot: the victim's width leaves that
    pilot's busy account as its slot leaves the capacity, so no pilot is
    ever charged more than it holds, and the retry lands off the dead
    pod."""
    from repro.runtime.faults import FaultInjector

    # staging on => slot-id tracking on, so pods are addressable to kill
    fleet = build_fleet(2, slots=2, mode="real", max_retries=2)
    fleet.pilots["p1"].faults = FaultInjector(kill_at=[(0.1, "p1:pod0")])
    sess = fleet.session()
    seen = []

    def check():
        seen.append({n: (sess.pilot_busy(n), rt.slots)
                     for n, rt in fleet.pilots.items()})

    occupy, kill = sess._occupy, sess._kill_pod
    sess._occupy = lambda t: (occupy(t), check())
    sess._kill_pod = lambda pod: (kill(pod), check())
    sess.submit([Task(name=f"r{i}", duration=0.3) for i in range(8)])
    prof = sess.drain()

    assert prof.n_failed == 0 and prof.n_pod_lost == 1
    assert all(t.state == TaskState.DONE for t in sess.graph.tasks.values())
    assert fleet.pilots["p1"].slots == 1
    assert seen and all(0 <= busy <= slots for snap in seen
                        for busy, slots in snap.values())
    assert {n: sess.pilot_busy(n) for n in fleet.pilots} == {"p1": 0,
                                                             "p2": 0}
    victim = next(t for t in sess.graph.tasks.values() if t.attempts == 2)
    assert victim.history[0]["pod"] == "p1:pod0"
    assert victim.history[-1]["pod"] != "p1:pod0"
    fleet.close()


# ------------------------------------------------------- static validator

def _fleet_pipes(cores):
    p = PipelineSpec([Stage([TaskSpec(_member(cores=cores))], name="s0")],
                     name="p")
    return [p]


def test_e114_fleet_slots_unsatisfiable():
    fleet = build_fleet(2, slots=4, staging=False)
    # 6 slots fits the fleet SUM but no pilot the fleet can ever field
    codes = validate_app(_fleet_pipes(6), runtime=fleet).codes()
    assert "E114" in codes
    # clean twin: same fleet, width one pilot can host
    assert validate_app(_fleet_pipes(4), runtime=fleet).ok


def test_e114_clean_when_recruiter_can_field_wider_pilot():
    rec = Recruiter(max_pilots=4, slots_per_pilot=8, budget_slots=16,
                    hysteresis_s=10.0, spinup_s=5.0)
    fleet = build_fleet(1, slots=4, staging=False, recruiter=rec)
    codes = validate_app(_fleet_pipes(6), runtime=fleet).codes()
    # no active pilot hosts 6 today, but the factory builds 8-slot pilots
    assert "E114" not in codes and "W202" in codes


def test_w205_recruiter_thrash_prone():
    rec = Recruiter(hysteresis_s=2.0, spinup_s=10.0)     # decides blind
    fleet = build_fleet(1, slots=4, staging=False, recruiter=rec)
    assert "W205" in validate_app(_fleet_pipes(1), runtime=fleet).codes()
    # clean twin: hysteresis covers the spin-up window
    rec2 = Recruiter(hysteresis_s=10.0, spinup_s=10.0)
    fleet2 = build_fleet(1, slots=4, staging=False, recruiter=rec2)
    assert validate_app(_fleet_pipes(1), runtime=fleet2).ok
