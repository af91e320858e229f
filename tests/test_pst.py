"""PST workflow API: streaming AppManager semantics, legacy-pattern
equivalence through the PST compilation path, profile invariants, and the
on-device Metropolis swap properties."""
import json
import os
import tempfile
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (AppManager, BagOfTasks, Kernel, PipelineSpec,
                        Pipeline, ReplicaExchange, SingleClusterEnvironment,
                        Stage, TaskSpec)
from repro.runtime.executor import PilotRuntime
from repro.runtime.journal import Journal
from repro.runtime.states import Task, TaskState


def _k(sim_duration=0.0, cores=1):
    k = Kernel("synthetic.noop")
    k.sim_duration = sim_duration
    k.cores = cores
    return k


def _re_pipeline(name, members, cycles, sim_dur, x_dur, events):
    """A replica-exchange ensemble written directly in PST: each exchange's
    on_done appends the next cycle's stages (adaptive extension)."""
    def cycle_stages(c):
        sims = Stage([TaskSpec(_k(sim_dur), name=f"{name}.c{c}.md{i}",
                               metadata={"instance": i, "iteration": c})
                      for i in range(members)], name="simulation")

        def on_x(stage, pipe):
            events.append((name, c))
            if c + 1 < cycles:
                pipe.extend(cycle_stages(c + 1))

        x = Stage([TaskSpec(_k(x_dur), name=f"{name}.c{c}.x",
                            metadata={"iteration": c})],
                  name="exchange", on_done=on_x)
        return [sims, x]

    return PipelineSpec(cycle_stages(0), name=name)


# -------------------------------------------------- streaming concurrency

def test_two_re_pipelines_interleave_out_of_order():
    """Ensemble A reaches cycle c+1 BEFORE ensemble B finishes cycle c
    under skewed sim durations — no global barrier across pipelines."""
    events = []
    A = _re_pipeline("A", members=2, cycles=3, sim_dur=1.0, x_dur=0.1,
                     events=events)
    B = _re_pipeline("B", members=2, cycles=3, sim_dur=50.0, x_dur=0.1,
                     events=events)
    am = AppManager(PilotRuntime(slots=4, mode="sim"))
    prof = am.run([A, B])

    g = am.session.graph
    a_c1_starts = [g.tasks[f"A.c1.md{i}"].v_started for i in range(2)]
    b_c0_finish = [g.tasks[f"B.c0.md{i}"].v_finished for i in range(2)]
    assert max(a_c1_starts) < min(b_c0_finish), \
        "A's cycle 1 must start while B still simulates cycle 0"
    # all three of A's exchanges complete before B's first
    assert events[:4] == [("A", 0), ("A", 1), ("A", 2), ("B", 0)]
    # makespan is B's chain alone; A rode along in the slack
    assert prof.ttc == pytest.approx(3 * 50.1)
    assert prof.n_tasks == 2 * 3 * 3
    assert prof.n_failed == 0
    assert prof.results["pipelines"]["A"]["state"] == "done"
    assert prof.results["pipelines"]["B"]["state"] == "done"


def test_real_mode_pipelines_interleave():
    """Real mode: a fast pipeline finishes all stages while a slow
    pipeline's first stage is still running."""
    events = []

    def tick(tag):
        def on_done(stage, pipe):
            events.append(tag)
        return on_done

    slow = Kernel("synthetic.sleep")
    slow.arguments = {"seconds": 0.4}
    A = PipelineSpec([Stage([TaskSpec(_k())], name="s0",
                            on_done=tick(("A", 0))),
                      Stage([TaskSpec(_k())], name="s1",
                            on_done=tick(("A", 1)))], name="A")
    B = PipelineSpec([Stage([TaskSpec(slow)], name="s0",
                            on_done=tick(("B", 0)))], name="B")
    prof = AppManager(PilotRuntime(slots=4, mode="real")).run([A, B])
    assert prof.n_failed == 0
    assert events.index(("A", 1)) < events.index(("B", 0))


# -------------------------------------------------- legacy equivalence

class _SimRE(ReplicaExchange):
    def prepare_replica_for_md(self, r):
        return _k(10.0)

    def prepare_exchange(self, replicas):
        return _k(1.0)


def test_legacy_re_profile_equivalent_through_pst():
    """SingleClusterEnvironment.run(pattern) now compiles to PST; the
    profile must match the legacy per-cycle-graph numbers exactly."""
    cl = SingleClusterEnvironment(cores=4, mode="sim")
    cl.allocate()
    prof = cl.run(_SimRE(cycles=3, replicas=4))
    cl.deallocate()
    # barrier per cycle: each cycle costs sim + exchange; 3 cycles chain
    assert prof.ttc == pytest.approx(3 * 11.0)
    assert prof.n_tasks == 3 * 5
    assert prof.n_failed == 0
    assert prof.per_stage["simulation"] == {"n": 12, "t_exec": 120.0}
    assert prof.per_stage["exchange"] == {"n": 3, "t_exec": 3.0}
    for c in range(3):
        assert f"exchange_{c}" in prof.results
    assert prof.t_exec == pytest.approx(123.0)
    assert prof.utilization == pytest.approx(123.0 / (33.0 * 4))


def test_legacy_pipeline_profile_equivalent_through_pst():
    class P(Pipeline):
        def stage_1(self, i):
            return _k(5.0)

        def stage_2(self, i):
            return _k(3.0)

    cl = SingleClusterEnvironment(cores=3, mode="sim")
    cl.allocate()
    prof = cl.run(P(stages=2, instances=3))
    cl.deallocate()
    assert prof.ttc == pytest.approx(8.0)
    assert prof.n_tasks == 6
    assert sorted(prof.results["tasks"]) == [
        f"pipe{p:05d}.stage{s}" for p in range(3) for s in (1, 2)]


def test_re_utilization_accumulates_across_cycles():
    """Regression for the per-cycle overwrite: utilization must cover ALL
    cycles, not just the last one."""
    class SkewRE(ReplicaExchange):
        durations = {0: [10.0, 10.0], 1: [4.0, 1.0]}

        def prepare_replica_for_md(self, r):
            return _k(self.durations[r.cycle][r.id])

        def prepare_exchange(self, replicas):
            return _k(0.0)

    cl = SingleClusterEnvironment(cores=2, mode="sim")
    cl.allocate()
    prof = cl.run(SkewRE(cycles=2, replicas=2))
    cl.deallocate()
    # busy = 20 (cycle0) + 5 (cycle1); ttc = 10 + 4; 2 slots
    assert prof.utilization == pytest.approx(25.0 / (14.0 * 2))
    # the old bug reported only cycle 1: 5 / (4 * 2)
    assert prof.utilization != pytest.approx(5.0 / 8.0)


def test_ttc_decomposition_invariant_sim():
    """Paper eq. (1): in sim mode on one slot the virtual makespan is
    exactly the execution time, and ttc ~ t_exec + t_enmd within the
    (real-clock, tiny) overhead tolerance."""
    class Bag(BagOfTasks):
        def task(self, i):
            return _k(2.0)

    cl = SingleClusterEnvironment(cores=1, mode="sim")
    cl.allocate()
    prof = cl.run(Bag(instances=5))
    cl.deallocate()
    assert prof.ttc == pytest.approx(prof.t_exec)
    assert prof.t_exec == pytest.approx(10.0)
    assert prof.t_enmd_overhead > 0.0
    assert abs(prof.ttc - (prof.t_exec + prof.t_enmd_overhead)) < 0.5


# -------------------------------------------------- adaptivity

def test_on_done_appends_stages_based_on_results():
    """The adaptivity hook: a stage's on_done inspects results and extends
    the pipeline until a convergence condition holds."""
    seen = []

    def make_stage(step):
        def on_done(stage, pipe):
            seen.append(step)
            if step < 3:                      # "not converged yet"
                pipe.add_stage(make_stage(step + 1))
        return Stage([TaskSpec(_k(1.0), name=f"refine{step}")],
                     name=f"refine{step}", on_done=on_done)

    prof = AppManager(PilotRuntime(slots=1, mode="sim")).run(
        PipelineSpec([make_stage(0)], name="adaptive"))
    assert seen == [0, 1, 2, 3]
    assert prof.n_tasks == 4
    assert prof.ttc == pytest.approx(4.0)


def test_unnamed_tasks_unique_across_repeated_stage_names():
    """The docstring's adaptive pattern: appended stages may REUSE a stage
    name; auto-generated task names must still be unique."""
    rounds = []

    def make_stage(r):
        def on_done(stage, pipe):
            rounds.append(r)
            if r < 2:
                pipe.add_stage(make_stage(r + 1))
        # same stage name every round, tasks left unnamed
        return Stage([TaskSpec(_k(1.0)), TaskSpec(_k(1.0))],
                     name="refine", on_done=on_done)

    prof = AppManager(PilotRuntime(slots=2, mode="sim")).run(
        PipelineSpec([make_stage(0)], name="p"))
    assert rounds == [0, 1, 2]
    assert prof.n_tasks == 6


def test_real_mode_cancels_never_fitting_task():
    """A task wider than the whole pilot must cancel, not hang the drain."""
    rt = PilotRuntime(slots=2, mode="real")
    sess = rt.session()
    sess.submit([Task(name="ok", run=lambda t: 1),
                 Task(name="wide", slots=5, run=lambda t: 2),
                 Task(name="after", deps=["wide"], run=lambda t: 3)])
    prof = sess.drain()
    g = sess.graph
    assert g.tasks["ok"].state == TaskState.DONE
    assert g.tasks["wide"].state == TaskState.CANCELED
    assert g.tasks["after"].state == TaskState.CANCELED
    assert prof.n_failed == 0
    assert prof.n_canceled == 2         # cancellation is visible in profile


@pytest.mark.parametrize("mode", ["sim", "real"])
def test_narrow_task_runs_behind_too_wide_one(mode):
    """Deadlock handling must cancel ONLY the unsatisfiable wide task;
    an independent narrow task queued behind it still executes.  Both
    clocks judge width against the pilot's slots, and read it only when
    nothing holds a slot, where free slots are the same number."""
    rt = PilotRuntime(slots=2, mode=mode)
    sess = rt.session()
    seen = []
    rule = sess._cancel_unsatisfiable

    def spy(reviving):
        seen.append(sess.busy_slots)
        return rule(reviving)

    sess._cancel_unsatisfiable = spy
    sess.submit([Task(name="wide", slots=4, duration=1.0),
                 Task(name="narrow", slots=1, duration=2.0,
                      run=lambda t: time.sleep(0.05))])
    prof = sess.drain()
    assert sess.graph.tasks["wide"].state == TaskState.CANCELED
    assert sess.graph.tasks["narrow"].state == TaskState.DONE
    assert seen and set(seen) == {0}
    if mode == "sim":
        assert prof.ttc == 2.0
    assert prof.n_canceled == 1


def test_app_manager_auto_names_survive_multiple_runs():
    am = AppManager(PilotRuntime(slots=1, mode="sim"))
    am.run(PipelineSpec([Stage([TaskSpec(_k(1.0))], name="s")]))
    prof = am.run(PipelineSpec([Stage([TaskSpec(_k(1.0))], name="s")]))
    assert sorted(prof.results["pipelines"]) == ["p0000", "p0001"]
    assert prof.n_tasks == 2            # cumulative across runs


def test_sal_should_continue_called_on_final_iteration():
    from repro.core import SimulationAnalysisLoop

    calls = []

    class SAL(SimulationAnalysisLoop):
        def simulation_stage(self, it, i):
            return _k(1.0)

        def analysis_stage(self, it, j):
            return _k(1.0)

        def should_continue(self, it, results):
            calls.append(it)
            return True

    cl = SingleClusterEnvironment(cores=2, mode="sim")
    cl.allocate()
    cl.run(SAL(maxiterations=3, simulation_instances=1,
               analysis_instances=1))
    cl.deallocate()
    assert calls == [0, 1, 2]       # legacy parity: final iteration included


def test_empty_control_stage_fires_on_done():
    fired = []
    ctrl = Stage([], name="ctrl",
                 on_done=lambda s, p: fired.append(1) or
                 [Stage([TaskSpec(_k(1.0))], name="work")])
    prof = AppManager(PilotRuntime(slots=1, mode="sim")).run(
        PipelineSpec([ctrl], name="p"))
    assert fired == [1]
    assert prof.n_tasks == 1


def test_failed_stage_halts_pipeline_only():
    """A failing task stops ITS pipeline; the sibling pipeline completes."""
    boom = Kernel("synthetic.fail")
    boom.arguments = {"fail_times": 99}
    bad = PipelineSpec([Stage([TaskSpec(boom)], name="s0"),
                        Stage([TaskSpec(_k())], name="s1")], name="bad")
    good = PipelineSpec([Stage([TaskSpec(_k())], name="s0"),
                         Stage([TaskSpec(_k())], name="s1")], name="good")
    prof = AppManager(PilotRuntime(slots=2, mode="real",
                                   max_retries=0)).run([bad, good])
    assert prof.results["pipelines"]["bad"]["state"] == "failed"
    assert prof.results["pipelines"]["good"]["state"] == "done"
    assert prof.n_failed == 1
    # the bad pipeline's stage 1 was never submitted (no global poisoning)
    assert prof.results["pipelines"]["bad"]["n_tasks"] == 1


# -------------------------------------------------- incremental session

def test_session_submit_drain_incremental():
    rt = PilotRuntime(slots=2, mode="sim")
    sess = rt.session()
    sess.submit(Task(name="a", duration=5.0))
    sess.drain()
    assert sess.vnow == 5.0
    sess.submit(Task(name="b", duration=3.0, deps=["a"]), dynamic=True)
    prof = sess.drain()
    assert sess.vnow == 8.0                   # the clock never reset
    assert prof.ttc == 8.0
    assert prof.n_tasks == 2
    with pytest.raises(ValueError, match="unknown dep"):
        sess.submit(Task(name="c", deps=["nope"]))


def test_session_journals_dynamic_injection_and_replays():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "j.jsonl")
        rt = PilotRuntime(slots=1, mode="sim", journal=Journal(path))
        sess = rt.session()
        sess.submit(Task(name="seed", duration=1.0))
        sess.drain()
        sess.submit(Task(name="injected", duration=1.0, deps=["seed"]),
                    dynamic=True)
        sess.drain()
        rt.journal.close()
        recs = [json.loads(ln) for ln in open(path)]
        sub = [r for r in recs if r["event"] == "submitted"]
        assert sub and sub[0]["task"] == "injected" and sub[0]["dynamic"]

        # restart: a fresh session replays both tasks (incl. the injected
        # one) from the journal and fires callbacks without re-running
        done = []
        rt2 = PilotRuntime(slots=1, mode="sim", journal=Journal(path))
        sess2 = rt2.session(on_task_done=lambda t, s: done.append(t.name))
        sess2.submit(Task(name="seed", duration=1.0))
        sess2.submit(Task(name="injected", duration=1.0, deps=["seed"]))
        prof = sess2.drain()
        assert prof.ttc == 0.0
        assert sorted(done) == ["injected", "seed"]


def test_journal_replays_results_to_callbacks():
    """Restart must hand callbacks the recorded RESULT, not None — pattern
    control flow (apply_exchange, should_continue) depends on it."""
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "j.jsonl")
        rt = PilotRuntime(slots=1, mode="real", journal=Journal(path))
        sess = rt.session()
        sess.submit(Task(name="a", run=lambda t: {"temps": [1.0, 2.0]}))
        sess.drain()
        rt.journal.close()

        got = []
        rt2 = PilotRuntime(slots=1, mode="real", journal=Journal(path))
        sess2 = rt2.session(on_task_done=lambda t, s: got.append(t.result))
        sess2.submit(Task(name="a", run=lambda t: {"temps": [9.0, 9.0]}))
        sess2.drain()
        assert got == [{"temps": [1.0, 2.0]}]   # replayed, not re-run


def test_device_swap_keeps_float64_temps_exact():
    """Non-float32-representable temperatures must come back bit-exact and
    unswapped pairs must not be reported accepted."""
    from repro.plugins.re_exchange import _device_swaps

    temps = [3e-4 * 1.3 ** i for i in range(4)]     # not f32-representable
    # equal losses: d = 0 -> log(u) < 0 always -> both pairs swap
    new_t, acc = _device_swaps([1.0, 1.0, 1.0, 1.0], temps, 0, 0, None)
    assert acc == [(0, 1), (2, 3)]
    assert list(new_t) == [temps[1], temps[0], temps[3], temps[2]]
    # huge gap favoring NO swap on (0,1): d = (0-10)*(1/t0-1/t1) << 0
    new_t, acc = _device_swaps([0.0, 10.0, 1.0, 1.0], temps, 0, 0, None)
    assert (0, 1) not in acc
    assert new_t[0] == temps[0] and new_t[1] == temps[1]   # bit-exact


# -------------------------------------------------- submesh placement

def test_exchange_kernel_swaps_on_granted_submesh():
    """Mesh-aware pilot: the PST task ctx carries submesh_for(task) and the
    re.exchange device path computes the swap on it."""
    import jax
    from repro.dist.topology import SlotTopology

    topo = SlotTopology.even(jax.devices(), 1, ("model",))
    rt = PilotRuntime(mode="real", topology=topo)
    xk = Kernel("re.exchange")
    temps = [1.0, 10.0, 20.0, 40.0]
    xk.arguments = {"replicas": 4, "cycle": 0, "temps": temps,
                    "losses": [10.0, 0.0, 0.0, 0.0], "device": True}
    prof = AppManager(rt).run(
        PipelineSpec([Stage([TaskSpec(xk, name="x")], name="exchange")],
                     name="re"))
    assert prof.n_failed == 0
    res = prof.results["tasks"]["x"]
    assert sorted(res["temps"]) == sorted(temps)
    # huge energy gap on pair (0, 1): deterministic accept
    assert res["temps"][0] == 10.0 and res["temps"][1] == 1.0
    assert (0, 1) in [tuple(p) for p in res["accepted"]]


# -------------------------------------------------- metropolis properties

@settings(max_examples=40, deadline=None)
@given(st.data())
def test_device_swap_preserves_temps_and_pair_symmetry(data):
    """A swap step permutes the temperature multiset and the decision is
    symmetric across pair orientation (right member mirrors left)."""
    import jax
    import jax.numpy as jnp
    from repro.core.ensemble import metropolis_swap_device

    n = data.draw(st.integers(2, 9))
    losses = jnp.array([data.draw(st.floats(0.0, 10.0)) for _ in range(n)],
                       jnp.float32)
    temps = jnp.array([data.draw(st.floats(0.1, 5.0)) for _ in range(n)],
                      jnp.float32)
    cycle = data.draw(st.integers(0, 3))
    key = jax.random.PRNGKey(data.draw(st.integers(0, 1000)))
    new_t, n_acc = metropolis_swap_device(losses, temps, cycle, key)
    new_t, temps = np.asarray(new_t), np.asarray(temps)

    # multiset preserved exactly (values only permute)
    np.testing.assert_array_equal(np.sort(new_t), np.sort(temps))
    # pairwise symmetry: each even/odd pair either swapped atomically or
    # stayed; members outside any pair never change
    start = cycle % 2
    paired = set()
    for i in range(start, n - 1, 2):
        j = i + 1
        paired |= {i, j}
        pair = (new_t[i], new_t[j])
        assert pair in ((temps[i], temps[j]), (temps[j], temps[i]))
    for i in set(range(n)) - paired:
        assert new_t[i] == temps[i]
    assert 0 <= int(n_acc) <= n // 2


# ------------------------------------------------------- SLA preemption

def test_sla_latency_preempts_throughput_through_pst():
    """TaskSpec(sla=...) plumbs priority/deadline onto the Task, and with
    PilotRuntime(preempt=True) a latency-class arrival evicts running
    throughput work instead of queueing behind it."""
    def app():
        bulk = PipelineSpec(
            [Stage([TaskSpec(_k(100.0), name=f"bulk{i}", sla="throughput")
                    for i in range(2)], name="work")], name="bulk")
        serve = PipelineSpec(
            [Stage([TaskSpec(_k(1.0), name="arrive")], name="arrive"),
             Stage([TaskSpec(_k(5.0, cores=2), name="lat", sla="latency")],
                   name="decode")], name="serve")
        return [serve, bulk]

    am = AppManager(PilotRuntime(slots=2, mode="sim", preempt=True))
    prof = am.run(app())
    g = am.session.graph
    lat = g.tasks["lat"]
    assert lat.priority == 10 and lat.meta["sla"] == "latency"
    assert lat.meta["deadline"] == pytest.approx(2.0)
    assert g.tasks["bulk0"].priority == 0
    assert prof.n_preempted == 1
    assert lat.v_started == 1.0 and lat.v_finished == 6.0
    victim = next(t for t in (g.tasks["bulk0"], g.tasks["bulk1"])
                  if any(h["outcome"] == "preempted" for h in t.history))
    assert victim.attempts == 2 and victim.state == TaskState.DONE
    assert prof.results["pipelines"]["bulk"]["state"] == "done"
    assert prof.n_failed == 0

    # baseline twin: same app, preemption off -> the latency task waits
    # out the full throughput occupancy (the p99 gap the bench measures)
    am2 = AppManager(PilotRuntime(slots=2, mode="sim", preempt=False))
    prof2 = am2.run(app())
    lat2 = am2.session.graph.tasks["lat"]
    assert prof2.n_preempted == 0
    assert lat2.v_started >= 100.0
    assert lat2.v_finished - 1.0 > 10 * (lat.v_finished - 1.0)


def test_explicit_priority_overrides_sla_class():
    p = PipelineSpec(
        [Stage([TaskSpec(_k(1.0), name="a", sla="throughput", priority=5,
                         deadline=9.0)], name="s0")], name="p")
    am = AppManager(PilotRuntime(slots=1, mode="sim"))
    am.run(p)
    t = am.session.graph.tasks["a"]
    assert t.priority == 5 and t.meta["deadline"] == 9.0
