"""Replica-exchange training ensembles: job kind ``ensemble``.

Each cycle runs one ``lm.train`` task per member (``steps_per_task`` steps
each) and then one ``re.exchange`` task that swaps learning rates between
neighbours by the Metropolis rule on the members' losses; the pilot has one
slot per member.  Everything goes through the program's own entry points:
``AppManager`` -> ``RuntimeSession`` real drain loop -> the registered
kernels -> ``train/step.py``.

Set-up makes each member's state (a zero-step ``lm.train`` task), then
drives the members through three one-step cycles with the window's own
calls.  The programs are compiled then, and those three steps are what the
plain reference follows once the window has closed.  The measured window
then continues the same members until the first cycle boundary after
``--seconds``; the loss each member reports for the window's first step is
held to the reference's loss after its three steps.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from lib import reference
from lib.checks import Checks, moving_leaves, worst_leaf_gap
from lib.metropolis import metropolis_swaps
from lib.model import derive_seed, program_config
from lib.stats import rate

ENSEMBLE = "bench"
SETUP_STEPS = 3


def _kernel(name: str, **arguments):
    from repro.core import Kernel
    k = Kernel(name)
    k.arguments = arguments
    return k


def synthetic_batch(data_seed: int, step: int, vocab: int, batch: int,
                    seq: int) -> dict:
    """The rows the program's synthetic feed gives step ``step``:
    ``default_rng((data_seed, step))`` draws ``batch x (seq + 1)`` ids."""
    rng = np.random.default_rng((data_seed, step))
    toks = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}


def _paths(tree) -> Dict[str, object]:
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)] = x
    return out


@jax.jit
def _device_norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree)


class Ensemble:
    """One replica-exchange ensemble of ``mix["members"]`` members."""

    def __init__(self, run):
        self.run = run
        mix, c = run.mix, run.cfg
        self.mix = mix
        self.n = int(mix["members"])
        self.B, self.S = int(mix["batch"]), int(mix["seq"])
        self.hyper = dict(mix["optimizer"])
        self.arch = program_config(c, f"bench:{run.entry['name']}").name
        self.init_seed = derive_seed(run.seed, "init")
        self.data_seeds = [derive_seed(run.seed, "data", i)
                           for i in range(self.n)]
        self.xseed = derive_seed(run.seed, "exchange")
        self.temps = [float(x) for x in mix["lr_ladder"]]
        if len(self.temps) != self.n:
            raise ValueError("lr_ladder needs one rate per member")
        self.topology = None
        if run.chips > 1:
            from repro.dist.topology import SlotTopology
            self.topology = SlotTopology.even(run.devices, self.n)
        self.cycle = 0
        self.step_lrs: List[List[float]] = [[] for _ in range(self.n)]
        self.first_losses: Dict[int, List[float]] = {}
        self.exchange_mismatch = 0
        self.not_done = 0
        self.tasks = 0
        self._refs: Dict[tuple, dict] = {}

    # ------------------------------------------------------------ pipeline
    def _train_task(self, i: int, steps: int):
        from repro.core import TaskSpec
        return TaskSpec(_kernel(
            "lm.train", arch=self.arch, ensemble=ENSEMBLE, member=i,
            seed=self.init_seed, data_seed=self.data_seeds[i],
            lr=self.temps[i], total_steps=int(self.hyper["total_steps"]),
            schedule="cosine", steps=steps, batch=self.B, seq=self.S),
            name=f"c{self.cycle:05d}.train.m{i:03d}")

    def cycle_stages(self, steps: int, on_cycle=None):
        """One cycle: the members' training stage, then the exchange."""
        from repro.core import Stage, TaskSpec
        c = self.cycle
        train = Stage([self._train_task(i, steps) for i in range(self.n)],
                      name="train")
        for i in range(self.n):
            self.step_lrs[i].extend([self.temps[i]] * steps)
        xname = f"c{c:05d}.exchange"
        temps_in = list(self.temps)

        def on_done(stage, pipe):
            self._settle(c, temps_in, train, stage.results[xname])
            if on_cycle is not None:
                on_cycle(c, pipe)

        exchange = Stage(
            [TaskSpec(_kernel("re.exchange", ensemble=ENSEMBLE,
                              replicas=self.n, cycle=c, temps=temps_in,
                              seed=self.xseed), name=xname)],
            name="exchange", inputs={"members": train.future()},
            on_done=on_done)
        self.cycle += 1
        return [train, exchange]

    def _settle(self, c: int, temps_in, train, xres):
        """Hold the exchange to the harness's own Metropolis rule on the
        losses the members reported, then adopt its learning rates."""
        losses = [None] * self.n
        first = [None] * self.n
        for res in train.results.values():
            losses[int(res["member"])] = float(res["loss"])
            first[int(res["member"])] = float(res["losses"][0])
        self.first_losses[c] = first
        want, accepted = metropolis_swaps(losses, temps_in, c, self.xseed)
        if [tuple(p) for p in xres["accepted"]] != accepted \
                or list(xres["temps"]) != [float(t) for t in want]:
            self.exchange_mismatch += 1
        self.temps = [float(t) for t in want]

    def _settle_tasks(self, am):
        from repro.runtime.states import TaskState
        for t in am.session.graph.tasks.values():
            self.tasks += 1
            if t.state != TaskState.DONE or t.attempts != 1:
                self.not_done += 1
                print(f"task {t.name}: {t.state.name} after {t.attempts} "
                      f"attempt(s): {t.error}", flush=True)

    def _runtime(self, tracer=None):
        from repro.runtime.executor import PilotRuntime
        return PilotRuntime(slots=self.n, mode="real",
                            topology=self.topology, tracer=tracer)

    def _run(self, pipes, tracer=None):
        from repro.core import AppManager
        am = AppManager(self._runtime(tracer))
        am.run(pipes)
        self._settle_tasks(am)
        return am

    def _state(self, i: int):
        from repro.plugins.lm import STATE_STORE
        return STATE_STORE[(ENSEMBLE, i)]

    # ------------------------------------------------------------ set-up
    def setup(self):
        """Make the members, then three one-step cycles through the
        window's own calls; read what the reference will be held to."""
        from repro.core import PipelineSpec, Stage
        self._run(PipelineSpec([Stage(
            [self._train_task(i, 0) for i in range(self.n)],
            name="init")], name="init"))
        p0 = [{k: np.asarray(v) for k, v in
               _paths(jax.device_get(self._state(i)["params"])).items()}
              for i in range(self.n)]
        self.prog = [{"losses": [], "grad_norms": None, "change_norms": None}
                     for _ in range(self.n)]
        b1 = float(self.hyper["b1"])
        for s in range(SETUP_STEPS):
            am = self._run(PipelineSpec(self.cycle_stages(1),
                                        name=f"setup{s}"))
            for t in am.session.graph.tasks.values():
                if ".train." in t.name:
                    r = t.result
                    self.prog[int(r["member"])]["losses"].extend(r["losses"])
            if s == 0:
                for i in range(self.n):
                    m = _device_norms(self._state(i)["opt"]["m"])
                    self.prog[i]["grad_norms"] = {
                        k: float(v) / (1.0 - b1)
                        for k, v in _paths(jax.device_get(m)).items()}
        for i in range(self.n):
            p3 = _paths(jax.device_get(self._state(i)["params"]))
            self.prog[i]["change_norms"] = {
                k: float(np.linalg.norm(
                    (np.asarray(p3[k], np.float32) - p0[i][k]).ravel()))
                for k in p3}
        del p0
        jax.block_until_ready([self._state(i) for i in range(self.n)])

    # ------------------------------------------------------------ window
    def window(self, seconds: float, tracer=None, profile=None):
        """Cycles until the first boundary after ``seconds``.  Returns
        (t0, t1, tokens trained)."""
        from repro.core import PipelineSpec
        steps = int(self.mix["steps_per_task"])
        first = self.cycle
        t_end = [0.0]

        def on_cycle(c, pipe):
            if profile is not None:
                profile.on_cycle(c - first)
            if time.perf_counter() < t_end[0]:
                pipe.extend(self.cycle_stages(steps, on_cycle))

        pipe = PipelineSpec([], name="window")
        pipe.extend(self.cycle_stages(steps, on_cycle))
        t0 = time.perf_counter()
        t_end[0] = t0 + seconds
        self._run(pipe, tracer)
        t1 = time.perf_counter()
        cycles = self.cycle - first
        self.window_cycles = cycles
        got = self.first_losses.get(first, [None] * self.n)
        for i in range(self.n):
            self.prog[i]["window_loss"] = got[i]
        return t0, t1, cycles * self.n * steps * self.B * self.S

    # ------------------------------------------------------------ checks
    def free(self, programs: bool = True):
        """Drop the members' state (and the compiled steps)."""
        from repro.plugins import lm
        lm.STATE_STORE.clear()
        if programs:
            lm._STEP_CACHE.clear()
        gc.collect()

    def reference_readings(self, i: int, policy: str = "f32") -> dict:
        """Member ``i``'s first steps through the plain reference (kept:
        the control and the faults of a seed share the f32 readings)."""
        key_ = (policy, i)
        if key_ not in self._refs:
            c = self.run.cfg
            batches = [synthetic_batch(self.data_seeds[i], t,
                                       c["vocab_size"], self.B, self.S)
                       for t in range(SETUP_STEPS + 1)]
            lrs = [reference.lr_at(t, {**self.hyper,
                                       "lr": self.step_lrs[i][t]})
                   for t in range(SETUP_STEPS)]
            key = jax.random.PRNGKey(self.init_seed + i)
            ref = reference.train_readings(
                c, policy, key, batches[:SETUP_STEPS], lrs, self.hyper,
                after=batches[SETUP_STEPS])
            ref["window_loss"] = ref.pop("after_loss")
            self._refs[key_] = ref
        return self._refs[key_]

    def compare(self, prog=None) -> dict:
        """The numbers compared, worst over the members.  ``prog`` stands
        in for the program's readings (the control passes its own)."""
        prog = prog if prog is not None else self.prog
        out = {"loss_gap": 0.0, "grad_norm_gap": 0.0, "change_norm_gap": 0.0,
               "window_loss_gap": 0.0}
        for i in range(self.n):
            ref = self.reference_readings(i)
            p = prog[i]
            out["loss_gap"] = max(out["loss_gap"], max(
                abs(a - b) / abs(b) for a, b in zip(p["losses"],
                                                    ref["losses"])))
            # a member that reported no loss in the window reads 1 (100%)
            w = p.get("window_loss")
            out["window_loss_gap"] = max(out["window_loss_gap"], 1.0
                                         if w is None else abs(
                w - ref["window_loss"]) / abs(ref["window_loss"]))
            out["grad_norm_gap"] = max(out["grad_norm_gap"], worst_leaf_gap(
                p["grad_norms"], ref["grad_norms"])[0])
            keys = moving_leaves(ref["grad_norms"])
            self.unmoved = sorted(set(ref["grad_norms"]) - set(keys))
            out["change_norm_gap"] = max(out["change_norm_gap"],
                                         worst_leaf_gap(
                p["change_norms"], ref["change_norms"], keys)[0])
        return out

    def checks(self, limits: dict) -> Checks:
        ck = Checks()
        ck.add("tasks_not_done", self.not_done, 0)
        ck.add("exchange_mismatch", self.exchange_mismatch, 0)
        for k, v in self.compare().items():
            ck.add(k, v, limits[k])
        print(f"leaves left out of the change (reference gradient under "
              f"1e-3 of the median leaf's): {self.unmoved}", flush=True)
        return ck


def run_cell(run) -> dict:
    """Set-up, window and checks of one ``ensemble`` cell."""
    from contextlib import nullcontext

    from lib.harness import annotate_kernels
    ens = Ensemble(run)
    ens.setup()
    run.mark_setup_done()
    spans = annotate_kernels(["lm.train", "re.exchange"]) if run.trace \
        else nullcontext()
    with spans:
        t0, t1, tokens = ens.window(run.seconds, tracer=run.tracer,
                                    profile=run.profiler)
    run.window = (t0, t1)
    if run.trace:
        from repro.obs.report import decompose, segment_from_tracer
        prof = run.profiler
        prof.stop()
        run.device_trace = prof.reduce()
        run.counters.update(
            traced_s=prof.t1 - prof.t0,
            traced_steps=(prof.k1 - prof.k0) * ens.n
            * int(run.mix["steps_per_task"]))
        run.obs = decompose(segment_from_tracer(run.tracer))
        run.extra["spans"] = run.tracer.spans
    run.counters.update(tokens=tokens, cycles=ens.window_cycles,
                        members=ens.n, steps=ens.window_cycles * ens.n
                        * int(run.mix["steps_per_task"]),
                        flops_per_step=run.flops.train_flops(ens.B, ens.S))
    run.e2e["train_tokens_per_s"] = rate(tokens, t0, t1)
    run.read_memory_peak()
    ens.free()
    run.checks = ens.checks(run.mix["limits"])
    run.attempted = ens.tasks
    run.failed = ens.not_done
    return run


# ---------------------------------------------------------------- faults

class planted:
    """A fault planted under the timed path, for the calibration and the
    tests: ``half_batch`` (each step sees the first half of its rows, the
    loss a mean over them), ``state_unchanged`` (each step returns the
    state it was given) or ``exchange_flipped`` (the exchange reports the
    opposite decision on its first pair)."""

    KINDS = ("half_batch", "state_unchanged", "exchange_flipped")

    def __init__(self, kind: str):
        if kind not in self.KINDS:
            raise ValueError(kind)
        self.kind = kind

    def __enter__(self):
        from repro.core import kernel_plugin
        from repro.plugins import lm
        self.saved_steps = lm._steps
        kernel_plugin._ensure_plugins()
        kd = kernel_plugin._KERNEL_REGISTRY["re.exchange"]
        self.saved_exchange = kd.fn
        real = lm._steps
        lm._STEP_CACHE.clear()
        if self.kind == "half_batch":
            def steps(cfg, kind, hyper=None, _real=real):
                fn = _real(cfg, kind, hyper)
                return lambda state, batch: fn(state, jax.tree.map(
                    lambda x: x[:x.shape[0] // 2], batch))
            lm._steps = steps
        elif self.kind == "state_unchanged":
            def steps(cfg, kind, hyper=None, _real=real):
                fn = _real(cfg, kind, hyper)
                return lambda state, batch: (state, fn(state, batch)[1])
            lm._steps = steps
        else:
            def exchange(args, ctx, _fn=self.saved_exchange):
                out = _fn(args, ctx)
                c = int(args["cycle"])
                if int(args["replicas"]) > 1 + c % 2:
                    i = c % 2
                    t = list(out["temps"])
                    t[i], t[i + 1] = t[i + 1], t[i]
                    out["temps"] = t
                    pair = (i, i + 1)
                    acc = [tuple(p) for p in out["accepted"]]
                    out["accepted"] = [p for p in acc if p != pair] \
                        if pair in acc else acc + [pair]
                return out
            kd.fn = exchange
        return self

    def __exit__(self, *exc):
        from repro.core import kernel_plugin
        from repro.plugins import lm
        lm._steps = self.saved_steps
        lm._STEP_CACHE.clear()
        kernel_plugin._KERNEL_REGISTRY["re.exchange"].fn = \
            self.saved_exchange
        return False


def calibrate(run, control: bool = False, faults: bool = False):
    """Yield the program's numbers for ``run``'s seed; with ``control``
    the control's (the reference in float8 in the program's place); with
    ``faults`` each planted fault's."""
    ens = Ensemble(run)
    ens.setup()
    ens.window(0.0)
    ens.free(programs=False)
    yield {"what": "program", "numbers": ens.compare()}
    if control:
        ctl = [ens.reference_readings(i, "fp8") for i in range(ens.n)]
        yield {"what": "control", "numbers": ens.compare(prog=ctl)}
    if faults:
        for kind in planted.KINDS:
            with planted(kind):
                bad = Ensemble(run)
                bad.setup()
                bad.window(0.0)
                bad.free(programs=False)
                numbers = bad.compare()
                numbers["exchange_mismatch"] = bad.exchange_mismatch
            yield {"what": kind, "numbers": numbers}
