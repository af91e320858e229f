"""Serving members: job kind ``serving``.

Open-loop traffic from the program's generator (``lib/traffic.py`` is the
benchmark's copy of it): windows of ``window_s`` seconds, Poisson arrivals
at ``rate_rps`` with no bursts, a share ``latency_frac`` of latency-class
requests.  Every seed gets the same set of windows, drawn once from
``pool_seed``, in its own order, so that a seed changes the order of the
work and not its amount.

Two pipelines on one pilot: the benchmark's paced source, which releases
window ``j`` when it closes on the run's clock and puts it on a channel,
and the program's ``serve.decode``, which regenerates the window's requests
and serves them through a ``BatchedServer`` (continuous batching: per-step
admit and evict) with ``decode_slots`` slots.  A request is due at the
window's start plus its window's start plus its offset, and completes when
the ``serve.decode`` task that served it ends.

The served tokens are observed at the engine's boundary: while the window
runs, the program's ``repro.serve.BatchedServer`` is a subclass that
records each request's prompt, the token its prefill chose, and the tokens
decoded after it.  Once the window has closed, a sample of them drawn from
the seed, the longest requests among them, goes through the plain
reference, teacher-forced, and the widest gap by which a served token's
reference logit lies below the reference's best is compared.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from lib import reference
from lib.checks import Checks
from lib.model import derive_seed, program_config
from lib.stats import latencies, percentile, rate
from lib.traffic import TrafficModel

SOURCE = "bench.paced_source"


def _register_source():
    from repro.core.kernel_plugin import kernel_registered, register_kernel
    if kernel_registered(SOURCE):
        return

    @register_kernel(SOURCE, description="release one traffic window when "
                     "it closes on the run's clock")
    def paced_source(args, ctx):
        clock = args["clock"]
        target = clock["t0"] + (int(args["slot"]) + 1) * float(
            args["window_s"])
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        with clock["lock"]:
            clock["late"] = max(clock["late"], time.perf_counter() - target)
        return {"slot": int(args["slot"]), "window": int(args["window"])}


class recording:
    """While the block runs, ``repro.serve.BatchedServer`` is a subclass
    that records, per request id, the prompt, the token the prefill chose
    and the tokens decoded after it (continuous batching only: the
    configurations served here have no sliding window).

    ``run()`` returns the served requests, but their ``out_tokens`` leave
    out the prefill's token, so that one is taken where the engine's
    ``_admit(slots, cache, positions, last)`` leaves it in ``last``.  Its
    element is kept as the engine left it (a host int today, a device
    array if the engine keeps ``last`` on the device) and read only after
    the window, so that the recorder adds no host sync to the timed path.
    A change to ``_admit``'s signature or to what ``last`` holds shows as
    ``tokens_missing`` or a wide ``served_token_gap``."""

    def __init__(self):
        self.first: Dict[int, object] = {}
        self.served: Dict[int, dict] = {}

    def __enter__(self):
        import repro.serve
        from repro.serve.engine import BatchedServer
        first, served = self.first, self.served

        class RecordingServer(BatchedServer):
            def _admit(self, slots, cache, positions, last):
                cache = super()._admit(slots, cache, positions, last)
                for i, r in enumerate(slots):
                    if r is not None and r.rid not in first:
                        first[r.rid] = last[i]
                return cache

            def run(self):
                done = super().run()
                for r in done:
                    served[r.rid] = {
                        "prompt": np.asarray(r.prompt, np.int32),
                        "tokens": list(r.out_tokens),
                        "max_new": int(r.max_new_tokens)}
                return done

        self.saved = repro.serve.BatchedServer
        repro.serve.BatchedServer = RecordingServer
        return self

    def __exit__(self, *exc):
        import repro.serve
        repro.serve.BatchedServer = self.saved
        for rid, s in self.served.items():
            tok = self.first.get(rid)
            s["first"] = None if tok is None else int(np.asarray(tok))
        self.first.clear()
        return False


class ServeCell:
    def __init__(self, run):
        self.run = run
        mix, c = run.mix, run.cfg
        self.mix = mix
        self.cfg = program_config(c, f"bench:{run.entry['name']}")
        self.S0 = int(mix["prompt_tokens"])
        self.slots = int(mix["decode_slots"])
        self.ws = float(mix["window_s"])
        self.param_seed = derive_seed(run.seed, "params")
        self.model = TrafficModel(
            seed=int(mix["pool_seed"]), window_s=self.ws,
            base_rps=float(mix["rate_rps"]), peak_rps=float(mix["rate_rps"]),
            burst_prob=0.0, latency_frac=float(mix["latency_frac"]),
            prompt_tokens=self.S0,
            latency_new_tokens=int(mix["latency_new_tokens"]),
            throughput_new_tokens=int(mix["throughput_new_tokens"]))
        self.clock = {"t0": None, "late": 0.0, "lock": threading.Lock()}
        self.rec = recording()
        _register_source()

    # ------------------------------------------------------------ set-up
    def order(self, n: int) -> List[int]:
        """The pool's windows 0..n-1 in this seed's order."""
        rng = np.random.default_rng(derive_seed(self.run.seed, "order"))
        return [int(k) for k in rng.permutation(n)]

    def setup(self, n_windows: int):
        """Weights from the seed (one jitted call, in the served dtype),
        then one engine run per cache length the windows will use."""
        from repro.plugins.serve import serving_params
        from repro.serve import BatchedServer, Request
        self.params = serving_params(self.cfg, self.param_seed)
        lens = sorted({self.S0 + max(r.max_new_tokens
                                     for r in self.model.window(k))
                       for k in range(n_windows) if self.model.window(k)})
        rng = np.random.default_rng(0)
        for max_len in lens:
            srv = BatchedServer(self.cfg, self.params, batch=self.slots,
                                prompt_len=self.S0, max_len=max_len)
            srv.submit([Request(rid=i, prompt=rng.integers(
                0, self.cfg.vocab_size, self.S0), max_new_tokens=2)
                for i in range(self.slots + 1)])
            srv.run()
        jax.block_until_ready(self.params)

    # ------------------------------------------------------------ window
    def pipelines(self, order: List[int]):
        from repro.core import Kernel, PipelineSpec, Stage, TaskSpec
        from repro.core.flow import Channel
        chan = Channel("bench.requests")
        margs = dataclasses.asdict(self.model)
        src, dec = [], []
        for j, k in enumerate(order):
            if not self.model.window(k):
                continue
            ks = Kernel(SOURCE)
            ks.arguments = {"slot": j, "window": k, "window_s": self.ws,
                            "clock": self.clock}
            src.append(Stage([TaskSpec(ks, name=f"src.w{j:05d}",
                                       outputs=chan)], name=f"w{j:05d}"))
            kd = Kernel("serve.decode")
            kd.arguments = {"model": margs, "window": k, "sla": None,
                            "arch": self.cfg.name, "prompt_len": self.S0,
                            "decode_slots": self.slots,
                            "param_seed": self.param_seed}
            dec.append(Stage([TaskSpec(kd, name=f"dec.w{j:05d}",
                                       inputs={"batch": chan})],
                             name=f"w{j:05d}"))
        return [PipelineSpec(src, name="source"),
                PipelineSpec(dec, name="decode")]

    def window(self, n_windows: int, tracer=None, profile=None):
        """Serve ``n_windows`` windows; returns per-request records."""
        from repro.core import AppManager
        from repro.runtime.executor import PilotRuntime
        order = self.order(n_windows)
        pipes = self.pipelines(order)
        am = AppManager(PilotRuntime(slots=2, mode="real", tracer=tracer))
        stop = threading.Event()
        if profile is not None:
            def traced():
                # a steady span: from the third window's close on
                if not stop.wait(3 * self.ws):
                    profile.start()
                    stop.wait(profile.seconds)
                    profile.end_span()
            th = threading.Thread(target=traced, daemon=True)
        with self.rec:
            t0 = self.clock["t0"] = time.perf_counter()
            if profile is not None:
                th.start()
            am.run(pipes)
            stop.set()
            if profile is not None:
                th.join()
        self.am, self.t0, self.order_ = am, t0, order
        tasks = am.session.graph.tasks
        records = []
        for j, k in enumerate(order):
            t = tasks.get(f"dec.w{j:05d}")
            done = t.t_finished if t is not None and t.result else None
            for r in self.model.window(k):
                records.append({"rid": r.rid, "due": t0 + j * self.ws
                                + r.offset_s, "done": done,
                                "tokens": r.max_new_tokens,
                                "served": r.rid in self.rec.served})
        self.stats = [t.result.get("stats", {}) for n, t in tasks.items()
                      if n.startswith("dec.") and isinstance(t.result, dict)]
        self.not_done = sum(1 for t in tasks.values()
                            if t.state.name != "DONE" or t.attempts != 1)
        return records

    # ------------------------------------------------------------ checks
    def free(self):
        from repro.plugins import serve
        serve._PARAMS_CACHE.clear()
        self.params = None
        gc.collect()

    def sample(self) -> List[int]:
        """Served requests compared: the longest ones and a few short
        ones, drawn from the seed."""
        rng = np.random.default_rng(derive_seed(self.run.seed, "sample"))
        by_len: Dict[int, List[int]] = {}
        for rid, s in sorted(self.rec.served.items()):
            by_len.setdefault(s["max_new"], []).append(rid)
        out = []
        for n, rids in sorted(by_len.items(), reverse=True):
            want = int(self.mix["sample"].get(str(n), 0))
            if rids and want:
                out += [rids[i] for i in sorted(rng.choice(
                    len(rids), min(want, len(rids)), replace=False))]
        return out

    def reference_gaps(self, rids: List[int], policy: str = "f32") -> dict:
        """Per sampled request: the gaps of its served tokens under the
        float32 reference, and with ``policy`` ``"fp8"`` also the gaps of
        the tokens the float8 control puts first."""
        c, served = self.run.cfg, self.rec.served
        params = jax.jit(lambda k: reference.init_params(
            c, k, jnp.bfloat16))(jax.random.PRNGKey(self.param_seed))
        out = {}
        groups: Dict[int, List[int]] = {}
        for rid in rids:
            groups.setdefault(len(served[rid]["tokens"]), []).append(rid)
        for n, group in groups.items():
            seqs = np.stack([np.concatenate(
                [served[r]["prompt"][:self.S0], [served[r]["first"]],
                 served[r]["tokens"][:-1]]) for r in group]).astype(np.int32)
            chosen = np.stack([[served[r]["first"]] + served[r]["tokens"]
                               for r in group])
            ref = np.asarray(reference.served_logits(
                c, "f32", params, jnp.asarray(seqs), self.S0 - 1))
            gaps = reference.token_gaps(ref, chosen)
            if policy == "fp8":
                ctl = np.asarray(reference.served_logits(
                    c, "fp8", params, jnp.asarray(seqs), self.S0 - 1))
                gaps = reference.token_gaps(ref, ctl.argmax(-1))
            for r, g in zip(group, gaps):
                out[r] = g
            del ref
        return out

    def checks(self, records) -> Checks:
        ck = Checks()
        ck.add("tasks_not_done", self.not_done, 0)
        ck.add("requests_not_served",
               sum(1 for r in records if not r["served"]), 0)
        ck.add("tokens_missing", sum(
            1 for s in self.rec.served.values()
            if len(s["tokens"]) != s["max_new"] or s["first"] is None), 0)
        rids = self.sample()
        gaps = self.reference_gaps(rids)
        widest = max((float(np.max(g)) for g in gaps.values()), default=0.0)
        self.compared_tokens = int(sum(len(g) for g in gaps.values()))
        ck.add("served_token_gap", widest,
               float(self.mix["limits"]["served_token_gap"]))
        return ck


def request_flops(flops, prompt: int, new: int) -> float:
    """Operations one request needs: its prompt's prefill, the token the
    prefill chooses, and ``new`` decoded tokens."""
    body = flops.prefill_flops(1, prompt)
    return body + sum(flops.decode_token_flops(prompt + t + 1)
                      for t in range(new))


def run_cell(run):
    from lib.harness import annotate_kernels, log
    cell = ServeCell(run)
    n = max(1, int(math.floor(run.seconds / cell.ws)))
    cell.setup(n)
    run.mark_setup_done()
    from contextlib import nullcontext
    spans = annotate_kernels(["serve.decode", SOURCE]) if run.trace \
        else nullcontext()
    with spans:
        records = cell.window(n, tracer=run.tracer, profile=run.profiler)
    t0 = cell.t0
    t_close = t0 + n * cell.ws
    deadline = t_close + float(run.mix["drain_s"])
    lat, failed = latencies([r["due"] for r in records],
                            [r["done"] for r in records], deadline)
    lat += [deadline - r["due"] for r in records
            if r["done"] is None or r["done"] > deadline]
    last = max((r["done"] for r in records if r["done"] is not None
                and r["done"] <= deadline), default=t_close)
    tokens = sum(r["tokens"] for r in records
                 if r["done"] is not None and r["done"] <= deadline)
    run.window = (t0, last)
    run.e2e["latency_p50_s"] = percentile(lat, 50)
    run.e2e["latency_p95_s"] = percentile(lat, 95)
    run.e2e["serve_tokens_per_s"] = rate(tokens, t0, last)
    stats = cell.stats
    run.counters.update(
        requests=len(records), failed=failed, windows=n,
        output_tokens=tokens, source_late_s=cell.clock["late"],
        decode_steps=sum(s.get("decode_steps", 0) for s in stats),
        slot_steps=sum(s.get("slot_steps", 0) for s in stats),
        prefills=sum(s.get("prefills", 0) for s in stats),
        slots=cell.slots,
        flops=sum(request_flops(run.flops, cell.S0, r["tokens"])
                  for r in records if r["done"] is not None
                  and r["done"] <= deadline))
    log(f"latency samples={len(lat)} (p95 has "
        f"{len(lat) - math.ceil(0.95 * len(lat))} beyond it); "
        f"generator late by up to {cell.clock['late']!r}s")
    if run.trace:
        from repro.obs.report import decompose, segment_from_tracer
        run.profiler.stop()
        run.device_trace = run.profiler.reduce()
        run.obs = decompose(segment_from_tracer(run.tracer))
        run.extra["spans"] = run.tracer.spans
    run.read_memory_peak()
    cell.free()
    run.checks = cell.checks(records)
    run.counters["tokens_compared"] = cell.compared_tokens
    run.attempted = len(records)
    run.failed = failed
    return run


# ---------------------------------------------------------------- faults

class planted:
    """A fault planted in the engine for the calibration and the tests:
    ``token_altered`` (one decode step's token for the first slot is the
    one the logits rank last) or ``cache_unchanged`` (each decode step
    returns the cache it was given)."""

    KINDS = ("token_altered", "cache_unchanged")

    def __init__(self, kind: str):
        if kind not in self.KINDS:
            raise ValueError(kind)
        self.kind = kind

    def __enter__(self):
        from repro.serve import engine
        self.saved = engine.build_serve_step
        real = engine.build_serve_step
        kind = self.kind

        def build(cfg, mesh=None):
            step = real(cfg, mesh)

            def bad(params, cache, tokens, positions):
                logits, new = step(params, cache, tokens, positions)
                if kind == "cache_unchanged":
                    return logits, cache
                flip = (positions[0] % 7) == 3
                return logits.at[0].set(jnp.where(flip, -logits[0],
                                                  logits[0])), new
            return bad
        engine.build_serve_step = build
        return self

    def __exit__(self, *exc):
        from repro.serve import engine
        engine.build_serve_step = self.saved
        return False


def _short_window(run, n_windows: int):
    cell = ServeCell(run)
    cell.setup(n_windows)
    records = cell.window(n_windows)
    cell.free()
    return cell, records


def calibrate(run, control: bool = False, faults: bool = False):
    """Yield the program's widest served-token gap for ``run``'s seed over
    a short window at the cell's own load; with ``control`` the gap of the
    tokens the float8 control puts first; with ``faults`` each planted
    fault's."""
    n = int(run.mix["calibration_windows"])
    cell, _ = _short_window(run, n)
    rids = cell.sample()
    gaps = cell.reference_gaps(rids)
    yield {"what": "program", "numbers": {
        "served_token_gap": max(float(np.max(g)) for g in gaps.values()),
        "tokens": int(sum(len(g) for g in gaps.values()))}}
    if control:
        gaps = cell.reference_gaps(rids, "fp8")
        yield {"what": "control", "numbers": {
            "served_token_gap": max(float(np.max(g))
                                    for g in gaps.values())}}
    if faults:
        for kind in planted.KINDS:
            with planted(kind):
                bad, _ = _short_window(run, n)
            gaps = bad.reference_gaps(bad.sample())
            yield {"what": kind, "numbers": {"served_token_gap": max(
                float(np.max(g)) for g in gaps.values())}}


def sweep(run, seconds: float) -> dict:
    """Serve ``run``'s mix for ``seconds`` after a set-up of its own, the
    cell's own path without the checks, and report every window: its
    requests, how long its ``serve.decode`` took, and how far that ended
    after the window closed.  A rate is sustained where every window's
    serving takes less than ``window_s``, so that the lag does not grow
    from window to window; above that the backlog grows through the run."""
    cell = ServeCell(run)
    n = max(1, int(seconds // cell.ws))
    cell.setup(n)
    records = cell.window(n)
    tasks = cell.am.session.graph.tasks
    windows = []
    for j, k in enumerate(cell.order_):
        t = tasks.get(f"dec.w{j:05d}")
        if t is None:
            continue
        windows.append({"window": k, "requests": len(cell.model.window(k)),
                        "serve_s": t.t_finished - t.t_started,
                        "lag_s": t.t_finished - (cell.t0 + (j + 1) * cell.ws)})
    cell.free()
    lat = [r["done"] - r["due"] for r in records if r["done"]]
    return {"rate_rps": float(run.mix["rate_rps"]), "requests": len(records),
            "latency_p50_s": percentile(lat, 50),
            "latency_p95_s": percentile(lat, 95),
            "serve_s_max": max(w["serve_s"] for w in windows),
            "windows": windows}
