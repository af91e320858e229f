"""One run of one cell: devices, set-up clock, traced window, per-layer
readers, and the result line.

A cell's traffic file names its job kind, and the kind names the module
that drives it: ``"kind": "ensemble"`` is ``bench/lib/ensemble.py``,
``"kind": "serving"`` is ``bench/lib/serving.py``.  A kind module's
``run_cell(run)`` runs set-up, the measured window and the checks on a
:class:`Run`; a new kind is a new module, found by its name.  Per-layer
metrics are read by ``bench/metrics/<metric>.py`` files, each with a
``read(run)`` that returns a number or ``None`` (nothing to read).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict

from lib import model
from lib.flops import Dense
from lib.peaks import peaks

def log(msg: str):
    print(msg, flush=True)


class Profiler:
    """``jax.profiler`` over a short steady span inside the window.  The
    span is marked on the host by a ``bench.traced`` annotation and ends
    at the first cycle boundary (or, for serving, the first moment) after
    ``seconds``; the profiler keeps running until the window has closed,
    so that writing the trace out costs the window nothing."""

    SPAN = "bench.traced"

    def __init__(self, run, seconds: float):
        self.run = run
        self.seconds = seconds
        self.dir = str(run.out_dir / "trace")
        self.t0 = self.t1 = None
        self.k0 = self.k1 = None
        self.done = False
        self._ann = None

    def start(self, k: int = 0):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(self.SPAN)
        self._ann.__enter__()
        self.t0, self.k0 = time.perf_counter(), k

    def end_span(self, k: int = 0):
        if self.t0 is not None and self.t1 is None:
            self._ann.__exit__(None, None, None)
            self.t1, self.k1 = time.perf_counter(), k

    def stop(self):
        import jax
        if self.t0 is None or self.done:
            return
        self.end_span()
        jax.profiler.stop_trace()
        self.done = True

    def on_cycle(self, k: int):
        """Called at the end of the window's cycle ``k`` (from 0)."""
        if self.t0 is None:
            self.start(k)
        elif time.perf_counter() - self.t0 >= self.seconds:
            self.end_span(k)

    def reduce(self) -> dict:
        from lib import trace
        tr = trace.Trace(trace.find_xplane(self.dir))
        window = tr.span(self.SPAN)
        if window is None:
            raise RuntimeError("the trace lost its bench.traced span")
        out = trace.reduce(tr, window, self.run.chips, host_span)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def host_span(name: str) -> bool:
    """Host spans an idle gap may be attributed to: the benchmark's own
    annotations around its calls into each layer, and JAX's dispatch of a
    jitted call."""
    return name.startswith(("bench:", "PjitFunction("))


class annotate_kernels:
    """Wrap the registered kernels ``names`` in host spans
    ``bench:<kernel>`` for the traced run, and restore them after."""

    def __init__(self, names):
        self.names = list(names)
        self.saved = {}

    def __enter__(self):
        import jax
        from repro.core import kernel_plugin
        reg = kernel_plugin._KERNEL_REGISTRY
        kernel_plugin._ensure_plugins()
        for n in self.names:
            kd = reg[n]
            fn = self.saved[n] = kd.fn

            def wrapped(args, ctx, _fn=fn, _label=f"bench:{n}"):
                with jax.profiler.TraceAnnotation(_label):
                    return _fn(args, ctx)
            kd.fn = wrapped
        return self

    def __exit__(self, *exc):
        from repro.core import kernel_plugin
        for n, fn in self.saved.items():
            kernel_plugin._KERNEL_REGISTRY[n].fn = fn
        return False


class Run:
    def __init__(self, spec: dict, seed: int, seconds: float,
                 trace: bool, t_start: float, devices=None):
        """``spec`` is what ``lib.model.cell`` returns for the cell."""
        self.benchmark = spec["benchmark"]
        self.cell = spec["workload"]
        self.workload = workload = self.cell["name"]
        self.entry = spec["config_entry"]
        self.cfg = spec["config"]
        self.mix = spec["mix"]
        self.kind = self.mix["kind"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.chips = int(self.cell["chips"])
        import jax
        self.all_devices = devices if devices is not None else jax.devices()
        self.devices = self.all_devices[:self.chips]
        self.device_kind = self.devices[0].device_kind
        self.platform = self.devices[0].platform
        self.out_dir = model.ROOT / ".bench_out" / workload
        self.out_dir.mkdir(parents=True, exist_ok=True)
        self.flops = Dense.from_config(self.cfg)
        self.e2e: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self.checks = None
        self.attempted = 0
        self.failed = 0
        self.window = None
        self.setup_s = None
        self.memory_peak = None
        self.obs = None
        self.device_trace = None
        self.extra: Dict[str, object] = {}
        self.tracer = None
        self.profiler = None
        if self.trace:
            from repro.obs import Tracer
            self.tracer = Tracer()
            self.profiler = Profiler(self, float(
                self.mix.get("trace_seconds", 3.0)))

    @property
    def peaks(self):
        return peaks(self.device_kind)

    def mark_setup_done(self):
        self.setup_s = time.perf_counter() - self.t_start
        log(f"setup_s={self.setup_s!r}")

    def read_memory_peak(self):
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak = peak

    # ------------------------------------------------------------ per layer
    def per_layer_names(self):
        out = []
        for m in self.benchmark["per_layer"]:
            if self.workload in m.get("workloads", [self.workload]):
                out.append(m)
        return out

    def read_per_layer(self) -> Dict[str, dict]:
        out = {}
        for m in self.per_layer_names():
            path = model.BENCH / "metrics" / f"{m['name']}.py"
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{m['name'].replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            value = mod.read(self)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    # ------------------------------------------------------------ result
    def result(self) -> dict:
        dev = {"platform": self.platform, "kind": self.device_kind,
               "count": len(self.all_devices),
               "memory_peak_bytes": int(self.memory_peak or 0)}
        if self.trace:
            metrics = self.read_per_layer()
            dev["busy_s"] = self.device_trace["busy_s"]
            dev["window_s"] = self.device_trace["window_s"]
        else:
            units = {m["name"]: m["unit"]
                     for m in self.benchmark["end_to_end"]}
            metrics = {"setup_s": {"value": self.setup_s, "unit": "s"}}
            for k, v in self.e2e.items():
                metrics[k] = {"value": float(v), "unit": units[k]}
        out = {"correct": bool(self.checks is not None and self.checks.ok),
               "attempted": int(self.attempted), "failed": int(self.failed),
               "metrics": metrics, "device": dev}
        if self.trace:
            out["breakdown"] = {
                "device_ops": self.device_trace["device_ops"],
                "idle_gaps": self.device_trace["idle_gaps"]}
        out["checks"] = self.checks.items if self.checks else {}
        return out


def kind_module(kind: str):
    """The module ``bench/lib/<kind>.py`` that drives mixes of ``kind``."""
    path = model.BENCH / "lib" / f"{kind}.py"
    if not kind.isidentifier() or not path.is_file():
        raise KeyError(f"no module for job kind {kind!r}: {path} is missing")
    mod = importlib.import_module(f"lib.{kind}")
    if not hasattr(mod, "run_cell"):
        raise KeyError(f"lib/{kind}.py has no run_cell(run)")
    return mod


def main(argv=None) -> int:
    import argparse
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = model.cell(args.workload)["workload"]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(model.ROOT / ".jax_cache")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found: JAX sees {len(devs)} "
              f"{devs[0].platform} device(s); the benchmark has no CPU "
              "branch", file=sys.stderr)
        return 3
    if len(devs) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"sees {len(devs)}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    log(f"bench: {args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} on {devs[0].device_kind} x{len(devs)}; "
        f"compile cache at {enable_compile_cache()}")
    run = Run(model.cell(args.workload), args.seed, args.seconds,
              bool(args.trace), t_start)
    kind_module(run.kind).run_cell(run)
    log(f"compile cache: hits={cache['hits']} misses={cache['misses']}")
    for k, v in run.counters.items():
        log(f"counter {k}={v!r}")
    res = run.result()
    run.checks.print_stderr()
    print(json.dumps(res), flush=True)
    return 0
