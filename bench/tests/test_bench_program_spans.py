"""Readers of the per-layer metrics built on the program's own spans
(``repro.obs.Tracer`` program spans): each reads a known value from a run
whose tracer was filled by hand, and nothing when the spans it reads are
absent, as in a program that records none."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.obs import Tracer

METRICS = Path(__file__).resolve().parents[1] / "metrics"
NEW = ("compile_s.train", "compile_s.serve", "decode_host_share",
       "member_host_share", "launch_ms.train", "launch_ms.serve")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_of(tr):
    """What the harness hands a reader after a traced window."""
    return SimpleNamespace(tracer=tr, extra={"spans": tr.spans})


def timed(span, t0, t1):
    span["t0"], span["t1"] = t0, t1


def parent_like():
    """A traced window of a program without program spans: task attempts
    only, as the runtime records them."""
    tr = Tracer()

    class T:
        name, attempts, slots, meta = "dec.w00000", 1, 1, {}
    tr.task_begin(T, 0.0)
    tr.task_end(T, 1.0, "done")
    return tr


def settled(tr):
    """The ``runtime.settle`` span every traced attempt leaves."""
    tr.record("runtime", "settle", 1.0, 1.001)


@pytest.mark.parametrize("name", NEW)
def test_reads_nothing_without_program_spans(name):
    assert reader(name)(run_of(parent_like())) is None
    assert reader(name)(SimpleNamespace(tracer=None, extra={})) is None


@pytest.mark.parametrize("name", ["compile_s.train", "compile_s.serve"])
def test_compile_seconds_are_the_union_of_compile_spans(name):
    tr = parent_like()
    settled(tr)
    assert reader(name)(run_of(tr)) == 0.0      # no compile in the window
    # a trace nested in a lowering, one overlapping it, one apart
    tr.record("compile", "jaxpr_to_mlir", 2.0, 3.0, fun_name="f")
    tr.record("compile", "jaxpr_trace", 2.2, 2.5, fun_name="g")
    tr.record("compile", "backend_compile", 2.8, 3.5, fun_name="f")
    tr.record("compile", "backend_compile", 5.0, 5.25, fun_name="h")
    assert reader(name)(run_of(tr)) == pytest.approx(1.5 + 0.25)


def test_decode_host_share_subtracts_only_its_own_waits():
    tr = parent_like()
    for t0 in (0.0, 1.0):
        with tr.span("engine", "decode", slots=2) as step:
            with tr.span("engine", "wait") as wait:
                pass
        timed(step, t0, t0 + 0.010)
        timed(wait, t0 + 0.002, t0 + 0.008)
    # an admission's wait is not a decode step's
    with tr.span("engine", "admit", joiners=1, rids=[7]) as admit:
        with tr.span("engine", "wait") as wait:
            pass
    timed(admit, 2.0, 2.5)
    timed(wait, 2.1, 2.4)
    assert reader("decode_host_share")(run_of(tr)) == pytest.approx(40.0)


def test_member_host_share_subtracts_only_its_own_waits():
    tr = parent_like()
    with tr.span("member", "train", member=0) as body:
        with tr.span("member", "wait") as w1:
            pass
        with tr.span("member", "data") as data:
            pass
        with tr.span("member", "wait") as w2:
            pass
    timed(body, 0.0, 0.100)
    timed(w1, 0.0, 0.020)
    timed(data, 0.020, 0.030)
    timed(w2, 0.060, 0.090)
    with tr.span("engine", "wait") as other:        # another layer's wait
        pass
    timed(other, 0.0, 0.050)
    assert reader("member_host_share")(run_of(tr)) == pytest.approx(50.0)


@pytest.mark.parametrize("name", ["launch_ms.train", "launch_ms.serve"])
def test_launch_ms_is_the_mean_launch_span(name):
    tr = parent_like()
    for i, (t0, t1) in enumerate([(1.0, 1.002), (1.0, 1.006)]):
        h = tr.open("runtime", "launch", t0=t0, stacked=False,
                    parent=(f"t{i}", 1), task=f"t{i}", attempt=1)
        timed(tr.close(h), t0, t1)
    settled(tr)
    assert reader(name)(run_of(tr)) == pytest.approx(4.0)
