"""Reader of ``decode_cache_inplace``: the share of ``engine.decode`` spans
whose ``cache_donated`` attribute is true.  It reads a known share from a
run whose tracer was filled by hand, and nothing where the program left no
decode steps or no such attribute, as in a program that records none."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.obs import Tracer

METRIC = Path(__file__).resolve().parents[1] / "metrics" / "decode_cache_inplace.py"


def reader():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_decode_cache_inplace", METRIC)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_of(tr):
    """What the harness hands a reader after a traced window."""
    return SimpleNamespace(tracer=tr, extra={"spans": tr.spans})


def window():
    """A traced window with one task attempt, as the runtime records it."""
    tr = Tracer()

    class T:
        name, attempts, slots, meta = "dec.w00000", 1, 1, {}
    tr.task_begin(T, 0.0)
    tr.task_end(T, 1.0, "done")
    return tr


def decode_steps(tr, flags):
    for flag in flags:
        with tr.span("engine", "decode", slots=2) as step:
            with tr.span("engine", "wait"):
                pass
        if flag is not None:
            step["cache_donated"] = flag
    with tr.span("engine", "admit", joiners=1, rids=[7]):  # not a step
        pass


@pytest.mark.parametrize("flags,want", [
    ((True, True, True, True), 100.0),
    ((True, False, True, False), 50.0),
    ((False, False), 0.0)])
def test_reads_the_share_of_donating_steps(flags, want):
    tr = window()
    decode_steps(tr, flags)
    assert reader()(run_of(tr)) == pytest.approx(want)


def test_reads_nothing_without_decode_steps():
    tr = window()
    decode_steps(tr, ())
    assert reader()(run_of(tr)) is None


def test_reads_nothing_from_steps_without_the_attribute():
    """The parent's decode spans carry no ``cache_donated``."""
    tr = window()
    decode_steps(tr, (None, None))
    assert reader()(run_of(tr)) is None


def test_reads_nothing_without_a_tracer():
    assert reader()(run_of(window())) is None
    assert reader()(SimpleNamespace(tracer=None, extra={})) is None
