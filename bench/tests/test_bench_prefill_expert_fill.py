"""Reader of ``prefill_expert_fill``: Σ ``expert_rows`` over Σ
``expert_row_slots`` of the ``engine.admit`` spans inside the traced
span.  It reads a known share from spans filled by hand, leaves out the
decode steps and the admissions outside the span, and reads nothing
where the program's spans carry no ``expert_row_slots``, as the spans of
a program that counts no layout rows do."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

METRIC = Path(__file__).resolve().parents[1] / "metrics" / \
    "prefill_expert_fill.py"


def reader():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_prefill_expert_fill", METRIC)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_of(spans, t=(10.0, 22.0)):
    """What the harness hands a reader after a traced serving window: the
    program spans and the traced span's ends on the tracer's clock
    (origin 100 s)."""
    return SimpleNamespace(
        extra={"spans": list(spans)},
        profiler=SimpleNamespace(t0=100.0 + t[0], t1=100.0 + t[1]),
        tracer=SimpleNamespace(t0=100.0))


def span(name, t0, t1, rows, slots):
    s = {"cat": "engine", "name": name, "t0": t0, "t1": t1,
         "expert_rows": rows, "experts_hit": 3, "expert_drops": 0}
    if slots is not None:
        s["expert_row_slots"] = slots
    return s


@pytest.mark.parametrize("admits,want", [
    ([(24_576, 28_672)], 100.0 * 24_576 / 28_672),
    ([(24_000, 28_672), (1_000, 4_096)], 100.0 * 25_000 / 32_768),
    ([(24_576, 262_144)], 100.0 * 24_576 / 262_144)])
def test_reads_the_fill_of_the_traced_admissions(admits, want):
    inside = [span("admit", 11.0 + i, 11.5 + i, r, s)
              for i, (r, s) in enumerate(admits)]
    others = [span("decode", 12.0, 12.01, 40, 128),           # a step
              span("admit", 9.5, 10.5, 100, 262_144),          # straddles
              span("admit", 21.9, 22.1, 100, 262_144)]         # the ends
    assert reader()(run_of(inside + others)) == pytest.approx(want)


@pytest.mark.parametrize("what", ["no counter", "no admissions",
                                  "no rows laid out", "no profile",
                                  "no tracer"])
def test_reads_nothing_without_its_inputs(what):
    spans = [span("admit", 11.0, 11.5, 24_576, 28_672)]
    run = run_of(spans)
    if what == "no counter":        # the spans of a program that counts
        run = run_of([span("admit", 11.0, 11.5, 24_576, None)])  # none
    elif what == "no admissions":
        run = run_of([span("decode", 11.0, 11.01, 40, 128)])
    elif what == "no rows laid out":
        run = run_of([span("admit", 11.0, 11.5, 0, 0)])
    elif what == "no profile":
        run.profiler = None
    else:
        run.tracer = None
    assert reader()(run) is None
