"""Model step layer, training cells: seconds of the window in which the
program traced, lowered or compiled a jitted function, or loaded one from
the persistent compile cache: the union of the ``compile`` spans that
``repro.obs.Tracer`` records from JAX's compile events while the drain
runs, so that nested and overlapping events count once.  A window without
``runtime`` spans comes from a program that records no program spans:
nothing to read."""
from lib.trace import union


def read(run):
    spans = run.extra.get("spans") or []
    if not any(s.get("cat") == "runtime" for s in spans):
        return None
    return sum(b - a for a, b in union(
        [(s["t0"], s["t1"]) for s in spans if s.get("cat") == "compile"]))
