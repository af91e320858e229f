"""Engine layer (``serve/engine.py`` continuous batching): the share of
the decode steps' time that the host spent other than blocked on the
chip, in %: the ``engine.decode`` spans less their ``engine.wait``
children (the blocking token read-back), over the ``engine.decode``
spans.  What is left is the step's host work: the token and position
transfers, the step's and the argmax's dispatch, and the bookkeeping."""


def read(run):
    spans = run.extra.get("spans") or []
    steps = {s["id"]: s for s in spans
             if s.get("cat") == "engine" and s.get("name") == "decode"}
    total = sum(s["t1"] - s["t0"] for s in steps.values())
    if total <= 0:
        return None
    wait = sum(s["t1"] - s["t0"] for s in spans
               if s.get("cat") == "engine" and s.get("name") == "wait"
               and s.get("parent") in steps)
    return 100.0 * (total - wait) / total
