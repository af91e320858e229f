"""Runtime layer, training cells: the mean ``runtime.launch`` span over the
window's task attempts, in ms.  The span runs from the moment the attempt
became runnable (its last dependency settled, the channel item it
consumes arrived, or its previous attempt settled) to its kernel's first
line: the frontier pop, ``dep_results``, the journal record, the worker
thread's start and stage-in (``runtime/executor.py``)."""


def read(run):
    spans = [s for s in run.extra.get("spans") or []
             if s.get("cat") == "runtime" and s.get("name") == "launch"]
    if not spans:
        return None
    return 1000.0 * sum(s["t1"] - s["t0"] for s in spans) / len(spans)
