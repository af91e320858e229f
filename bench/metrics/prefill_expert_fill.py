"""Model-step layer: how full the serving prefill's expert layout is.  The
``engine.admit`` spans that lie inside the traced span carry the
prefill's expert counters, summed over the layers: ``expert_rows``, the
rows routed to the held experts, and ``expert_row_slots``, the rows of
the layout that the grouped matmuls ran, live and padding (a buffer of C
rows for each held expert, or the chunks of the ragged row list).  In %
of Σ ``expert_rows`` over Σ ``expert_row_slots``; nothing to read
without those spans and counters."""


def read(run):
    prof, tracer = run.profiler, run.tracer
    if prof is None or prof.t1 is None or tracer is None \
            or tracer.t0 is None:
        return None
    w0, w1 = prof.t0 - tracer.t0, prof.t1 - tracer.t0
    admits = [s for s in run.extra.get("spans") or []
              if s.get("cat") == "engine" and s.get("name") == "admit"
              and s["t0"] >= w0 and s["t1"] <= w1
              and "expert_row_slots" in s]
    slots = sum(s["expert_row_slots"] for s in admits)
    if not slots:
        return None
    return 100.0 * sum(s["expert_rows"] for s in admits) / slots
