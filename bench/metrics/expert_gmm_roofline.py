"""Kernel layer: the held experts' grouped matmul (the Pallas ``moe_gmm``
kernel) in the serving decode step.  Its device time is the sum of the
``custom-call`` ops in the runs of ``jit_serve_step`` inside the traced
span (the decode step's only kernel).  Its least time is the roofline
(``lib/flops_mla_moe.py`` ``expert_gmm_cost``, at the published peaks) of
the rows and hit experts that the ``engine.decode`` spans inside the
traced span counted (``expert_rows``, ``experts_hit``, summed over the
layers): each hit expert's weights read once, each row in and out once,
6 * d_model * expert_ff operations a row.  The steps counted lie inside
the span, so their device work is among the runs timed.  In % of the
least time over the device time; nothing to read without those spans and
counters."""
from lib.flops_mla_moe import expert_gmm_cost
from lib.flops import least_time
from lib.trace import opcode

PROGRAM = "jit_serve_step"


def traced_steps(run, name: str):
    """The program spans ``engine.<name>`` that lie inside the traced
    span, on the tracer's clock."""
    prof, tracer = run.profiler, run.tracer
    spans = run.extra.get("spans") or []
    if prof is None or prof.t1 is None or tracer is None \
            or tracer.t0 is None:
        return []
    w0, w1 = prof.t0 - tracer.t0, prof.t1 - tracer.t0
    return [s for s in spans if s.get("cat") == "engine"
            and s.get("name") == name and s["t0"] >= w0 and s["t1"] <= w1]


def read(run):
    tr = run.device_trace
    if tr is None or PROGRAM not in tr["program_ops"]:
        return None
    kernel = sum(e - s for s, e, n in tr["program_ops"][PROGRAM]
                 if opcode(n) == "custom-call")
    steps = [s for s in traced_steps(run, "decode") if "expert_rows" in s]
    if kernel <= 0 or not steps:
        return None
    c = run.cfg
    ops, nbytes = expert_gmm_cost(
        sum(s["expert_rows"] for s in steps),
        sum(s["experts_hit"] for s in steps), c["hidden_size"],
        c["moe_intermediate_size"])
    t, _ = least_time(ops, nbytes, run.peaks.flops,
                      run.peaks.hbm_bytes_per_s)
    return 100.0 * t / kernel
