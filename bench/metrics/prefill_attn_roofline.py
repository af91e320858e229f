"""Kernel layer: the Pallas flash-attention kernel of the serving
prefill.  In the traced span, its device time is the sum of the
``custom-call`` ops of the ``jit_prefill_step`` program; its least time is
the roofline of the calls it made (``lib/flops.py``
``flash_attention_cost``: causal scores and values of every row of the
prefill batch, q, k, v read and the output written once, per layer), at
the published peaks (``lib/peaks.py``).  In % of the least time over the
device time."""
from lib.flops import flash_attention_cost, least_time
from lib.trace import opcode

PROGRAM = "jit_prefill_step"


def read(run):
    tr = run.device_trace
    if tr is None or PROGRAM not in tr["program_ops"]:
        return None
    ops = tr["program_ops"][PROGRAM]
    kernel = [e - s for s, e, n in ops if opcode(n) == "custom-call"]
    calls = tr["program_runs"].get(PROGRAM, 0)
    if not kernel or not calls:
        return None
    f, m = run.flops, run.mix
    ops_, nbytes = flash_attention_cost(
        int(m["decode_slots"]), int(m["prompt_tokens"]), f.heads,
        f.kv_heads, f.head_dim)
    t, _ = least_time(ops_, nbytes, run.peaks.flops,
                      run.peaks.hbm_bytes_per_s)
    return 100.0 * calls * f.layers * t / sum(kernel)
