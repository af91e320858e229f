"""Kernel layer: the Pallas flash-attention kernel of the serving prefill
of a latent-attention configuration, which expands the latent to per-head
keys and values.  In the traced span, its device time is the sum of the
``custom-call`` ops of the ``jit_prefill_step`` runs whose result type is
the attention's output (bf16[slots * heads, 1, prompt, v_head_dim]), told
apart from the expert kernel's ops that share the program; its least time
is the roofline of the calls it made (``lib/flops_mla_moe.py``
``mla_attention_cost``: causal scores over the q/k width, nope + rope, and
values over ``v_head_dim`` for every row of the prefill batch, q, k and v
read and the output written once, per layer), at the published peaks.
In % of the least time over the device time."""
from lib.flops import least_time
from lib.flops_mla_moe import mla_attention_cost
from lib.trace import op_name

PROGRAM = "jit_prefill_step"


def read(run):
    tr = run.device_trace
    if tr is None or PROGRAM not in tr["program_ops"]:
        return None
    c, m = run.cfg, run.mix
    B, S = int(m["decode_slots"]), int(m["prompt_tokens"])
    H, dv = c["num_attention_heads"], c["v_head_dim"]
    out = f"custom-call:bf16[{B * H},1,{S},{dv}]"
    kernel = [e - s for s, e, n in tr["program_ops"][PROGRAM]
              if op_name(n) == out]
    calls = tr["program_runs"].get(PROGRAM, 0)
    if not kernel or not calls:
        return None
    ops, nbytes = mla_attention_cost(
        B, S, H, c["qk_nope_head_dim"] + c["qk_rope_head_dim"], dv)
    t, _ = least_time(ops, nbytes, run.peaks.flops,
                      run.peaks.hbm_bytes_per_s)
    return 100.0 * calls * c["num_hidden_layers"] * t / sum(kernel)
