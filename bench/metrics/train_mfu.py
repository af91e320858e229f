"""Model step layer, training cells: the whole training step's share of
the chips' peak, in %: the operations a step needs (``lib/flops.py``,
forward and backward, rematerialisation not counted) times the steps the
traced span's cycles completed, over the span's seconds, over chips times
the published bf16 peak (``lib/peaks.py``)."""


def read(run):
    c = run.counters
    if not c.get("traced_steps") or not c.get("traced_s"):
        return None
    achieved = c["flops_per_step"] * c["traced_steps"] / c["traced_s"]
    return 100.0 * achieved / (run.chips * run.peaks.flops)
