"""Model step layer, serving cells: the operations that the served
requests needed (each prompt's prefill, the token it chose and the tokens
decoded after it, ``lib/flops.py``) over the seconds from the window's
start to the last of them completing, over the chip's published bf16 peak
(``lib/peaks.py``).  In %."""


def read(run):
    c = run.counters
    if not run.window or not c.get("flops"):
        return None
    seconds = run.window[1] - run.window[0]
    return 100.0 * c["flops"] / seconds / (run.chips * run.peaks.flops)
