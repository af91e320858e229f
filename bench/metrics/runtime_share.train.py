"""Runtime layer, training cells: the share of slot-seconds in the window
that the runtime spent scheduling, blocked or idle (``t_sched + t_block +
t_idle`` over all five terms of ``repro.obs`` ``decompose``), in %."""


def read(run):
    if run.obs is None:
        return None
    t = run.obs["totals"]
    total = sum(t[k] for k in ("t_exec", "t_data", "t_sched", "t_block",
                               "t_idle"))
    if total <= 0:
        return None
    return 100.0 * (t["t_sched"] + t["t_block"] + t["t_idle"]) / total
