"""Runtime layer, serving cells: the share of slot-seconds in the window
that the runtime spent scheduling, blocked or idle (``repro.obs``
``decompose``), over the slot-seconds not spent in the paced source: the
source's sleep is waiting for requests, not work.  In %."""


def read(run):
    if run.obs is None:
        return None
    t = run.obs["totals"]
    source = sum(p["t1"] - p["t0"] for comp in run.obs["slots"].values()
                 for p in comp["pieces"]
                 if p["kind"] == "exec" and p.get("task", "").startswith(
                     "src."))
    total = sum(t[k] for k in ("t_exec", "t_data", "t_sched", "t_block",
                               "t_idle")) - source
    if total <= 0:
        return None
    return 100.0 * (t["t_sched"] + t["t_block"] + t["t_idle"]) / total
