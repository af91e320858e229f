"""Member kernel layer: seconds of ``lm.train`` task execution per training
step, from the runtime's task spans in the traced window (``repro.obs``):
the sum of the spans of finished ``lm.train`` tasks over the steps they
took."""


def read(run):
    spans = run.extra.get("spans") or []
    steps = int(run.mix["steps_per_task"])
    done = [s for s in spans if s.get("cat") == "task"
            and ".train." in s["task"] and s["outcome"] == "done"]
    if not done or steps <= 0:
        return None
    return sum(s["t1"] - s["t0"] for s in done) / (len(done) * steps)
