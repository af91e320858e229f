"""Engine layer (``serve/engine.py`` continuous batching): the share of
decode slot-steps that served a request, from the ``stats`` that each
``serve.decode`` task returns: sum of ``slot_steps`` over sum of
``decode_steps`` times the slots.  In %."""


def read(run):
    c = run.counters
    if not c.get("decode_steps"):
        return None
    return 100.0 * c["slot_steps"] / (c["decode_steps"] * c["slots"])
