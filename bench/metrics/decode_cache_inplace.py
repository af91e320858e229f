"""Model-step layer (``serve/engine.py`` decode step): the share of the
decode steps, in %, whose step consumed the cache it was given, so that
the step wrote the new k/v rows into the cache's own buffers rather than
into a copy: ``engine.decode`` spans with ``cache_donated`` true over all
``engine.decode`` spans.  A program whose spans carry no ``cache_donated``
reads nothing."""


def read(run):
    spans = run.extra.get("spans") or []
    flags = [s.get("cache_donated") for s in spans
             if s.get("cat") == "engine" and s.get("name") == "decode"]
    if not flags or any(f is None for f in flags):
        return None
    return 100.0 * sum(1 for f in flags if f) / len(flags)
