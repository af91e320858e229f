"""Member kernel layer (``plugins/lm.py`` ``lm.train``): the share of the
kernel's time that the host spent other than blocked on the chip, in %:
the ``member.train`` spans less their ``member.wait`` children (the
read-backs of the state's step and of the losses), over the
``member.train`` spans.  What is left is building and placing each
step's batch (``member.data``), the step's dispatch and Python."""


def read(run):
    spans = run.extra.get("spans") or []
    bodies = {s["id"]: s for s in spans
              if s.get("cat") == "member" and s.get("name") == "train"}
    total = sum(s["t1"] - s["t0"] for s in bodies.values())
    if total <= 0:
        return None
    wait = sum(s["t1"] - s["t0"] for s in spans
               if s.get("cat") == "member" and s.get("name") == "wait"
               and s.get("parent") in bodies)
    return 100.0 * (total - wait) / total
