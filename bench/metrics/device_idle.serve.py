"""Device layer, serving cells: the share of the traced span in which no
operation ran on the chip (one minus the union of ``XLA Ops`` intervals
over the span, mean over the cell's chips), in %."""


def read(run):
    if run.device_trace is None:
        return None
    return 100.0 * run.device_trace["idle_share"]
