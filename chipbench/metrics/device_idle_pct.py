"""The share of the window in which no operation ran on the chip: 1 minus
the union of the device's ``XLA Ops`` intervals over the traced window,
averaged over the chips."""
from chipbench import trace


def read(run):
    busy = trace.device_busy(run.trace) if run.trace else None
    if busy is None or busy[1] <= 0:
        return None
    return 100.0 * (1.0 - busy[0] / busy[1])
