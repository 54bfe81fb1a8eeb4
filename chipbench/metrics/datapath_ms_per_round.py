"""Socket datapath time per round: the ``LibraStack.recv_batch`` and
``forward_batch`` spans inside each step, averaged over the window's
steps, from the profiler trace. Includes the device work they wait for."""
from chipbench import trace


def read(run):
    steps = trace.step_times(run.trace) if run.trace else []
    if not steps:
        return None
    return sum(d for _, d in steps) / len(steps) / 1e6
