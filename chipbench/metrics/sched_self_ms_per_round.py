"""Scheduler self time per round (``ProxyRuntime.step``): each step's span
minus the ``recv_batch``/``forward_batch`` spans inside it, averaged over
the window's steps, from the profiler trace."""
from chipbench import trace


def read(run):
    steps = trace.step_times(run.trace) if run.trace else []
    if not steps:
        return None
    return sum(s - d for s, d in steps) / len(steps) / 1e6
