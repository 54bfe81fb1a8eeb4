"""Resident pool device calls per round: the self time of the program's
``pool.call`` (row upload and dispatch of a jitted round) and
``pool.wait`` (waiting for the kernel and the device-to-host transfer of
its output) spans per window step, from the profiler trace."""
from chipbench import phases


def read(run):
    return phases.ms_per_round(run.trace, phases.PHASES["device_call"])
