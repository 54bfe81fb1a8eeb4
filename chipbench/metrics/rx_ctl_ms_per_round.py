"""RX control plane per round: the self time of the program's
``rx.admit``, ``rx.verdicts`` and ``rx.scatter`` spans (admission scan,
RX state machines, allocation, metadata copy, policy verdicts, VPI
registration) per window step, from the profiler trace."""
from chipbench import phases


def read(run):
    return phases.ms_per_round(run.trace, phases.PHASES["rx_ctl"])
