"""TX path per round: the self time of the program's ``tx.prepare``
(message peeks and the speculation check) and ``tx.transmit`` (each
send's transmit) spans per window step, from the profiler trace."""
from chipbench import phases


def read(run):
    return phases.ms_per_round(run.trace, phases.PHASES["tx_ctl"])
