"""Round staging per round: the self time of the program's ``rx.stage``
(the fused round's int32 operands and speculative TX keystreams),
``tx.stage`` (the miss gather's tables and keystreams) and ``pool.widen``
(the returned block widened to int64) spans per window step, from the
profiler trace."""
from chipbench import phases


def read(run):
    return phases.ms_per_round(run.trace, phases.PHASES["staging"])
