"""Record layer per round: the self time of the program's ``tls.rx_open``
(decrypt and tag check of the round's records) and ``tls.keystream`` (each
batched keystream sweep) spans per window step, from the profiler trace;
0.0 in a plaintext cell."""
from chipbench import phases


def read(run):
    return phases.ms_per_round(run.trace, phases.PHASES["record_layer"])
