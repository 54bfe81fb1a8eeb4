"""The share of the fused round's speculative TX gathers that the forward
used: ``pool.xfer`` ``tx_spec_hits`` over hits plus ``tx_spec_misses``
(sends whose speculation failed and were gathered again) over the window.
None where nothing was speculated, or where the program does not count
misses."""


def read(run):
    if "tx_spec_misses" not in run.xfer:
        return None
    hits = run.xfer.get("tx_spec_hits", 0)
    tried = hits + run.xfer["tx_spec_misses"]
    return 100.0 * hits / tried if tried else None
