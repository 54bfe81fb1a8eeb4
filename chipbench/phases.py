"""The program's own spans in a traced window: the socket datapath's time
per scheduling round split into phases, and the chip's idle gaps put down
to the program span open in them.

The program opens ``jax.profiler.TraceAnnotation`` spans per round
(``repro.core.trace``): ``runtime.step`` around a scheduling round,
``stack.recv_batch`` and ``stack.forward_batch`` around the two datapath
calls, and inside them the phase spans of :data:`PHASES`. They land on the
trace's host plane beside the harness's own spans (``chipbench.trace``),
on the clock the device plane shares.

A phase's time is the **self time** of its spans: every instant of a step
goes to the innermost phase span open at that instant (the one that
started last), so a ``tls.keystream`` sweep inside ``rx.stage`` counts
once, as record layer, and the phases add up. Time is summed over the
``runtime.step`` spans that start inside the harness's ``window`` span and
divided by their number. A trace with no ``runtime.step`` there comes from
a program without the spans: the readers then find nothing (``None``).

    python3 -m chipbench.phases <trace dir>

run from the checkout's root, prints the per-step phase table and the ten
longest idle gaps of the trace under ``<trace dir>`` as one JSON object.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace

STEP = "runtime.step"
CALLS = ("stack.recv_batch", "stack.forward_batch")
#: metric stem -> the phase spans whose self time it sums
PHASES: Dict[str, Tuple[str, ...]] = {
    "rx_ctl": ("rx.admit", "rx.verdicts", "rx.scatter"),
    "record_layer": ("tls.rx_open", "tls.keystream"),
    "staging": ("rx.stage", "tx.stage", "pool.widen"),
    "device_call": ("pool.call", "pool.wait"),
    "tx_ctl": ("tx.prepare", "tx.transmit"),
}
PHASE_SPANS = tuple(n for names in PHASES.values() for n in names)
PROGRAM_SPANS = (STEP,) + CALLS + PHASE_SPANS

Span = Tuple[str, float, float]


def self_ns(spans: Sequence[Span], lo: float, hi: float) -> Dict[str, float]:
    """Each span name's self time inside ``[lo, hi]``: every instant goes
    to the innermost span open there (latest start; of two that start
    together, the one that ends first). Instants no span covers go
    nowhere."""
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in spans
              if s < hi and e > lo]
    cuts = sorted({t for _, s, e in inside for t in (s, e)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(s, -e, n) for n, s, e in inside if s <= a and e >= b]
        if open_:
            name = max(open_)[2]
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def window_steps(tr: dict) -> List[Tuple[float, float]]:
    """``(start, end)`` of each ``runtime.step`` span that starts inside
    the harness's ``window`` span."""
    bounds = trace.window_bounds(tr)
    if bounds is None:
        return []
    lo, hi = bounds
    return [(s, e) for _, s, e in trace.host_spans(tr, (STEP,))
            if lo <= s < hi]


def step_phases(tr: dict) -> List[Dict[str, float]]:
    """Per window step, the self time (ns) of each phase span name."""
    spans = trace.host_spans(tr, PHASE_SPANS)
    return [self_ns(spans, s, e) for s, e in window_steps(tr)]


def ms_per_round(tr: Optional[dict], names: Iterable[str]
                 ) -> Optional[float]:
    """Self time of the ``names`` spans per window step, in ms: ``None``
    where the trace has no program step, 0.0 where it has steps but none
    of these spans."""
    steps = step_phases(tr) if tr else []
    if not steps:
        return None
    names = tuple(names)
    return sum(p.get(n, 0.0) for p in steps for n in names) \
        / len(steps) / 1e6


def phase_table(tr: dict) -> List[Dict[str, float]]:
    """One row per window step, in ms: the step, the two datapath calls,
    each phase of :data:`PHASES`, and what in the calls no phase covers."""
    calls = trace.host_spans(tr, CALLS)
    rows = []
    for (s, e), ph in zip(window_steps(tr), step_phases(tr)):
        row = {"step": (e - s) / 1e6,
               "datapath": trace.busy_ns(
                   [(a, b) for _, a, b in calls], s, e) / 1e6}
        for stem, names in PHASES.items():
            row[stem] = sum(ph.get(n, 0.0) for n in names) / 1e6
        row["unattributed"] = row["datapath"] - sum(
            row[stem] for stem in PHASES)
        rows.append(row)
    return rows


def idle_gaps(tr: dict, top: int = 10) -> List[Dict[str, object]]:
    """The ``top`` longest stretches of the window in which the chip ran
    nothing, longest first; each with its length in ms and its time split
    by the innermost span open in it (program spans, else the harness's)."""
    bounds = trace.window_bounds(tr)
    planes = trace.device_planes(tr)
    if bounds is None or not planes:
        return []
    lo, hi = bounds
    busy = [iv for p in planes
            for iv in trace.op_intervals(trace.device_ops(p))]
    spans = trace.host_spans(tr, PROGRAM_SPANS + trace.SPANS)
    longest = sorted(trace.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
    out = []
    for s, e in longest[:top]:
        parts = sorted(self_ns(spans, s, e).items(), key=lambda kv: -kv[1])
        out.append({"ms": (e - s) / 1e6,
                    "in": [[n, ns / 1e6] for n, ns in parts]})
    return out


def report(tr: dict) -> dict:
    rows = phase_table(tr)
    mean = {k: sum(r[k] for r in rows) / len(rows) for k in rows[0]} \
        if rows else {}
    return {"steps": len(rows), "mean_ms": mean, "per_step_ms": rows,
            "idle_gaps": idle_gaps(tr)}


def main(argv: Sequence[str]) -> int:
    if len(argv) != 1:
        print("usage: python3 -m chipbench.phases <trace dir>",
              file=sys.stderr)
        return 2
    print(json.dumps(report(trace.load(Path(argv[0])))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
