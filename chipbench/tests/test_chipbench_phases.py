"""The program's spans reduced to per-round phase times
(``chipbench/phases.py`` and the readers that use it): self time on
hand-made spans, and the shared clock on an excerpt of a trace taken on a
TPU v5e with the program's spans (the first steps of a traced window of
``l7route-bulk-c256``, committed under ``data/``)."""
import json

import pytest

from chipbench import phases, spec, trace

DATA = spec.HERE / "tests" / "data"
PHASE_METRICS = [f"{stem}_ms_per_round" for stem in phases.PHASES]
MS = 1e6     # ns per ms: the hand-made spans below are given in ms


def _trace(spans, ops=()):
    """A trace with ``spans`` (name, start ms, end ms) on one host line and
    ``ops`` (name, start ms, end ms) on one chip's ``XLA Ops`` line."""
    host = [[n, s * MS, (e - s) * MS] for n, s, e in spans]
    dev = [[n, s * MS, (e - s) * MS] for n, s, e in ops]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.OPS_LINE, "events": dev},
            {"name": trace.MODULES_LINE, "events": dev}]}]}


class _Run:
    def __init__(self, tr=None, xfer=None):
        self.trace = tr
        self.xfer = xfer or {}


def _read(name, tr):
    return spec.reader(name)(_Run(tr))


#: one fused bulk round: every phase once, a keystream sweep inside staging
ROUND = [
    ("window", 0, 1000),
    ("runtime.step", 100, 400),
    ("stack.recv_batch", 110, 300),
    ("rx.admit", 110, 150),
    ("rx.stage", 150, 250),
    ("tls.keystream", 160, 200),
    ("pool.call", 250, 260),
    ("pool.wait", 260, 280),
    ("pool.widen", 280, 290),
    ("rx.scatter", 290, 300),
    ("stack.forward_batch", 300, 395),
    ("tx.prepare", 300, 320),
    ("tx.transmit", 330, 390),
]


def test_self_time_takes_nested_spans_out_once():
    tr = _trace(ROUND)
    got = {m: _read(m, tr) for m in PHASE_METRICS}
    assert got == {"rx_ctl_ms_per_round": 50.0,          # 40 + 10
                   "record_layer_ms_per_round": 40.0,
                   "staging_ms_per_round": 70.0,          # 100 - 40 + 10
                   "device_call_ms_per_round": 30.0,
                   "tx_ctl_ms_per_round": 80.0}
    # the phases add up: no instant counts twice
    row, = phases.phase_table(tr)
    assert row["datapath"] == 285.0
    assert sum(got.values()) == 270.0
    assert row["unattributed"] == pytest.approx(15.0)


def test_overlapping_spans_split_their_overlap():
    """Spans that overlap without nesting (never on one thread, but the
    reduction must not count the overlap twice): the later one takes it,
    and of two that start together the one that ends first."""
    tr = _trace([("window", 0, 100), ("runtime.step", 0, 50),
                 ("rx.admit", 0, 10), ("rx.scatter", 5, 15),
                 ("tx.prepare", 12, 20), ("tx.transmit", 12, 30)])
    assert phases.self_ns(trace.host_spans(tr, phases.PHASE_SPANS),
                          0, 50 * MS) == {"rx.admit": 5 * MS,
                                          "rx.scatter": 7 * MS,
                                          "tx.prepare": 8 * MS,
                                          "tx.transmit": 10 * MS}
    assert _read("rx_ctl_ms_per_round", tr) == 12.0
    assert _read("tx_ctl_ms_per_round", tr) == 18.0


def test_only_steps_that_start_inside_the_window_count():
    spans = [("window", 100, 500),
             ("runtime.step", 0, 90), ("rx.admit", 10, 80),
             ("runtime.step", 110, 210), ("rx.admit", 120, 140),
             ("runtime.step", 300, 400), ("rx.admit", 300, 360),
             ("runtime.step", 600, 700), ("rx.admit", 600, 700)]
    assert _read("rx_ctl_ms_per_round", _trace(spans)) == 40.0
    # a span cut by its step's end counts only inside the step
    spans += [("runtime.step", 450, 520), ("rx.scatter", 500, 560)]
    assert _read("rx_ctl_ms_per_round", _trace(spans)) == \
        pytest.approx((20 + 60 + 20) / 3)


def test_no_program_step_reads_nothing_and_no_phase_reads_zero():
    harness_only = _trace([("window", 0, 100), ("step", 0, 50),
                           ("recv_batch", 0, 20), ("rx.admit", 1, 5)])
    for m in PHASE_METRICS:
        assert _read(m, harness_only) is None
        assert _read(m, None) is None
    plaintext = _trace([r for r in ROUND if not r[0].startswith("tls.")])
    assert _read("record_layer_ms_per_round", plaintext) == 0.0
    assert _read("staging_ms_per_round", plaintext) == 110.0


def test_tx_spec_hit_pct_reads_the_two_counters():
    read = spec.reader("tx_spec_hit_pct")
    assert read(_Run(xfer={"tx_spec_hits": 3, "tx_spec_misses": 1})) == 75.0
    assert read(_Run(xfer={"tx_spec_hits": 5, "tx_spec_misses": 0})) == 100.0
    assert read(_Run(xfer={"tx_spec_hits": 0, "tx_spec_misses": 0})) is None
    # a program that does not count misses: nothing to read
    assert read(_Run(xfer={"tx_spec_hits": 5})) is None


def test_idle_gaps_are_split_by_the_innermost_span():
    tr = _trace(ROUND + [("client", 400, 420)],
                ops=[("%k tpu_custom_call", 255, 275)])
    gaps = phases.idle_gaps(tr)
    assert [g["ms"] for g in gaps] == [725.0, 255.0]
    tail, head = gaps
    assert head["in"][:3] == [["window", 100.0], ["rx.stage", 60.0],
                              ["rx.admit", 40.0]]
    assert dict(tail["in"])["tx.transmit"] == 60.0
    assert dict(tail["in"])["client"] == 20.0
    assert sum(ms for _, ms in tail["in"]) == pytest.approx(725.0)


# -- a trace taken on the chip ----------------------------------------------
def _excerpt():
    with open(DATA / "trace_excerpt_spans_bulk.json") as f:
        return json.load(f)


def test_chip_excerpt_kernels_sit_inside_their_device_call():
    """The shared clock: each fused-round kernel runs inside its own
    step's ``pool.call``..``pool.wait`` of ``stack.recv_batch``."""
    tr = _excerpt()
    (plane,) = trace.device_planes(tr)
    kernels = [e for module, e in trace.with_module(plane)
               if "fused_round" in module
               and e[0].endswith("tpu_custom_call")]
    steps = phases.window_steps(tr)
    assert len(kernels) == len(steps) == 3
    spans = trace.host_spans(tr, phases.PROGRAM_SPANS)
    for (s, e), k in zip(steps, kernels):
        (rs, re_), = [(a, b) for n, a, b in spans
                      if n == "stack.recv_batch" and s <= a < e]
        call = min(a for n, a, b in spans
                   if n == "pool.call" and rs <= a < re_)
        wait = min(b for n, a, b in spans
                   if n == "pool.wait" and rs <= a < re_)
        assert call <= k[1] and k[1] + k[2] <= wait


def test_chip_excerpt_phases_cover_the_datapath():
    tr = _excerpt()
    got = {m: _read(m, tr) for m in PHASE_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["record_layer_ms_per_round"] == 0.0
    datapath = spec.reader("datapath_ms_per_round")(_Run(tr))
    assert sum(got.values()) >= 0.9 * datapath
    for row in phases.phase_table(tr):
        assert row["unattributed"] >= 0
