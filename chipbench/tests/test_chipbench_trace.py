"""The reduction from a profiler trace to the per-layer metrics, checked on
small excerpts of traces taken on a TPU v5e (the first steps of a traced
window of each cell, committed under ``data/``) and on hand-made
intervals."""
import json

import pytest

from chipbench import spec, trace

DATA = spec.HERE / "tests" / "data"
ROOFLINE = spec.reader("fused_round_roofline").__globals__


def _excerpt(name):
    with open(DATA / f"trace_excerpt_{name}.json") as f:
        return json.load(f)


def test_merge_gaps_and_innermost_on_hand_made_intervals():
    iv = [(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)]
    assert trace.merge(iv, 0, 25) == [(0, 3), (5, 12), (20, 25)]
    assert trace.busy_ns(iv, 0, 25) == 3 + 7 + 5
    assert trace.gaps(iv, -1, 26) == [(-1, 0), (3, 5), (12, 20)]
    assert trace.gaps(iv, 0, 10) == [(3, 5)]
    assert trace.gaps([(0, 2)], 0, 10) == [(2, 10)]
    spans = [("window", 0, 100), ("step", 10, 50), ("recv_batch", 12, 20)]
    assert trace.innermost(spans, 15) == "recv_batch"
    assert trace.innermost(spans, 30) == "step"
    assert trace.innermost(spans, 70) == "window"
    assert trace.innermost(spans, 170) == "outside"


def test_short_names_of_hlo_instructions():
    kernel = ('%_fused_round_impl.1 = (s32[256,1,384]{2,1,0:T(1,128)S(1)}, '
              's32[4097,1,4096]{2,1,0:T(1,128)}) custom-call(s32[256]{0:T('
              '256)} %meta_len.1), custom_call_target="tpu_custom_call"')
    assert trace.short_op(kernel) == "%_fused_round_impl.1 tpu_custom_call"
    assert trace.short_op("%copy.7 = s32[2,1]{1,0:T(2,128)S(1)} copy("
                          "s32[2,1]{0,1:T(1,128)} %cond_hi.1)") == \
        "%copy.7 copy"
    assert trace.short_module("jit__fused_round_impl(6497137482155181611)") \
        == "jit__fused_round_impl"


@pytest.mark.parametrize("name,launches", [("bulk", 3), ("hwktls", 3)])
def test_reduction_on_a_chip_trace_excerpt(name, launches):
    tr = _excerpt(name)
    lo, hi = trace.window_bounds(tr)
    (plane,) = trace.device_planes(tr)
    ops = trace.device_ops(plane)
    # one core runs one operation at a time: the busy union is the plain
    # sum of the operations' durations, every one inside the window
    assert all(lo <= e[1] and e[1] + e[2] <= hi for e in ops)
    busy = trace.busy_ns(trace.op_intervals(ops), lo, hi)
    assert busy == pytest.approx(sum(e[2] for e in ops))
    assert 0 < busy < hi - lo
    idle = spec.reader("device_idle_pct")(_Run(tr))
    assert idle == pytest.approx(100 * (1 - busy / (hi - lo)))
    # every operation ran inside a program; the kernel is one custom call
    # per launch of the fused-round program
    attributed = trace.with_module(plane)
    assert all(module for module, _ in attributed)
    kernels = [e for module, e in attributed if "fused_round" in module
               and e[0].endswith("tpu_custom_call")]
    assert len(kernels) == launches
    assert ROOFLINE["kernel_ns"](tr, lo, hi) == pytest.approx(
        sum(e[2] for e in kernels))
    # per launch: a ~0.5 ms (hw-kTLS, 4 pages) to ~1.4 ms (bulk, 16 pages)
    per_launch = sum(e[2] for e in kernels) / launches
    assert 0.2e6 < per_launch < 3e6


def test_breakdown_names_ops_and_gaps():
    tr = _excerpt("bulk")
    lo, hi = trace.window_bounds(tr)
    b = trace.breakdown(tr, lo, hi)
    assert b["device_ops"][0][0] == \
        "jit__fused_round_impl/%_fused_round_impl.1 tpu_custom_call"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert {n for n, _ in b["idle_gaps"]} <= set(trace.SPANS) | {"outside"}


def test_step_times_split_scheduler_and_datapath():
    tr = _excerpt("bulk")
    steps = trace.step_times(tr)
    assert len(steps) == 3
    for step_ns, dp_ns in steps:
        assert 0 < dp_ns <= step_ns


class _Run:
    def __init__(self, tr):
        self.trace = tr
