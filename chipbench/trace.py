"""Profiler traces: taking one around the window, reading it into plain
lists, and the reductions every per-layer reader shares.

A trace is read into ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``, the form the committed test
excerpt has too. Host planes are named ``/host:...``; a chip's plane
``/device:TPU:<n>`` (as a TPU v5e's trace names it), whose ``XLA Modules``
line holds one event per jitted program run (``jit_<function>``) and whose
``XLA Ops`` line one per operation inside it. The profiler names an
operation by its whole HLO instruction text; reading keeps
``"<instruction> <opcode>"`` of it, with ``tpu_custom_call`` as the opcode
of a Pallas kernel (``%_fused_round_impl.1 tpu_custom_call``), and drops
the hash from a program's name. The harness's own spans (``window``,
``step``, ``recv_batch``, ``forward_batch``, ``client``) are
``jax.profiler.TraceAnnotation`` events on a host line, on the clock the
device events share.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPANS = ("window", "step", "recv_batch", "forward_batch", "client")

Interval = Tuple[float, float]


def start(log_dir: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # no per-Python-call events
    opts.host_tracer_level = 1       # annotations and runtime calls only
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(log_dir: Path) -> dict:
    """The newest trace under ``log_dir``, as plain lists."""
    from jax.profiler import ProfileData

    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(paths[-1]))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            name = {OPS_LINE: short_op, MODULES_LINE: short_module}.get(
                line.name) if DEVICE_PLANE.match(plane.name) else None
            lines.append({"name": line.name, "events": [
                [name(e.name) if name else e.name, float(e.start_ns),
                 float(e.duration_ns)] for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_op(text: str) -> str:
    """``"%name = shape opcode(operands), attrs"`` -> ``"%name opcode"``,
    with ``tpu_custom_call`` for a Pallas kernel's custom call."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    if rest.startswith("("):             # a tuple shape: skip its parens
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:                                # one shape: no spaces inside
        rest = rest.partition(" ")[2]
    op = rest.lstrip().split("(", 1)[0]
    if op == "custom-call" and 'custom_call_target="tpu_custom_call"' in text:
        op = "tpu_custom_call"
    return f"{head} {op}"


def short_module(text: str) -> str:
    """``"jit_f(1234)"`` -> ``"jit_f"``."""
    return text.split("(", 1)[0]


# -- reductions ---------------------------------------------------------------
def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def device_ops(plane: dict) -> List[list]:
    """Every operation the chip ran: ``[name, start_ns, dur_ns]``."""
    return [e for line in plane["lines"] if line["name"] == OPS_LINE
            for e in line["events"]]


def with_module(plane: dict) -> List[Tuple[str, list]]:
    """Each operation of ``plane`` with the program it ran in (the
    ``XLA Modules`` event that holds its start; ``""`` for none)."""
    mods = sorted((e for line in plane["lines"]
                   if line["name"] == MODULES_LINE for e in line["events"]),
                  key=lambda e: e[1])
    starts = [m[1] for m in mods]
    out = []
    for e in device_ops(plane):
        j = bisect.bisect_right(starts, e[1]) - 1
        inside = j >= 0 and e[1] <= mods[j][1] + mods[j][2]
        out.append((mods[j][0] if inside else "", e))
    return out


def host_spans(trace: dict, names: Iterable[str] = SPANS
               ) -> List[Tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of the harness's spans, by start."""
    want = set(names)
    out = [(e[0], e[1], e[1] + e[2]) for p in trace["planes"]
           if p["name"].startswith("/host:") for line in p["lines"]
           for e in line["events"] if e[0] in want]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def window_bounds(trace: dict) -> Optional[Interval]:
    w = host_spans(trace, ("window",))
    return (w[0][1], w[0][2]) if w else None


def device_busy(trace: dict) -> Optional[Tuple[float, float]]:
    """``(busy_ns, window_ns)`` of the traced window: the union of the
    chip's operation intervals inside it, averaged over the chips."""
    bounds = window_bounds(trace)
    planes = device_planes(trace)
    if bounds is None or not planes:
        return None
    lo, hi = bounds
    busy = sum(busy_ns(op_intervals(device_ops(p)), lo, hi) for p in planes)
    return busy / len(planes), hi - lo


def merge(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as disjoint
    sorted intervals."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The idle stretches of ``[lo, hi]`` between merged intervals."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """The name of the latest-starting span that holds ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside"


def op_intervals(ops: Sequence[list]) -> List[Interval]:
    return [(e[1], e[1] + e[2]) for e in ops]


def breakdown(trace: dict, lo: float, hi: float, top: int = 10
              ) -> Dict[str, list]:
    """The device operations that took the most time in ``[lo, hi]``, and
    the longest idle gaps, each named by the harness span open there."""
    spans = host_spans(trace)
    per_op: Dict[str, float] = {}
    intervals: List[Interval] = []
    for plane in device_planes(trace):
        for module, e in with_module(plane):
            s, t = max(e[1], lo), min(e[1] + e[2], hi)
            if t > s:
                key = f"{module}/{e[0]}"
                per_op[key] = per_op.get(key, 0.0) + (t - s) / 1e9
                intervals.append((e[1], e[1] + e[2]))
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(intervals, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[innermost(spans, (s + e) / 2), (e - s) / 1e9]
                          for s, e in idle]}


def step_times(trace: dict) -> List[Tuple[float, float]]:
    """``(step_ns, datapath_ns)`` of each step in the window: the step's
    span and the part of it its ``recv_batch``/``forward_batch`` spans
    cover."""
    bounds = window_bounds(trace)
    if bounds is None:
        return []
    lo, hi = bounds
    spans = host_spans(trace, ("step", "recv_batch", "forward_batch"))
    steps = [(s, e) for n, s, e in spans if n == "step" and lo <= s < hi]
    dp = [(s, e) for n, s, e in spans if n != "step"]
    return [(e - s, busy_ns(dp, s, e)) for s, e in steps]
