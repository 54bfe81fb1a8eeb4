"""One run of one cell: set-up, warm-up, the timed window, the check
against the reference, and the result line.

The entry points (``run.py``, ``control.py``) look for the chip with
:func:`check_chip` first; the tests drive :func:`run` on the CPU with a
small configuration. ``run.py`` prints a result only where every round of
the window took the fused device round and nothing compiled inside the
window (:func:`finish`): either is a window off the path the cell times.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, Optional

from chipbench import reference, refcipher, spec
from chipbench import trace as tracing
from chipbench.loop import ClosedLoop, OpenLoop, Window
from chipbench.proxy import Proxy, build
from chipbench.traffic import Traffic

#: size of one token on the device stream (int32), for byte counters
TOKEN_BYTES = 4
#: how long after the window requests sent in it may still be forwarded
LATE_S = 60.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class CompileCounter:
    """Backend compiles (including those the persistent cache serves) and
    persistent-cache hits, counted through ``jax.monitoring``. JAX keeps
    its listeners for the life of the process, so there is one counter per
    process."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = CompileCounter()
        return cls._instance


@dataclasses.dataclass
class Run:
    """What the metric readers read (``metrics/<name>.py``)."""
    cell: spec.Cell
    traffic: Traffic
    window: Window
    setup_s: float
    xfer: Dict[str, int]           # pool.xfer growth over the window
    compiles_in_window: int
    device_kind: str
    trace: Optional[dict] = None   # see chipbench.trace

    @property
    def tls(self) -> bool:
        return bool(self.cell.config.get("tls"))


class GcClock:
    """Times the interpreter's full (generation 2) collections while it is
    registered: a pause of the whole process that lands inside one step."""

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.longest_s = 0.0
        self._t = 0.0

    def __call__(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
            return
        d = time.perf_counter() - self._t
        self.count += 1
        self.total_s += d
        self.longest_s = max(self.longest_s, d)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _wrap_datapath(stack) -> None:
    """Span the stack's two datapath calls from outside the program."""
    for name in ("recv_batch", "forward_batch"):
        fn = getattr(stack, name)

        def wrapped(*a, _fn=fn, _name=name, **kw):
            with _annotate(_name):
                return _fn(*a, **kw)

        setattr(stack, name, wrapped)


def _keys(proxy: Proxy, secret: bytes) -> Callable[[int, int], bytes]:
    return lambda i, u: refcipher.session_key(
        secret, b"tls-tx", proxy.upstreams[i][u].fileno())


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_chip(cell: spec.Cell) -> None:
    """Raise :class:`NoChip` unless JAX finds the TPU chips ``cell`` needs.
    The entry points call it before :func:`run`; the tests drive ``run``
    on the CPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"finds {len(devices)} {dev.platform} ({dev.device_kind})")


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, out_dir: Path, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line. ``control=True``
    also reads the control (the int8 reference in the proxy's place) under
    the line's ``control`` key; the benchmark's own runs never do."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    counter = CompileCounter.get()
    marks = {"start": time.perf_counter() - t_start}
    secret = f"chipbench-{seed}".encode()
    traffic = Traffic(cell.mix, cell.config, seed, seconds)
    marks["traffic"] = time.perf_counter() - t_start
    proxy = build(cell.config, traffic.connections, secret)
    marks["proxy"] = time.perf_counter() - t_start
    tls = bool(cell.config.get("tls"))
    if tls:
        traffic.seal([refcipher.session_key(secret, b"tls-rx", c.fileno())
                      for c in proxy.clients])
    marks["seal"] = time.perf_counter() - t_start
    loop = (ClosedLoop if traffic.arrivals is None else OpenLoop)(
        proxy, traffic)
    c_warm = counter.compiles
    loop.warmup(int(cell.mix["warmup_rounds"]))
    proxy.stack.pool.block_until_ready()
    xfer0 = dict(proxy.stack.pool.xfer)
    fallbacks0 = proxy.stack.counters.device_fallbacks
    c0 = counter.compiles
    setup_s = time.perf_counter() - t_start
    marks["warmup"] = setup_s
    _log(f"set-up {setup_s:.3f} s (" + ", ".join(
        f"{k} until {v:.3f} s" for k, v in marks.items())
        + f"): {traffic.connections} connections, {traffic.rounds} requests "
        f"each made, compiles {c0} ({c0 - c_warm} in warm-up), "
        f"persistent-cache hits {counter.cache_hits}")

    trace_dir = out_dir / "trace"
    if traced:
        # spans only from here on: set-up is the same with and without them
        _wrap_datapath(proxy.stack)
        loop.annotate = _annotate
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing.start(trace_dir)
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    try:
        with (_annotate("window") if traced else contextlib.nullcontext()):
            w = loop.window(seconds)
    finally:
        gc.callbacks.remove(gc_clock)
    compiles_in_window = counter.compiles - c0
    xfer = {k: v - xfer0.get(k, 0) for k, v in proxy.stack.pool.xfer.items()}
    path = {"steps": len(w.steps),
            "fused_rounds": xfer.get("fused_rounds", 0),
            "device_fallbacks":
                proxy.stack.counters.device_fallbacks - fallbacks0,
            "compiles_in_window": compiles_in_window}
    trace = None
    if traced:
        tracing.stop()
        trace = tracing.load(trace_dir)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    _log(f"window {w.seconds:.6f} s: {w.completed} of {w.attempted} "
         f"requests forwarded in {len(w.steps)} steps; client work "
         f"{w.client_s:.6f} s; compiles {compiles_in_window}; fused rounds "
         f"{path['fused_rounds']}; device rounds "
         f"{xfer.get('device_rounds', 0)}; device fallbacks "
         f"{path['device_fallbacks']}; full garbage collections "
         f"{gc_clock.count}, {gc_clock.total_s * 1e3:.1f} ms in all, "
         f"longest {gc_clock.longest_s * 1e3:.1f} ms")
    _log("step ms: " + " ".join(f"{(e - s) * 1e3:.1f}" for s, e in w.steps))
    if isinstance(loop, OpenLoop):
        a = cell.mix["arrival"]
        _log(f"open loop: offered {w.attempted / w.seconds:.3f} msgs/s in "
             f"the window, schedule {a['rate_msgs_per_s']} msgs/s (on "
             f"{a['on_s']} s, off {a['off_s']} s)")
    if w.ran_out:
        _log(f"the requests set-up made ran out after {w.seconds:.3f} s of "
             f"the window's {seconds} s and closed it: raise the mix's "
             + ("max_rounds_per_s" if traffic.arrivals is None
                else "warmup_s, or the schedule's margin"))

    # -- after the window: late requests, shutdown, the check -----------------
    t_drain, c_drain, n_late = time.perf_counter(), counter.compiles, \
        len(w.latencies_s)
    loop.drain(w, LATE_S)
    _log(f"after the window: {len(w.latencies_s) - n_late} late requests "
         f"forwarded in {time.perf_counter() - t_drain:.3f} s, compiles "
         f"{counter.compiles - c_drain}, {loop.outstanding()} left "
         f"outstanding")
    proxy.runtime.shutdown()
    keys = _keys(proxy, secret) if tls else None
    numbers, failed = reference.check(
        traffic, cell.config, loop.sent, proxy.received, keys,
        leaked_pages=proxy.pages_in_use())
    correct = all(numbers[k] <= lim for k, lim in reference.LIMITS.items())

    r = Run(cell, traffic, w, setup_s, xfer, compiles_in_window,
            dev.device_kind, trace)
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": w.attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if traced:
        busy, span = tracing.device_busy(trace) or (0.0, 0.0)
        device["busy_s"] = busy / 1e9
        device["window_s"] = span / 1e9
        lo, hi = tracing.window_bounds(trace) or (0.0, 0.0)
        line["breakdown"] = tracing.breakdown(trace, lo, hi)
    if control:
        line["control"] = reference.check(
            traffic, cell.config, loop.sent,
            reference.control_wires(traffic, cell.config, loop.sent, keys),
            keys)[0]
    line["path"] = path
    line["checks"] = {k: {"value": numbers[k], "limit": lim}
                      for k, lim in reference.LIMITS.items()}
    return line


def off_path(path: dict) -> Optional[str]:
    """Why the window is not the path the cell times, or None where it
    is: nothing compiled inside it, one fused round per step, and no round
    bounced to the program's host or multi-pass paths."""
    if path["compiles_in_window"]:
        return f"{path['compiles_in_window']} compiles in the window"
    if path["device_fallbacks"]:
        return f"{path['device_fallbacks']} rounds fell back off the device"
    if path["fused_rounds"] != path["steps"]:
        return (f"{path['fused_rounds']} fused rounds in "
                f"{path['steps']} steps")
    return None


def emit(line: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error; then the result as the last line of standard output."""
    _log(f"correct = {line['correct']}")
    for k, c in line["checks"].items():
        _log(f"check {k} = {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)


def finish(line: dict) -> int:
    """The exit code of a run: 0 with the result emitted; non-zero, with
    no result, where the window left the fused device round or compiled."""
    why = off_path(line["path"])
    if why is None:
        emit(line)
        return 0
    _log(f"chipbench: the window left the fused device round or compiled "
         f"({why}); no result")
    return 4
