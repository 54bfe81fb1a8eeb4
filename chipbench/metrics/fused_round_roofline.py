"""The fused round's share of its HBM roofline: the least time its work
needs (the bytes of ``chipbench.kernel_bytes.fused_round_bytes`` for every
request the window forwarded, over the chip's peak HBM bandwidth) over the
fused-round kernel's device time in the window, from the trace.

The kernel has no name of its own: it is the Pallas custom call
(``tpu_custom_call``) inside the jitted fused-round program, whose
``XLA Modules`` event a TPU v5e trace names ``jit__fused_round_impl``.
"""
from chipbench import spec, trace
from chipbench.kernel_bytes import fused_round_bytes

PROGRAM = "fused_round"


def kernel_ns(tr, lo: float, hi: float) -> float:
    """Device time of the fused-round kernel inside ``[lo, hi]``."""
    return sum(min(e[1] + e[2], hi) - max(e[1], lo)
               for p in trace.device_planes(tr)
               for module, e in trace.with_module(p)
               if PROGRAM in module and e[0].endswith(" tpu_custom_call")
               and e[1] < hi and e[1] + e[2] > lo)


def read(run):
    bounds = trace.window_bounds(run.trace) if run.trace else None
    if bounds is None:
        return None
    ns = kernel_ns(run.trace, *bounds)
    if ns <= 0:
        return None
    t = run.traffic
    nbytes = fused_round_bytes(
        ((int(t.head_len[k, i]), int(t.body_len[k, i]))
         for step in run.window.forwarded for i, k in step), run.tls)
    least_s = nbytes / spec.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
