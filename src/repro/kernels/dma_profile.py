"""DMA-vs-compute profiler for the one-kernel scheduling round.

The fused round's ``n_buffers >= 2`` mode stages each [1, S] stream row
(and its RX keystream) into a VMEM ring by an async copy issued one row
ahead of compute — classic double/quad buffering. Whether that pipelining
*wins* depends on the DMA:compute ratio of the deployment shape: when the
per-row copy is slower than the per-row compute, deeper rings hide more
of it; when compute dominates, the staging is pure overhead and the
blocked layout (``n_buffers == 0``) is faster.

This module measures that trade-off empirically and picks the depth:

* :func:`profile_fused_depths` — wall-clock the fused round at each
  candidate depth on a representative operand bundle (the same
  ``testing.fused_round_case`` shapes the parity gate runs), warmup
  excluded so compile time never biases the pick.
* :func:`dma_compute_profile` — decompose one round into its *transfer*
  leg (host→device staging of the stream operands) and its *compute* leg
  (the round with operands already resident), and report the measured
  overlap ratio ``(transfer + compute - fused) / min(transfer, compute)``
  — 1.0 means the staged round fully hides the cheaper leg, 0 means the
  legs serialized.
* :func:`auto_buffer_depth` — the selection policy: fastest measured
  depth, with the ``LIBRA_FUSED_BUFFERS`` env var as an explicit
  override (set it to pin a depth, e.g. on a box where profiling at
  import time is unwanted).

On the host (interpret-mode) backend the async copies execute eagerly,
so staging usually loses and the profiler correctly selects depth 0 —
the point is that the *selection is measured, not assumed*, and the same
harness picks 2/4 on hardware where the DMA engines are real. Results
feed ``benchmarks/bench_dma_overlap.py`` (BENCH_dma_overlap.json rows).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

#: candidate ring depths: blocked, double-, quad-buffered
DEFAULT_DEPTHS: Tuple[int, ...] = (0, 2, 4)


@dataclass
class DepthProfile:
    """Measured cost of one fused round at one staging depth."""
    depth: int
    round_s: float        # best-of-iters wall time per round
    rounds_per_s: float


def _case(b: int, page: int, pps: int, meta_max: int, seed: int):
    from repro.kernels.testing import fused_round_case
    rng = np.random.default_rng(seed)
    return fused_round_case(rng, b=b, page=page, pps=pps, meta_max=meta_max)


def _run(case: Dict, *, meta_max: int, n_buffers: int, interpret: bool):
    from repro.kernels.selective_copy import fused_round
    got = fused_round(
        case["stream"], case["meta_len"], case["total_len"], case["pool"],
        case["tables"], meta_max=meta_max, interpret=interpret,
        n_buffers=n_buffers, keystream=case["keystream"],
        tx_keystream=case["tx_keystream"], cond_off=case["cond_off"],
        cond_lo=case["cond_lo"], cond_hi=case["cond_hi"],
        live=case["live"], meta_ks=case["meta_ks"])
    for g in got:
        if g is not None:
            np.asarray(g)          # block until the round is done
    return got


def profile_fused_depths(*, interpret: bool, b: int = 8, page: int = 16,
                         pps: int = 4, meta_max: int = 16,
                         depths: Sequence[int] = DEFAULT_DEPTHS,
                         iters: int = 5, warmup: int = 2,
                         seed: int = 0) -> Dict[int, DepthProfile]:
    """Wall-clock the full-operand fused round per candidate depth.
    ``interpret`` has no default: the caller states whether it times the
    compiled kernel (a TPU, lane-multiple ``page``/``meta_max``) or the
    interpreter.

    Warmup rounds absorb tracing/compile; the reported figure is the
    best of ``iters`` timed rounds (min is the right statistic for a
    deterministic kernel under scheduler noise)."""
    case = _case(b, page, pps, meta_max, seed)
    out: Dict[int, DepthProfile] = {}
    for d in depths:
        for _ in range(warmup):
            _run(case, meta_max=meta_max, n_buffers=d, interpret=interpret)
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            _run(case, meta_max=meta_max, n_buffers=d, interpret=interpret)
            best = min(best, time.perf_counter() - t0)
        out[d] = DepthProfile(depth=d, round_s=best,
                              rounds_per_s=1.0 / max(best, 1e-12))
    return out


def dma_compute_profile(*, interpret: bool, b: int = 8, page: int = 16,
                        pps: int = 4, meta_max: int = 16, iters: int = 5,
                        warmup: int = 2, n_buffers: int = 2,
                        seed: int = 0) -> Dict[str, float]:
    """Decompose the staged round into transfer vs compute legs.

    * ``transfer_s`` — host→device placement of the stream + RX keystream
      operands (the bytes the DMA ring stages row-by-row inside the
      kernel), measured as a standalone device_put sweep.
    * ``compute_s`` — the blocked-layout round with every operand already
      device-resident: pure kernel work, no staging.
    * ``fused_s``   — the staged (``n_buffers``) round end to end.
    * ``overlap_ratio`` — ``(transfer_s + compute_s - fused_s) /
      min(transfer_s, compute_s)``, clamped to [0, 1]: the fraction of
      the cheaper leg the pipeline actually hid.
    """
    import jax

    case = _case(b, page, pps, meta_max, seed)

    def _transfer():
        ops = [jax.device_put(np.asarray(case["stream"])),
               jax.device_put(np.asarray(case["keystream"]))]
        for o in ops:
            o.block_until_ready()
        return ops

    def _best(fn) -> float:
        for _ in range(warmup):
            fn()
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    transfer_s = _best(_transfer)
    resident = dict(case)
    for k in ("stream", "keystream", "pool", "tx_keystream"):
        resident[k] = jax.device_put(np.asarray(case[k]))
    compute_s = _best(lambda: _run(resident, meta_max=meta_max, n_buffers=0,
                                   interpret=interpret))
    fused_s = _best(lambda: _run(case, meta_max=meta_max,
                                 n_buffers=n_buffers, interpret=interpret))
    hidden = transfer_s + compute_s - fused_s
    overlap = hidden / max(min(transfer_s, compute_s), 1e-12)
    return {"transfer_s": transfer_s, "compute_s": compute_s,
            "fused_s": fused_s,
            "overlap_ratio": float(np.clip(overlap, 0.0, 1.0))}


def auto_buffer_depth(*, interpret: bool, b: int = 8, page: int = 16,
                      pps: int = 4, meta_max: int = 16,
                      depths: Sequence[int] = DEFAULT_DEPTHS,
                      iters: int = 3, warmup: int = 1, seed: int = 0,
                      profiles: Optional[Dict[int, DepthProfile]] = None,
                      ) -> int:
    """The staging depth the fused datapath should run with.

    ``LIBRA_FUSED_BUFFERS`` overrides (0 disables staging, >= 2 pins a
    ring depth); otherwise the fastest measured depth wins. Pass
    ``profiles`` to reuse an existing :func:`profile_fused_depths` sweep
    instead of re-measuring."""
    env = os.environ.get("LIBRA_FUSED_BUFFERS", "")
    if env:
        depth = int(env)
        assert depth == 0 or depth >= 2, f"LIBRA_FUSED_BUFFERS={depth}"
        return depth
    if profiles is None:
        profiles = profile_fused_depths(
            b=b, page=page, pps=pps, meta_max=meta_max, depths=depths,
            iters=iters, warmup=warmup, interpret=interpret, seed=seed)
    return min(profiles.values(), key=lambda p: p.round_s).depth
