"""DMA-vs-compute overlap profile for the one-kernel scheduling round.

Runs :mod:`repro.kernels.dma_profile` at a representative fused-round
shape and reports, per candidate staging depth (blocked / double / quad
buffered), the measured rounds/s — plus the transfer/compute
decomposition of one staged round and the overlap ratio the DMA ring
actually achieved. The final row is the depth :func:`auto_buffer_depth`
selects (what ``DevicePool.fused_buffers`` should be pinned to on this
box); on the host interpret backend the async copies execute eagerly, so
the profile documents the *measurement*, not a predetermined winner.

The depth rows carry ``rounds_per_s`` so ``scripts/check_bench_trend.py``
gates them like every other throughput series.
"""
from __future__ import annotations

from benchmarks.common import csv, is_smoke, record
from repro.kernels.dma_profile import (
    DEFAULT_DEPTHS,
    auto_buffer_depth,
    dma_compute_profile,
    profile_fused_depths,
)


def main() -> None:
    import jax

    smoke = is_smoke()
    # one lane-aligned shape everywhere: the compiled kernel on a TPU, the
    # interpreter elsewhere — never the interpreter timed on a chip
    shape = dict(b=8, page=128, pps=4, meta_max=128,
                 interpret=jax.default_backend() != "tpu")
    iters = 3 if smoke else 8
    warmup = 1 if smoke else 2

    profs = profile_fused_depths(iters=iters, warmup=warmup, **shape)
    for d in DEFAULT_DEPTHS:
        p = profs[d]
        csv(f"dma_overlap_depth{d}", p.round_s * 1e6,
            f"rounds_per_s={p.rounds_per_s:.0f} n_buffers={d}")
        record(f"dma_overlap_depth{d}_series", n_buffers=d,
               rounds_per_s=p.rounds_per_s, round_us=p.round_s * 1e6,
               **shape)

    decomp = dma_compute_profile(iters=iters, warmup=warmup, n_buffers=2,
                                 **shape)
    csv("dma_overlap_decomposition", decomp["fused_s"] * 1e6,
        f"transfer_us={decomp['transfer_s'] * 1e6:.1f} "
        f"compute_us={decomp['compute_s'] * 1e6:.1f} "
        f"overlap_ratio={decomp['overlap_ratio']:.2f}")
    record("dma_overlap_decomposition_series",
           overlap_ratio=decomp["overlap_ratio"],
           transfer_us=decomp["transfer_s"] * 1e6,
           compute_us=decomp["compute_s"] * 1e6,
           fused_us=decomp["fused_s"] * 1e6, **shape)

    depth = auto_buffer_depth(interpret=shape["interpret"], profiles=profs)
    csv("dma_overlap_selected", 0.0,
        f"auto_depth={depth} "
        f"candidates={'/'.join(str(d) for d in DEFAULT_DEPTHS)}")
    record("dma_overlap_selected_series", auto_depth=depth)


if __name__ == "__main__":
    main()
