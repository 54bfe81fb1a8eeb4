"""Run one cell of the chip benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json`` at the checkout's root. Set-up builds the proxy and the
seeded traffic and serves the warm-up rounds (every shape the window uses
compiles there, into JAX's persistent cache at ``<checkout>/.jax_cache``);
then the mix's loop, closed or open (``chipbench/loop.py``), runs for
``--seconds`` and every request is checked against the plain reference.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window. The last line of
standard output is one JSON object; the numbers the check compared, each
beside its limit, are the last lines of standard error. Exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for, where a round of the window left the fused device round, or
where anything compiled inside the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import harness, spec

    try:
        cell = spec.cell(args.workload)
    except (FileNotFoundError, KeyError, ValueError) as e:
        print(f"chipbench: no cell {args.workload!r}: {e}", file=sys.stderr)
        return 2
    # the compile cache lives in this checkout, whatever the environment says
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        from repro.common.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chipbench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    use_compile_cache(ROOT)
    out = HERE / "out" / f"{args.workload}-{args.seed}"
    try:
        harness.check_chip(cell)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    line = harness.run(cell, args.seed & ((1 << 63) - 1), args.seconds,
                       bool(args.trace), t_start=T_START, out_dir=out)
    return harness.finish(line)


if __name__ == "__main__":
    sys.exit(main())
