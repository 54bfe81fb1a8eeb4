"""Readings for the limits of the check that decides ``correct``: one
process runs a cell on the chip at its own size for each of several seeds
and prints, per seed, the numbers the check compares for the program and,
for the first ``--control-seeds`` seeds, for the control (the reference
with its tokens stored as signed int8 in the proxy's place).

    python3 chipbench/control.py --workload <cell> --seconds 3 \
        --seeds 1 2 3 ... [--control-seeds 3]

The benchmark's own runs (``run.py``) never read the control. One JSON
line per seed on standard output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import harness, spec
    from repro.common.compile_cache import use_compile_cache

    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    use_compile_cache(ROOT)
    cell = spec.cell(args.workload)
    harness.check_chip(cell)
    for n, seed in enumerate(args.seeds):
        line = harness.run(cell, seed, args.seconds, False,
                           t_start=time.perf_counter(),
                           out_dir=HERE / "out" / f"control-{seed}",
                           control=n < args.control_seeds)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": line["correct"], "attempted": line["attempted"],
            "failed": line["failed"],
            "program": {k: c["value"] for k, c in line["checks"].items()},
            "path": line["path"],
            "control": line.get("control"),
            "msgs_per_s": line["metrics"].get("msgs_per_s", {}).get("value"),
            "device": line["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
