#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python benchmarks/chip/run.py --workload vgg16.train_swap --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout that holds ``BENCHMARK.json`` and ``src/``.
One process: device check (a TPU, or a non-zero exit with no result),
set-up and warm-up, the measured window, the comparison with the plain
reference, then one JSON object as the last line of standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiled window.  The
numbers that decide ``correct`` are printed, each beside its limit, as the
last lines of standard error and under the result's last key, ``checks``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import harness
    try:
        cell = harness.find_cell(args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T0)
    except harness.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
