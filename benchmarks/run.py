"""Benchmark driver: one function per paper table/figure + kernel tiles +
roofline summary from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.run [--only table4,fig9]

Prints ``name,us_per_call,derived`` CSV (the middle column is KiB/MiB for
memory benchmarks, us for latency ones — unit noted in ``derived``).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from benchmarks.paper_tables import ALL as PAPER          # noqa: E402
from benchmarks.kernel_bench import ALL as KERNELS        # noqa: E402
from benchmarks import swap_bench                         # noqa: E402
from benchmarks.swap_bench import ALL as SWAP             # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402


def roofline_rows():
    from repro.launch.roofline import load_all
    rows = []
    for r in load_all():
        rows.append((
            f"roofline/{r['arch']}/{r['shape']}",
            r["t_compute_s"] * 1e6,
            f"t_mem={r['t_memory_s']*1e6:.0f}us "
            f"t_coll={r['t_collective_s']*1e6:.0f}us "
            f"dominant={r['dominant']} useful={r['useful_compute_ratio']:.2f} "
            f"roofline={r['roofline_fraction']:.2%}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--bench-json", default=None, metavar="PATH",
                    help="where to write BENCH_swap.json when swap benches "
                         "run (default: results/BENCH_swap.json)")
    args = ap.parse_args()
    enable_compile_cache()

    benches = dict(PAPER)
    benches.update(KERNELS)
    benches.update(SWAP)
    benches["roofline"] = roofline_rows
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches.items():
        try:
            for row in fn():
                print(f"{row[0]},{row[1]:.2f},{row[2]}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,ERROR {e}")
    if swap_bench.JSON_RECORDS:
        path = swap_bench.dump_json(args.bench_json)
        print(f"# wrote {len(swap_bench.JSON_RECORDS)} records to {path}",
              file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
