"""Benchmark orchestrator — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV per the harness contract and writes
full JSON rows to experiments/results/."""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale traces (default: quick CI sizes)")
    ap.add_argument("--only", type=str, default=None)
    args, _ = ap.parse_known_args()
    quick = not args.full
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (bench_strawman, bench_zipf, bench_youtube, bench_wiki,
                   bench_traces, bench_window, bench_errors, bench_serving,
                   bench_sketch, bench_device)
    suites = {
        "fig4_strawman": bench_strawman.run,
        "fig6_zipf": bench_zipf.run,
        "fig7_youtube": bench_youtube.run,
        "fig8_wiki": bench_wiki.run,
        "fig9_20_traces": bench_traces.run,
        "fig21_window": bench_window.run,
        "fig22_errors": bench_errors.run,
        "serving_prefix": bench_serving.run,
        "sketch_micro": bench_sketch.run,
        "device_throughput": bench_device.run,
    }
    if args.only:
        suites = {k: v for k, v in suites.items() if args.only in k}
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        t0 = time.perf_counter()
        rows = fn(quick=quick)
        wall = time.perf_counter() - t0
        n = max(1, sum(r.get("accesses", 1) for r in rows))
        # derived: the headline number of each table
        derived = ""
        hits = [r["hit_ratio"] for r in rows if "hit_ratio" in r]
        if hits:
            derived = f"best_hit={max(hits):.4f}"
        elif rows and "reduction" in rows[0]:
            derived = f"reduction={rows[0]['reduction']:.1%}"
        elif rows and "us_per_op" in rows[0]:
            derived = f"host_us={rows[0]['us_per_op']:.2f}"
        print(f"{name},{wall / n * 1e6:.4f},{derived}")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
