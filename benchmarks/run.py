"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (assignment format).

    PYTHONPATH=src python -m benchmarks.run            # full
    PYTHONPATH=src python -m benchmarks.run --fast     # CI-scale
    PYTHONPATH=src python -m benchmarks.run --only fig2,roofline
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import CSV
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "fig2": ("bench_moe_topk", "throughput vs active experts under pruning"),
    "dispatch": ("bench_moe_dispatch",
                 "dense vs gmm dispatch tokens/s -> BENCH_moe_dispatch.json"),
    "fig3": ("bench_sensitivity", "per-layer top-k sensitivity heatmap"),
    "fig4": ("bench_lexi_vs_pruning", "LExI vs pruning quality/throughput"),
    "alg2": ("bench_search", "EA vs exact-DP allocator"),
    "kernels": ("bench_kernels", "Pallas kernel microbenchmarks vs refs"),
    "serving": ("bench_serving", "engine throughput w/ and w/o LExI plan"),
    "roofline": ("bench_roofline", "40-cell roofline table from dry-run"),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true", help="CI-scale sizes")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args()
    enable_compile_cache()

    names = list(BENCHES) if not args.only else args.only.split(",")
    csv = CSV()
    csv.header()
    t0 = time.time()
    for name in names:
        mod_name, desc = BENCHES[name]
        print(f"# --- {name}: {desc} ---", flush=True)
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        t1 = time.time()
        try:
            mod.run(csv, fast=args.fast)
        except Exception as e:  # keep the harness going; record the failure
            csv.add(f"{name}/ERROR", 0.0, f"{type(e).__name__}:{e}")
        print(f"# {name} took {time.time() - t1:.1f}s", flush=True)
    print(f"# total {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
