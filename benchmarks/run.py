"""Benchmark harness — one module per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (and tees them to
results/bench.csv).  Paper figures: fig2 (landscape), fig3 (s-sweep),
fig4 (financial).  Framework: kernels, serving, roofline (reads the
dry-run records; compile happens in repro.launch.dryrun).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks pkg

from repro.launch import compile_cache  # noqa: E402
from benchmarks import (bench_kernels, bench_paper_fig2, bench_paper_fig3,
                        bench_paper_fig4, bench_roofline, bench_serving)

SUITES = {
    "paper_fig2": bench_paper_fig2.run,
    "paper_fig3": bench_paper_fig3.run,
    "paper_fig4": bench_paper_fig4.run,
    "kernels": bench_kernels.run,
    "serving": bench_serving.run,
    "roofline": bench_roofline.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (default: all)")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(SUITES)
    compile_cache.enable()

    rows: List[str] = ["name,us_per_call,derived"]
    t0 = time.time()
    for name in names:
        print(f"### suite: {name}", flush=True)
        SUITES[name](rows)
    out = os.path.join(os.path.dirname(__file__), "..", "results", "bench.csv")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as fh:
        fh.write("\n".join(rows) + "\n")
    print(f"\nwrote {len(rows)-1} rows to {out} in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
