#!/usr/bin/env python3
"""Find an open-loop mix's knee on the chip: the highest tick rate at which
the backlog does not grow.  The cell runs closed-loop once (the next tick
when the step returns), which gives the rate at which the chip is never
idle between steps; then open-loop at given fractions of that rate, each
for the cell's window.  At each rate it prints how late the generator
issued ticks, as the mean of each third of the window.  The backlog grows
at a rate where the last third's mean lateness exceeds the first third's
by more than a tenth of the tick period.  The knee is the highest swept
rate below which none grows; the cell runs at 0.8 of it, written into its
traffic file as a number.

    python bench/knee.py --workload granite-8b.sparse --seconds 51 \\
        --seed 2147480001 --fractions 0.6 0.7 0.8 0.9

The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]

GROWTH = 0.1        # of the tick period, between the first and last third


def summary(tag: str, r: dict) -> dict:
    st = r["steps"]
    n = len(st)
    late = [(s["issue"] - s["due"]) * 1e3 for s in st]
    dur = [(s["ret"] - s["issue"]) * 1e3 for s in st]
    rounds = [max([p1 - p0 + 1 for _, p0, p1 in s["replays"]] or [0])
              for s in st]
    q = lambda x: [float(np.percentile(x, k)) for k in (5, 50, 95, 100)]
    return {"run": tag, "ticks": n, "correct": r["correct"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "late_ms_mean_by_third": thirds(late),
            "late_ms_q5_50_95_100": q(late), "step_ms_q5_50_95_100": q(dur),
            "rounds_q5_50_95_100": q(rounds),
            "ticks_per_s": n / (st[-1]["ret"] - st[0]["due"])}


def thirds(late_ms) -> list:
    n = len(late_ms)
    return [float(np.mean(late_ms[i * n // 3:(i + 1) * n // 3]))
            for i in range(3)]


def grows(late_by_third, tick_rate: float) -> bool:
    """Whether the backlog grew over the window."""
    period_ms = 1e3 / tick_rate
    return late_by_third[2] - late_by_third[0] > GROWTH * period_ms


def knee(swept) -> float | None:
    """``swept``: [(tick_rate, late_by_third)].  The highest rate below
    which (itself included) the backlog grew at no swept rate."""
    best = None
    for rate, by_third in sorted(swept):
        if grows(by_third, rate):
            break
        best = rate
    return best


def main(argv=None) -> int:
    import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=2147480001)
    ap.add_argument("--fractions", type=float, nargs="+",
                    default=[0.6, 0.7, 0.8, 0.9])
    args = ap.parse_args(argv)
    run = lambda traffic: harness.run_cell(
        args.workload, args.seed, args.seconds, False,
        overrides={"traffic": traffic}, keep_steps=True)
    closed = summary("closed", run({"loop": "closed"}))
    print(json.dumps(closed), flush=True)
    busy_rate = closed["ticks_per_s"]
    swept = []
    for f in args.fractions:
        rate = f * busy_rate
        s = summary(f"open {f} x {busy_rate!r}",
                    run({"loop": "open", "tick_rate": rate}))
        s["grows"] = grows(s["late_ms_mean_by_third"], rate)
        print(json.dumps(s), flush=True)
        swept.append((rate, s["late_ms_mean_by_third"]))
    k = knee(swept)
    print(json.dumps({"closed_loop_ticks_per_s": busy_rate,
                      "knee_ticks_per_s": k,
                      "tick_rate": None if k is None
                      else float(f"{0.8 * k:.4g}")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
