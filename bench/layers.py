"""What the per-layer metric readers share: which jitted programs make up
each layer, read from the device trace by name, and the least work of
the steps the traced window held (``bench/work.py``).

A reader (``bench/metrics/<metric>.py``) has one function,
``read(ctx) -> value | (value, extra) | None``; ``None`` when the run has
nothing for it to read.  ``ctx`` holds the trace summary
(``trace_reduce.reduce``), the window's steps, the host-clock latencies,
the configuration's sizes and module, the peaks of one chip and
``chips``, the devices the cell runs on.

A share of a peak is the least work of the whole batch over ``chips``
times the chip's peaks: the same work whatever implements it, so a
data-parallel program that reads the weights on every chip reads lower
than the same batch on one chip.
"""
from __future__ import annotations

from peaks import least_seconds

# jitted programs by layer, as the device trace names them
EDGE = ("_record_at_impl", "_step_masked_impl", "_u_head_impl")
CATCHUP = ("_catchup_impl",)


def device_s(ctx, programs) -> float:
    return sum(ctx["summary"]["programs"].get(p, 0.0) for p in programs)


def ms_per_step(ctx, programs):
    t, n = device_s(ctx, programs), len(ctx["steps"])
    return t / n * 1e3 if t > 0 and n else None


def edge_work(ctx):
    """Per step: (flops, bytes) of the edge step over the step's rows (the
    pool is one cohort, so every row is at the step's position)."""
    memo = {}
    for s in ctx["steps"]:
        if s["pos"] not in memo:
            memo[s["pos"]] = ctx["work"].edge_step(ctx["mon"],
                                                   [s["pos"]] * s["n"])
        yield memo[s["pos"]]


def catchup_work(ctx):
    """Per step with a catch-up: (flops, bytes) of its replays."""
    for s in ctx["steps"]:
        if s["replays"]:
            yield ctx["work"].catchup(ctx["mod"], ctx["sz"],
                                      [(p0, p1) for _, p0, p1 in s["replays"]])


def roofline(ctx, programs, works):
    """Least time of the work over its device time (averaged over the
    devices), in %, and which peak bounds most of it: each step's least
    time is the larger of its FLOPs over the peak rate and its bytes over
    the peak bandwidth, on ``chips`` chips."""
    t = device_s(ctx, programs)
    if not t or not ctx["steps"]:
        return None
    by = {"compute": 0.0, "memory": 0.0}
    for flops, nbytes in works:
        least, bound = least_seconds(flops, nbytes, ctx["peaks"])
        by[bound] += least
    least = (by["compute"] + by["memory"]) / ctx["chips"]
    if not least:
        return None
    return 100.0 * least / t, {"bound": max(by, key=by.get)}


def step_mfu(ctx):
    """Useful model FLOPs of the traced window (edge and catch-up) over the
    window's length times ``chips`` times the chip's bf16 peak, in %."""
    if not ctx["steps"]:
        return None
    flops = sum(f for f, _ in edge_work(ctx))
    flops += sum(f for f, _ in catchup_work(ctx))
    w = ctx["summary"]["window_s"]
    return 100.0 * flops / (w * ctx["chips"] * ctx["peaks"]["flops_bf16"])
