"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device's busy and idle intervals, device time per jitted
program, the operations that took most time, and the idle gaps by what the
host was doing.

Planes named ``/device:TPU:<n>`` are devices.  On each, the line
``XLA Ops`` holds one event per operation (busy time is the union of
their intervals) and ``XLA Modules`` one per run of a jitted program,
named ``jit_<function>(<id>)``.  The host's plane ``/host:CPU`` holds the
benchmark's own ``jax.profiler.TraceAnnotation`` spans on the Python
thread; the span named ``bench.window`` bounds the traced window, and
the thread that holds it is the one whose spans explain idle gaps.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

WINDOW = "bench.window"
# control flow whose event spans the events of the operations inside it
CONTAINERS = ("%while", "%conditional", "%call")
# the benchmark's own host spans (harness.py), by which idle gaps are told
SPANS = ("session.step", "loadgen.wait", "pool.turnover")
_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(module: str) -> str:
    """``jit__catchup_impl(42)`` -> ``_catchup_impl``."""
    name = _SUFFIX.sub("", module)
    return name[4:] if name.startswith("jit_") else name


def union(intervals):
    """Merge (start, end) pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[32,8]{1,0:T(8,128)} fusion(...)`` ->
    ``%fusion.3 = bf16[32,8]``: the operation and its output's shape."""
    return hlo.split("{", 1)[0].split("(", 1)[0].strip()


def timeline(spans, ours=None):
    """The Python thread's spans (nested, as one thread's are) as
    disjoint segments ``(start, end, activity)``: the innermost of the
    benchmark's own spans, and within it the innermost event the profiler
    recorded (a readback, a dispatch, a sleep)."""
    ours = SPANS if ours is None else ours
    marks = []
    for name, s, e in spans:
        marks.append((s, 1, name))
        marks.append((e, 0, name))
    marks.sort(key=lambda m: (m[0], m[1]))
    stack, segs, last = [], [], None
    for t, opening, name in marks:
        if last is not None and t > last and stack:
            mine = next((n for n in reversed(stack) if n in ours),
                        "bench.window")
            inner = stack[-1]
            segs.append((last, t, mine if inner == mine
                         else f"{mine}: {inner}"))
        if opening:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        last = t
    return segs


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(data, top: int = 10) -> dict:
    """The trace's summary over the traced window:

    ``window_s``; per device ``busy_s``; ``busy_s`` averaged over devices;
    ``programs``: device seconds per jitted program, averaged over
    devices; ``device_ops``: the ``top`` operations by device seconds
    (summed over devices, keyed ``program/op``); ``idle_gaps``: idle
    seconds of the first device by what the host's Python thread was
    doing through them (``timeline``), the ``top`` largest;
    ``n_devices``."""
    host_spans, window = [], None
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = list(_events(line))
                if any(n == WINDOW for n, _, _ in evs):   # the Python thread
                    for name, s, e in evs:
                        if name == WINDOW:
                            window = (s, e)
                        elif e > s:
                            host_spans.append((name, s, e))
        elif re.match(r"^/device:TPU:\d+$", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append((plane.name, lines))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} span")
    if not devices:
        raise ValueError("trace has no TPU device plane")
    lo, hi = window
    programs = defaultdict(float)
    ops = defaultdict(float)
    busy = []
    first_busy = None
    for dev_name, lines in sorted(devices):
        op_line, mod_line = lines.get("XLA Ops"), lines.get("XLA Modules")
        if op_line is None or mod_line is None:
            raise ValueError(f"{dev_name}: no 'XLA Ops' / 'XLA Modules' "
                             f"line (lines: {sorted(lines)})")
        mods = sorted((max(s, lo), min(e, hi), program_name(n))
                      for n, s, e in _events(mod_line) if e > lo and s < hi)
        for s, e, name in mods:
            programs[name] += (e - s) * 1e-9
        op_iv = []
        starts = [m[0] for m in mods]
        for n, s, e in _events(op_line):
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            op_iv.append((s, e))
            if n.startswith(CONTAINERS):    # its body's ops are listed too
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            ops[f"{prog}/{op_name(n)}"] += (e - s) * 1e-9
        u = union(op_iv)
        busy.append(sum(e - s for s, e in u) * 1e-9)
        if first_busy is None:
            first_busy = u
    nd = len(devices)
    gaps = defaultdict(float)
    segs = timeline(host_spans)
    starts = [sg[0] for sg in segs]
    prev = lo
    for s, e in first_busy + [[hi, hi]]:
        if s > prev:                      # idle from prev to s
            covered = 0
            i = max(bisect.bisect_right(starts, prev) - 1, 0)
            while i < len(segs) and segs[i][0] < s:
                a, b = max(segs[i][0], prev), min(segs[i][1], s)
                if b > a:
                    gaps[segs[i][2]] += (b - a) * 1e-9
                    covered += b - a
                i += 1
            if s - prev > covered:
                gaps["bench.window"] += (s - prev - covered) * 1e-9
        prev = max(prev, e)
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "n_devices": nd,
        "busy_s": sum(busy) / nd,
        "busy_s_per_device": busy,
        "programs": {k: v / nd for k, v in programs.items()},
        "device_ops": rank(ops),
        "idle_gaps": rank(gaps),
    }
