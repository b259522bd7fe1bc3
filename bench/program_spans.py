"""What the program's own spans and the device's operations say about a
traced run: the catch-up's device time split into its replay loop and the
rest of each call, the rounds and rows the device ran against the backlog
tokens the program shipped, and the device's idle time by the program span
the host was in.

The session opens its spans as ``jax.profiler.TraceAnnotation``s
(``SessionConfig(trace=True)``, ``docs/observability.md``), so they lie on
the profiler's host plane beside the benchmark's own: on the Python
thread, inside ``bench.window``, each ``edge.catchup`` carrying
``replayed`` (backlog tokens shipped), ``rounds`` and ``rows`` as event
stats.

The counts come from the device.  The replay loop is the outermost
``%while`` operation inside each run of ``_catchup_impl`` (its body is the
``catchup.round`` named scope).  Its rounds are its body's iterations:
each operation directly in the body (in no loop or branch inside it) runs
once a round, so the count most of them share is the round count.  Its
rows are the rows of the round's hidden state: the leading size of the
two-dimensional ``[rows, hidden_size]`` operations directly in the body
(the token embedding, the carried last hidden state), the most common;
the layers' operations, nested deeper, hold weight slices such as
``[1, hidden_size]``.  The ``rounds`` and ``rows`` the program reports on each
``edge.catchup`` span are a cross-check only: a call where they differ
from the device's, or a window where calls and spans do not pair one to
one, reads ``None``, so no report of the program's can move a number here.

A trace with no ``edge.catchup`` span, or a catch-up with no loop, has
nothing here to read: every function returns ``None``.  The xplane is
read once per process (``load``), from ``harness.find_xplane()`` unless a
path is given.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import shutil
import tempfile
from collections import Counter

import trace_reduce

CATCHUP = "_catchup_impl"
LOOP = "%while"
STEP = "session.step"
# the program's spans on the sync step's path (serving/collaborative.py,
# serving/api.py)
PROGRAM = ("edge.decode", "edge.trigger", "edge.catchup",
           "catchup.dispatch", "catchup.wait", "session.policy")
# the spans that block on a device readback: u for the trigger compare,
# fhat after the catch-up
READBACK = ("edge.trigger", "catchup.wait")
_MATRIX = re.compile(r"= \w+\[(\d+),(\d+)\]$")
# an operation writing bf16 from a float32 operand (layouts left out): a
# weight convert
_CONVERT = re.compile(r"^%\S+ = bf16\[[^\]]*\] [\w-]+\(.*\bf32\[")
_LAYOUT = re.compile(r"\{[^}]*\}")

_loaded = {}
_hlo_stats = {}


def load(path: str | None = None):
    """The reduced trace (``reduce``) of ``path``, by default the traced
    run's xplane, read once per process."""
    if path is None:
        import harness
        path = harness.find_xplane()
    if path not in _loaded:
        r = _loaded[path] = reduce(trace_reduce.load(path))
        if r is not None:
            r["path"] = path
    return _loaded[path]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _instruction(name: str) -> str:
    """``%fusion.3 = bf16[32,8]{...} fusion(...)`` -> ``%fusion.3``."""
    return name.split(" ", 1)[0]


def _loops(ops, runs):
    """The outermost ``%while`` of each run in ``runs`` (``(start, end,
    program id)``), as ``(start, end, direct, shapes)``: ``direct`` counts
    each instruction directly in the loop's body (in no loop, branch or
    call inside it), ``shapes`` the ``(rows, cols)`` of the
    two-dimensional ones; and the device time of the runs' operations
    (those that hold no others) by ``(program id, instruction)``, inside
    the loops and outside them.  ``ops`` are ``(start, end, name)``, each
    name cut by ``trace_reduce.op_name``."""
    starts = [s for s, _, _ in runs]
    out, stack = [], []          # stack: ends of the open containers
    cur = None                   # the open outermost loop (an ``out`` entry)
    inside, outside = Counter(), Counter()
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1] <= s:
            stack.pop()
        if cur is not None and s >= cur[1]:
            cur = None
        i = bisect.bisect_right(starts, s) - 1
        run = runs[i] if i >= 0 and runs[i][1] >= e else None
        container = name.startswith(trace_reduce.CONTAINERS)
        if cur is not None:
            if len(stack) == 1:
                cur[2][_instruction(name)] += 1
                m = _MATRIX.search(name)
                if m:
                    cur[3][(int(m.group(1)), int(m.group(2)))] += 1
        elif name.startswith(LOOP) and run is not None:
            cur = [s, e, Counter(), Counter()]
            out.append(cur)
            stack = []
        if run is not None and not container:
            (inside if cur is not None else outside)[
                (run[2], _instruction(name))] += e - s
        if container:
            stack.append(e)
    return [tuple(lp) for lp in out], inside, outside


def _iterations(direct: Counter) -> int:
    """The count most of the body's instructions share (the smaller where
    two tie): once a round each, where a loop's condition may run once
    more."""
    if not direct:
        return 0
    freq = Counter(direct.values())
    return min(freq, key=lambda n: (-freq[n], n))


def reduce(data) -> dict | None:
    """``None`` where the trace has no ``edge.catchup`` span; otherwise

    ``steps``: ``session.step`` spans in the window; ``catchups``: the
    args of each ``edge.catchup`` span; ``calls``: per run of
    ``_catchup_impl`` with a loop, ``rounds`` (the loop's iterations) and
    ``shapes`` (``_loops``); ``catchup_s``, ``loop_s``, ``runs``: the
    first device's seconds in ``_catchup_impl`` and in its outermost
    ``%while``, and the program's runs; ``loop_ops``, ``fixed_ops``: the
    nanoseconds of its operations inside and outside that loop by
    ``(program id, instruction)``; ``program_ids``: the ids of
    ``_catchup_impl`` in the module names; ``idle_s``: the window's idle
    seconds on that device; ``idle_by_span``: idle seconds by the
    innermost of ``session.step`` and the program's spans open through
    them (``trace_reduce.timeline``)."""
    window, spans, catchups = None, [], []
    device = None
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = list(line.events)
                if not any(ev.name == trace_reduce.WINDOW for ev in evs):
                    continue                      # not the Python thread
                for ev in evs:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == trace_reduce.WINDOW:
                        window = (s, e)
                    elif e > s:
                        spans.append((ev.name, s, e))
                        if ev.name == "edge.catchup":
                            catchups.append((s, _stats(ev)))
        elif device is None and re.match(r"^/device:TPU:\d+$", plane.name):
            device = {ln.name: ln for ln in plane.lines}
    if window is None or device is None or not catchups \
            or "XLA Modules" not in device or "XLA Ops" not in device:
        return None
    lo, hi = window
    catchups = [a for s, a in catchups if lo <= s < hi]
    steps = sum(1 for n, s, _ in spans if n == STEP and lo <= s < hi)

    runs, ids = [], set()
    for ev in device["XLA Modules"].events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if trace_reduce.program_name(ev.name) == CATCHUP and e > lo and s < hi:
            pid = ev.name[ev.name.rfind("(") + 1:-1]
            runs.append((max(s, lo), min(e, hi), pid))
            ids.add(pid)
    runs.sort()
    ops, short = [], {}
    for ev in device["XLA Ops"].events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if e <= lo or s >= hi:
            continue
        name = ev.name
        if name not in short:
            short[name] = trace_reduce.op_name(name)
        ops.append((max(s, lo), min(e, hi), short[name]))
    loops, loop_ops, fixed_ops = _loops(ops, runs)

    busy = trace_reduce.union([(s, e) for s, e, _ in ops])
    idle, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    by = {}
    segs = trace_reduce.timeline(spans, ours=(STEP,) + PROGRAM)
    seg_starts = [sg[0] for sg in segs]
    for a, b in idle:
        i = max(bisect.bisect_right(seg_starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            s, e = max(segs[i][0], a), min(segs[i][1], b)
            if e > s:
                mine = segs[i][2].split(": ", 1)[0]
                by[mine] = by.get(mine, 0.0) + (e - s) * 1e-9
            i += 1
    return {
        "steps": steps, "catchups": catchups,
        "calls": [{"rounds": _iterations(d), "shapes": sh}
                  for _, _, d, sh in loops],
        "catchup_s": sum(e - s for s, e, _ in runs) * 1e-9,
        "loop_s": sum(e - s for s, e, _, _ in loops) * 1e-9,
        "loop_ops": loop_ops, "fixed_ops": fixed_ops,
        "runs": len(runs), "program_ids": sorted(ids),
        "idle_s": sum(e - s for s, e in idle) * 1e-9,
        "idle_by_span": by,
    }


def rows(shapes: Counter, hidden: int) -> int:
    """The rows a round decodes: the most common leading size of the
    ``[rows, hidden]`` operations directly in the loop's body (0 where
    there is none)."""
    got = Counter({r: n for (r, c), n in shapes.items() if c == hidden})
    return got.most_common(1)[0][0] if got else 0


def counts(r, hidden: int) -> dict | None:
    """The window's catch-up counts, from the device: ``calls``,
    ``rounds`` (sum of each call's loop iterations), ``rows_decoded``
    (sum of rounds x rows) and ``replayed`` (the backlog tokens the
    ``edge.catchup`` spans report).  ``None`` where there is no call, a
    run has no loop, or calls and spans do not agree call by call on the
    rounds and rows."""
    if r is None or not r["calls"] or len(r["calls"]) != r["runs"] \
            or len(r["calls"]) != len(r["catchups"]):
        return None
    rounds = decoded = 0
    for call, span in zip(r["calls"], r["catchups"]):
        n, b = call["rounds"], rows(call["shapes"], hidden)
        if n != span.get("rounds") or b != span.get("rows"):
            return None
        rounds += n
        decoded += n * b
    return {"calls": len(r["calls"]), "rounds": rounds,
            "rows_decoded": decoded,
            "replayed": sum(a["replayed"] for a in r["catchups"])}


def ms_per_round(r, hidden: int) -> float | None:
    """Device ms inside the replay loop per round the device ran."""
    c = counts(r, hidden)
    return r["loop_s"] / c["rounds"] * 1e3 if c and c["rounds"] else None


def fixed_ms_per_call(r, hidden: int) -> float | None:
    """Device ms of ``_catchup_impl`` outside its replay loop per call:
    the weight converts, the v head and the combine."""
    c = counts(r, hidden)
    return (r["catchup_s"] - r["loop_s"]) / c["calls"] * 1e3 if c else None


def useful_row_share(r, hidden: int) -> float | None:
    """Backlog tokens replayed over rows the device decoded, in %."""
    c = counts(r, hidden)
    return 100.0 * c["replayed"] / c["rows_decoded"] \
        if c and c["rows_decoded"] else None


def idle_ms_per_step(r, spans) -> tuple | None:
    """Device-idle ms per step while the innermost program span open on
    the host was one of ``spans``, and that time by span."""
    if r is None or not r["steps"]:
        return None
    by = {k: v / r["steps"] * 1e3 for k, v in r["idle_by_span"].items()
          if k in spans}
    return sum(by.values()), by


def sync_idle_ms_per_step(r):
    """Idle while the host blocked on a readback (``READBACK``)."""
    return idle_ms_per_step(r, READBACK)


def host_idle_ms_per_step(r):
    """Idle inside ``session.step`` under no readback span: dispatch,
    argument upload, policy and bookkeeping."""
    return idle_ms_per_step(r, tuple(n for n in (STEP,) + PROGRAM
                                     if n not in READBACK))


def _op_stats(path: str) -> dict | None:
    """xprof's ``hlo_stats`` of the trace at ``path``: each HLO
    operation's framework op name (its named scopes) and expression by
    ``(program id, instruction)``, read once; ``None`` where xprof is not
    installed."""
    if path not in _hlo_stats:
        try:
            from xprof.convert import raw_to_tool_data
        except ImportError:
            _hlo_stats[path] = None
            return None
        # xprof writes its cache beside the xplane it reads: read a copy
        with tempfile.TemporaryDirectory() as d:
            copy = os.path.join(d, os.path.basename(path))
            shutil.copyfile(path, copy)
            raw, _ = raw_to_tool_data.xspace_to_tool_data(
                [copy], "hlo_stats", {"use_saved_result": False})
        table = json.loads(raw)
        cols = [col["id"] for col in table["cols"]]
        ops = {}
        for row in table["rows"]:
            v = dict(zip(cols, (cell.get("v") for cell in row["c"])))
            if v.get("hlo_op_expression"):
                ops[(v.get("program_id"), "%" + v["hlo_op_name"])] = (
                    v.get("tf_op_name") or "", v["hlo_op_expression"])
        _hlo_stats[path] = ops
    return _hlo_stats[path]


def _part(kind: str, scope: str, expr: str) -> str:
    """The part of the catch-up one operation belongs to: in the replay
    loop (``kind`` ``round``) ``decode.merge`` or the rest of
    ``catchup.round``; outside it its named scope, or else what it does:
    ``f32 -> bf16`` (writes bf16 from a float32 operand: the weight
    converts), ``copies``, the rest."""
    if kind == "round":
        return "decode.merge" if "decode.merge" in scope \
            else "catchup.round (rest)"
    for name in ("catchup.v_head", "catchup.combine"):
        if name in scope:
            return name
    expr = _LAYOUT.sub("", expr)
    if _CONVERT.match(expr):
        return "f32 -> bf16"
    if _instruction(expr).startswith("%copy"):
        return "copies"
    return "rest"


def scope_ms(r, hidden: int) -> dict | None:
    """Device ms of the catch-up by part: ``round``, per round, and
    ``fixed``, per call (``_part``), each operation's time on the device
    trace placed by xprof's ``hlo_stats`` (its scopes and expression).
    ``(no operation)`` is the time of the loop, or of the program outside
    it, that no operation covers, so each split sums to its metric.
    ``None`` where xprof is not installed or the trace holds no HLO for
    an operation of the program."""
    c = counts(r, hidden)
    if not c or not c["rounds"]:
        return None
    ops = _op_stats(r["path"])
    if not ops:
        return None
    by = {}
    for kind, times, total, per in (
            ("round", r["loop_ops"], r["loop_s"], c["rounds"]),
            ("fixed", r["fixed_ops"], r["catchup_s"] - r["loop_s"],
             c["calls"])):
        part = {}
        for key, ns in times.items():
            if key not in ops:
                return None
            name = _part(kind, *ops[key])
            part[name] = part.get(name, 0.0) + ns * 1e-6 / per
        part["(no operation)"] = (total * 1e3 - sum(part.values()) * per) / per
        by[kind] = part
    return by
