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

The counts come from the devices, every ``/device:TPU:<n>`` plane of
the trace.  The replay loop is the outermost
``%while`` operation inside each run of ``_catchup_impl`` (its body is the
``catchup.round`` named scope).  Its rounds are its body's iterations:
each operation directly in the body (in no loop or branch inside it) runs
once a round, so the count most of them share is the round count.  Its
rows are the rows of the round's hidden state: the leading size of the
two-dimensional ``[rows, hidden_size]`` operations directly in the body
(the token embedding, the carried last hidden state), the most common;
the layers' operations, nested deeper, hold weight slices such as
``[1, hidden_size]``.  On a mesh (``data:<n>``) the catch-up is one SPMD
program: each device runs the loop over its own block of rows, so a
call's rows are the sum over devices, and its rounds are each device's
loop count, which the devices have to agree on.  The ``rounds`` and
``rows`` the program reports on each ``edge.catchup`` span are a
cross-check only: a call where they differ from the devices', where the
devices disagree on the rounds, or a window where calls and spans do not
pair one to one on every device, reads ``None``, so no report of the
program's can move a number here.  Device times and idle splits are
averaged over the devices; on one device they are that device's.

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


def _device(lines, lo, hi, segs) -> dict:
    """One device's part of ``reduce``, inside the window ``[lo, hi)``:
    its runs of ``_catchup_impl``, their loops, their operations' time
    inside and outside the loops, and its idle seconds, in all and by the
    host's segment ``segs`` (``trace_reduce.timeline``) they fall in."""
    runs, ids = [], set()
    for ev in lines["XLA Modules"].events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if trace_reduce.program_name(ev.name) == CATCHUP and e > lo and s < hi:
            pid = ev.name[ev.name.rfind("(") + 1:-1]
            runs.append((max(s, lo), min(e, hi), pid))
            ids.add(pid)
    runs.sort()
    ops, short = [], {}
    for ev in lines["XLA Ops"].events:
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
        "calls": [{"rounds": _iterations(d), "shapes": sh}
                  for _, _, d, sh in loops],
        "catchup_s": sum(e - s for s, e, _ in runs) * 1e-9,
        "loop_s": sum(e - s for s, e, _, _ in loops) * 1e-9,
        "loop_ops": loop_ops, "fixed_ops": fixed_ops,
        "runs": len(runs), "program_ids": ids,
        "idle_s": sum(e - s for s, e in idle) * 1e-9,
        "idle_by_span": by,
    }


def _mean(dicts) -> dict:
    """Key by key, the mean of ``dicts`` (a missing key counts 0)."""
    keys = {k: None for d in dicts for k in d}
    return {k: sum(d.get(k, 0) for d in dicts) / len(dicts) for k in keys}


def reduce(data) -> dict | None:
    """``None`` where the trace has no ``edge.catchup`` span; otherwise

    ``steps``: ``session.step`` spans in the window; ``catchups``: the
    args of each ``edge.catchup`` span; ``devices``: per device, in the
    order of their ids, ``calls`` (per run of ``_catchup_impl`` with a
    loop, ``rounds``, the loop's iterations, and ``shapes``, ``_loops``)
    and ``runs`` (the program's runs); ``calls``, ``runs``: the first
    device's; ``catchup_s``, ``loop_s``: the seconds in
    ``_catchup_impl`` and in its outermost ``%while``; ``loop_ops``,
    ``fixed_ops``: the nanoseconds of its operations inside and outside
    that loop by ``(program id, instruction)``; ``idle_s``: the window's
    idle seconds; ``idle_by_span``: idle seconds by the innermost of
    ``session.step`` and the program's spans open through them
    (``trace_reduce.timeline``): each of these a mean over the devices;
    ``program_ids``: the ids of ``_catchup_impl`` in the module names."""
    window, spans, catchups = None, [], []
    devices = []
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
        else:
            m = re.match(r"^/device:TPU:(\d+)$", plane.name)
            if m:
                devices.append((int(m.group(1)),
                                {ln.name: ln for ln in plane.lines}))
    if window is None or not devices or not catchups or any(
            "XLA Modules" not in d or "XLA Ops" not in d
            for _, d in devices):
        return None
    lo, hi = window
    catchups = [a for s, a in catchups if lo <= s < hi]
    steps = sum(1 for n, s, _ in spans if n == STEP and lo <= s < hi)
    segs = trace_reduce.timeline(spans, ours=(STEP,) + PROGRAM)
    per = [_device(lines, lo, hi, segs)
           for _, lines in sorted(devices, key=lambda d: d[0])]
    nd = len(per)
    mean = lambda key: sum(d[key] for d in per) / nd
    return {
        "steps": steps, "catchups": catchups,
        "devices": [{"calls": d["calls"], "runs": d["runs"]} for d in per],
        "calls": per[0]["calls"], "runs": per[0]["runs"],
        "catchup_s": mean("catchup_s"), "loop_s": mean("loop_s"),
        "loop_ops": _mean([d["loop_ops"] for d in per]),
        "fixed_ops": _mean([d["fixed_ops"] for d in per]),
        "program_ids": sorted(set().union(*(d["program_ids"]
                                             for d in per))),
        "idle_s": mean("idle_s"),
        "idle_by_span": _mean([d["idle_by_span"] for d in per]),
    }


def rows(shapes: Counter, hidden: int) -> int:
    """The rows a round decodes: the most common leading size of the
    ``[rows, hidden]`` operations directly in the loop's body (0 where
    there is none)."""
    got = Counter({r: n for (r, c), n in shapes.items() if c == hidden})
    return got.most_common(1)[0][0] if got else 0


def counts(r, hidden: int) -> dict | None:
    """The window's catch-up counts, from the devices: ``calls``,
    ``rounds`` (sum of each call's loop iterations, which every device
    ran alike), ``rows_decoded`` (sum of rounds x rows, a call's rows
    summed over the devices), ``replayed`` (the backlog tokens the
    ``edge.catchup`` spans report), ``devices``, and ``by_call``: each
    call's ``rounds`` and ``rows`` per device.  ``None`` where there is no
    call, a device's runs and calls do not pair one to one with the
    spans, the devices disagree on a call's rounds, or a call's rounds and
    rows disagree with its span's."""
    if r is None or not r["catchups"]:
        return None
    n = len(r["catchups"])
    devs = r["devices"]
    if any(len(d["calls"]) != n or d["runs"] != n for d in devs):
        return None
    rounds = decoded = 0
    by_call = []
    for i, span in enumerate(r["catchups"]):
        calls = [d["calls"][i] for d in devs]
        k = calls[0]["rounds"]
        b = [rows(c["shapes"], hidden) for c in calls]
        if any(c["rounds"] != k for c in calls) \
                or k != span.get("rounds") or sum(b) != span.get("rows"):
            return None
        rounds += k
        decoded += k * sum(b)
        by_call.append({"rounds": k, "rows": b})
    return {"calls": n, "rounds": rounds, "rows_decoded": decoded,
            "replayed": sum(a["replayed"] for a in r["catchups"]),
            "devices": len(devs), "by_call": by_call}


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


def shard_idle_rounds(r, hidden: int, steps) -> tuple | None:
    """The share of the rounds a device's replay loop ran in which none of
    its own rows replayed a token, in %: sum(R - r_d) / sum(R) over the
    window's calls and the devices, R the rounds the device's loop ran
    and r_d the longest backlog among the rows it holds, read from the
    window's ``steps`` (each step's ``replays``, ``(row, first, last)``)
    with the rows split as ``P("data")`` splits them, in contiguous
    blocks of B / devices.  Since every device runs R rounds, the sum does
    not depend on which device holds which block.  Extra: the mean
    ``rounds`` a device ran a call and the mean ``needed`` by its own
    rows.  ``None`` where the counts read nothing, where the steps with a
    replay do not pair one to one with the calls, or where a call's
    rounds or a device's rows are not what the replays and the split
    say."""
    c = counts(r, hidden)
    calls = [s for s in steps if s["replays"]]
    if not c or len(calls) != c["calls"]:
        return None
    nd = c["devices"]
    ran = needed = 0
    for call, step in zip(c["by_call"], calls):
        block = step["n"] // nd
        need = [0] * nd
        for row, first, last in step["replays"]:
            need[row // block] = max(need[row // block], last - first + 1)
        if max(need) != call["rounds"] or call["rows"] != [block] * nd:
            return None
        ran += nd * call["rounds"]
        needed += sum(need)
    if not ran:
        return None
    return 100.0 * (ran - needed) / ran, {
        "rounds": ran / (nd * c["calls"]),
        "needed": needed / (nd * c["calls"])}


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
