"""One run of one benchmark cell: set-up, the measured window, the check of
what the window produced, and the result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``
(sizes) and ``.py`` (plain reference, least work), its traffic in
``bench/traffic/<traffic>.json`` and each per-layer metric in
``bench/metrics/<metric>.py``.

The window drives ``MonitorSession.step`` in ``sync`` mode with the
in-process transport: each step is one tick of every attached stream, its
trigger points set from the mix's trigger pattern through the session's
trigger policy.  A cell on ``chips`` > 1 devices opens the session on its
own mesh path, ``SessionConfig(mesh="data:<chips>")``: the weights are
made replicated on that mesh, and the session splits every per-stream
buffer over it.
The host clock is read around each step; the moment a step's trigger
decision is on the host is the end of the program's own ``edge.trigger``
span (``SessionConfig(trace=True)``, ``docs/observability.md``), which is
on the same clock.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WARM_MAX = 200          # warm-up steps at most, until a catch-up has run
TRACE_S = 10.0          # a traced run's window (the trace grows ~5 MB/s)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, bad cell)."""


def process_start_wall() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.time() - (uptime - started)
    except (OSError, ValueError, IndexError):
        return _IMPORTED


_IMPORTED = time.time()


# --- finding a cell's parts by name ---------------------------------------

def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        sz = json.load(f)
    mod = load_module(os.path.join(BENCH, "configs", f"{name}.py"),
                      f"bench_config_{name.replace('-', '_')}")
    return sz, mod


def arch_config(sz: dict):
    """The program's ArchConfig as the configuration file states it."""
    from repro.configs.base import ArchConfig, MonitorConfig
    prog = sz["program"]
    kw = {k: sz[v] for k, v in prog["map"].items()}
    kw.update(prog["set"])
    mon = {k: v for k, v in sz["monitor"].items()}
    return ArchConfig(family=prog["family"], monitor=MonitorConfig(**mon),
                      **kw)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


# --- the device -------------------------------------------------------------

def find_devices(chips: int, allow_cpu: bool):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise BenchError(f"no TPU: JAX sees {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), so only a cell's
    first run in a checkout compiles."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


class CompileCounter:
    """Counts jit traces and backend compiles, and the seconds compiling."""

    def __init__(self):
        from jax import monitoring
        self.traces = self.compiles = 0
        self.compile_s = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, dur, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += dur

    def snapshot(self):
        return self.traces, self.compiles


# --- the run ----------------------------------------------------------------

def decided_at(tracer) -> float:
    """When the last step's trigger decision reached the host: the end of
    its ``edge.trigger`` span, on the tracer's clock."""
    for sp in reversed(tracer.spans()):
        if sp.name == "edge.trigger":
            return sp.ts + sp.dur
    raise BenchError("the step recorded no edge.trigger span")


def _null_ctx(_name):
    import contextlib
    return contextlib.nullcontext()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             allow_cpu: bool = False, overrides: dict | None = None,
             max_steps: int | None = None, patch=None,
             keep_steps: bool = False) -> dict:
    """One run of the cell ``workload``; returns the result object.

    ``allow_cpu``, ``overrides`` (sizes for "config" / "traffic"),
    ``max_steps``, ``patch(engine)`` and ``keep_steps`` (the window's
    step records under ``"steps"``) exist for the benchmark's own tests,
    which drive a run at a small size on the CPU, and for sweeps."""
    start_wall = process_start_wall()
    bench = load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    import loadgen
    import work
    from peaks import peaks_for
    sz, mod = load_config(cell["config"])
    traffic = loadgen.load_traffic(cell["traffic"])
    overrides = overrides or {}
    sz.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))

    devs = find_devices(cell["chips"], allow_cpu)
    import jax
    peaks = peaks_for(devs[0].device_kind) if not allow_cpu else None
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    from jax.profiler import TraceAnnotation
    annotate = TraceAnnotation if trace else _null_ctx

    cfg = arch_config(sz)
    B, L = traffic["streams"], traffic["max_len"]
    say(f"[cell] {workload}: {cell['config']} x {cell['traffic']}, "
        f"{B} streams x {L}, {traffic['loop']} loop, seed {seed}, "
        f"{seconds} s, trace {int(trace)}; device {devs[0].device_kind} "
        f"x {len(devs)}; compile cache {cache_dir}")

    # -- set-up: weights, threshold, session, warm-up -----------------------
    from repro.core import decomposition as deco
    from repro.serving import MonitorSession, SessionConfig
    import weights
    shapes = jax.eval_shape(lambda k: deco.init_collab_lm(k, cfg),
                            jax.random.PRNGKey(0))
    mesh, replicated = None, None
    if len(devs) > 1:                        # the session's own mesh path
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = f"data:{len(devs)}"
        replicated = NamedSharding(Mesh(np.asarray(devs), ("data",)),
                                   PartitionSpec())
    params = weights.make(shapes, loadgen.jax_seed(seed, loadgen.WEIGHTS),
                          t_init=sz["monitor"]["t_init"],
                          out_shardings=replicated)
    import check
    reference = check.Reference(sz, mod)
    policy = scripted_policy()
    # the policy's floor, the engine's operating point, passes every u
    served = cfg.replace(monitor=dataclasses.replace(
        cfg.monitor, threshold=loadgen.ALWAYS, trigger_margin=0.0))
    session = MonitorSession.open(
        params, served, batch=B, max_len=L,
        config=SessionConfig(mode="sync", policy=policy, trace=True,
                             trace_capacity=16, mesh=mesh))
    session.__enter__()
    eng, tracer = session.engine, session.tracer
    clock = tracer.clock
    if patch is not None:
        patch(eng)
    ids = list(range(B))
    n_catchup = 0
    for k in range(WARM_MAX):                # until a catch-up has run
        if k % L == 0:
            if k:
                ids = turnover(session, ids, B)
            warm = loadgen.streams(traffic, cfg.vocab_size, seed,
                                   loadgen.WARM + k // L)
            pattern = loadgen.triggers(traffic, seed, loadgen.WARM + k // L)
        policy.next = pattern[k % L]
        r = session.step(warm[:, k % L])
        n_catchup += bool(np.asarray(r["triggered"]).any())
        if n_catchup and k >= 2:
            break
    if not n_catchup:
        raise BenchError(f"no step triggered in {WARM_MAX} warm-up steps")
    cohort = 0
    tokens = loadgen.streams(traffic, cfg.vocab_size, seed, loadgen.COHORT)
    pattern = loadgen.triggers(traffic, seed, loadgen.COHORT)
    ids = turnover(session, ids, B)          # warms detach and attach
    jax.block_until_ready(eng.server.cache)
    setup_compiles = counter.snapshot()
    setup_compile_s = counter.compile_s

    # -- the measured window -------------------------------------------------
    if trace:
        seconds = min(seconds, TRACE_S)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans from TraceMe only
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    steps, outs = [], []
    pos = 0
    server_pos = np.zeros(B, np.int64)
    with annotate("bench.window"):
        t0 = clock()
        setup_s = time.time() - start_wall
        sched = loadgen.Schedule(traffic, t0)
        k = 0
        while True:
            now = clock()
            due = sched.due(k, now)
            if due >= t0 + seconds:
                break
            if max_steps is not None and k >= max_steps:
                break
            if due > now:
                with annotate("loadgen.wait"):
                    time.sleep(due - now)
            policy.next = pattern[pos]
            issue = clock()
            with annotate("session.step"):
                r = session.step(tokens[:, pos])
            ret = clock()
            trig = np.asarray(r["triggered"], bool)
            replays = [(int(i), int(server_pos[i]), pos)
                       for i in np.flatnonzero(trig)]
            server_pos[trig] = pos + 1
            steps.append({"due": due, "issue": issue, "ret": ret,
                          "decide": decided_at(tracer),
                          "cohort": cohort, "pos": pos, "n": B,
                          "replays": replays})
            outs.append((np.asarray(r["u"], np.float32),
                         np.asarray(r["fhat"], np.float32), trig,
                         loadgen.trigger_points(pattern[pos])))
            k += 1
            pos += 1
            if pos == L:                      # the cohort is spent
                with annotate("pool.turnover"):
                    ids = turnover(session, ids, B)
                cohort += 1
                pos = 0
                server_pos[:] = 0
                tokens = loadgen.streams(traffic, cfg.vocab_size, seed,
                                         loadgen.COHORT + cohort)
                pattern = loadgen.triggers(traffic, seed,
                                           loadgen.COHORT + cohort)
        t_end = clock()
    if trace:
        jax.profiler.stop_trace()
    window_compiles = [a - b for a, b in zip(counter.snapshot(),
                                             setup_compiles)]
    peak = memory_peak(devs)

    # -- free the program's state, then check against the reference --------
    session.close()
    del session, eng, tracer
    gc.collect()
    u = np.stack([o[0] for o in outs])
    fhat = np.stack([o[1] for o in outs])
    trig = np.stack([o[2] for o in outs])
    tau = np.stack([o[3] for o in outs])
    checks = check.compare(reference, params, sz, traffic, steps, u, fhat,
                           trig, tau, cohort_tokens=lambda c: loadgen.streams(
                               traffic, cfg.vocab_size, seed,
                               loadgen.COHORT + c))
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # -- metrics -------------------------------------------------------------
    n_tok = sum(s["n"] for s in steps)
    window_s = t_end - t0
    lat = latencies(steps)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": n_tok, "failed": 0,
              "metrics": {}, "device": device}
    if not trace:
        e2e = {"tokens_per_s": n_tok / window_s,
               "trigger_p95_ms": lat["trigger_p95_ms"],
               "fhat_p95_ms": lat["fhat_p95_ms"], "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if applies(m, workload):
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    else:
        import trace_reduce
        summary = trace_reduce.reduce(trace_reduce.load(find_xplane()))
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        ctx = {"summary": summary, "steps": steps, "lat": lat,
               "sz": sz, "mod": mod, "mon": sz["monitor"], "work": work,
               "peaks": peaks, "traffic": traffic, "chips": len(devs)}
        for m in bench["per_layer"]:
            if not applies(m, workload):
                continue
            reader = load_module(os.path.join(BENCH, "metrics",
                                              f"{m['name']}.py"),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            got = reader.read(ctx)
            if got is None:
                continue
            value, extra = got if isinstance(got, tuple) else (got, {})
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"],
                                            **extra}
    if keep_steps:
        result["steps"] = steps
    result["checks"] = checks
    say(f"[setup] {setup_s!r} s to the first tick, {setup_compile_s!r} s "
        f"of it compiling ({setup_compiles[1]} compiles)")
    say(f"[window] {len(steps)} steps, {n_tok} tokens in {window_s!r} s; "
        f"{int(trig.sum())} triggered; traces in window "
        f"{window_compiles[0]}, compiles in window {window_compiles[1]}")
    say(f"[memory] peak_bytes_in_use {peak} on the fullest chip")
    for name, c in checks.items():
        say(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    return result


def scripted_policy():
    """The session's trigger policy: each step's trigger points come from
    the mix's trigger pattern (``loadgen.triggers``), set by the harness in
    ``next`` before the step."""
    from repro.serving.policy import TriggerPolicy
    import loadgen

    class Scripted(TriggerPolicy):
        name = "scripted"
        next = None

        def step_thresholds(self):
            return loadgen.trigger_points(self.next)

    return Scripted()


def turnover(session, ids, B):
    """Detach the whole pool and attach a fresh cohort of B streams."""
    for sid in ids:
        session.detach(sid)
    new = [sid + B for sid in ids]
    for sid in new:
        session.attach(sid)
    return new


def latencies(steps) -> dict:
    trig_ms, fhat_ms, late_ms = [], [], []
    for s in steps:
        trig_ms.extend([(s["decide"] - s["due"]) * 1e3] * s["n"])
        fhat_ms.extend([(s["ret"] - s["due"]) * 1e3] * len(s["replays"]))
        late_ms.append((s["issue"] - s["due"]) * 1e3)
    p95 = lambda x: float(np.percentile(x, 95)) if x else None
    return {"trigger_p95_ms": p95(trig_ms), "fhat_p95_ms": p95(fhat_ms),
            "late_p95_ms": p95(late_ms), "n_trigger": len(trig_ms),
            "n_fhat": len(fhat_ms)}


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else 0


def find_xplane() -> str:
    for dirpath, _, files in os.walk(TRACE_DIR):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise BenchError(f"no .xplane.pb under {TRACE_DIR}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        say(f"bench: the system under test is missing ({SRC}/repro)")
        return 2
    for p in (SRC, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        say(f"bench: {e}")
        return 3
    checks = result.pop("checks")
    result["checks"] = checks                # the compared numbers come last
    print(json.dumps(result), flush=True)
    return 0
