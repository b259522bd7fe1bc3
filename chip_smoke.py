#!/usr/bin/env python3
"""Chip smoke test: the collaborative monitoring path on one TPU v5e at
granite-8b's published widths (depth cut to 5 layers, ``configs/granite_8b.py``
``CHIP``), through the normal ``MonitorSession`` -> engine -> correction-server
path, with seeded random weights.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the mesh-sharded session
                                     # ("data:4") against the one-chip session

Phases (one chip), in order, each session closed before the next opens:

  1. reference — ``core.decomposition.collab_forward`` on a float32 copy of
     the config under ``jax.default_matmul_precision("highest")`` with the
     XLA attention path: u, v, fhat at every position of the stream;
  2. modes     — ``scan``, ``sync`` and ``async`` (in-process ``stream``
     transport, ``max_staleness=1``) sessions over the same seeded stream;
  3. checks    — ``fhat <= u`` everywhere, the modes agree per row, and the
     sync session agrees with the reference;
  4. wire      — a ``CorrectionServer`` on a thread of this process answers a
     ``wire``-transport session's catch-up requests; the replies agree with
     the in-process sync session.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Any failure exits nonzero; so does a
host where JAX finds no TPU, and a directory without the repository's
``src/``.  The phase functions are importable: ``tests/test_chip_smoke.py``
runs them on the CPU at the SMOKE preset.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# the served workload: 32 monitored streams, 2048-token caches, 32 steps
BATCH, MAX_LEN, STEPS = 32, 2048, 32
WIRE_STEPS = 4          # catch-up requests the correction server answers
TRIGGER_RATE = 0.8      # threshold at this quantile of u: most steps trigger
SEED = 0

# --- tolerances -------------------------------------------------------------
# bf16 keeps 8 significant bits (unit roundoff 2^-8).  The serving path rounds
# every matmul output, residual add and KV-cache write to bf16: about 7
# roundings a layer, 35 over the CHIP preset's 5 layers.  Random rounding
# errors add like a random walk, sqrt(35) * 2^-8 = 0.023 of the signal's rms.
# The normalised rms error is allowed under three times that, 2^-4; the
# largest single error, taken over the B x S grid, 4x the rms bound.
# Measured on a TPU v5e at the CHIP preset (PERF.md): the serving path and
# the plain bf16 forward both err by rms 0.011-0.014, max 0.033-0.052; the
# float32 forward on weights rounded to fp8 e4m3 (4 significant bits) errs
# by rms 0.13-0.19, max 0.43-0.67, and fails both bounds.
BF16_REL_RMS = 2.0 ** -4
BF16_REL_MAX = 2.0 ** -2
# float32 arithmetic on identical inputs: fhat = u - s*sigma(v) is rounded
# once by the fused combine and once by the subtraction that recovers the
# corrector, so two paths differ by a few ulps of max(|u|, 1).
F32_ULPS = 8 * 2.0 ** -23


class PhaseError(AssertionError):
    """A phase's check failed."""


def _repo():
    """Put the repository's ``src/`` on the path; refuse without it."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no repository next to this script "
                         f"({SRC}/repro is missing)")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phases (importable; the CPU test drives them at the SMOKE preset)
# ---------------------------------------------------------------------------


def make_params(cfg, seed: int = SEED, out_shardings=None):
    """Seeded random weights of the whole collaborative system, built by
    one jitted init (on device, in bulk)."""
    import jax
    from repro.core import decomposition as deco
    init = jax.jit(deco.init_collab_lm, static_argnums=1,
                   out_shardings=out_shardings)
    return init(jax.random.PRNGKey(seed), cfg)


def make_stream(cfg, batch: int, steps: int, seed: int = SEED) -> np.ndarray:
    """(batch, steps) token ids from the repo's seeded Zipf/bigram stream."""
    from repro.data import tokens as tok
    return next(tok.lm_batches(seed, cfg, batch, steps,
                               with_monitor=False))["tokens"]


def pick_threshold(params, cfg, tokens, rate: float = TRIGGER_RATE) -> float:
    """The trigger point at which about ``rate`` of the (stream, step)
    cells trigger: that quantile of the edge tower's u over the stream."""
    import jax
    from repro.core import decomposition as deco
    u = jax.jit(lambda p, t: deco.monitor_score(p, cfg, {"tokens": t}))(
        params, tokens)
    return float(np.quantile(np.asarray(u), 1.0 - rate))


def reference(params, cfg, tokens) -> dict:
    """u, v, fhat from the plain forward pass: a float32 copy of the config,
    highest matmul precision, XLA attention (``ops.get_impl() == "xla"``)."""
    import jax
    from repro.core import decomposition as deco
    from repro.kernels import ops
    if ops.get_impl() != "xla":
        raise PhaseError(f"reference needs the XLA attention path, kernel "
                         f"impl is {ops.get_impl()!r}")
    rcfg = cfg.replace(dtype="float32")
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, t: deco.collab_forward(
            p, rcfg, {"tokens": t}))(params, tokens)
        out = {k: np.asarray(out[k]) for k in ("u", "v", "fhat")}
    return out


def session_config(mode: str, thr: float, **kw):
    from repro.serving import SessionConfig
    return SessionConfig(mode=mode, threshold=thr, trigger_margin=0.0, **kw)


def run_session(params, cfg, tokens, max_len: int, config) -> dict:
    """Open one session through ``MonitorSession.open``, serve the whole
    stream, close it; numpy traces plus the engine's final server_pos."""
    from repro.serving import MonitorSession
    B = tokens.shape[0]
    t0 = time.perf_counter()
    with MonitorSession.open(params, cfg, batch=B, max_len=max_len,
                             config=config) as s:
        r = s.run(tokens)
        server_pos = np.asarray(s.engine.server_pos).copy()
    out = {k: np.asarray(r[k]) for k in ("u", "fhat", "triggered")}
    out["server_pos"] = server_pos
    out["seconds"] = time.perf_counter() - t0
    del s, r
    gc.collect()
    return out


def run_modes(params, cfg, tokens, thr: float, max_len: int) -> dict:
    """scan, sync and async sessions over the same stream, one at a time."""
    from repro.serving import TransportSpec
    res = {}
    for mode, kw in (("scan", {}), ("sync", {}),
                     ("async", dict(transport=TransportSpec("stream"),
                                    max_staleness=1))):
        res[mode] = run_session(params, cfg, tokens, max_len,
                                session_config(mode, thr, **kw))
        say(f"[modes] {mode}: {tokens.shape[0]} streams x "
            f"{tokens.shape[1]} steps in {res[mode]['seconds']:.2f} s "
            f"(compile included), trigger rate "
            f"{res[mode]['triggered'].mean():.4f}")
    return res


def run_wire(params, cfg, tokens, thr: float, max_len: int) -> dict:
    """A ``wire``-transport sync session whose catch-ups a
    ``CorrectionServer`` on a thread of this process answers over TCP on
    localhost."""
    from repro.serving import TransportSpec
    from repro.serving.server import CorrectionServer
    B = tokens.shape[0]
    srv = CorrectionServer(cfg, params, slots=B, max_len=max_len,
                           host="127.0.0.1", port=0)
    stop = threading.Event()
    th = threading.Thread(target=srv.serve_forever, kwargs={"stop": stop},
                          daemon=True, name="chip-smoke-server")
    th.start()
    try:
        out = run_session(params, cfg, tokens, max_len, session_config(
            "sync", thr, transport=TransportSpec("wire",
                                                 address=srv.address)))
        out["requests"] = int(srv.stats["requests"])
    finally:
        stop.set()
        th.join(timeout=60)
        srv.close()
    if th.is_alive():
        raise PhaseError("correction server thread did not stop")
    if out["requests"] == 0:
        raise PhaseError("the wire session sent no catch-up request")
    say(f"[wire] {out['requests']} catch-up requests answered in "
        f"{out['seconds']:.2f} s (compile included)")
    return out


# --- checks -----------------------------------------------------------------


def _rms(x) -> float:
    return float(np.sqrt(np.mean(np.square(np.asarray(x, np.float64)))))


def _maxdiff(a, b) -> float:
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a.astype(np.float64) - b.astype(np.float64))))


def check_safety(name: str, r: dict) -> None:
    """fhat <= u, exactly: the corrector s*sigma(v) is >= 0."""
    bad = int(np.sum(r["fhat"] > r["u"]))
    say(f"[safety] {name}: fhat <= u at all {r['u'].size} cells "
        f"({bad} violations)")
    if bad:
        raise PhaseError(f"{name}: fhat > u at {bad} cells")


def compare_paths(name: str, a: dict, b: dict) -> None:
    """Path ``a`` against path ``b`` (the in-process sync session) per row.
    One contract on the CPU and on the chip (tests/test_serving.py asserts
    it on the CPU; the chip kept it, PERF.md): u and the trigger decisions
    bitwise, fhat to float32 rounding of the combine.  A break prints the
    largest difference of each output before it fails."""
    du = _maxdiff(a["u"], b["u"])
    flips = int((a["triggered"] != b["triggered"]).sum())
    df = _maxdiff(a["fhat"], b["fhat"])
    tol = F32_ULPS * max(1.0, float(np.abs(b["u"]).max()))
    bitwise = (np.array_equal(a["u"], b["u"]) and flips == 0
               and np.array_equal(a["fhat"], b["fhat"]))
    say(f"[agree] {name} vs sync: max|du| {du!r}  max|dfhat| {df!r} "
        f"(tol {tol!r})  trigger flips {flips}  bitwise {bitwise}")
    if not np.array_equal(a["u"], b["u"]) or flips:
        raise PhaseError(f"{name}: u/trigger not bitwise equal to sync "
                         f"(max|du| {du!r}, {flips} flips)")
    if not df <= tol:
        raise PhaseError(f"{name}: max|dfhat| {df!r} > {tol!r}")


def check_async(res: dict, s: float) -> None:
    """async (max_staleness=1) against sync: the monitor path is the same
    compiled programs, so u and the triggers are bitwise equal; the
    corrector computed at step t lands at step t+1, applied to that step's
    u."""
    a, y = res["async"], res["sync"]
    if not (np.array_equal(a["u"], y["u"])
            and np.array_equal(a["triggered"], y["triggered"])):
        raise PhaseError(
            f"async vs sync: u/trigger not bitwise (max|du| "
            f"{_maxdiff(a['u'], y['u'])!r}, flips "
            f"{int((a['triggered'] != y['triggered']).sum())})")
    corr = y["u"] - y["fhat"]              # s*sigma(v_t) where triggered
    want = y["u"].copy()
    want[:, 1:] = np.where(y["triggered"][:, :-1],
                           y["u"][:, 1:] - corr[:, :-1], y["u"][:, 1:])
    d = _maxdiff(a["fhat"], want)
    tol = F32_ULPS * max(1.0, float(np.abs(y["u"]).max()), s)
    say(f"[agree] async vs sync: u/trigger bitwise True  late-merge "
        f"max|dfhat| {d!r} (tol {tol!r})")
    if not d <= tol:
        raise PhaseError(f"async late merge: max|dfhat| {d!r} > {tol!r}")


def check_reference(sync: dict, ref: dict, s: float) -> None:
    """The sync session against the float32 reference.  u at every cell;
    v and fhat where the session triggered (that is where the server ran).
    v is read back from the corrector the session applied,
    v = logit((u - fhat) / s); the fused combine computes exactly
    fhat = u - s*sigmoid(v), so this costs only float32 rounding."""
    trig = sync["triggered"]
    c = np.clip((sync["u"] - sync["fhat"])[trig] / s, 1e-7, 1 - 1e-7)
    got = {"u": sync["u"], "v": np.log(c) - np.log1p(-c),
           "fhat": sync["fhat"][trig]}
    want = {"u": ref["u"], "v": ref["v"][trig], "fhat": ref["fhat"][trig]}
    for k in ("u", "v", "fhat"):
        err = got[k].astype(np.float64) - want[k].astype(np.float64)
        scale = _rms(want[k])
        rel_rms, rel_max = _rms(err) / scale, np.abs(err).max() / scale
        say(f"[reference] {k}: {err.size} cells  rms(ref) {scale!r}  "
            f"rms err/rms {rel_rms!r} (tol {BF16_REL_RMS!r})  "
            f"max err/rms {float(rel_max)!r} (tol {BF16_REL_MAX!r})")
        if not (rel_rms <= BF16_REL_RMS and rel_max <= BF16_REL_MAX):
            raise PhaseError(f"reference: {k} off by rms {rel_rms!r} / "
                             f"max {float(rel_max)!r} of rms(ref)")


def check_wire(wire: dict, sync: dict) -> None:
    n = wire["u"].shape[1]
    head = {k: sync[k][:, :n] for k in ("u", "fhat", "triggered")}
    compare_paths("wire", wire, head)
    # the catch-up position the server reported home for each stream: one
    # past its last trigger
    trig = head["triggered"]
    last = np.where(trig.any(1), n - np.argmax(trig[:, ::-1], 1), 0)
    if not np.array_equal(wire["server_pos"], last):
        raise PhaseError(f"wire server_pos {wire['server_pos']} != {last}")


# ---------------------------------------------------------------------------
# the one-chip and four-chip runs
# ---------------------------------------------------------------------------


def one_chip(cfg, *, batch: int, max_len: int, steps: int,
             wire_steps: int, seed: int = SEED) -> dict:
    """Every one-chip phase at ``cfg``; raises ``PhaseError`` on a failed
    check.  Returns the results for the caller to inspect."""
    params = make_params(cfg, seed)
    tokens = make_stream(cfg, batch, steps, seed)
    thr = pick_threshold(params, cfg, tokens)
    say(f"[setup] threshold {thr!r} (u quantile {1 - TRIGGER_RATE})")
    ref = reference(params, cfg, tokens)
    say(f"[reference] float32 forward: u {ref['u'].shape}, "
        f"v rms {_rms(ref['v'])!r}")
    res = run_modes(params, cfg, tokens, thr, max_len)
    for mode, r in res.items():
        check_safety(mode, r)
    check_async(res, cfg.monitor.s)
    compare_paths("scan", res["scan"], res["sync"])
    check_reference(res["sync"], ref, cfg.monitor.s)
    wire = run_wire(params, cfg, tokens[:, :wire_steps], thr, max_len)
    check_safety("wire", wire)
    check_wire(wire, res["sync"])
    return {"thr": thr, "ref": ref, "modes": res, "wire": wire}


def four_chips(cfg, *, batch: int, max_len: int, steps: int, n: int,
               seed: int = SEED) -> dict:
    """The mesh-sharded sync session (``mesh="data:n"``: params replicated,
    per-stream state split over n chips) against the unsharded session on
    one chip, same stream."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.serving.mesh import MeshSpec
    tokens = make_stream(cfg, batch, steps, seed)
    params = make_params(cfg, seed)
    thr = pick_threshold(params, cfg, tokens)
    say(f"[setup] threshold {thr!r} (u quantile {1 - TRIGGER_RATE})")
    one = run_session(params, cfg, tokens, max_len,
                      session_config("sync", thr))
    say(f"[mesh] one chip: {one['seconds']:.2f} s (compile included), "
        f"trigger rate {one['triggered'].mean():.4f}")
    # free the one-chip copy before the replicated one: a chip holds one
    del params
    gc.collect()
    spec = MeshSpec.parse(f"data:{n}")
    params = make_params(cfg, seed, out_shardings=NamedSharding(
        spec.build(), P()))
    sharded = run_session(params, cfg, tokens, max_len,
                          session_config("sync", thr, mesh=spec))
    say(f"[mesh] data:{n}: {sharded['seconds']:.2f} s (compile included)")
    for name, r in (("one chip", one), (f"data:{n}", sharded)):
        check_safety(name, r)
    compare_paths(f"data:{n}", sharded, one)
    if not np.array_equal(sharded["server_pos"], one["server_pos"]):
        raise PhaseError("sharded server_pos differs from one chip")
    return {"thr": thr, "one": one, "sharded": sharded}


def _peak(dev) -> str:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "not reported"
    return (f"{stats['peak_bytes_in_use']} B of "
            f"{stats.get('bytes_limit', 'unknown')} B")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded session against the "
                         "one-chip session")
    args = ap.parse_args(argv)
    _repo()
    import jax
    from jax import monitoring
    devs = jax.devices()
    if devs[0].platform != "tpu":
        say(f"chip_smoke: no TPU found: JAX sees {len(devs)} "
            f"{devs[0].platform} device(s); this test needs a TPU")
        return 1
    if len(devs) < args.chips:
        say(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
            "TPU device(s)")
        return 1
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()  # before the first compile
    compile_s = [0.0]
    monitoring.register_event_duration_secs_listener(
        lambda ev, d, **_: compile_s.__setitem__(0, compile_s[0] + d)
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    from repro.configs import registry
    from repro.kernels import ops
    cfg = registry.get_preset("granite-8b", "chip")
    full = registry.get_preset("granite-8b", "full")
    say(f"[device] {devs[0].platform} {devs[0].device_kind} x {len(devs)}; "
        f"compile cache {cache_dir}")
    say(f"[config] {cfg.name} CHIP preset: d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads} x {cfg.resolved_head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; depth cut {full.n_layers} -> "
        f"{cfg.n_layers} layers; dtype {cfg.dtype}, params "
        f"{cfg.param_dtype}; {cfg.param_count() / 1e6:.1f} M server params")
    say(f"[config] batch {BATCH} x max_len {MAX_LEN}, {STEPS} steps; "
        f"monitor_combine impl {ops.combine_impl()}")
    if ops.combine_impl() != "pallas":
        say("chip_smoke: monitor_combine would not run the Pallas kernel")
        return 1

    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(cfg, batch=BATCH, max_len=MAX_LEN, steps=STEPS,
                     wire_steps=WIRE_STEPS)
        else:
            four_chips(cfg, batch=BATCH, max_len=MAX_LEN, steps=STEPS,
                       n=args.chips)
    except PhaseError as e:
        say(f"chip_smoke: FAILED: {e}")
        return 1
    for d in devs[:args.chips]:
        say(f"[memory] {d}: peak_bytes_in_use {_peak(d)}")
    say(f"[time] {time.perf_counter() - t0:.1f} s in phases, "
        f"{compile_s[0]:.1f} s of it compiling")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
