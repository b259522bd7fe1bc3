"""Serving launcher: batched collaborative monitoring over token streams.

Two engines:

  * the default jitted serve step (server decode + corrector, edge decode
    + monitor, gated combine) — the same function the dry-run lowers for
    decode_32k / long_500k; it runs on the host mesh with a reduced config.
  * ``--engine collab`` — the trigger-gated collaborative engine, served
    through the ``MonitorSession`` API: one ``SessionConfig`` describes
    the mode (sync / async), transport, staleness, and address
    (``--transport``, ``--max-staleness``, ``--latency-ms`` — see
    docs/api.md and docs/protocol.md).

Run:  PYTHONPATH=src python -m repro.launch.serve --arch zamba2-7b \
          --tokens 64 --batch 4
      PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
          --preset chip --engine collab --batch 32 --tokens 32
      PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
          --engine collab --mode async --latency-ms 20 --max-staleness 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core import decomposition as deco
from repro.data import tokens as tok
from repro.launch import compile_cache
from repro.launch.steps import EDGE_CACHE_LEN, make_serve_step
from repro.models import api as model_api
from repro.training import checkpoint as ckpt


def run_collab(args, cfg, params) -> None:
    """Trigger-gated collaborative serving through the MonitorSession API
    (sync, or async-pipelined; any transport incl. the real wire)."""
    from repro.serving import SessionConfig, TransportSpec
    from repro.serving.collaborative import CollaborativeEngine

    B, S = args.batch, args.tokens
    stream = next(tok.lm_batches(5, cfg, B, S))["tokens"]
    eng = CollaborativeEngine(params, cfg, batch=B, max_len=S + 8)
    if args.transport in ("wire", "shm") and not args.address:
        raise SystemExit(f"--transport {args.transport} needs --address "
                         "(start: python -m repro.launch.server)")
    latency_s = (None if args.latency_ms is None or args.transport in
                 ("inproc", "wire", "shm") else args.latency_ms * 1e-3)
    # one config describes the whole session: mode="sync" over the wire is
    # the strict max_staleness=0 boundary (every trigger pays the measured
    # round trip); plain sync uses the blocking in-process path
    spec = (TransportSpec(args.transport, address=args.address,
                          latency_s=latency_s)
            if (args.mode == "async" or args.transport in ("wire", "shm"))
            else TransportSpec())
    config = SessionConfig(mode=args.mode, transport=spec,
                           max_staleness=args.max_staleness,
                           mesh=args.mesh, trace=args.trace is not None)
    t0 = time.time()
    with eng.session(config) as session:
        res = session.run(stream)
        if args.trace is not None:
            n = session.export_trace(args.trace)
            print(f"trace: {n} spans -> {args.trace} "
                  "(load in Perfetto / chrome://tracing)")
            from repro.observability.report import breakdown_table
            for line in breakdown_table(session.tracer.spans()):
                print(line)
    dt = (time.time() - t0) / S
    print(f"{args.mode} collab engine: {S} steps x batch {B}:  "
          f"{dt * 1e3:.1f} ms/step  ({B / dt:.1f} tok/s)")
    for b in range(B):
        print(f"  stream {b}: "
              + "".join("!" if x else "." for x in res["triggered"][b]))
    rep = res["comms"]
    print(f"trigger rate {rep['trigger_rate']:.3f}  |  "
          f"reduction {rep['reduction_x']:.1f}x")
    if "async" in rep:
        a = rep["async"]
        print(f"async: {a['requests']} requests, {a['merged_late']} merged "
              f"late, overlap {a['overlap_ratio']:.2f}, "
              f"stall {a['stall_s'] * 1e3:.0f} ms")
    if "wire" in rep:
        w = rep["wire"]
        print(f"wire (measured): {w['tx_bytes']:,}B tx / "
              f"{w['rx_bytes']:,}B rx, RTT mean "
              f"{w['rtt_mean_s'] * 1e3:.2f} ms / max "
              f"{w['rtt_max_s'] * 1e3:.2f} ms over {w['replies']} replies")
    if "shm" in rep:
        s = rep["shm"]
        print(f"shm rings (measured): {s['tx_bytes']:,}B tx / "
              f"{s['rx_bytes']:,}B rx, RTT mean "
              f"{s['rtt_mean_s'] * 1e3:.2f} ms / max "
              f"{s['rtt_max_s'] * 1e3:.2f} ms over {s['replies']} replies")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.names())
    ap.add_argument("--preset", choices=registry.PRESETS, default="smoke",
                    help="smoke: CPU toy; chip: one chip's share at the "
                         "published widths (where defined); full")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--engine", choices=("step", "collab"), default="step")
    ap.add_argument("--mode", choices=("sync", "async"), default="sync")
    ap.add_argument("--transport", default="stream",
                    choices=("inproc", "stream", "thread", "mock_remote",
                             "wire", "shm"))
    ap.add_argument("--address", default=None,
                    help="wire/shm transport: correction server UDS path "
                         "or host:port (python -m repro.launch.server; "
                         "shm needs a UDS on the same host)")
    ap.add_argument("--max-staleness", type=int, default=8)
    ap.add_argument("--latency-ms", type=float, default=None,
                    help="simulated RTT; default keeps the transport's own")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="collab engine only: mesh-shard per-stream state, "
                         "e.g. 'data:8' (batch must divide; see "
                         "docs/sharding.md)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="collab engine only: trace the session "
                         "(SessionConfig(trace=True)) and export Perfetto "
                         "JSON to FILE, printing the critical-path "
                         "breakdown (docs/observability.md)")
    args = ap.parse_args()
    if args.trace is not None and args.engine != "collab":
        ap.error("--trace serves the collab engine (use --engine collab)")

    if args.mesh is not None:
        if args.engine != "collab":
            ap.error("--mesh serves the collab engine (use --engine collab)")
        from repro.launch.server import _force_host_devices
        _force_host_devices(args.mesh)  # before the first jax computation

    compile_cache.enable()
    cfg = registry.get_preset(args.arch, args.preset)
    key = jax.random.PRNGKey(0)
    params = deco.init_collab_lm(key, cfg)
    if args.ckpt_dir:
        _, params, _ = ckpt.load(args.ckpt_dir, params)
        print(f"restored {args.ckpt_dir}")

    if args.engine == "collab":
        run_collab(args, cfg, params)
        return

    B, cap = args.batch, args.tokens + 8
    ecfg = deco.edge_arch(cfg)
    server_cache = model_api.init_cache(cfg, B, cap)
    edge_cache = model_api.init_cache(ecfg, B, min(cap, EDGE_CACHE_LEN))
    serve_step = jax.jit(make_serve_step(cfg))

    stream = next(tok.lm_batches(5, cfg, B, args.tokens))["tokens"]
    trig = np.zeros((B, args.tokens), bool)
    t0 = time.time()
    for t in range(args.tokens):
        out = serve_step(params, server_cache, edge_cache,
                         jnp.asarray(stream[:, t]), jnp.asarray(t, jnp.int32))
        server_cache, edge_cache = out["server_cache"], out["edge_cache"]
        trig[:, t] = np.asarray(out["mask"]) > 0
    dt = (time.time() - t0) / args.tokens
    print(f"{args.tokens} steps x batch {B}:  {dt*1e3:.1f} ms/step  "
          f"({B/dt:.1f} tok/s)")
    for b in range(B):
        print(f"  stream {b}: " + "".join("!" if x else "." for x in trig[b]))
    print(f"trigger rate {trig.mean():.3f}")


if __name__ == "__main__":
    main()
