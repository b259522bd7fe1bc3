"""Correction-server launcher: run the server half of the collaborative
protocol as its own process (``serving/server.py``), listening on a
Unix-domain or TCP socket for ``wire``-transport edge engines.

Client and server must agree on the model: both sides build the SAME
config and deterministic PRNGKey(0) init (or both restore the same
checkpoint via ``--ckpt-dir``) — parameters never cross the wire, only
protocol bytes (backlog tokens, scores) do.

Run:  PYTHONPATH=src python -m repro.launch.server --arch granite-8b \
          --uds /tmp/corr.sock --slots 16 --max-len 72
      PYTHONPATH=src python -m repro.launch.server \
          --arch paper-synthetic-serving --port 7431 --slots 128

then point clients at it:

      PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
          --engine collab --mode async --transport wire \
          --address /tmp/corr.sock
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time
from typing import Optional, Tuple

import jax

from repro.configs import registry
from repro.core import decomposition as deco
from repro.training import checkpoint as ckpt


def resolve_config(name: str, preset: str = "smoke"):
    """Registry archs at ``preset`` (``registry.PRESETS``) plus the
    paper-synthetic SERVING preset (the bench_serving workload, which
    lives outside the registry)."""
    if name == "paper-synthetic-serving":
        from repro.configs.paper_synthetic import SERVING
        return SERVING
    return registry.get_preset(name, preset)


def config_names():
    return registry.names() + ["paper-synthetic-serving"]


def check_chip_free(n_children: int = 1) -> None:
    """Refuse to start correction-server processes that would fight over
    an accelerator.

    A chip belongs to one process at a time: a parent that has opened a
    TPU backend holds every chip of the host, and each child started
    without its own chip asks for all of them.  Children may start when
    they run on the CPU (``JAX_PLATFORMS=cpu``, or the parent's own
    backend is the CPU), or when the parent is off JAX and starts one.
    Otherwise this raises; serve from one process instead (a
    ``CorrectionServer`` on a thread, fleet ``backend="thread"``)."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        backend = jax.default_backend()
        if backend == "cpu":
            return
        raise RuntimeError(
            f"this process holds the {backend} devices, so a correction-"
            "server child could not open them: run the server in this "
            "process (a CorrectionServer on a thread, or fleet "
            "backend='thread'), or run both on the CPU with "
            "JAX_PLATFORMS=cpu")
    if n_children > 1:
        raise RuntimeError(
            f"{n_children} correction-server processes would each open "
            "every accelerator chip of this host: start one, serve the "
            "replicas from one process (fleet backend='thread'), or run "
            "them on the CPU with JAX_PLATFORMS=cpu")


def spawn_subprocess(arch: str, *, uds: str, slots: int, max_len: int,
                     ready_file: str, ckpt_dir: Optional[str] = None,
                     extra_args: Tuple[str, ...] = (), quiet: bool = True,
                     timeout_s: Optional[float] = None,
                     wait: bool = True) -> "subprocess.Popen":
    """Start ``python -m repro.launch.server`` as a subprocess and block
    until it is listening (the ready file appears) or ``timeout_s``
    elapses.  Shared by the bench, the example demo, tests, and the
    fleet supervisor so the spawn/ready/teardown dance exists once.

    ``timeout_s=None`` uses the ``REPRO_SPAWN_DEADLINE_S`` env override
    (default 240 s — jax import on a loaded 2-core CI container can eat
    most of the old hardcoded 180 s).  ``wait=False`` returns the Popen
    immediately (the fleet supervisor ready-waits N servers in parallel
    with ``wait_ready``).  Refuses where the child would contend for an
    accelerator (``check_chip_free``)."""
    import subprocess

    check_chip_free()
    if timeout_s is None:
        timeout_s = float(os.environ.get("REPRO_SPAWN_DEADLINE_S", "240"))
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "repro.launch.server", "--arch", arch,
           "--uds", uds, "--slots", str(slots), "--max-len", str(max_len),
           "--ready-file", ready_file]
    if ckpt_dir:
        cmd += ["--ckpt-dir", ckpt_dir]
    cmd += list(extra_args)
    pipe = subprocess.PIPE if quiet else None
    proc = subprocess.Popen(cmd, env=env, stdout=pipe, stderr=pipe,
                            text=quiet or None)
    if wait:
        wait_ready(proc, ready_file, timeout_s, quiet=quiet)
    return proc


def wait_ready(proc: "subprocess.Popen", ready_file: str,
               timeout_s: float, *, quiet: bool = True) -> None:
    """Block until ``ready_file`` exists or the process dies/times out."""
    deadline = time.monotonic() + timeout_s
    while not os.path.exists(ready_file):
        if proc.poll() is not None:
            err = proc.stderr.read()[-2000:] if quiet else ""
            raise RuntimeError(f"correction server died: {err}")
        if time.monotonic() > deadline:
            proc.terminate()
            raise RuntimeError("correction server startup timed out")
        time.sleep(0.05)


def _force_host_devices(mesh: str) -> None:
    """CPU convenience for ``--mesh data:N``: pin the placeholder host
    device count so a plain CPU host (which exposes ONE device) can
    build the mesh.  Must run before the first jax computation — the
    backend initialises lazily, so appending to XLA_FLAGS here works as
    long as nothing has touched devices yet.  A count already pinned in
    XLA_FLAGS wins; the flag only affects the host (CPU) platform."""
    from repro.serving.mesh import MeshSpec
    n = MeshSpec.parse(mesh).n_devices
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", required=True, choices=config_names())
    ap.add_argument("--preset", choices=registry.PRESETS, default="smoke",
                    help="registry archs: smoke (CPU toy), chip (one "
                         "chip's share at the published widths, where "
                         "defined) or full")
    ap.add_argument("--uds", default=None, help="Unix-domain socket path")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=None,
                    help="TCP port (0 = ephemeral); default is UDS")
    ap.add_argument("--slots", type=int, default=16,
                    help="super-batch rows leased to client sessions")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--no-coalesce", action="store_true",
                    help="disable request coalescing server-wide "
                         "(per-request replays; the bench baseline)")
    ap.add_argument("--transport", choices=("wire", "shm"), default="wire",
                    help="'shm' additionally offers same-host clients a "
                         "shared-memory ring arena on the HELLO handshake "
                         "(UDS only; wire clients are still served)")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="shard the super-batch cache over a device mesh, "
                         "e.g. 'data:8' (slots must divide; on a CPU host "
                         "the placeholder device count is forced "
                         "automatically — see docs/sharding.md)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ready-file", default=None,
                    help="touch this path once listening (subprocess sync)")
    ap.add_argument("--idle-exit-s", type=float, default=None,
                    help="exit after all sessions have been gone this long")
    ap.add_argument("--stats-file", default=None,
                    help="heartbeat: atomically rewrite this JSON file "
                         "with a stats snapshot every --stats-interval-s "
                         "(the fleet supervisor's load/liveness channel)")
    ap.add_argument("--stats-interval-s", type=float, default=0.5)
    ap.add_argument("--trace-file", default=None,
                    help="record server-side spans (queue wait / replay) "
                         "and export Perfetto JSON here on shutdown")
    args = ap.parse_args(argv)

    if (args.uds is None) == (args.port is None):
        ap.error("exactly one of --uds / --port is required")

    if args.mesh is not None:
        # must precede the first jax computation: a CPU host exposes one
        # device unless the platform device count is forced.  jax was
        # only IMPORTED above (the backend initialises lazily at first
        # use), so setting XLA_FLAGS here still takes effect.
        _force_host_devices(args.mesh)

    from repro.launch import compile_cache
    compile_cache.enable()
    cfg = resolve_config(args.arch, args.preset)
    params = deco.init_collab_lm(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        _, params, _ = ckpt.load(args.ckpt_dir, params)
        print(f"restored {args.ckpt_dir}", flush=True)

    from repro.serving.server import CorrectionServer
    from repro.serving.tracker import JsonFileTracker
    tracker = (JsonFileTracker(args.stats_file)
               if args.stats_file else None)
    tracer = None
    if args.trace_file:
        from repro.observability import Tracer
        tracer = Tracer()
    srv = CorrectionServer(cfg, params, slots=args.slots,
                           max_len=args.max_len, uds=args.uds,
                           host=args.host,
                           port=args.port if args.port is not None else 0,
                           coalesce=not args.no_coalesce, mesh=args.mesh,
                           tracker=tracker, tracer=tracer,
                           stats_interval_s=args.stats_interval_s,
                           shm=args.transport == "shm")
    print(f"correction server: arch={args.arch} slots={args.slots} "
          f"max_len={args.max_len} coalesce={not args.no_coalesce} "
          f"transport={args.transport} "
          f"mesh={srv.mesh_spec} listening on {srv.address}", flush=True)
    if args.ready_file:
        with open(args.ready_file, "w") as fh:
            fh.write(srv.address + "\n")

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, lambda *_: stop.set())
        except ValueError:
            pass  # not the main thread
    try:
        # SIGUSR1 = drain: GOAWAY the sessions, refuse new HELLOs, exit
        # once empty — the fleet supervisor's graceful-retire signal
        signal.signal(signal.SIGUSR1, lambda *_: srv.request_drain())
    except (ValueError, AttributeError):
        pass  # not the main thread / platform without SIGUSR1
    try:
        srv.serve_forever(stop=stop, idle_exit_s=args.idle_exit_s)
    finally:
        st = srv.stats
        if tracker is not None:
            tracker.log_summary(srv.stats_snapshot())
        if tracer is not None:
            n = tracer.export(args.trace_file)
            print(f"trace: {n} spans -> {args.trace_file}", flush=True)
        print(f"served {st['sessions']} sessions, {st['requests']} requests "
              f"in {st['replays']} replays ({st['coalesced']} coalesced), "
              f"{st['attaches']} attaches / {st['detaches']} detaches, "
              f"{st['defrags']} lease defrags "
              f"(lease_fragmentation={srv.fragmentation():.3f}), "
              f"rx {st['bytes_rx']:,}B tx {st['bytes_tx']:,}B", flush=True)
        srv.close()


if __name__ == "__main__":
    main()
