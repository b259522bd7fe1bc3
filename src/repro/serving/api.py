"""The public serving API: ``MonitorSession`` — one session-oriented
entrypoint over the collaborative engine, with dynamic stream membership.

After PRs 1-3 the engine had grown seven overlapping entrypoints
(``step``/``run``/``run_scan``/``start_async``/``step_async``/
``finish_async``/``run_async``) with transport, address, staleness and
coalescing knobs split across the constructor, method kwargs, and two
CLIs — and batch membership frozen at construction.  This module folds
all of that into three small objects:

  * ``TransportSpec``  — WHERE the server half runs: one parsed spec
    unifying the five transports (``inproc`` / ``stream`` / ``thread`` /
    ``mock_remote`` / ``wire``) with their address / simulated-latency /
    coalescing knobs, parseable from a string
    (``"wire:/tmp/corr.sock"``).
  * ``SessionConfig``  — HOW a session serves: execution mode
    (``sync`` | ``scan`` | ``async``), the transport, the staleness
    merge window, and optional monitor-operating-point overrides
    (threshold / margin / scan capacity / truncation n).  Frozen: a
    config can be shared, logged, and compared.
  * ``MonitorSession`` — the session itself: a context manager that
    dispatches ``step`` / ``run`` / ``stream`` to the engine's private
    jitted sync, scan, and async paths, and manages the SLOT POOL —
    ``attach(stream_id)`` admits a monitored stream into a free slot of
    the engine's batch mid-flight, ``detach(stream_id)`` retires one;
    results are keyed by the caller's stream ids.

Slot-pool semantics (the paper's fleet-of-devices deployment — devices
arrive and depart; cf. the device-session framing of *Collaborative
Inference for AI-Empowered IoT Devices*):

  * every stream occupies one slot (batch row) of the engine; a freshly
    attached stream starts bit-cold (edge + server cache rows, token
    history, positions all zeroed — exactly a fresh engine's row) at its
    own position 0 while co-resident streams keep their clocks;
  * same-position cohorts decode in ONE dense masked call
    (``ServeEngine.decode_masked``), which is per-row bitwise identical
    to the plain batched decode — so streams present for a whole run
    produce bit-identical u/trigger traces to a fixed-batch run, churn
    or no churn (asserted in tests);
  * detached slots are masked out of decode, triggers, and the
    ``CommsMeter`` — they stop accruing communication charges;
  * in async mode a membership change first drains the pipeline (a
    reply must never land on a re-leased slot); over the ``wire``
    transport the change is mirrored to the correction server with
    ATTACH/DETACH frames so it zeroes and re-leases the single
    super-batch row without disturbing co-resident clients.

Typical use::

    from repro.serving import MonitorSession, SessionConfig, TransportSpec

    eng = CollaborativeEngine(params, cfg, batch=8, max_len=128)
    with eng.session(SessionConfig(mode="async", max_staleness=8)) as s:
        for out in s.stream(token_batches):   # dicts keyed by stream id
            ...
        s.detach(3)                           # device 3 went offline
        s.attach("device-9")                  # a new device joined

See docs/api.md for the full lifecycle state machine and the migration
table from the deprecated per-method API.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Hashable, Iterable, Iterator, Optional, Tuple, Union

import numpy as np

from repro.observability import span
from repro.serving.async_rpc import TRANSPORTS

MODES = ("sync", "scan", "async")


@dataclass(frozen=True)
class TransportSpec:
    """Where (and over what) the server half of the protocol runs.

    kind      — one of ``inproc`` (compute at dispatch, deterministic),
                ``stream`` (JAX async dispatch overlap), ``thread``
                (worker thread), ``mock_remote`` (thread + simulated
                RTT), ``wire`` (real socket to a standalone correction
                server — ``python -m repro.launch.server``), ``shm``
                (wire protocol over same-host shared-memory rings —
                ``TransportSpec.parse("shm:/tmp/corr.sock")``; falls
                back to plain wire, with a logged reason, when the
                server is remote or offers no arena).
    address   — ``wire``/``shm``: UDS path or ``host:port`` of a server,
                or ``fleet:<router-address>`` to connect through a
                ``FleetSupervisor`` router (``python -m
                repro.launch.fleet``): the session HELLOs the router,
                follows its REDIRECT to the least-loaded live server,
                and transparently fails over — re-HELLO + replay — if
                that server dies or drains (serving/fleet.py,
                docs/fleet.md).  ``TransportSpec.parse("fleet:...")``
                is shorthand for ``wire`` with a fleet address.
    latency_s — simulated round trip (stream/thread/mock_remote only;
                the wire has whatever latency it actually has).
    coalesce  — ``wire`` only: opt out of server-side request
                coalescing when False (per-request replays).
    """

    kind: str = "inproc"
    address: Optional[str] = None
    latency_s: Optional[float] = None
    coalesce: bool = True

    def __post_init__(self):
        if self.kind not in TRANSPORTS:
            raise ValueError(
                f"unknown transport {self.kind!r}: valid transports are "
                + ", ".join(repr(t) for t in TRANSPORTS))
        if self.address is not None and self.kind not in ("wire", "shm"):
            raise ValueError(
                f"transport {self.kind!r} takes no address "
                "(only 'wire' and 'shm')")
        if self.kind in ("wire", "shm") and self.address is None:
            raise ValueError(
                f"{self.kind} transport needs an address (the correction "
                "server's UDS path or host:port — python -m "
                "repro.launch.server)")
        if self.latency_s is not None and self.kind in ("inproc", "wire",
                                                        "shm"):
            raise ValueError(
                f"transport {self.kind!r} has no latency model"
                + (": RTT is measured on the real socket"
                   if self.kind in ("wire", "shm") else ""))

    @classmethod
    def parse(cls, spec: Union[str, "TransportSpec"]) -> "TransportSpec":
        """``"stream"`` -> TransportSpec("stream");
        ``"wire:/tmp/corr.sock"`` / ``"wire:host:port"`` -> wire + address;
        ``"shm:/tmp/corr.sock"`` -> same-host shared-memory rings;
        ``"fleet:/tmp/router.sock"`` -> wire through a fleet router.
        A TransportSpec passes through unchanged."""
        if isinstance(spec, cls):
            return spec
        s = str(spec)
        if s.startswith("fleet:"):
            return cls("wire", address=s)
        kind, sep, rest = s.partition(":")
        return cls(kind, address=rest if sep else None)


@dataclass(frozen=True)
class SessionConfig:
    """How a ``MonitorSession`` serves.  Frozen and validated.

    mode           — ``sync`` (each trigger blocks on the server; with a
                     non-inproc transport this is the strict
                     ``max_staleness=0`` boundary), ``scan`` (offline
                     compiled trace evaluation, fixed membership), or
                     ``async`` (pipelined: corrections merge 1..
                     ``max_staleness`` steps late, the monitor path
                     never waits).
    transport      — a ``TransportSpec`` or parseable string.
    max_staleness  — async merge window (ignored for sync/scan).
    mesh           — mesh-sharded serving (``serving/mesh.py``): a
                     ``MeshSpec`` or ``"data:8"``-style string.  The
                     session shards the engine at open — params
                     replicated, per-stream state batch-sharded over
                     the mesh ``data`` axis, monitor path asserted
                     collective-free.  Per-row numerics are unchanged
                     (NOT an operating point: an engine already sharded
                     over the same mesh is accepted as-is).
    threshold / trigger_margin — monitor operating-point overrides,
                     applied at engine construction by
                     ``MonitorSession.open`` (an existing engine must
                     already match — ``engine.session`` refuses silent
                     mismatches).
    policy         — a ``repro.serving.policy.TriggerPolicy``: per-stream
                     online threshold control.  The session binds it to
                     the engine's calibrated operating point at open,
                     reads its (B,) thresholds before every step, and
                     feeds the step outcome (+ the CommsMeter's windowed
                     rate gauge) back.  Mutually exclusive with
                     ``threshold`` — a policy OWNS the trigger point, so
                     combining them is refused loudly rather than
                     silently ignoring one.  ``None`` (default): the
                     fixed calibrated threshold, bit-identical to
                     pre-policy behavior.  Controller state is
                     client-held: fleet failover replay preserves it;
                     ``attach`` cold-starts the slot's controller.
    capacity       — scan mode's static correction capacity.
    monitor_n      — Eq.-8 truncation override for the serving u head.
    trace          — span tracing (``docs/observability.md``): the
                     session installs a ``repro.observability.Tracer``
                     on the engine for its lifetime; read it via
                     ``MonitorSession.tracer`` / ``export_trace``.
                     Default OFF: the disabled path is a flag check per
                     instrumentation site, and traced sessions are
                     bitwise identical to untraced ones (tested).
    trace_capacity — span ring bound when tracing (oldest dropped).
    """

    mode: str = "sync"
    transport: TransportSpec = field(default_factory=TransportSpec)
    max_staleness: int = 1
    policy: Optional[Any] = None  # TriggerPolicy | None (fixed threshold)
    threshold: Optional[float] = None
    trigger_margin: Optional[float] = None
    capacity: Optional[int] = None
    monitor_n: Optional[int] = None
    mesh: Optional[Any] = None  # MeshSpec | "data:8" | None (unsharded)
    trace: bool = False
    trace_capacity: int = 65536

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r}: valid modes are "
                             + ", ".join(repr(m) for m in MODES))
        if not isinstance(self.transport, TransportSpec):
            object.__setattr__(self, "transport",
                               TransportSpec.parse(self.transport))
        if self.max_staleness < 0:
            raise ValueError("max_staleness must be >= 0")
        if self.trace_capacity <= 0:
            raise ValueError("trace_capacity must be >= 1")
        if self.mode == "scan" and self.transport != TransportSpec():
            raise ValueError("scan mode is offline: it takes no transport")
        if self.policy is not None:
            if self.threshold is not None:
                # refuse rather than silently ignore one of them: a
                # policy OWNS the trigger point (its floor is the
                # engine's calibrated threshold)
                raise ValueError(
                    f"SessionConfig.threshold={self.threshold} and "
                    f"SessionConfig.policy={type(self.policy).__name__} "
                    "are mutually exclusive: a policy owns the trigger "
                    "point (bound to the engine's calibrated operating "
                    "point at open) — set the operating point via "
                    "threshold= alone, or let the policy drive it")
            from repro.serving.policy import TriggerPolicy
            if not isinstance(self.policy, TriggerPolicy):
                raise ValueError(
                    f"SessionConfig.policy must be a TriggerPolicy, got "
                    f"{type(self.policy).__name__}")
        if self.mesh is not None:
            from repro.serving.mesh import MeshSpec
            object.__setattr__(self, "mesh", MeshSpec.parse(self.mesh))

    @property
    def needs_worker(self) -> bool:
        """Whether this session runs through the dispatch/merge layer
        (async mode, or sync over a real/simulated transport)."""
        return (self.mode == "async"
                or (self.mode == "sync" and self.transport.kind != "inproc"))

    @property
    def effective_staleness(self) -> int:
        """sync mode over a transport is the strict boundary."""
        return self.max_staleness if self.mode == "async" else 0


class MonitorSession:
    """A context-managed serving session over one ``CollaborativeEngine``
    — the single public serving entrypoint.

    Lifecycle: ``new`` -> (first step/run/enter) ``open`` -> ``closed``.
    ``run`` on a worker-backed session (async, or sync over a transport)
    drains the pipeline tail and closes the session when the stream
    ends; ``step``-driven sessions close at ``__exit__``/``close()``.
    The session assumes it owns the engine's protocol state for its
    lifetime; one engine serves one session at a time.

    Results (``step``/``stream`` dicts, ``run`` stacked traces) carry
    the attached streams' rows in slot order, with the ids under
    ``"streams"``.
    """

    def __init__(self, engine, config: Optional[SessionConfig] = None, *,
                 streams: Optional[Iterable[Hashable]] = None, worker=None):
        self._engine = engine
        self.config = config if config is not None else SessionConfig()
        self._check_engine_matches(engine, self.config)
        self._worker = worker
        self._state = "new"
        # bind the threshold policy to the engine's calibrated operating
        # point.  Controller state lives HERE (client side, like the
        # token history): fleet failover replays without touching it.
        self._policy = self.config.policy
        if self._policy is not None:
            self._policy.bind(threshold=engine.m.threshold,
                              margin=engine.m.trigger_margin,
                              batch=engine.batch)
        B = engine.batch
        ids = list(range(B)) if streams is None else list(streams)
        if len(ids) > B:
            raise ValueError(f"{len(ids)} initial streams > {B} slots")
        if len(set(ids)) != len(ids):
            raise ValueError("duplicate stream ids")
        # initial membership: ids occupy slots 0..n-1.  On a fresh engine
        # the rows have never been used, so no zeroing is needed.  When
        # EXPLICIT stream ids are given on a previously-stepped engine,
        # the bit-cold guarantee applies: every initial slot is reset
        # exactly like a mid-session attach.  Default membership
        # (streams=None) on a used engine instead RESUMES the engine's
        # protocol state — the continuation semantics the deprecated
        # run* shims rely on.
        self._slots: list = [None] * B
        for slot, sid in enumerate(ids):
            self._slots[slot] = sid
        engine.active = np.asarray([s is not None for s in self._slots])
        if streams is not None and engine.t > 0:
            for slot, sid in enumerate(self._slots):
                if sid is not None:
                    engine._attach_slot(slot)

    @staticmethod
    def _check_engine_matches(engine, config: SessionConfig) -> None:
        m = engine.m
        for name, want, have in (
                ("threshold", config.threshold, m.threshold),
                ("trigger_margin", config.trigger_margin, m.trigger_margin),
                ("capacity", config.capacity, engine.capacity),
                ("monitor_n", config.monitor_n, engine.monitor_n)):
            if want is not None and want != have:
                raise ValueError(
                    f"SessionConfig.{name}={want} != the engine's {have}: "
                    "operating-point overrides apply at engine construction "
                    "— build the session with MonitorSession.open(...)")

    @classmethod
    def open(cls, params, arch_cfg, *, batch: int, max_len: int,
             config: Optional[SessionConfig] = None,
             streams: Optional[Iterable[Hashable]] = None) -> "MonitorSession":
        """Build engine + session in one call, applying the config's
        monitor operating-point overrides (threshold / margin /
        capacity / monitor_n) at engine construction."""
        from repro.serving.collaborative import CollaborativeEngine
        config = config if config is not None else SessionConfig()
        if config.threshold is not None or config.trigger_margin is not None:
            mon = arch_cfg.monitor
            kw = {**mon.__dict__}
            if config.threshold is not None:
                kw["threshold"] = config.threshold
            if config.trigger_margin is not None:
                kw["trigger_margin"] = config.trigger_margin
            arch_cfg = arch_cfg.replace(monitor=mon.__class__(**kw))
        eng = CollaborativeEngine(params, arch_cfg, batch=batch,
                                  max_len=max_len, capacity=config.capacity,
                                  monitor_n=config.monitor_n)
        return cls(eng, config, streams=streams)

    # -- lifecycle -----------------------------------------------------------
    @property
    def state(self) -> str:
        return self._state

    @property
    def engine(self):
        return self._engine

    def __enter__(self) -> "MonitorSession":
        self._ensure_open()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _ensure_open(self) -> None:
        if self._state == "open":
            return
        if self._state == "closed":
            raise RuntimeError("session is closed")
        if self.config.mesh is not None:
            # transparently shard at open (BEFORE any worker is built:
            # the worker must adopt the sharded cache + re-jitted
            # catch-up).  Idempotent when the engine already carries the
            # same mesh; loud on a mismatch.
            from repro.serving.mesh import ensure_sharded
            ensure_sharded(self._engine, self.config.mesh)
        if self.config.trace:
            # install the tracer BEFORE any worker is built so the
            # dispatcher / socket worker capture it at construction
            from repro.observability import Tracer
            self._engine._tracer = Tracer(self.config.trace_capacity)
        else:
            # don't inherit a previous session's tracer on a reused engine
            self._engine._tracer = None
        if self.config.needs_worker:
            spec = self.config.transport
            self._engine._start_async(
                transport=spec.kind,
                max_staleness=self.config.effective_staleness,
                latency_s=spec.latency_s, address=spec.address,
                wire_coalesce=spec.coalesce, worker=self._worker)
        self._state = "open"

    def close(self) -> None:
        """Drain + close.  Idempotent."""
        if self._state == "open" and self.config.needs_worker:
            self._engine._finish_async()
        self._state = "closed"

    # -- membership (the slot pool) ------------------------------------------
    @property
    def streams(self) -> Tuple[Hashable, ...]:
        """Attached stream ids, in slot order (the row order of every
        result)."""
        return tuple(s for s in self._slots if s is not None)

    @property
    def n_attached(self) -> int:
        return sum(s is not None for s in self._slots)

    def slot_of(self, stream_id: Hashable) -> int:
        for slot, sid in enumerate(self._slots):
            if sid == stream_id:
                return slot
        raise KeyError(f"stream {stream_id!r} is not attached")

    def attach(self, stream_id: Hashable) -> int:
        """Admit ``stream_id`` into a free slot (bit-cold state; its
        position starts at 0 regardless of the session's age).  Returns
        the slot index.  Raises when the pool is full, the id is already
        attached, or the session is scan-mode/closed."""
        if self.config.mode == "scan":
            raise RuntimeError("scan sessions have fixed membership")
        if self._state == "closed":
            raise RuntimeError("session is closed")
        if any(sid == stream_id for sid in self._slots if sid is not None):
            raise ValueError(f"stream {stream_id!r} is already attached")
        for slot, sid in enumerate(self._slots):
            if sid is None:
                break
        else:
            raise RuntimeError(
                f"slot pool full ({self._engine.batch} slots): detach a "
                "stream first or build a larger engine")
        self._engine._attach_slot(slot)
        if self._policy is not None:
            # fresh tenant -> cold controller: no threshold or evidence
            # leakage from the slot's previous stream
            self._policy.reset_stream(slot)
        self._slots[slot] = stream_id
        return slot

    def detach(self, stream_id: Hashable) -> None:
        """Retire ``stream_id``: its slot stops decoding, triggering, and
        accruing comms charges, and becomes reusable by ``attach``.  In
        async mode the pipeline drains first (no reply may land on a
        re-leased slot)."""
        if self.config.mode == "scan":
            raise RuntimeError("scan sessions have fixed membership")
        if self._state == "closed":
            raise RuntimeError("session is closed")
        slot = self.slot_of(stream_id)
        self._engine._detach_slot(slot)
        self._slots[slot] = None

    # -- serving -------------------------------------------------------------
    def _attached_slot_idx(self) -> np.ndarray:
        return np.asarray([i for i, s in enumerate(self._slots)
                           if s is not None], np.int64)

    def _full_pool(self) -> bool:
        return all(s is not None for s in self._slots)

    def _expand(self, tokens) -> Any:
        """Caller tokens (dict by stream id, or an array over the
        attached streams in slot order) -> full-batch array."""
        ids = self.streams
        if isinstance(tokens, dict):
            missing = set(ids) - set(tokens)
            extra = set(tokens) - set(ids)
            if missing or extra:
                raise ValueError(
                    f"token dict mismatch: missing {sorted(missing, key=str)}, "
                    f"unknown {sorted(extra, key=str)}")
            tokens = np.stack([np.asarray(tokens[sid]) for sid in ids])
        if self._full_pool():
            return tokens  # pass-through: the fixed-batch fast path
        arr = np.asarray(tokens)
        if arr.shape[0] != len(ids):
            raise ValueError(
                f"tokens first axis {arr.shape[0]} != {len(ids)} attached "
                "streams")
        full = np.zeros((self._engine.batch,) + arr.shape[1:], arr.dtype)
        full[self._attached_slot_idx()] = arr
        return full

    def _narrow(self, r: Dict[str, np.ndarray]) -> Dict[str, Any]:
        ids = self.streams
        if self._full_pool():
            out = dict(r)
        else:
            sl = self._attached_slot_idx()
            out = {k: v[sl] for k, v in r.items()}
        out["streams"] = ids
        return out

    def step(self, tokens) -> Dict[str, Any]:
        """One monitoring step over the attached streams.  ``tokens``: a
        dict ``{stream_id: token}`` or an array ``(n_attached,[K])`` in
        slot order.  Returns u/fhat/triggered rows in slot order plus
        the ``streams`` id tuple."""
        if self.config.mode == "scan":
            raise RuntimeError(
                "scan sessions are offline: use run(token_stream)")
        self._ensure_open()
        full = self._expand(tokens)
        eng = self._engine
        tr, step = eng._tracer, eng.t
        if self._policy is not None:
            # thresholds are data, not structure: writing the vector
            # never retraces a jitted path (recompile-guard-tested)
            with span(tr, "session.policy", "edge", step=step):
                eng._thr_eff = np.asarray(self._policy.step_thresholds(),
                                          np.float32)
        if self.config.needs_worker:
            r = eng._step_async(full)
        else:
            r = eng._step(full)
        if self._policy is not None:
            with span(tr, "session.policy", "edge", step=step):
                self._policy.update(r["u"], r["fhat"], r["triggered"],
                                    eng.active.copy(), eng.comms)
        return self._narrow(r)

    def stream(self, token_iter: Iterable) -> Iterator[Dict[str, Any]]:
        """Drive the session from an iterable of per-step tokens,
        yielding one result dict per step.  Membership may change
        between steps (each yielded dict carries its own ``streams``)."""
        for tokens in token_iter:
            yield self.step(tokens)

    def run(self, token_stream) -> Dict[str, Any]:
        """Serve a full fixed stream ``(n_attached, S[,K])`` and return
        stacked traces + the comms report.  Worker-backed sessions
        (async / sync-over-transport) drain their pipeline tail and
        CLOSE when the stream ends — the report covers the whole
        session."""
        if self.config.mode == "scan":
            self._ensure_open()
            if not self._full_pool():
                raise RuntimeError("scan mode requires the full slot pool")
            if self._policy is not None:
                # offline trace: the policy's CURRENT per-stream
                # thresholds apply statically (no per-step feedback —
                # scan is one compiled pass)
                self._engine._thr_eff = np.asarray(
                    self._policy.step_thresholds(), np.float32)
            return self._engine._run_scan(token_stream)
        self._ensure_open()
        S = token_stream.shape[1]
        us, fhats, trigs = [], [], []
        try:
            for t in range(S):
                r = self.step(token_stream[:, t])
                us.append(r["u"]); fhats.append(r["fhat"])
                trigs.append(r["triggered"])
        finally:
            if self.config.needs_worker:
                self.close()
        return {"u": np.stack(us, 1), "fhat": np.stack(fhats, 1),
                "triggered": np.stack(trigs, 1), "streams": self.streams,
                "comms": self.report()}

    def report(self) -> Dict[str, Any]:
        """The engine's communication/overlap report (see CommsMeter)."""
        return self._engine.comms.report()

    # -- observability --------------------------------------------------------
    @property
    def tracer(self):
        """The session's span tracer (``SessionConfig(trace=True)``), or
        ``None`` when tracing is off."""
        return self._engine._tracer

    def export_trace(self, path: str) -> int:
        """Write the session's spans as Chrome trace-event / Perfetto
        JSON; returns the span count.  Requires ``trace=True``."""
        tr = self._engine._tracer
        if tr is None:
            raise RuntimeError(
                "tracing is off: open the session with "
                "SessionConfig(trace=True)")
        return tr.export(path)

    def metrics(self) -> Dict[str, Any]:
        """One flat metrics snapshot for the whole session: the engine's
        registry (wire RTT breakdown histograms as
        ``rtt_*_s_{n,mean,max,p50,p99}``), the flattened ``CommsMeter``
        report under ``comms/...`` keys, and — when tracing — the
        tracer's ring stats under ``trace/...``."""
        from repro.observability import flatten
        snap = self._engine.metrics.snapshot()
        snap.update(flatten(self._engine.comms.report(), "comms"))
        tr = self._engine._tracer
        if tr is not None:
            snap.update(flatten(tr.stats(), "trace"))
        return snap

    def arm_recompile_guard(self, *, track_global: bool = True,
                            warm_only: bool = False):
        """Arm a ``analysis.recompile.RecompileGuard`` over every jitted
        path of this session's engine and return it.  Call AFTER warmup
        (each shape signature legitimately compiles once — a ragged pool
        adds a vector-t catch-up variant); from then on, any retrace
        across churn makes ``guard.assert_stable()`` raise.  The guard
        the ROADMAP autoscaling work keys its batch buckets on.

        ``warm_only`` watches only paths the episode already compiled —
        use when the workload may leave optional paths (e.g. the
        triggered catch-up) cold through warmup."""
        from repro.analysis.recompile import RecompileGuard
        return RecompileGuard(self._engine.jitted_paths(),
                              track_global=track_global,
                              warm_only=warm_only).arm()
