"""Collaborative monitor->trigger->correct serving (the paper's protocol,
deployed, batched over independent streams):

  device: tiny edge tower decodes every token of every stream, computes
          u_t (monitor head, truncated-basis Eq. 8 — same truncation as
          ``core.decomposition.monitor_score``); alarm candidate when
          u_t > gamma - margin.
  server: large backbone; receives data ONLY on trigger, catches up its
          KV/SSM cache on the shipped token backlog, returns the corrector
          -s*sigma(v_t) so the device reports f_hat = u - s*sigma(v).

PUBLIC SURFACE.  The one public serving entrypoint is
``repro.serving.MonitorSession`` (``serving/api.py``): construct a
``CollaborativeEngine`` (parameters + caches + protocol state), then open
a session over it — ``engine.session(SessionConfig(...))`` — and drive
``session.step`` / ``session.run`` / ``session.stream``.  The session
also owns batch MEMBERSHIP: its slot pool admits and retires monitored
streams mid-flight (``attach``/``detach``), reusing this engine's
per-element masked decode and per-stream protocol state.  The legacy
``run`` / ``run_scan`` / ``run_async`` methods survive only as thin
deprecated shims over a session.

PER-ELEMENT PROTOCOL.  Each batch element (SLOT) is an independent
monitored stream with its own backlog, clock, and server catch-up
position:

  * ``edge_pos[i]`` — stream i's own time axis: how many tokens its edge
    tower has decoded.  Streams attached mid-session start at 0 while
    co-resident slots keep counting — same-position cohorts advance in
    one dense masked decode (``ServeEngine.decode_masked``, bitwise
    identical per-row to the plain batched decode).
  * ``server_pos[i]`` — how far the server cache has caught up on stream
    i.  A trigger on stream i ships ONLY stream i's backlog (tokens
    server_pos[i]..t_i) and advances ONLY server_pos[i]; stream j's
    backlog, cache rows, and communication accounting are bit-untouched
    (``ServeEngine.step_at_fn`` masked per-element decode).
  * the backlog itself is implicit: the engine keeps the token history
    (B, max_len) on device, so stream i's backlog is
    ``history[i, server_pos[i]:t_i+1]`` — no per-stream Python lists.
  * ``active[i]`` — slot-pool membership.  Detached slots are masked out
    of decode, trigger, and comms accounting; a reattached slot is
    bit-cold (caches, history, positions zeroed — ``_attach_slot``).
  * ``CommsMeter`` accounts token-level bytes per slot: a trigger on
    stream i charges len(backlog_i) tokens against slot i only, so the
    paper's Fig-4 "reduction x" is measured per stream.  Each token ships
    at most once => bytes_sent <= bytes_baseline invariantly; detached
    slots accrue nothing.

Three execution paths (selected by ``SessionConfig.mode``; all private
here, dispatched to by ``MonitorSession``):

  * ``_step`` (mode="sync") — the ONLINE protocol path: per-token, lazily
    consults the server (the server cache stays cold until a trigger).
    The fused Pallas ``kernels.monitor_combine`` op (via ``kernels.ops``)
    computes fhat/trigger-mask/safety counters in one pass in the decode
    hot loop.  Each trigger BLOCKS on the server catch-up.
  * ``_step_async`` (mode="async") — the PIPELINED online path: a trigger
    dispatches the same masked catch-up to a ``ServerWorker`` (in-process,
    worker-thread, mock-remote, or real-socket ``wire`` transport —
    ``serving/async_rpc.py``; the wire transport talks to the standalone
    correction-server process of ``serving/server.py``, which coalesces
    queued requests across clients) and the edge loop keeps decoding;
    corrections merge one step late (``fhat`` picks up the corrector at
    t+1..t+max_staleness) while the monitor-only u/trigger path stays
    exact and never waits on the server.  ``max_staleness=0`` is the
    strict synchronous fallback, bit-identical to ``_step``.  See
    docs/protocol.md for the timelines.
  * ``_run_scan`` (mode="scan") — the OFFLINE trace-evaluation fast path:
    one ``jax.lax.scan`` over time (edge + server decoded in lockstep
    inside jit), routing corrections through
    ``core.gating.compact_correction`` with static capacity (the MoE
    trick: only ``capacity`` rows hit the corrector head per step).
    Produces traces equivalent to the online path (exact when capacity >=
    batch) at compiled-loop throughput, plus the same per-stream
    communication accounting derived from the trigger trace.  It does not
    mutate the engine's protocol state, and membership is fixed (scan
    sessions reject attach/detach).

All three paths run unchanged on a MESH-SHARDED engine
(``serving/mesh.py``, ``SessionConfig(mesh="data:8")`` or
``CollaborativeEngine(..., mesh=...)``): params replicate, every
per-stream buffer shards over the mesh data axis, and because the
protocol is elementwise across the batch the sharded engine is per-row
BITWISE identical to the unsharded one, with the monitor path
HLO-asserted collective-free (docs/sharding.md).
"""
from __future__ import annotations

import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import decomposition as deco
from repro.core.gating import CommsMeter, compact_correction
from repro.kernels import ops
from repro.nn.module import linear
from repro.observability import MetricsRegistry, span
from repro.observability.trace import NO_SPAN
from repro.serving.engine import ServeEngine, zero_cache_rows

# payload: one token id (4B) + edge score (4B) per shipped token
TOKEN_BYTES = 8


class CollaborativeEngine:
    """Parameters, caches, and per-slot protocol state for one batch of
    monitored streams.  Public surface: construction and the
    ``session()`` factory (plus the deprecated ``run*`` shims); all
    serving goes through ``repro.serving.MonitorSession``."""

    def __init__(self, params: Dict, cfg: ArchConfig, batch: int, max_len: int,
                 *, capacity: Optional[int] = None,
                 monitor_n: Optional[int] = None, mesh=None):
        self.cfg, self.m = cfg, cfg.monitor
        self.params = params
        self.batch, self.max_len = batch, max_len
        self.edge = ServeEngine(params["edge"], deco.edge_arch(cfg), batch, max_len)
        self.server = ServeEngine(params["server"], cfg, batch, max_len)
        # static correction capacity for the compacted scan path; the full
        # batch by default (exact protocol semantics)
        self.capacity = batch if capacity is None else min(capacity, batch)
        # truncation n for the serving u head (paper Eq. 8); defaults to the
        # training-time n_features, overridable for truncation sweeps
        self.monitor_n = self.m.n_features if monitor_n is None else monitor_n
        # per-slot protocol state (the MonitorSession slot pool drives
        # active/edge_pos; a fixed full batch is the all-active special case)
        self.server_pos = np.zeros(batch, np.int64)
        self.edge_pos = np.zeros(batch, np.int64)
        self.active = np.ones(batch, bool)
        self.t = 0  # session step counter (staleness clock, NOT a position)
        tok_tail = (cfg.n_codebooks,) if cfg.family == "audio" else ()
        self._history = jnp.zeros((batch, max_len) + tok_tail, jnp.int32)
        self.comms = CommsMeter(bytes_per_request=TOKEN_BYTES, n_streams=batch)
        # per-stream effective trigger points (serving/policy.py): the
        # engine triggers stream i when u_i > _thr_eff[i].  Seeded at the
        # calibrated scalar the comparison always used — with no policy
        # attached every path is bitwise-identical to the scalar compare.
        # Thresholds are DATA, not structure: policies mutate this vector
        # between steps without retracing any jitted path.
        self._thr_eff = np.full(batch, np.float32(self.m.threshold -
                                                  self.m.trigger_margin),
                                np.float32)
        # unified metrics registry (repro/observability): always on — the
        # wire transport feeds its measured RTT breakdown here, and
        # MonitorSession.metrics() snapshots it.  The span tracer is OFF
        # by default (None): SessionConfig(trace=True) installs one for
        # the session's lifetime, and every instrumentation site below is
        # a single `is None` check when disabled (observability.span).
        # The sync catch-up counts its calls, rounds and backlog tokens
        # replayed here, traced or not (a round decodes all B rows).
        self.metrics = MetricsRegistry()
        self._tracer = None
        self._dispatcher = None
        self._worker = None
        self._u_head = jax.jit(self._u_head_impl)
        self._v_head = jax.jit(self._v_head_impl)
        self._record_at = jax.jit(self._record_at_impl)
        self._catchup = jax.jit(self._catchup_impl)
        self._scan = jax.jit(self._scan_impl)
        # mesh-sharded serving (serving/mesh.py): params replicated,
        # per-stream state batch-sharded over the mesh data axis, hot
        # paths re-jitted with explicit shardings.  ``mesh``: a MeshSpec
        # or "data:8"-style string; per-row numerics are unchanged.
        self.mesh = None
        self.mesh_spec = None
        if mesh is not None:
            from repro.serving.mesh import shard_engine
            shard_engine(self, mesh)

    def jitted_paths(self) -> Dict[str, object]:
        """Name -> jit wrapper for every jitted path in the serving
        stack (this engine's heads/catch-up/scan plus both towers'
        decode kernels) — the watch list a
        ``analysis.recompile.RecompileGuard`` snapshots to assert each
        path compiles exactly once across a churn episode."""
        paths = {"u_head": self._u_head, "v_head": self._v_head,
                 "record_at": self._record_at, "catchup": self._catchup,
                 "scan": self._scan}
        for tower, se in (("edge", self.edge), ("server", self.server)):
            for name, fn in se.jitted_paths().items():
                paths[f"{tower}.{name}"] = fn
        return paths

    # -- session factory -----------------------------------------------------
    def session(self, config=None, *, streams=None, worker=None):
        """Open a ``MonitorSession`` over this engine — THE public serving
        entrypoint (see ``serving/api.py``).  ``config``: a
        ``SessionConfig`` (default: sync mode); ``streams``: initial
        stream ids to admit (default: ids ``0..batch-1``, the full pool)."""
        from repro.serving.api import MonitorSession
        return MonitorSession(self, config, streams=streams, worker=worker)

    # -- heads ---------------------------------------------------------------
    # Both heads end in a matvec over the feature axis.  They are written
    # as elementwise-mul + single-axis reduce rather than ``x @ w``: XLA's
    # CPU matvec lowering is M-dependent at ~1 ulp (a (2,64)@(64,1) dot
    # rounds differently from a (16,64)@(64,1) dot), so the dot form
    # would break per-row bitwise identity between a mesh-sharded engine
    # (each device holds B/N rows) and the unsharded one, and between the
    # scan path's capacity-compacted corrector buffer and the online
    # path.  The reduce form is row-local by construction (asserted
    # sharded-vs-unsharded in tests/test_mesh.py).
    def _u_head_impl(self, params, hidden_t):
        hd = params["u_head"]
        feats = jnp.tanh(linear(hd["w_feat"], hidden_t.astype(jnp.float32)))
        # Eq. 8 truncation: only the first n basis features reach the device
        # — must match core.decomposition.monitor_score (serving u ==
        # training u)
        mask = (jnp.arange(feats.shape[-1]) < self.monitor_n).astype(jnp.float32)
        t = jax.nn.softplus(hd["raw_t"])
        return jnp.sum(feats * (hd["a"] * mask), axis=-1) + t

    def _v_head_impl(self, params, hidden_t):
        hd = params["v_head"]
        h = hidden_t.astype(jnp.float32)
        return jnp.sum(h * hd["w"][:, 0], axis=-1) + hd["b"][0]

    # -- online (lazy, per-element) path -------------------------------------
    def _record_at_impl(self, history, tokens_t, pos, active):
        """Write tokens_t[i] into history[i, pos[i]] where active (inactive
        slots bit-untouched).  Integer writes: bit-identical to the old
        uniform dynamic_update_slice when pos is uniform.

        Expressed as a one-hot time select rather than a scatter: the
        update is elementwise over the batch, so a batch-sharded history
        (serving/mesh.py) lowers collective-free — XLA's scatter
        partitioner cannot see that ``[arange(B), idx]`` is row-local
        and would all-gather the indices (HLO-asserted in test_mesh)."""
        B, L = history.shape[0], history.shape[1]
        idx = jnp.clip(pos, 0, self.max_len - 1)
        onehot = jnp.arange(L, dtype=idx.dtype) == idx[:, None]      # (B, L)
        sel = (onehot & active[:, None]).reshape(
            (B, L) + (1,) * (history.ndim - 2))
        val = tokens_t.astype(history.dtype)[:, None]                # (B, 1[,K])
        return jnp.where(sel, val, history)

    def _catchup_impl(self, params, cache, history, server_pos, t, triggered, u):
        """Masked per-element server catch-up + fused correction.

        Each triggered stream i replays its own backlog
        history[i, server_pos[i]:t+1] into the server cache at its own
        positions; untriggered streams' cache rows stay bit-identical.
        Rounds run to the LONGEST triggered backlog; streams that finish
        early (or never started) are masked out per round.  ``t`` may be
        a scalar (uniform pool) or a (B,) vector of per-stream end
        positions (ragged slot pool / server-side coalescing) — the round
        mask ``pos <= t`` is elementwise either way.

        Named scopes (``jax.named_scope``: op metadata only, numerics
        untouched) split this one program in a device trace: the replay
        loop's body is ``catchup.round`` (in it ``decode.merge``, the cache
        writes under the row mask: K/V slot writes, recurrent-state
        selects), then ``catchup.v_head`` and ``catchup.combine``.
        """
        B = triggered.shape[0]
        step_at = self.server.get_step_at(with_logits=False)
        n_rounds = jnp.max(jnp.where(triggered, t + 1 - server_pos, 0))

        @jax.named_scope("catchup.round")
        def round_body(r, carry):
            cache, last_hidden = carry
            pos = (server_pos + r).astype(jnp.int32)
            active = triggered & (pos <= t)
            idx = jnp.clip(pos, 0, self.max_len - 1)
            idxe = idx.reshape((B,) + (1,) * (history.ndim - 1))
            tok = jnp.take_along_axis(history, idxe, axis=1)[:, 0]
            _, hidden, cache = step_at(params["server"], cache, tok, pos, active)
            last_hidden = jnp.where(active[:, None], hidden.astype(jnp.float32),
                                    last_hidden)
            return cache, last_hidden

        last_hidden = jnp.zeros((B, self.cfg.d_model), jnp.float32)
        cache, last_hidden = jax.lax.fori_loop(
            0, n_rounds, round_body, (cache, last_hidden))
        with jax.named_scope("catchup.v_head"):
            v = self._v_head(params, last_hidden)
        # fused combine (Pallas on TPU, XLA elsewhere): fhat and the
        # trigger mask in one pass over the batch
        with jax.named_scope("catchup.combine"):
            if self.m.sigma == "sigmoid":
                fhat_all, mask = self._combine(u, v)
            else:
                corr = self.m.s * deco.sigma(v, self.m.sigma)
                fhat_all, mask = u - corr, triggered.astype(jnp.float32)
            fhat = jnp.where(triggered, fhat_all, u)
        return cache, v, fhat

    def _combine(self, u, v):
        """``ops.monitor_combine`` over the (B,) scores.  On a mesh-sharded
        engine it runs per data shard under ``shard_map``: the combine is
        elementwise, and a Pallas kernel is not partitioned automatically."""
        def combine(u, v):
            fhat, mask, _ = ops.monitor_combine(
                u, v, u, s=self.m.s, threshold=self.m.threshold,
                margin=self.m.trigger_margin)
            return fhat, mask

        if self.mesh is None:
            return combine(u, v)
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import data_axes
        daxes = data_axes(self.mesh)
        spec = P(daxes if len(daxes) > 1 else daxes[0])
        # check_vma off: the Pallas call's outputs carry no varying-axes
        # annotation; every output is per-shard (out_specs) by construction
        return jax.shard_map(combine, mesh=self.mesh, in_specs=(spec, spec),
                             out_specs=(spec, spec), check_vma=False)(u, v)

    def _monitor_prologue(self, tokens_t):
        """The edge-only half of one step, shared by ``_step`` and
        ``_step_async`` so the two stay bit-identical by construction:
        record each active slot's token at ITS position, decode on the
        edge tower (one dense masked call per same-position cohort),
        score u, decide the trigger.  Touches no server state.  Inactive
        slots report u = 0 and never trigger."""
        pos, active = self.edge_pos, self.active
        if not active.any():
            raise ValueError("no attached streams (empty slot pool)")
        if (pos[active] >= self.max_len).any():
            raise ValueError(f"stream longer than max_len={self.max_len}")
        tr = self._tracer
        with span(tr, "edge.decode", "edge", step=self.t):
            tokens_t = jnp.asarray(tokens_t)
            act_j = jnp.asarray(active)
            self._history = self._record_at(
                self._history, tokens_t, jnp.asarray(pos, jnp.int32), act_j)
            # cohort decode: active slots sharing a position advance in one
            # dense masked decode — per-row bitwise identical to the plain
            # batched decode, so a uniform pool reproduces the fixed-batch
            # path bit-for-bit and churn survivors match a fixed-batch run
            u = None
            for p in sorted(set(pos[active].tolist())):
                mask = active & (pos == p)
                _, hidden = self.edge.decode_masked(tokens_t, int(p),
                                                    jnp.asarray(mask))
                u_p = self._u_head(self.params, hidden)  # (B,) device array
                u = u_p if u is None else jnp.where(jnp.asarray(mask), u_p, u)
            if not active.all():
                u = jnp.where(act_j, u, 0.0)
        # the sync point: host readback of u for the trigger compare
        with span(tr, "edge.trigger", "edge", step=self.t) as sp:
            # per-stream effective thresholds (policy-driven; seeded at the
            # calibrated scalar, so the no-policy compare is bit-identical)
            triggered = (np.asarray(u) > self._thr_eff) & active
            sp.set(n_triggered=int(triggered.sum()))
        return u, triggered

    def _check_not_detached(self) -> None:
        """After a ``wire`` session the engine's server-side state lived
        in the remote correction server and was DISCARDED when the
        session closed (the server frees and zeroes the lease at BYE).
        The local server cache is cold while ``server_pos`` records the
        remote progress, so continued serving on this engine would replay
        partial backlogs into an empty cache — refuse loudly instead."""
        if getattr(self, "_remote_detached", False):
            raise RuntimeError(
                "this engine's server state lived in a remote correction "
                "server (wire transport) and was discarded when the "
                "session closed; create a fresh engine to serve again")

    def _step(self, tokens_t: jnp.ndarray) -> Dict[str, np.ndarray]:
        """One synchronous monitoring step over the slot pool.  Returns
        full-batch u, fhat, triggered (inactive slots: 0/0/False)."""
        B = self.batch
        self._check_not_detached()
        active = self.active.copy()
        t_vec = self.edge_pos.copy()  # per-slot time BEFORE this step
        u, triggered = self._monitor_prologue(tokens_t)
        fhat = np.asarray(u).copy()
        if triggered.any():
            tr = self._tracer
            # each triggered stream ships ITS backlog; others untouched.
            # The catch-up runs rounds to the longest backlog (its loop's
            # trip count), each round decoding all B rows.
            shipped = np.where(triggered, t_vec + 1 - self.server_pos, 0)
            rounds, replayed = int(shipped.max()), int(shipped.sum())
            with span(tr, "edge.catchup", "edge", step=self.t,
                      n_triggered=int(triggered.sum()), rounds=rounds,
                      rows=B, replayed=replayed):
                with span(tr, "catchup.dispatch", "edge", step=self.t):
                    uniform = active.all() and (t_vec == t_vec[0]).all()
                    # uniform pools pass the scalar t (the original
                    # compiled program); ragged pools pass per-slot end
                    # positions
                    t_arg = (jnp.asarray(int(t_vec[0]), jnp.int32)
                             if uniform else jnp.asarray(t_vec, jnp.int32))
                    cache, v, fhat_j = self._catchup(
                        self.params, self.server.cache, self._history,
                        jnp.asarray(self.server_pos, jnp.int32), t_arg,
                        jnp.asarray(triggered), u)
                    self.server.cache = cache
                # the sync path BLOCKS on the server here
                with span(tr, "catchup.wait", "edge", step=self.t):
                    fhat = np.asarray(fhat_j)
            self.metrics.inc("catchup.calls")
            self.metrics.inc("catchup.rounds", rounds)
            self.metrics.inc("catchup.replayed", replayed)
            self.comms.update_per_stream(shipped, active.astype(np.int64))
            self.server_pos = np.where(triggered, t_vec + 1, self.server_pos)
            self.server.pos = int(self.server_pos.max())
        else:
            self.comms.update_per_stream(np.zeros(B, np.int64),
                                         active.astype(np.int64))
        self.edge_pos = t_vec + active
        self.t += 1
        return {"u": np.asarray(u), "fhat": fhat, "triggered": triggered}

    # -- async pipelined online path -----------------------------------------
    def _start_async(self, *, transport: str = "stream",
                     max_staleness: int = 1,
                     latency_s: Optional[float] = None,
                     address: Optional[str] = None,
                     wire_coalesce: bool = True,
                     worker=None) -> None:
        """Open an async serving session: hand the server cache to a
        ``ServerWorker`` and set up the dispatch/merge layer.

        transport: "inproc" | "stream" | "thread" | "mock_remote" | "wire"
        | "shm" (see async_rpc; "stream" overlaps via JAX async dispatch;
        "wire" talks to a standalone correction-server PROCESS over a
        socket — the real boundary, RTT/bytes measured not simulated;
        "shm" is the wire protocol with the data plane moved into a
        same-host shared-memory ring pair, falling back to plain wire
        when the server is remote or offers no arena).
        max_staleness: merge window — 0 is the strict synchronous
        fallback (bit-identical to ``_step``); k >= 1 lets a reply land
        1..k steps after its trigger, blocking the edge loop only at k.
        latency_s: simulated server round trip (stream/thread/mock_remote);
        None keeps the transport's own default.  Rejected for "wire"/"shm".
        address: "wire"/"shm" only — the server's UDS path or "host:port"
        (start one with ``python -m repro.launch.server``).  With these
        the server process owns the session's server cache; the engine's
        local server cache stays cold and only ``server_pos`` (carried by
        replies) comes home.
        wire_coalesce: "wire"/"shm" only — opt this session out of
        server-side request coalescing (per-request replays) when False.
        """
        from repro.serving import async_rpc
        if self._dispatcher is not None:
            raise RuntimeError("async session already open")
        self._check_not_detached()
        if worker is None:
            wire_opts = None
            if transport in ("wire", "shm") and address is not None:
                wire_opts = dict(address=address, batch=self.batch,
                                 max_len=self.max_len,
                                 tok_tail=tuple(self._history.shape[2:]),
                                 coalesce=wire_coalesce, comms=self.comms,
                                 metrics=self.metrics, tracer=self._tracer)
            worker = async_rpc.make_worker(transport, self._catchup,
                                           self.params, self.server.cache,
                                           latency_s=latency_s,
                                           wire_opts=wire_opts)
        self._worker = worker
        self._dispatcher = async_rpc.Dispatcher(
            worker, max_staleness=max_staleness, comms=self.comms,
            tracer=self._tracer)
        # what has been SHIPPED (dispatched) per stream; merges move
        # ``server_pos`` (what the protocol state reflects) up to this
        self._dispatch_pos = self.server_pos.copy()

    def _step_async(self, tokens_t: jnp.ndarray) -> Dict[str, np.ndarray]:
        """One pipelined monitoring step.  Identical monitor semantics to
        ``_step`` (u and the trigger decision never wait on the server);
        corrections from earlier triggers merge into THIS step's fhat.
        """
        if self._dispatcher is None:
            raise RuntimeError("no open async session (use MonitorSession)")
        m, B = self.m, self.batch
        active = self.active.copy()
        t_vec = self.edge_pos.copy()
        u, triggered = self._monitor_prologue(tokens_t)
        u_np = np.asarray(u)
        # dispatch first so the synchronous fallback (max_staleness=0)
        # merges this step's own reply below
        tr = self._tracer
        if triggered.any():
            with span(tr, "edge.dispatch", "edge", step=self.t,
                      n_triggered=int(triggered.sum())):
                shipped = np.where(triggered, t_vec + 1 - self._dispatch_pos,
                                   0)
                # one request per same-position cohort, so every request
                # keeps the scalar-t backlog/wire semantics (a uniform
                # pool is the single-request special case, bit-identical
                # to before); cork the socket workers around the cohort
                # fan-out: N same-tick requests leave in ONE transmit (the
                # client half of wire micro-batching; a no-op for local
                # transports)
                worker = self._worker
                corked = hasattr(worker, "cork")
                if corked:
                    worker.cork()
                try:
                    for p in sorted(set(t_vec[triggered].tolist())):
                        mask_p = triggered & (t_vec == p)
                        self._dispatcher.dispatch(
                            t=int(p), triggered=mask_p,
                            server_pos=self._dispatch_pos,
                            history=self._history, u=u, step_t=self.t)
                finally:
                    if corked:
                        worker.uncork()
                self.comms.update_per_stream(shipped,
                                             active.astype(np.int64))
                self._dispatch_pos = np.where(triggered, t_vec + 1,
                                              self._dispatch_pos)
        else:
            self.comms.update_per_stream(np.zeros(B, np.int64),
                                         active.astype(np.int64))
        fhat = u_np.copy()
        # waiting for a reply past the staleness window is edge.stall,
        # inside collect; edge.merge covers the merge of what arrived
        replies = self._dispatcher.collect(self.t)
        with (span(tr, "edge.merge", "edge", step=self.t,
                   n_replies=len(replies)) if replies else NO_SPAN):
            for r in replies:
                # churn drains before rewriting membership, so a reply's
                # mask can only reference still-attached slots; the
                # `live` gate is defensive against both
                live = r.triggered & self.active
                if r.step_t == self.t:
                    # same-step merge (sync fallback): the fused fhat
                    # computed from this step's u — bit-identical to
                    # ``_step``
                    fhat = np.where(live, r.fhat, fhat)
                else:
                    # late merge: the stale corrector applied to TODAY's
                    # u.  corr >= 0, so fhat <= u — staleness can only
                    # keep a warning raised, never suppress one (safety
                    # semantics)
                    corr = np.asarray(m.s * deco.sigma(jnp.asarray(r.v),
                                                       m.sigma))
                    fhat = np.where(live, u_np - corr, fhat)
                self.server_pos = np.where(live, r.t + 1, self.server_pos)
        self.edge_pos = t_vec + active
        self.t += 1
        return {"u": u_np, "fhat": fhat, "triggered": triggered}

    def _drain_async(self) -> None:
        """Settle every in-flight request (their replies update protocol
        state only — there is no report step for them).  Required before
        any slot-pool membership change in async mode: a reply must never
        land on a slot that has been re-leased since its dispatch."""
        for r in self._dispatcher.drain():
            live = r.triggered & self.active
            self.server_pos = np.where(live, r.t + 1, self.server_pos)

    def _finish_async(self) -> None:
        """Drain outstanding replies (pipeline tail: they update protocol
        state but have no edge step left to report into), re-adopt the
        worker's server cache, and close the session."""
        if self._dispatcher is None:
            return
        self._drain_async()
        self.server.cache = self._worker.cache
        self.server.pos = int(self.server_pos.max())
        if getattr(self._worker, "kind", None) in ("wire", "shm"):
            # the worker's cache is the engine's untouched cold cache (the
            # real one lived — and died — in the server process): any
            # further serving on this engine would be silently wrong
            self._remote_detached = True
        self._worker.close()
        self._dispatcher = self._worker = None

    # -- slot pool (driven by MonitorSession.attach/detach) -------------------
    def _attach_slot(self, slot: int) -> None:
        """Admit a new stream into ``slot``: every per-slot state the
        previous tenant left behind is reset to bit-cold zeros (edge +
        server cache rows, token history, positions), exactly as if the
        slot belonged to a freshly-built engine.  In async mode the
        pipeline is drained first and, over the wire, an ATTACH frame
        tells the correction server to zero and re-lease its row."""
        rows = np.zeros(self.batch, bool)
        rows[slot] = True
        if self._dispatcher is not None:
            self._drain_async()
        self.edge.zero_rows(rows)
        if (self._dispatcher is not None
                and getattr(self._worker, "kind", None) in ("wire", "shm")):
            self._worker.attach_slot(slot)
        elif self._dispatcher is not None:
            # the worker owns the server cache for the session; after the
            # drain no compute is in flight, so the functional row reset
            # is race-free on every local transport (spec-aware: a
            # sharded cache keeps its placement through the reset)
            self._worker.cache = zero_cache_rows(
                self._worker.cache, self.server.axes, jnp.asarray(rows),
                shardings=self.server._cache_shardings)
        else:
            self.server.zero_rows(rows)
        self._history = self._history.at[slot].set(0)
        if getattr(self, "_history_sharding", None) is not None:
            # eager row scatter may lose the committed placement
            self._history = jax.device_put(self._history,
                                           self._history_sharding)
        self.server_pos[slot] = 0
        self.edge_pos[slot] = 0
        # a fresh tenant starts at the calibrated operating point; any
        # policy-raised threshold the previous tenant earned must not
        # leak (the session also cold-starts its controller state)
        self._thr_eff[slot] = np.float32(self.m.threshold -
                                         self.m.trigger_margin)
        if self._dispatcher is not None:
            self._dispatch_pos[slot] = 0
        self.active[slot] = True

    def _detach_slot(self, slot: int) -> None:
        """Retire the stream in ``slot``: masked out of decode, trigger,
        and comms accounting from the next step on.  Its state is left in
        place (attach zeroes on reuse); in async mode the pipeline is
        drained first so no in-flight reply can land on the freed slot."""
        if self._dispatcher is not None:
            self._drain_async()
            if getattr(self._worker, "kind", None) in ("wire", "shm"):
                self._worker.detach_slot(slot)
        self.active[slot] = False

    # -- offline scan fast path ----------------------------------------------
    def _scan_impl(self, params, tokens, thr_eff):
        """One lax.scan over time: edge + server decode in lockstep,
        corrections routed through compact_correction (static capacity).
        Scratch caches are built inside jit (zeros at the engine's max_len
        capacity, so attention reduction widths match the online path
        bit-for-bit) — no per-call host allocation.  ``thr_eff``: (B,)
        f32 per-stream effective trigger points (traced DATA, like the
        tokens — static-policy scans pass a different vector without
        retracing)."""
        ecfg = deco.edge_arch(self.cfg)
        cfg, m = self.cfg, self.m
        B = tokens.shape[0]
        from repro.models import api as model_api
        edge_cache = model_api.init_cache(ecfg, B, self.max_len)
        server_cache = model_api.init_cache(cfg, B, self.max_len)

        def body(carry, tok_t):
            edge_cache, server_cache, pos = carry
            _, eh, edge_cache = model_api.decode_step(
                params["edge"], ecfg, edge_cache, tok_t, pos,
                with_logits=False)
            u = self._u_head(params, eh)
            _, sh, server_cache = model_api.decode_step(
                params["server"], cfg, server_cache, tok_t, pos,
                with_logits=False)

            def corrector(buf):  # (capacity, d) gathered server hiddens
                v = self._v_head(params, buf)
                return m.s * deco.sigma(v, m.sigma)

            # per-stream trigger points: urgency u - (thr_eff - 0.0) is
            # bit-identical to the scalar u - (threshold - margin) when
            # thr_eff is the calibrated f32 (x - 0.0 is an identity in
            # round-to-nearest f32)
            fhat, served, _ = compact_correction(
                u, sh.astype(jnp.float32), corrector, thr_eff,
                0.0, self.capacity)
            trig = u > thr_eff
            return (edge_cache, server_cache, pos + 1), (u, fhat, trig, served)

        toks = jnp.moveaxis(tokens, 1, 0)
        carry = (edge_cache, server_cache, jnp.asarray(0, jnp.int32))
        _, (u, fhat, trig, served) = jax.lax.scan(body, carry, toks)
        # time-major -> batch-major
        return (jnp.moveaxis(u, 0, 1), jnp.moveaxis(fhat, 0, 1),
                jnp.moveaxis(trig, 0, 1), jnp.moveaxis(served, 0, 1))

    def _run_scan(self, token_stream: np.ndarray) -> Dict[str, np.ndarray]:
        """Offline trace evaluation: same protocol semantics as the sync
        online path (exact when capacity == batch; capacity-limited
        correction otherwise), compiled into a single scan.  Scratch
        caches — the engine's online protocol state (server laziness,
        comms meter) is not mutated.  Comms are derived per stream from
        the trigger trace: a trigger at time t ships the backlog since
        that stream's previous trigger, so total shipped = last-trigger
        index + 1."""
        tokens = jnp.asarray(token_stream)
        B, S = tokens.shape[0], tokens.shape[1]
        if S > self.max_len:
            raise ValueError(f"stream longer than max_len={self.max_len}")
        with span(self._tracer, "scan.run", "edge", batch=int(B),
                  steps=int(S)):
            if B == self.batch:
                thr_eff = jnp.asarray(self._thr_eff)
            else:  # narrower offline trace: calibrated point for every row
                thr_eff = jnp.full((B,), np.float32(self.m.threshold -
                                                    self.m.trigger_margin),
                                   jnp.float32)
            u, fhat, trig, served = self._scan(self.params, tokens, thr_eff)
            trig_np = np.asarray(trig)
        comms = CommsMeter(bytes_per_request=TOKEN_BYTES, n_streams=B)
        any_trig = trig_np.any(axis=1)
        last = np.where(any_trig, S - 1 - np.argmax(trig_np[:, ::-1], axis=1), -1)
        comms.update_per_stream(last + 1, np.full(B, S, np.int64),
                                events=trig_np.sum(axis=1))
        return {"u": np.asarray(u), "fhat": np.asarray(fhat),
                "triggered": trig_np, "served": np.asarray(served),
                "comms": comms.report()}

    # -- deprecated shims (the pre-session public surface) --------------------
    def _session_shim(self, mode, name, worker=None, **cfg_kw):
        from repro.serving.api import SessionConfig
        warnings.warn(
            f"CollaborativeEngine.{name}() is deprecated: open a "
            f"MonitorSession instead — engine.session(SessionConfig("
            f"mode={mode!r}, ...)).run(stream)  (see docs/api.md)",
            DeprecationWarning, stacklevel=3)
        return self.session(SessionConfig(mode=mode, **cfg_kw),
                            worker=worker)

    def run(self, token_stream: np.ndarray) -> Dict[str, np.ndarray]:
        """DEPRECATED: thin shim over ``MonitorSession`` (sync mode).
        Bit-identical to ``session(SessionConfig(mode="sync")).run(...)``
        — asserted in tests."""
        with self._session_shim("sync", "run") as s:
            return s.run(token_stream)

    def run_scan(self, token_stream: np.ndarray) -> Dict[str, np.ndarray]:
        """DEPRECATED: thin shim over ``MonitorSession`` (scan mode)."""
        with self._session_shim("scan", "run_scan") as s:
            return s.run(token_stream)

    def run_async(self, token_stream: np.ndarray, *,
                  transport: str = "stream", max_staleness: int = 1,
                  latency_s: Optional[float] = None,
                  address: Optional[str] = None, wire_coalesce: bool = True,
                  worker=None) -> Dict[str, np.ndarray]:
        """DEPRECATED: thin shim over ``MonitorSession`` (async mode)."""
        from repro.serving.api import TransportSpec
        spec = TransportSpec(kind=transport, address=address,
                             latency_s=latency_s, coalesce=wire_coalesce)
        with self._session_shim("async", "run_async", worker=worker,
                                transport=spec,
                                max_staleness=max_staleness) as s:
            return s.run(token_stream)
