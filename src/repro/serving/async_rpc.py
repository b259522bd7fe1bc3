"""Async pipelined server catch-up: the dispatch/merge layer between the
edge decode loop and the server corrector.

The paper's deployment story is an edge monitor ``u`` that runs on EVERY
token while the server corrector ``v`` is consulted only on trigger — so
server latency (catch-up compute + network round trip) must be hideable
behind edge decode.  This module provides the two halves of that overlap:

  * ``ServerWorker`` — owns the server-side protocol state (params + the
    batched KV/SSM cache) and applies ``CatchupRequest``s strictly in FIFO
    order, so the cache replay is identical to the synchronous engine's.
    Three transports:

      - ``inproc``      — computes at dispatch, on the caller's thread.
        Zero latency, fully deterministic; the functional transport used
        by equivalence tests (it exercises the one-step-late merge policy
        without real concurrency).
      - ``stream``      — the side-stream transport: exploits JAX's async
        dispatch.  The jitted catch-up is ENQUEUED from the caller's
        thread (returns in well under a millisecond) and XLA's runtime
        executes it concurrently with the edge loop's subsequent
        ``decode_step`` dispatches; readiness is observed via
        ``Array.is_ready()`` without blocking.  Successive requests chain
        through the worker's cache arrays, so XLA serializes the replay
        exactly like a real server while everything else overlaps.  This
        is the preferred overlap transport on shared hosts (it uses XLA's
        own scheduler — no OS-thread oversubscription) and the
        single-device analogue of dispatching onto a second device via
        ``jax.device_put`` (the worker exclusively owns its cache buffers,
        so they are also donation-safe).  ``latency_s`` adds a simulated
        wire delay on top of compute readiness.
      - ``thread``      — a single daemon worker thread runs the jitted
        catch-up.  The GIL is released during XLA execution, so the edge
        loop overlaps the server replay; prefer ``stream`` on hosts with
        few cores (two thread pools can thrash each other).
      - ``mock_remote`` — ``thread`` plus a simulated network round trip:
        a reply becomes visible ``latency_s`` after its compute finishes.
        Latency is modelled as a concurrent wire delay (replies overlap in
        flight); compute stays serialized like a real single server.
      - ``wire``        — the REAL boundary: a ``SocketWorker`` speaking
        the versioned binary protocol of ``serving/wire.py`` to a
        standalone correction-server process (``serving/server.py``,
        started via ``python -m repro.launch.server``) over a
        Unix-domain or TCP socket.  The server owns the cache; only
        backlog tokens + scores cross the wire; RTT and bytes are
        MEASURED (``CommsMeter.record_wire_*``), not modelled, and the
        server coalesces queued requests across clients and pipeline
        depth.  ``latency_s`` is rejected here — the wire has whatever
        latency it actually has.

  * ``Dispatcher`` — the edge-side bookkeeping: tracks in-flight requests,
    polls/blocks for replies, and enforces the staleness window.

STALENESS SEMANTICS (``max_staleness``):

  * ``max_staleness == 0`` — strict synchronous fallback: the reply for a
    trigger at step t is merged AT step t (the dispatcher blocks
    immediately).  Bit-identical to the engine's synchronous step path
    (what ``SessionConfig(mode="sync")`` over a transport means).
  * ``max_staleness == k >= 1`` — pipelined: a reply merges at the first
    step AFTER its trigger once it has arrived ("corrections merge one
    step late"), and no later than ``t + k`` — the dispatcher blocks the
    edge loop only when the oldest in-flight request reaches age k.
    The monitor path (u, trigger decision) NEVER waits on the server.

Replies deliberately do not carry the server cache: the worker owns it for
the duration of the async session and the engine re-adopts it once when
the ``MonitorSession`` closes (after a full drain), which keeps
cross-thread ownership trivial.  See ``docs/protocol.md`` for the full
timeline diagrams.

MESH-SHARDED SESSIONS (``SessionConfig(mesh=...)``, serving/mesh.py):
the session shards the engine BEFORE the worker is built, so every local
transport adopts the batch-sharded server cache and the re-jitted
catch-up (whose in/out shardings are compiled in) — requests chain
through sharded buffers exactly as through unsharded ones, and slot
churn's row resets on the worker-owned cache are spec-aware
(placement-preserving).  The ``wire`` transport is orthogonal: the
client's mesh shards its edge, while the server process shards its own
super-batch via ``CorrectionServer(mesh=...)``; only protocol bytes
cross the boundary either way.
"""
from __future__ import annotations

import queue
import socket
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.observability import span

TRANSPORTS = ("inproc", "stream", "thread", "mock_remote", "wire", "shm")


@dataclass
class CatchupRequest:
    """One trigger-step's worth of server work.

    ``server_pos`` is the DISPATCH-time catch-up base: stream i's backlog is
    ``history[i, server_pos[i]:t+1]``.  ``history`` is the engine's on-device
    token history at dispatch; jnp arrays are immutable, so the snapshot is
    free and stable while later edge steps keep recording.
    """

    req_id: int
    t: int                      # trigger POSITION (inclusive end of backlog)
    triggered: np.ndarray       # (B,) bool — which streams this request serves
    server_pos: np.ndarray      # (B,) int — catch-up base per stream
    history: jax.Array          # (B, max_len[, K]) token history snapshot
    u: jax.Array                # (B,) monitor scores at the trigger step
    wall_dispatch: float = 0.0  # time.monotonic() at dispatch
    # the SESSION step index at dispatch: the staleness clock.  With a
    # uniform pool it equals ``t``; under slot-pool churn streams carry
    # their own positions, so ``t`` (a position) and the session clock
    # diverge — ages are measured on step_t, backlogs on t.
    step_t: int = -1


@dataclass
class CatchupReply:
    req_id: int
    t: int                      # the request's trigger step
    triggered: np.ndarray
    v: np.ndarray               # (B,) server scores (valid where triggered)
    fhat: np.ndarray            # (B,) fused fhat from the DISPATCH-time u
    server_time_s: float        # compute time inside the worker
    wall_ready: float = 0.0     # when the reply became visible (incl. latency)
    step_t: int = -1            # filled by the Dispatcher from the request


class ServerWorker:
    """Base transport: owns the server cache, applies requests in FIFO order.

    ``catchup_fn(params, cache, history, server_pos, t, triggered, u)``
    -> (cache, v, fhat) — the engine's jitted masked per-element catch-up.
    """

    kind = "inproc"

    def __init__(self, catchup_fn: Callable, params: Any, cache: Any):
        self._fn = catchup_fn
        self._params = params
        self.cache = cache
        self._ready: deque = deque()  # replies visible to poll(), FIFO
        self._closed = False

    # -- server side ---------------------------------------------------------
    def _compute(self, req: CatchupRequest) -> CatchupReply:
        t0 = time.monotonic()
        cache, v, fhat = self._fn(
            self._params, self.cache, req.history,
            jnp.asarray(req.server_pos, jnp.int32),
            jnp.asarray(req.t, jnp.int32),
            jnp.asarray(req.triggered), req.u)
        v, fhat = jax.block_until_ready((v, fhat))
        self.cache = cache
        done = time.monotonic()
        return CatchupReply(req.req_id, req.t, np.asarray(req.triggered),
                            np.asarray(v), np.asarray(fhat), done - t0,
                            wall_ready=done)

    # -- edge side -----------------------------------------------------------
    def dispatch(self, req: CatchupRequest) -> None:
        """inproc: compute now, on the caller's thread."""
        self._ready.append(self._compute(req))

    def poll(self) -> List[CatchupReply]:
        """All replies that are ready, in FIFO order.  Non-blocking."""
        out = list(self._ready)
        self._ready.clear()
        return out

    def wait(self, req_id: int) -> List[CatchupReply]:
        """Block until ``req_id`` is done; returns every reply up to and
        including it, in FIFO order.  inproc computes at dispatch, so the
        reply is already here."""
        taken: List[CatchupReply] = []
        while self._ready:
            r = self._ready.popleft()
            taken.append(r)
            if r.req_id == req_id:
                break
        return taken

    def close(self) -> None:
        """Idempotent on every transport: safe to call twice, and again
        after the ``MonitorSession`` closed (which closes the worker
        itself)."""
        self._closed = True


class StreamWorker(ServerWorker):
    """Side-stream transport: overlap via JAX async dispatch, no threads.

    ``dispatch`` enqueues the jitted catch-up and returns immediately with
    async result arrays; XLA executes it concurrently with whatever the
    edge loop dispatches next.  Requests chain through ``self.cache`` (an
    async array after the first dispatch), so the replay order is enforced
    by XLA's data dependencies — FIFO by construction.  ``poll`` observes
    readiness with ``Array.is_ready()``; conversion to numpy happens only
    at release, so nothing blocks early.

    ``latency_s`` simulates the network: a reply becomes visible
    ``latency_s`` after its compute is first OBSERVED ready (the edge loop
    polls every step, so the observation error is at most one step).
    """

    kind = "stream"

    def __init__(self, catchup_fn, params, cache, *, latency_s: float = 0.0):
        super().__init__(catchup_fn, params, cache)
        self.latency_s = float(latency_s)
        self._pending: deque = deque()  # [req, v, fhat, ready_at | None]

    def dispatch(self, req: CatchupRequest) -> None:
        cache, v, fhat = self._fn(
            self._params, self.cache, req.history,
            jnp.asarray(req.server_pos, jnp.int32),
            jnp.asarray(req.t, jnp.int32),
            jnp.asarray(req.triggered), req.u)
        self.cache = cache
        self._pending.append([req, v, fhat, None])

    def _release(self, item) -> CatchupReply:
        req, v, fhat, ready_at = item
        return CatchupReply(req.req_id, req.t, np.asarray(req.triggered),
                            np.asarray(v), np.asarray(fhat),
                            server_time_s=0.0,  # not observable without blocking
                            wall_ready=ready_at + self.latency_s)

    def _stamp_ready(self) -> None:
        # stamp readiness for EVERY pending request, not just the head —
        # the wire delays of distinct requests overlap (concurrent flights);
        # compute is FIFO (cache-chained), so stop at the first not-ready
        now = time.monotonic()
        for item in self._pending:
            if item[3] is None:
                if not item[1].is_ready():
                    break
                item[3] = now

    def poll(self) -> List[CatchupReply]:
        self._stamp_ready()
        out: List[CatchupReply] = []
        while self._pending:
            item = self._pending[0]
            if item[3] is None or item[3] + self.latency_s > time.monotonic():
                break
            self._pending.popleft()
            out.append(self._release(item))
        return out

    def wait(self, req_id: int) -> List[CatchupReply]:
        out: List[CatchupReply] = []
        while not out or out[-1].req_id < req_id:
            if not self._pending:
                raise RuntimeError(f"stream worker: request {req_id} was "
                                   "never dispatched")
            item = self._pending.popleft()
            if item[3] is None:
                jax.block_until_ready(item[1])
                item[3] = time.monotonic()
                # later requests may have finished compute while we
                # blocked: start their wire clocks NOW so their delays
                # overlap this item's sleep (concurrent flights — same
                # rule as poll)
                self._stamp_ready()
            dt = item[3] + self.latency_s - time.monotonic()
            if dt > 0:              # still on the simulated wire
                time.sleep(dt)
            out.append(self._release(item))
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        jax.block_until_ready(self.cache)


class ThreadWorker(ServerWorker):
    """Single worker thread; the edge loop overlaps the jitted catch-up.

    ``latency_s`` models the network round trip: a reply becomes visible
    ``latency_s`` after its compute finishes.  The delay is concurrent
    (multiple replies can be "on the wire" at once) while compute stays
    serialized — the realistic shape for a remote corrector, where RTT
    dominates and the server itself is fast.
    """

    kind = "thread"

    def __init__(self, catchup_fn, params, cache, *, latency_s: float = 0.0):
        super().__init__(catchup_fn, params, cache)
        self.latency_s = float(latency_s)
        self._q: "queue.Queue[Optional[CatchupRequest]]" = queue.Queue()
        self._cv = threading.Condition()
        self._done: deque = deque()  # (reply, visible_at) in FIFO order
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while True:
            req = self._q.get()
            if req is None:
                return
            reply = self._compute(req)
            visible_at = reply.wall_ready + self.latency_s
            reply.wall_ready = visible_at
            with self._cv:
                self._done.append((reply, visible_at))
                self._cv.notify_all()

    def dispatch(self, req: CatchupRequest) -> None:
        self._q.put(req)

    def poll(self) -> List[CatchupReply]:
        now = time.monotonic()
        out: List[CatchupReply] = []
        with self._cv:
            while self._done and self._done[0][1] <= now:
                out.append(self._done.popleft()[0])
        return out

    def wait(self, req_id: int) -> List[CatchupReply]:
        out: List[CatchupReply] = []
        while not out or out[-1].req_id < req_id:
            with self._cv:
                while not self._done:
                    if not self._thread.is_alive():
                        raise RuntimeError(
                            "server worker thread died (catch-up raised)")
                    self._cv.wait(timeout=0.05)
                reply, visible_at = self._done.popleft()
            dt = visible_at - time.monotonic()
            if dt > 0:              # still on the simulated wire
                time.sleep(dt)
            out.append(reply)
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._thread.is_alive():
            self._q.put(None)
            self._thread.join()


class MockRemoteWorker(ThreadWorker):
    """``thread`` + a nonzero simulated network round trip."""

    kind = "mock_remote"

    def __init__(self, catchup_fn, params, cache, *, latency_s: float = 0.02):
        super().__init__(catchup_fn, params, cache, latency_s=latency_s)


class _Flight:
    """One unanswered request on the socket, kept until its reply lands
    so a fleet failover can resend it verbatim.  ``internal`` flights are
    the recovery replay's own synthetic requests (their replies are
    consumed silently — the engine never sees them)."""

    __slots__ = ("req_id", "internal", "buf", "t", "triggered", "n_tokens")

    def __init__(self, req_id: int, internal: bool, buf: bytes, t: int,
                 triggered: np.ndarray, n_tokens: int):
        self.req_id = req_id
        self.internal = internal
        self.buf = buf
        self.t = t
        self.triggered = triggered
        self.n_tokens = n_tokens


class SocketWorker(ServerWorker):
    """The ``wire`` transport: catch-up requests cross a REAL socket to a
    standalone correction-server process (``serving/server.py``).

    The server owns the authoritative server cache (leased super-batch
    rows) and the replayed token history for the whole session; locally,
    ``self.cache`` keeps the engine's initial (cold) cache — with a real
    boundary there is nothing to re-adopt at session close, the
    protocol state that comes home is ``server_pos`` (carried by every
    reply).  Only the protocol bytes move: each dispatch serializes the
    trigger mask, per-stream catch-up bases, dispatch-time u scores and
    the BACKLOG token slices (never the full history snapshot); each
    reply carries (v, fhat) and the server's replay time.  Wire latency
    is whatever the kernel + scheduler + server actually take — the
    worker measures it per request (``CommsMeter.record_wire_rtt``) along
    with exact tx/rx byte counts, including the handshake.

    ``coalesce=False`` opts the session out of server-side request
    coalescing (per-request replays — the bench baseline).

    FLEET MODE (``address="fleet:<router>"``, serving/fleet.py): the
    worker HELLOs the router, follows its REDIRECT to the least-loaded
    live server, and treats the connection as expendable.  Because the
    client is the source of truth for its own token history, a dead or
    draining server costs a re-HELLO plus a cold replay — never state:

      * every request stays in ``self._flights`` until its reply lands
        (FIFO, mirroring the server's ordering contract), and
        ``self._acked_pos`` tracks the per-row position the server has
        CONFIRMED via replies;
      * on EOF/reset (or a GOAWAY once the pipeline is empty) the worker
        re-resolves through the router, re-HELLOs, replays each row's
        acked prefix ``history[i, :acked_pos[i]]`` from position 0 via
        synthetic internal requests, then resends the unanswered real
        requests verbatim — reconstructing the server state bit-exactly
        (the masked replay is position-deterministic), so survivors stay
        bitwise identical to an uninterrupted run;
      * every byte of that recovery (handshake, replay, resends) is
        charged to ``CommsMeter``'s ``failover`` bucket, keeping the
        steady-state ``wire`` byte invariants auditable.

    Duplicate or stale replies (a chaos proxy re-sending a REPLY, or a
    late frame racing a reconnect) are dropped by the head-of-flights
    req_id check — the Dispatcher's FIFO contract is enforced here.
    """

    kind = "wire"

    _FLEET_PREFIX = "fleet:"

    def __init__(self, cache, *, address: str, batch: int, max_len: int,
                 tok_tail: Tuple[int, ...] = (), coalesce: bool = True,
                 comms=None, metrics=None, tracer=None,
                 connect_timeout: float = 60.0,
                 client: str = "edge"):
        from repro.serving import wire  # local import: keep module light

        self._wire = wire
        self._fn = None          # the server process owns catchup + params
        self._params = None
        self.cache = cache       # stays cold locally (see class docstring)
        self._closed = False
        self._comms = comms
        # observability (both optional): ``metrics`` is the engine's
        # MetricsRegistry — the measured RTT breakdown (serialize / socket
        # / queue / compute, via the v4 REPLY timing payload) lands there;
        # ``tracer`` additionally records wire/server spans per request
        self._metrics = metrics
        self._tracer = tracer
        self._batch = int(batch)
        self._hello = wire.Hello(batch, max_len, tuple(tok_tail), coalesce,
                                 client)
        self._fleet = address.startswith(self._FLEET_PREFIX)
        self._target = address[len(self._FLEET_PREFIX):] if self._fleet \
            else address
        self._connect_timeout = connect_timeout
        self._replies: deque = deque()
        # req_id -> (dispatch wall time, serialize duration): the client
        # half of the per-request RTT breakdown
        self._dispatch_wall: Dict[int, Tuple[float, float]] = {}
        # -- failover state (fleet mode; harmless bookkeeping otherwise) -----
        self._flights: "deque[_Flight]" = deque()
        self._acked_pos = np.zeros(self._batch, np.int32)
        self._last_history: Optional[np.ndarray] = None
        self._must_move = False      # GOAWAY received: migrate when empty
        self._failing_over = False   # routes _tx/_rx to the failover bucket
        self._internal_next = 1 << 62  # clear of the Dispatcher's req_ids
        self.server_address: Optional[str] = None
        # cork/uncork: while corked, outgoing frames gather into one
        # buffer and leave in a single transmit at uncork — the engine
        # corks around a step's dispatch fan-out so N cohort requests
        # cost one syscall (the client half of wire micro-batching)
        self._corked: Optional[List[bytes]] = None
        self._sock, self._reader = None, wire.FrameReader()
        self._establish(self._connect_timeout)

    # -- metering ------------------------------------------------------------
    def _tx(self, n: int) -> None:
        if self._comms is not None:
            if self._failing_over:
                self._comms.record_failover_tx(n)
            else:
                self._comms.record_wire_tx(n)

    def _rx(self, n: int) -> None:
        if self._comms is not None:
            if self._failing_over:
                self._comms.record_failover_rx(n)
            else:
                self._comms.record_wire_rx(n)

    # -- connection management -----------------------------------------------
    def _establish(self, timeout: float) -> None:
        """Connect + HELLO (via the router in fleet mode, following its
        REDIRECT).  Fleet mode keeps retrying the router on a refused or
        dead target until ``timeout`` — a SIGKILLed server is replaced by
        a sibling on the next resolve; a direct address surfaces
        ``HandshakeRefused`` / ``PeerGone`` to the caller unchanged (the
        two failure modes the old ``connect()`` loop conflated)."""
        wire = self._wire
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise wire.PeerGone(
                    f"no usable server via {self._target!r} "
                    f"within {timeout:.1f}s")
            # short per-attempt timeout in fleet mode: a dead redirect
            # target must bounce us back to the router, not eat the
            # whole deadline
            per = min(2.0, remaining) if self._fleet else remaining
            try:
                sock, ack, reader, tx, rx = self._handshake(per)
                break
            except (wire.HandshakeRefused, wire.PeerGone, OSError):
                if not self._fleet:
                    raise
                time.sleep(0.05)
        self._sock, self._reader = sock, reader
        self._tx(tx)
        self._rx(rx)
        self.session_id = ack.session_id
        self.slot_lo = ack.slot_lo
        try:
            peer = sock.getpeername()
            self.server_address = (peer if isinstance(peer, str)
                                   else f"{peer[0]}:{peer[1]}")
        except OSError:
            self.server_address = None
        self._must_move = False

    def _handshake(self, timeout: float):
        """One connect + HELLO attempt — the transport-specific half of
        ``_establish`` (the shm transport overrides this to negotiate an
        arena on the same handshake)."""
        return self._wire.connect_hello(self._target, self._hello,
                                        timeout=timeout)

    def _failover(self, why: str) -> None:
        """Migrate to another server: re-resolve, re-HELLO, replay each
        row's ACKED history prefix from position 0, resend unanswered
        requests verbatim.  Deterministic by construction: the server
        state after recovery is bitwise what the dead server had acked,
        so the resent requests see exactly the bases they were built on."""
        wire = self._wire
        if not self._fleet:
            raise wire.WireError(why)
        try:
            self._sock.close()
        except OSError:
            pass
        if self._comms is not None:
            self._comms.record_failover()
        real = [f for f in self._flights if not f.internal]
        self._failing_over = True
        deadline = time.monotonic() + self._connect_timeout
        try:
            while True:
                self._flights = deque()
                self._establish(max(0.1, deadline - time.monotonic()))
                try:
                    self._recover(real)
                    return
                except (wire.PeerGone, OSError):
                    # the NEW server died mid-recovery: route again
                    try:
                        self._sock.close()
                    except OSError:
                        pass
                    if time.monotonic() > deadline:
                        raise
        finally:
            self._failing_over = False

    def _recover(self, real: List[_Flight]) -> None:
        """On the fresh session: synthetic replay of the acked prefixes,
        then the unanswered real requests, all in FIFO order."""
        wire = self._wire
        acked = self._acked_pos
        if self._last_history is not None:
            zeros_pos = np.zeros(self._batch, np.int32)
            zeros_u = np.zeros(self._batch, np.float32)
            for p in sorted({int(x) for x in acked if x > 0}):
                trig = acked == p
                rid = self._internal_next
                self._internal_next += 1
                buf = wire.encode_request(rid, p - 1, trig, zeros_pos,
                                          zeros_u, self._last_history)
                self._flights.append(_Flight(rid, True, buf, p - 1,
                                             trig.copy(),
                                             int(trig.sum()) * p))
                self._send_frame(buf)
                if self._comms is not None:
                    self._comms.record_failover_tokens(int(trig.sum()) * p)
        for f in real:
            self._flights.append(f)
            self._send_frame(f.buf)
            if self._comms is not None:
                self._comms.record_failover_tokens(f.n_tokens, resent=True)

    def _move_now(self) -> None:
        """GOAWAY honored: pipeline is empty, leave politely and rebuild
        on a sibling (the replay machinery is identical to a crash — the
        only difference is the BYE)."""
        try:
            self._sock.settimeout(1.0)
            bye = self._wire.encode_bye()
            self._sock.sendall(bye)
            self._tx(len(bye))
        except OSError:
            pass
        self._failover("server draining")

    # -- socket pump ---------------------------------------------------------
    # the tracer span name for the transport-hop stage of the RTT: the
    # shm transport reports "shm.ring" (same stage key in the breakdown
    # table — docs/observability.md)
    _socket_span = "wire.socket"

    def _record_rtt(self, rtt: float) -> None:
        if self._comms is not None:
            self._comms.record_wire_rtt(rtt)

    def _to_reply(self, msg) -> CatchupReply:
        now = time.monotonic()
        disp, ser = self._dispatch_wall.pop(msg.req_id, (now, 0.0))
        rtt = now - disp
        self._record_rtt(rtt)
        if self._metrics is not None or self._tracer is not None:
            self._breakdown(msg, now, disp, ser, rtt)
        return CatchupReply(msg.req_id, msg.t, np.asarray(msg.triggered),
                            np.asarray(msg.v), np.asarray(msg.fhat),
                            msg.server_time_s, wall_ready=now)

    def _breakdown(self, msg, now: float, disp: float, ser: float,
                   rtt: float) -> None:
        """Split one measured RTT into serialize / socket / queue /
        compute using the REPLY's duration-only timing fields, observe
        the pieces into the registry, and (when tracing) synthesize the
        server-side spans — anchored BACKWARDS from reply arrival, since
        the server reported durations, not timestamps (no clock sync)."""
        compute = max(msg.server_time_s, 0.0)
        queue = msg.queue_s if msg.queue_s >= 0 else None   # None: v3 peer
        if self._metrics is not None:
            m = self._metrics
            m.observe("rtt_s", max(rtt, 1e-9))
            m.observe("rtt_serialize_s", max(ser, 1e-9))
            m.observe("rtt_compute_s", max(compute, 1e-9))
            if queue is not None:
                m.observe("rtt_queue_s", max(queue, 1e-9))
                m.observe("rtt_socket_s",
                          max(rtt - queue - compute, 1e-9))
        if self._tracer is not None:
            tr = self._tracer
            tr.add("wire.request", "wire", disp, rtt, track="wire",
                   req_id=msg.req_id, coalesced=msg.coalesced)
            # compute ends at arrival; queue precedes compute; the rest
            # of the gap after dispatch is both socket directions
            tr.add("server.catchup", "server", now - compute, compute,
                   track="server", req_id=msg.req_id,
                   coalesced=msg.coalesced)
            if queue is not None:
                tr.add("server.queue", "server", now - compute - queue,
                       queue, track="server", req_id=msg.req_id)
                tr.add(self._socket_span, "wire", disp,
                       max(rtt - queue - compute, 0.0), track="wire",
                       req_id=msg.req_id)

    def _accept_reply(self, msg) -> bool:
        """Match a REPLY against the head of the flight queue.  Anything
        else — a duplicated frame, a stale reply racing a reconnect — is
        dropped here so the Dispatcher's FIFO assert never fires.
        Returns True when a REAL (engine-visible) reply landed."""
        if not self._flights or self._flights[0].req_id != msg.req_id:
            return False
        f = self._flights.popleft()
        self._acked_pos = np.where(f.triggered, f.t + 1,
                                   self._acked_pos).astype(np.int32)
        if f.internal:
            return False
        self._replies.append(self._to_reply(msg))
        return True

    def _pump(self, block: bool) -> None:
        """Drain the socket into ``self._replies``.  Non-blocking drains
        whatever the kernel has; blocking returns once >= 1 reply landed.
        In fleet mode a dead connection triggers failover instead of
        raising, and a GOAWAY schedules a migration for when the
        pipeline is empty."""
        wire = self._wire
        got = False
        while True:
            if self._must_move and not self._flights:
                self._move_now()
            self._sock.settimeout(None if (block and not got) else 0.0)
            try:
                data = self._sock.recv(1 << 16)
            except (BlockingIOError, socket.timeout):
                return
            except InterruptedError:
                continue
            except OSError as e:
                self._failover(f"connection lost: {e}")
                continue
            if not data:
                self._failover("server closed connection")
                continue
            self._rx(len(data))
            got |= self._on_payloads(self._reader.feed(data))

    def _on_payloads(self, payloads: List[bytes]) -> bool:
        """Decode and act on frame payloads from either plane (socket or
        ring).  Returns True when a REAL reply landed."""
        wire = self._wire
        got = False
        for p in payloads:
            msg = wire.decode(p)
            if isinstance(msg, wire.Error):
                raise wire.WireError(f"server: {msg.message}")
            if isinstance(msg, wire.GoAway):
                self._must_move = True
            elif isinstance(msg, wire.WireReply):
                got |= self._accept_reply(msg)
        return got

    # -- ServerWorker API ----------------------------------------------------
    def dispatch(self, req: CatchupRequest) -> None:
        if self._must_move and not self._flights:
            self._move_now()
        hist = np.asarray(req.history)
        self._last_history = hist
        trig = np.asarray(req.triggered, bool)
        pos = np.asarray(req.server_pos, np.int32)
        n_tok = int(np.where(trig, int(req.t) + 1 - pos, 0).sum())
        with span(self._tracer, "wire.encode", "wire", track="wire",
                  req_id=req.req_id, tokens=n_tok) as sp:
            t_enc = time.monotonic()
            buf = self._wire.encode_request(
                req.req_id, int(req.t), trig, pos,
                np.asarray(req.u, np.float32), hist)
            t_send = time.monotonic()
            sp.set(bytes=len(buf))
        self._dispatch_wall[req.req_id] = (t_send, t_send - t_enc)
        self._flights.append(_Flight(req.req_id, False, buf, int(req.t),
                                     trig.copy(), n_tok))
        try:
            self._send_frame(buf)
        except OSError as e:
            # the flight is queued: failover re-establishes and resends
            self._failover(f"send failed: {e}")

    def poll(self) -> List[CatchupReply]:
        self._pump(block=False)
        out = list(self._replies)
        self._replies.clear()
        return out

    def wait(self, req_id: int) -> List[CatchupReply]:
        out: List[CatchupReply] = []
        while True:
            while self._replies:
                r = self._replies.popleft()
                out.append(r)
                if r.req_id == req_id:
                    return out
            self._pump(block=True)

    # -- frame egress --------------------------------------------------------
    def _send_frame(self, buf: bytes) -> None:
        if self._corked is not None:
            self._corked.append(buf)
            return
        self._transmit(buf)

    def _transmit(self, buf: bytes) -> None:
        """Hand one (possibly gathered) buffer to the transport — the
        only place client bytes actually leave."""
        self._sock.settimeout(None)
        self._sock.sendall(buf)
        self._tx(len(buf))

    def cork(self) -> None:
        """Start gathering outgoing frames (idempotent).  Frames queue
        locally until ``uncork`` sends them as ONE transmit — callers
        wrap a dispatch fan-out, never a wait."""
        if self._corked is None:
            self._corked = []

    def uncork(self) -> None:
        bufs, self._corked = self._corked, None
        if not bufs:
            return
        try:
            self._transmit(b"".join(bufs))
        except OSError as e:
            # every corked frame is already in _flights: failover
            # re-establishes and resends them verbatim
            self._failover(f"send failed: {e}")

    # -- slot-pool churn (MonitorSession.attach/detach over the wire) --------

    def attach_slot(self, slot: int) -> None:
        """Tell the server to zero and re-lease row ``slot`` of this
        session's lease (a new stream moved in).  Fire-and-forget: the
        socket is FIFO, so the reset lands before any later REQUEST that
        includes the slot.  The caller (engine) drains the pipeline
        first, so no earlier request is still in flight."""
        if self._must_move and not self._flights:
            self._move_now()
        self._acked_pos[slot] = 0  # the new tenant's history starts cold
        try:
            self._send_frame(self._wire.encode_attach(slot))
        except OSError as e:
            # a post-failover lease is freshly zeroed: the reset the
            # ATTACH asked for has already happened on the new server
            self._failover(f"send failed: {e}")

    def detach_slot(self, slot: int) -> None:
        """Tell the server the stream in row ``slot`` departed (the row
        is zeroed server-side as hygiene; ATTACH re-zeroes on reuse)."""
        self._acked_pos[slot] = 0
        try:
            self._send_frame(self._wire.encode_detach(slot))
        except OSError as e:
            self._failover(f"send failed: {e}")

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._sock.settimeout(1.0)
            bye = self._wire.encode_bye()
            self._sock.sendall(bye)
            self._tx(len(bye))
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass


class ShmWorker(SocketWorker):
    """The ``shm`` transport: ``SocketWorker`` with the DATA plane moved
    into a same-host shared-memory ring pair (``serving/shm.py``).

    The handshake negotiates an arena over the ordinary UDS control
    socket (HELLO asks, HELLO_ACK offers + ships the fds via
    SCM_RIGHTS, SHM_OPEN confirms); REQUEST frames then go out through
    the client->server ring and REPLY frames come back through the
    server->client ring — byte-identical wire-codec frames, so every
    protocol invariant (FIFO replies, head-of-flights dedup, replay
    failover) is inherited unchanged.  Control frames (BYE / ATTACH /
    DETACH / GOAWAY / ERROR) stay on the socket.

    FALLBACK (always to plain wire, with a logged reason): a TCP server
    address, a server that offers no arena (wire-only or pre-v5), or a
    failed arena attach all leave ``self._peer`` as None and this class
    behaves exactly like its parent.  Fleet failover composes the same
    way: on server death the usual re-HELLO runs through the router —
    if the surviving sibling doesn't offer shm, the session continues
    pure-wire (``tests/test_shm.py`` asserts bitwise identity through
    that migration).

    Metering: ring payload bytes and ring-transport RTTs land in
    ``comms["shm"]``, socket (handshake/control) bytes in
    ``comms["wire"]`` — shm traffic is measured, never silently free.
    """

    kind = "shm"

    def __init__(self, cache, **kw):
        self._peer = None
        self.fallback_reason = ""
        super().__init__(cache, **kw)

    # -- handshake -----------------------------------------------------------
    def _handshake(self, timeout: float):
        import dataclasses

        from repro.serving import shm, wire

        self._teardown_peer()
        family, _ = wire.parse_address(self._target)
        if family != socket.AF_UNIX:
            # SCM_RIGHTS and a shared arena need a shared host: don't
            # even ask, the session is pure wire
            self.fallback_reason = ("remote (TCP) server address: shm "
                                    "needs a shared host")
            shm.log.info("shm fallback to pure wire for %s: %s",
                         self._target, self.fallback_reason)
            self._socket_span = "wire.socket"
            return super()._handshake(timeout)
        hello = dataclasses.replace(self._hello, shm=True)
        sock, ack, reader, tx, rx, peer, reason = shm.connect_hello_shm(
            self._target, hello, timeout=timeout)
        self._peer = peer
        self.fallback_reason = reason
        # the transport-hop span in the traced RTT breakdown tracks the
        # plane actually carrying data frames
        self._socket_span = "shm.ring" if peer is not None else "wire.socket"
        return sock, ack, reader, tx, rx

    def _teardown_peer(self) -> None:
        if self._peer is not None:
            self._peer.close()
            self._peer = None

    # -- metering (ring plane -> comms["shm"]) -------------------------------
    def _tx_shm(self, n: int) -> None:
        if self._comms is not None:
            if self._failing_over:
                self._comms.record_failover_tx(n)
            else:
                self._comms.record_shm_tx(n)

    def _rx_shm(self, n: int) -> None:
        if self._comms is not None:
            if self._failing_over:
                self._comms.record_failover_rx(n)
            else:
                self._comms.record_shm_rx(n)

    def _record_rtt(self, rtt: float) -> None:
        if self._comms is None:
            return
        if self._peer is not None:
            self._comms.record_shm_rtt(rtt)
        else:
            self._comms.record_wire_rtt(rtt)

    # -- data plane ----------------------------------------------------------
    _SEND_DEADLINE_S = 60.0   # ring-full backpressure cap (server dead?)

    def _transmit(self, buf: bytes) -> None:
        peer = self._peer
        if peer is None:
            return super()._transmit(buf)
        mv = memoryview(buf)
        off = 0
        deadline = time.monotonic() + self._SEND_DEADLINE_S
        while off < len(mv):
            off += peer.send_all(mv[off:],
                                 timeout=deadline - time.monotonic(),
                                 wake_fds=(self._sock.fileno(),))
            if off >= len(mv):
                break
            # the ring is full AND the control socket has traffic (or
            # the deadline passed): service control frames — a dead
            # server surfaces here as OSError, which callers turn into
            # failover; backpressure with a live server just resumes
            self._drain_control()
            if time.monotonic() > deadline:
                raise OSError("shm ring backpressure timeout "
                              f"({self._SEND_DEADLINE_S:.0f}s)")
        self._tx_shm(len(buf))

    def _drain_control(self) -> None:
        """Non-blocking read of the control socket (raises OSError on a
        closed peer so the caller's failover path takes over)."""
        self._sock.settimeout(0.0)
        try:
            data = self._sock.recv(1 << 16)
        except (BlockingIOError, socket.timeout, InterruptedError):
            return
        if not data:
            raise OSError("server closed control socket")
        self._rx(len(data))
        self._on_payloads(self._reader.feed(data))

    def _pump(self, block: bool) -> None:
        if self._peer is None:
            return super()._pump(block)
        import select as _select
        got = False
        while True:
            if self._must_move and not self._flights:
                self._move_now()
                if self._peer is None:  # migrated onto a wire sibling
                    return super()._pump(block and not got)
            peer = self._peer
            # ring first (the data plane), then the control socket
            frames = peer.recv_frames()
            if frames:
                self._rx_shm(sum(len(p) + 4 for p in frames))
                got |= self._on_payloads(frames)
            try:
                self._drain_control()
            except OSError as e:
                self._failover(f"connection lost: {e}")
                if self._peer is None:
                    return super()._pump(block and not got)
                continue
            if got or not block:
                return
            # nothing yet: sleep on doorbell + socket.  Drain BEFORE the
            # ring re-check so a wakeup racing the select is never lost
            peer.db_own.drain()
            if peer.reader.available():
                continue
            _select.select([self._sock.fileno(), peer.fileno()],
                           [], [], 0.25)

    # -- lifecycle -----------------------------------------------------------
    def _failover(self, why: str) -> None:
        self._teardown_peer()
        super()._failover(why)

    def close(self) -> None:
        if self._closed:
            return
        super().close()
        self._teardown_peer()


def make_worker(transport: str, catchup_fn, params, cache, *,
                latency_s: Optional[float] = None,
                wire_opts: Optional[Dict[str, Any]] = None) -> ServerWorker:
    """``latency_s=None`` keeps each transport's own default (0 for
    stream/thread, 20 ms for mock_remote).  ``wire_opts`` configures the
    ``wire`` transport (at minimum ``address``; see ``SocketWorker``)."""
    if transport not in TRANSPORTS:
        raise ValueError(
            f"unknown transport {transport!r}: valid transports are "
            + ", ".join(repr(t) for t in TRANSPORTS))
    if transport == "inproc":
        if latency_s:
            raise ValueError("inproc transport has no latency model")
        return ServerWorker(catchup_fn, params, cache)
    if transport in ("wire", "shm"):
        if latency_s:
            raise ValueError(
                f"{transport} transport has no simulated latency: RTT is "
                "measured on the real socket (drop latency_s)")
        if not wire_opts or "address" not in wire_opts:
            raise ValueError(
                f"{transport} transport needs wire_opts={{'address': ...}} "
                "pointing at a running correction server (python -m "
                "repro.launch.server)")
        cls = SocketWorker if transport == "wire" else ShmWorker
        return cls(cache, **wire_opts)
    kw = {} if latency_s is None else {"latency_s": latency_s}
    if transport == "stream":
        return StreamWorker(catchup_fn, params, cache, **kw)
    if transport == "thread":
        return ThreadWorker(catchup_fn, params, cache, **kw)
    return MockRemoteWorker(catchup_fn, params, cache, **kw)


class Dispatcher:
    """Edge-side request tracking + the staleness merge policy.

    ``collect(now_t)`` is called once per edge step and returns the replies
    to merge at this step, already in FIFO (request) order:

      1. poll the worker (non-blocking) into a held buffer;
      2. while the oldest in-flight request has age >= max_staleness,
         BLOCK on it (this is the only place the edge loop ever waits, and
         it never gates the monitor/trigger path — the engine calls
         ``collect`` after u is computed);
      3. release held replies that satisfy the merge window: age >= 1 in
         pipelined mode (max_staleness >= 1), age >= 0 in strict sync mode.

    Stall time (step 2) and per-request wall/compute times feed the
    ``CommsMeter`` async accounting (overlap ratio, in-flight counts).
    """

    def __init__(self, worker: ServerWorker, *, max_staleness: int = 1,
                 comms=None, tracer=None):
        if max_staleness < 0:
            raise ValueError("max_staleness must be >= 0")
        self.worker = worker
        self.max_staleness = int(max_staleness)
        self.comms = comms
        self.tracer = tracer   # optional span tracer (edge.stall spans)
        self._inflight: deque = deque()   # CatchupRequest, FIFO
        self._held: deque = deque()       # arrived, not yet merge-eligible
        self._next_id = 0

    @property
    def n_inflight(self) -> int:
        return len(self._inflight) + len(self._held)

    def dispatch(self, *, t: int, triggered: np.ndarray,
                 server_pos: np.ndarray, history, u,
                 step_t: Optional[int] = None) -> CatchupRequest:
        req = CatchupRequest(self._next_id, int(t), np.asarray(triggered),
                             np.asarray(server_pos), history, u,
                             wall_dispatch=time.monotonic(),
                             step_t=int(t) if step_t is None else int(step_t))
        self._next_id += 1
        # a dispatch that raises (a device out of memory) leaves nothing in
        # flight, so closing the session drains only what was really sent
        # and the caller sees the original error
        self.worker.dispatch(req)
        self._inflight.append(req)
        if self.comms is not None:
            self.comms.record_dispatch(req.triggered)
        return req

    def _arrived(self, replies: List[CatchupReply]) -> None:
        for r in replies:
            req = self._inflight.popleft()
            assert req.req_id == r.req_id, "worker must reply in FIFO order"
            r.step_t = req.step_t  # the staleness clock rides the request
            if self.comms is not None:
                self.comms.record_server_busy(
                    r.server_time_s, r.wall_ready - req.wall_dispatch)
            self._held.append(r)

    def collect(self, now_t: int) -> List[CatchupReply]:
        # ages are measured on the SESSION step clock (step_t), not the
        # request's trigger position t — the two coincide for a uniform
        # pool but diverge under slot-pool churn
        self._arrived(self.worker.poll())
        while (self._inflight
               and now_t - self._inflight[0].step_t >= self.max_staleness):
            head = self._inflight[0].req_id
            with span(self.tracer, "edge.stall", "edge", req_id=head,
                      step=now_t):
                t0 = time.monotonic()
                replies = self.worker.wait(head)
                if self.comms is not None:
                    self.comms.record_stall(time.monotonic() - t0)
            self._arrived(replies)
        min_age = 1 if self.max_staleness > 0 else 0
        out: List[CatchupReply] = []
        while self._held and now_t - self._held[0].step_t >= min_age:
            r = self._held.popleft()
            if self.comms is not None:
                self.comms.record_merge(r.triggered, now_t - r.step_t)
            out.append(r)
        return out

    def drain(self) -> List[CatchupReply]:
        """Block for every outstanding reply (end of stream).  Tail replies
        have no edge step left to report into; the engine folds them into
        protocol state (server_pos) only.

        Re-entrant: once drained (or when nothing was ever dispatched) a
        further ``drain`` touches no worker state and returns ``[]`` —
        safe to call again after the session closed or on a closed
        worker.
        """
        if self._inflight:
            with span(self.tracer, "edge.stall", "edge", drain=True):
                t0 = time.monotonic()
                self._arrived(self.worker.wait(self._inflight[-1].req_id))
                if self.comms is not None:
                    self.comms.record_stall(time.monotonic() - t0)
        out = list(self._held)
        self._held.clear()
        if self.comms is not None:
            for r in out:
                self.comms.record_merge(r.triggered, self.max_staleness)
        return out
