"""Standalone correction server: the server half of the paper's
``f = u + v`` decomposition as its own PROCESS, behind a real socket.

``serving/async_rpc.py``'s in-process transports simulate the network
round trip; this module is the measured counterpart.  One
``CorrectionServer`` owns a **super-batch** of cache rows (``slots``) and
leases contiguous row ranges to edge-client *sessions*: each connected
``CollaborativeEngine`` (the ``wire`` transport / ``SocketWorker``) gets
``batch`` rows of the shared server KV/SSM cache plus a matching region
of the server-side token-history mirror.  All protocol state the paper
assigns to the server — the per-stream catch-up cache and the replayed
token history — therefore lives HERE, across a serialization boundary
from the edge; the client's local server cache stays cold for the whole
session.

CROSS-CLIENT REQUEST COALESCING (the throughput mechanism):

Queued catch-up requests — from many edge clients, and from the deep
pipeline of a single async client — are merged into ONE masked replay per
event-loop tick through the engine's existing jitted ``_catchup_impl``:

  * ``triggered``  = union of the requests' trigger masks (slot-indexed);
  * ``server_pos`` = per-slot MIN of the requests' catch-up bases;
  * ``t``          = per-slot max trigger step (a (slots,) vector — the
    masked replay already supports per-stream end positions, since its
    round mask is ``server_pos + r <= t`` elementwise);
  * ``u``          = per-slot dispatch-time score of the latest request.

Because the replay is per-element masked (``engine.make_step_at``), rows
belonging to different sessions never interact: client A's triggers
cannot perturb client B's cache rows bit-wise (asserted in tests).  The
merge is safe for the protocol because every reply's corrector satisfies
``s*sigma(v) >= 0``: a coalesced reply can only carry a *fresher* v (the
replay may have advanced a shared row past an older queued request's
trigger step), and a fresher or staler corrector applied to the current
``u`` still only lowers ``fhat`` — the monitor's upper-bound safety
story is untouched (see docs/transport.md for the full argument).

What coalescing buys: the async bench at batch 64 is compute-bound on
per-request dense replay rounds (each queued request costs a full masked
pass over the batch).  Merging k queued requests costs max-rounds once
instead of sum-of-rounds — the per-request dispatch floor drops by ~k.

Replies are FIFO per session (the Dispatcher's ordering contract): a
session either coalesces (all its queued requests merge, replies emitted
in arrival order) or opted out via HELLO (``coalesce=False`` — the
bench's per-request baseline), in which case its requests replay one by
one, still in arrival order.

The event loop is a single-threaded ``selectors`` reactor: drain every
readable socket (and every session doorbell, for shared-memory
sessions), then run at most one coalesced replay, then flush writes.
JAX compute happens on the loop thread — the server is itself a batched
inference engine, not a proxy.  Run it with
``python -m repro.launch.server`` (see that module for the CLI) or embed
it in a thread via ``serve_forever(stop=threading.Event())`` (tests).

Same-host shared-memory sessions (``shm=True`` + a v5 client asking for
it): the HELLO_ACK carries an arena offer and its fds via SCM_RIGHTS,
data frames then move through the arena's ring pair
(``serving/shm.py``) while every control frame stays on the socket.
The reactor registers each session's doorbell fd alongside the sockets
— ring traffic wakes the same ``select``, no busy-spinning — and the
server NEVER blocks on a full reply ring: residue buffers in the
session and flushes when the client's consume-side doorbell fires.
"""
from __future__ import annotations

import logging
import selectors
import socket
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import decomposition as deco
from repro.observability import MetricsRegistry, Tracer
from repro.serving import shm as shm_mod
from repro.serving import wire
from repro.serving.collaborative import CollaborativeEngine
from repro.serving.engine import cache_batch_axes, zero_cache_rows
from repro.serving.tracker import Histogram, Tracker

log = logging.getLogger("repro.serving.server")

# sendmsg gather limit per flush: comfortably under any IOV_MAX (Linux
# has 1024); a tick queueing more frames than this simply loops
_IOV_MAX = 64


@dataclass
class Session:
    """One connected edge client: a leased range of super-batch rows."""

    sid: int
    conn: socket.socket
    lo: int = -1            # first super-batch row (−1 until HELLO)
    batch: int = 0
    max_len: int = 0
    coalesce: bool = True
    client: str = "?"
    reader: wire.FrameReader = field(default_factory=wire.FrameReader)
    # per-frame output buffers, gathered into ONE sendmsg per flush
    out: List[bytes] = field(default_factory=list)
    # -- shared-memory transport (serving/shm.py) ---------------------------
    shm_arena: Optional["shm_mod.ServerArena"] = None  # offered at HELLO
    shm_live: bool = False     # client confirmed with SHM_OPEN(ok=True)
    shm_out: bytearray = field(default_factory=bytearray)  # reply-ring residue

    @property
    def hi(self) -> int:
        return self.lo + self.batch


class CorrectionServer:
    """Socket front-end + coalescing replay core over one super-batch."""

    def __init__(self, cfg: ArchConfig, params, *, slots: int = 16,
                 max_len: int = 128, uds: Optional[str] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 coalesce: bool = True, mesh: Optional[str] = None,
                 tracker: Optional[Tracker] = None,
                 tracer: Optional[Tracer] = None,
                 stats_interval_s: float = 0.5,
                 shm: bool = False,
                 shm_ring_bytes: int = shm_mod.DEFAULT_RING_BYTES):
        self.cfg, self.m = cfg, cfg.monitor
        self.slots, self.max_len = int(slots), int(max_len)
        self.coalesce = bool(coalesce)   # server-wide kill switch
        # offer a shared-memory arena to v5 clients that ask for one
        # (same-host UDS connections only; TCP peers stay pure-wire)
        self.shm = bool(shm)
        self.shm_ring_bytes = int(shm_ring_bytes)
        # the replay core IS the engine's jitted masked catch-up: one
        # CollaborativeEngine at batch=slots supplies the compiled
        # _catchup_impl and the super-batch server cache.  (Its edge tower
        # and comms meter are unused here — the edge lives in the clients.)
        # ``mesh`` ("data:8"-style, serving/mesh.py) shards that
        # super-batch over a device mesh: the coalesced replay runs with
        # each device holding slots/N cache rows, and leases/resets stay
        # row-local (the per-stream protocol is elementwise).
        eng = CollaborativeEngine(params, cfg, batch=self.slots,
                                  max_len=self.max_len, mesh=mesh)
        self._eng = eng
        self.mesh_spec = eng.mesh_spec
        self._cache = eng.server.cache
        self._cache_shardings = eng.server._cache_shardings
        self._axes = cache_batch_axes(cfg, self.slots, self.max_len)
        tok_tail = (cfg.n_codebooks,) if cfg.family == "audio" else ()
        self.tok_tail: Tuple[int, ...] = tok_tail
        # server-side token-history mirror: requests carry only backlog
        # slices; the replay needs them at absolute positions
        self._history = np.zeros((self.slots, self.max_len) + tok_tail,
                                 np.int32)
        # per-reply fusion from the REQUEST's own trigger mask and u (the
        # server's threshold is irrelevant: the client already decided)
        s, sig = self.m.s, self.m.sigma
        self._fuse = jax.jit(lambda u, v, trig: jnp.where(
            trig, u - s * deco.sigma(v, sig), u))

        # -- sessions / slots ------------------------------------------------
        self._sessions: Dict[socket.socket, Session] = {}
        self._free: List[Tuple[int, int]] = [(0, self.slots)]  # [lo, hi)
        self._next_sid = 1
        self._pending: List[Tuple[Session, wire.WireRequest, float]] = []

        # -- observability (repro/observability) ------------------------------
        # One MetricsRegistry backs every counter and histogram below;
        # ``stats``/``hist`` remain the public read surface (tests, the
        # launch CLI's SIGTERM dump) but the heartbeat snapshot is now
        # just ``registry.snapshot()`` plus identity fields — same keys
        # the FleetSupervisor always scraped.
        self.metrics = MetricsRegistry()
        for name in ("requests", "replays", "coalesced", "sessions",
                     "bytes_rx", "bytes_tx", "attaches", "detaches",
                     "defrags", "refused_draining",
                     # tx_flushes counts sendmsg syscalls: frames queued
                     # in one tick gather into ONE flush (the
                     # micro-batching regression gauge)
                     "tx_flushes",
                     # ring-plane bytes, metered separately from the
                     # socket so shm payloads are never silently free
                     "shm_bytes_rx", "shm_bytes_tx", "shm_sessions"):
            self.metrics.counter(name)   # pre-create: zeros still report
        # replay compute time per coalesced group (seconds)
        self.metrics.histogram("replay_s", 1e-5, 60.0)
        # requests merged per replay (the coalescing win)
        self.metrics.histogram("coalesce_width", 1.0, 4096.0)
        # request arrival -> reply enqueued, server-side (seconds)
        self.metrics.histogram("turnaround_s", 1e-5, 60.0)
        # request arrival -> replay start: the v4 REPLY timing payload,
        # so clients can split queueing from compute in their RTT
        self.metrics.histogram("queue_wait_s", 1e-6, 60.0)

        # ``tracker`` turns the one-shot SIGTERM stats print into a live
        # surface: serve_forever logs a full snapshot every
        # ``stats_interval_s`` — with a JsonFileTracker that IS the fleet
        # heartbeat the supervisor scrapes for load + liveness.
        self.tracker = tracker
        self.stats_interval_s = float(stats_interval_s)
        self._last_stats_log = 0.0
        # optional server-LOCAL span tracer (launch/server.py
        # --trace-file): records server.queue / server.replay spans on
        # the server's own clock; None (the default) costs one flag check
        self.tracer = tracer

        # -- drain (fleet lifecycle) ------------------------------------------
        # request_drain() is signal-safe (launch/server.py maps SIGUSR1 to
        # it); the reactor applies it on its own thread at the next tick:
        # GOAWAY to every leased session, ERROR to new HELLOs.  Sessions
        # finish their in-flight requests, BYE, and re-HELLO elsewhere —
        # zero streams dropped (tests/test_fleet.py::test_drain_*).
        self.draining = False
        self._drain_req = threading.Event()

        # -- listener ---------------------------------------------------------
        self.uds = uds
        if uds is not None:
            self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            self._listener.bind(uds)
            self.address = uds
        else:
            self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._listener.bind((host, port))
            h, p = self._listener.getsockname()
            self.address = f"{h}:{p}"
        self._listener.listen(64)
        self._listener.setblocking(False)
        self._sel = selectors.DefaultSelector()
        self._sel.register(self._listener, selectors.EVENT_READ, "accept")
        self._closed = False

    # -- observability / fleet surface ---------------------------------------
    @property
    def stats(self) -> Dict[str, object]:
        """Counter snapshot (name -> value) — the pre-registry ``stats``
        dict, now a registry view.  Read-only: mutate via
        ``self.metrics.inc``."""
        return self.metrics.counters()

    @property
    def hist(self) -> Dict[str, Histogram]:
        """The registry's histograms, by name (``replay_s`` etc.)."""
        return self.metrics.hists

    def leased_rows(self) -> int:
        """Super-batch rows currently leased — the routing load signal."""
        return self.slots - sum(h - l for l, h in self._free)

    def sessions_live(self) -> int:
        return sum(1 for s in self._sessions.values() if s.lo >= 0)

    def stats_snapshot(self) -> Dict[str, object]:
        """One scrapeable heartbeat record: identity, load, health, and
        the counter/histogram state.  This dict is what JsonFileTracker
        writes and what ``FleetSupervisor`` reads."""
        snap: Dict[str, object] = {
            "ts": time.time(),
            "address": self.address,
            "slots": self.slots,
            "leased_rows": self.leased_rows(),
            "sessions_live": self.sessions_live(),
            "fragmentation": self.fragmentation(),
            "draining": self.draining,
        }
        snap.update(self.metrics.snapshot())
        return snap

    # -- drain (fleet lifecycle) ---------------------------------------------
    def request_drain(self) -> None:
        """Ask the reactor to start draining (safe from signal handlers
        and other threads; applied at the next ``serve_tick``)."""
        self._drain_req.set()

    def start_drain(self) -> None:
        """Stop taking work: GOAWAY every leased session, refuse new
        HELLOs.  In-flight requests still complete — the client decides
        when its pipeline is empty and moves."""
        if self.draining:
            return
        self.draining = True
        for sess in list(self._sessions.values()):
            if sess.lo >= 0:
                self._send(sess, wire.encode_goaway("draining"))

    # -- slot allocation -----------------------------------------------------
    def _alloc(self, n: int) -> int:
        for i, (lo, hi) in enumerate(self._free):
            if hi - lo >= n:
                self._free[i] = (lo + n, hi)
                if self._free[i][0] == self._free[i][1]:
                    del self._free[i]
                return lo
        return -1

    def _release(self, lo: int, n: int) -> None:
        self._free.append((lo, lo + n))
        self._free.sort()
        merged: List[Tuple[int, int]] = []
        for a, b in self._free:
            if merged and merged[-1][1] == a:
                merged[-1] = (merged[-1][0], b)
            else:
                merged.append((a, b))
        self._free = merged

    def _reset_rows(self, lo: int, hi: int) -> None:
        """Zero a leased range: a new session (or a re-leased slot — the
        ATTACH churn frame) must see cold cache rows even if a previous
        tenant used them.  Spec-aware: on a mesh-sharded super-batch the
        reset preserves the cache placement (each device rewrites only
        its own rows — no gather-to-host)."""
        rows = np.zeros(self.slots, bool)
        rows[lo:hi] = True
        self._cache = zero_cache_rows(self._cache, self._axes,
                                      jnp.asarray(rows),
                                      shardings=self._cache_shardings)
        self._history[lo:hi] = 0

    # -- lease defrag --------------------------------------------------------
    def fragmentation(self) -> float:
        """Lease-fragmentation gauge in [0, 1): the fraction of free
        super-batch rows NOT in the largest free extent.  0 when the
        free space is one contiguous block (or there is none); reported
        in the SIGTERM stats dump of ``launch/server.py``."""
        free = sum(h - l for l, h in self._free)
        if free == 0:
            return 0.0
        return 1.0 - max(h - l for l, h in self._free) / free

    def _defrag(self) -> None:
        """Compact live leases to the low end of the super-batch so the
        free rows form ONE contiguous tail (a long-lived multi-tenant
        server must not refuse a batch-N HELLO while N rows sit free in
        scattered holes).  Cache rows and the history mirror move WITH
        their sessions — a client's rows are bit-identical before and
        after, only their physical position changes, and clients address
        slots relative to ``sess.lo`` so nothing crosses the wire.
        Queued requests stay valid: the replay reads ``sess.lo`` at
        replay time, after the rows have moved."""
        live = sorted((s for s in self._sessions.values() if s.lo >= 0),
                      key=lambda s: s.lo)
        if not any(s.lo != lo for s, lo in
                   zip(live, np.cumsum([0] + [s.batch for s in live]))):
            return  # already compact
        order: List[int] = []
        for s in live:
            order.extend(range(s.lo, s.lo + s.batch))
        taken = set(order)
        perm = np.asarray(order + [r for r in range(self.slots)
                                   if r not in taken])
        permj = jnp.asarray(perm)
        self._cache = jax.tree.map(
            lambda a, ax: jnp.take(a, permj, axis=ax), self._cache,
            self._axes)
        if self._cache_shardings is not None:
            self._cache = jax.tree.map(jax.device_put, self._cache,
                                       self._cache_shardings)
        self._history = self._history[perm]
        lo = 0
        for s in live:
            s.lo = lo
            lo += s.batch
        self._free = [(lo, self.slots)] if lo < self.slots else []
        self.metrics.inc("defrags")

    # -- socket plumbing -----------------------------------------------------
    def _send(self, sess: Session, data: bytes, *,
              flush: bool = True) -> None:
        """Queue a frame; ``flush=False`` defers the syscall so a tick
        that produces many frames for one session (a coalesced replay's
        reply fan-out) gathers them into ONE ``sendmsg``."""
        sess.out.append(data)
        if flush:
            self._flush(sess)

    def _flush(self, sess: Session) -> None:
        while sess.out:
            try:
                n = sess.conn.sendmsg(sess.out[:_IOV_MAX])
            except (BlockingIOError, InterruptedError):
                break
            except OSError:
                self._drop(sess)
                return
            self.metrics.inc("bytes_tx", n)
            self.metrics.inc("tx_flushes")
            # retire fully-sent buffers; re-head a partially-sent one
            while n > 0:
                head = sess.out[0]
                if n >= len(head):
                    n -= len(head)
                    sess.out.pop(0)
                else:
                    sess.out[0] = head[n:]
                    n = 0
        events = selectors.EVENT_READ | (selectors.EVENT_WRITE if sess.out
                                         else 0)
        try:
            self._sel.modify(sess.conn, events, "conn")
        except KeyError:
            pass

    # -- shared-memory plumbing (serving/shm.py) -----------------------------
    def _send_reply(self, sess: Session, data: bytes) -> None:
        """Data-plane send: the reply ring when the session is shm-live,
        the (deferred, gathered) socket path otherwise."""
        if sess.shm_live:
            sess.shm_out.extend(data)
            self._shm_flush(sess)
        else:
            self._send(sess, data, flush=False)

    def _shm_flush(self, sess: Session) -> None:
        """Move reply residue into the ring — as much as fits.  The
        server never blocks here: leftovers stay buffered and this runs
        again when the client's consume rings our doorbell."""
        arena = sess.shm_arena
        if arena is None or not sess.shm_out:
            return
        wrote = 0
        while sess.shm_out:
            n = arena.peer.writer.write(sess.shm_out)
            if n == 0:
                break
            del sess.shm_out[:n]
            wrote += n
        if wrote:
            self.metrics.inc("shm_bytes_tx", wrote)
            arena.peer.db_peer.ring()

    def _shm_wake(self, sess: Session) -> None:
        """Session doorbell fired: the client produced requests and/or
        consumed replies.  Drain-then-check so a ring racing the select
        is never lost."""
        arena = sess.shm_arena
        if arena is None or sess.conn not in self._sessions:
            return
        arena.peer.db_own.drain()
        self._shm_flush(sess)           # the client may have freed space
        try:
            frames = arena.peer.recv_frames()
        except wire.WireError as e:
            try:
                self._send(sess, wire.encode_error(str(e)))
            finally:
                self._drop(sess)
            return
        for p in frames:
            if sess.conn not in self._sessions:
                return
            self.metrics.inc("shm_bytes_rx", len(p) + 4)
            try:
                self._handle(sess, wire.decode(p))
            except wire.WireError as e:
                try:
                    self._send(sess, wire.encode_error(str(e)))
                finally:
                    self._drop(sess)
                return

    def _offer_shm(self, sess: Session) -> bool:
        """Answer a shm-requesting HELLO with an arena offer: the ack
        frame plus the arena/doorbell fds in ONE sendmsg (SCM_RIGHTS),
        after which the arena file is unlinked — the crash-safe window
        closes before the client even replies.  Returns False (and
        leaks nothing) when anything fails; the caller then sends the
        plain ack and the session stays pure-wire."""
        if sess.out:
            self._flush(sess)
            if sess.out:
                return False  # can't append fds to a backlogged stream
        try:
            arena = shm_mod.ServerArena.create(self.shm_ring_bytes)
        except (OSError, shm_mod.ShmError) as e:
            log.warning("arena creation failed (%s); session %d stays on "
                        "wire", e, sess.sid)
            return False
        buf = wire.encode_hello_ack(wire.HelloAck(
            sess.sid, sess.lo, self.max_len, shm_path=arena.path,
            ring_bytes=arena.ring_bytes, db_kind=arena.db_kind))
        try:
            n = socket.send_fds(sess.conn, [buf], arena.fds())
        except OSError as e:
            arena.close()
            log.warning("SCM_RIGHTS send failed (%s); session %d stays on "
                        "wire", e, sess.sid)
            return False
        arena.sent()  # fds are kernel-referenced in flight: unlink now
        sess.shm_arena = arena
        self.metrics.inc("bytes_tx", n)
        self.metrics.inc("tx_flushes")
        if n < len(buf):  # partial ack frame: finish on the normal path
            sess.out.append(buf[n:])
            self._flush(sess)
        return True

    def _shm_teardown(self, sess: Session) -> None:
        arena = sess.shm_arena
        if arena is None:
            return
        try:
            self._sel.unregister(arena.peer.fileno())
        except (KeyError, ValueError):
            pass
        arena.close()
        sess.shm_arena = None
        sess.shm_live = False
        sess.shm_out.clear()

    def _drop(self, sess: Session) -> None:
        self._shm_teardown(sess)
        try:
            self._sel.unregister(sess.conn)
        except (KeyError, ValueError):
            pass
        try:
            sess.conn.close()
        except OSError:
            pass
        released = sess.lo >= 0
        if released:
            self._release(sess.lo, sess.batch)
            # _drop can re-enter for the same session (the BYE handler
            # flushes then drops, and the flush itself drops on a broken
            # pipe when the peer closed first): releasing twice would
            # duplicate free ranges and later double-lease rows to two
            # tenants — mark the lease gone
            sess.lo = -1
        self._sessions.pop(sess.conn, None)
        self._pending = [p for p in self._pending if p[0] is not sess]
        # BYE/disconnect defrag: keep the freed rows one contiguous tail.
        # Deferred while catch-up requests are queued — the compaction
        # permutes the whole super-batch cache on the reactor thread, and
        # co-resident clients' replays must not stall behind it (a
        # fragmented map is still compacted lazily at the next HELLO that
        # needs it, see ``_handle``)
        if released and len(self._free) > 1 and not self._pending:
            self._defrag()

    def _accept(self) -> None:
        while True:
            try:
                conn, _ = self._listener.accept()
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return
            conn.setblocking(False)
            if conn.family == socket.AF_INET:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sess = Session(self._next_sid, conn)
            self._next_sid += 1
            self._sessions[conn] = sess
            self._sel.register(conn, selectors.EVENT_READ, "conn")

    def _read(self, sess: Session) -> None:
        while True:
            try:
                data = sess.conn.recv(1 << 16)
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                self._drop(sess)
                return
            if not data:
                self._drop(sess)
                return
            self.metrics.inc("bytes_rx", len(data))
            try:
                payloads = sess.reader.feed(data)
                for p in payloads:
                    if sess.conn not in self._sessions:
                        return  # dropped mid-batch (BYE/protocol error)
                    self._handle(sess, wire.decode(p))
            except wire.WireError as e:
                try:
                    self._send(sess, wire.encode_error(str(e)))
                finally:
                    self._drop(sess)
                return

    # -- protocol ------------------------------------------------------------
    def _handle(self, sess: Session, msg: wire.Message) -> None:
        if isinstance(msg, wire.Hello):
            if self.draining:
                # a REFUSAL, not a death: the client sees HandshakeRefused
                # and tries a sibling (the router stopped advertising us)
                self.metrics.inc("refused_draining")
                self._send(sess, wire.encode_error(
                    "draining: no new sessions"))
                return
            if sess.lo >= 0:
                self._send(sess, wire.encode_error("duplicate HELLO"))
                return
            if msg.max_len > self.max_len:
                self._send(sess, wire.encode_error(
                    f"client max_len {msg.max_len} > server {self.max_len}"))
                return
            if msg.tok_tail != self.tok_tail:
                self._send(sess, wire.encode_error(
                    f"token tail {msg.tok_tail} != server {self.tok_tail}"))
                return
            lo = self._alloc(msg.batch)
            if lo < 0 and len(self._free) > 1 \
                    and sum(h - l for l, h in self._free) >= msg.batch:
                # enough rows free in total, just fragmented: compact and
                # retry — a HELLO that fits is never refused for holes
                self._defrag()
                lo = self._alloc(msg.batch)
            if lo < 0:
                self._send(sess, wire.encode_error(
                    f"server full: {msg.batch} slots requested, "
                    f"{sum(h - l for l, h in self._free)} free of {self.slots}"))
                return
            sess.lo, sess.batch = lo, msg.batch
            sess.max_len = msg.max_len
            sess.coalesce = bool(msg.coalesce) and self.coalesce
            sess.client = msg.client
            self._reset_rows(lo, lo + msg.batch)
            self.metrics.inc("sessions")
            if (self.shm and msg.shm
                    and sess.conn.family == socket.AF_UNIX):
                if self._offer_shm(sess):
                    return
            self._send(sess, wire.encode_hello_ack(
                wire.HelloAck(sess.sid, lo, self.max_len)))
        elif isinstance(msg, wire.ShmOpen):
            # the client's verdict on our arena offer: ok moves data
            # frames to the rings (register the doorbell with the
            # reactor); a decline tears the arena down — the session
            # continues pure-wire either way
            if sess.shm_arena is None:
                self._send(sess, wire.encode_error("SHM_OPEN without offer"))
                self._drop(sess)
                return
            if msg.ok:
                sess.shm_live = True
                self.metrics.inc("shm_sessions")
                self._sel.register(sess.shm_arena.peer.fileno(),
                                   selectors.EVENT_READ, ("shm", sess))
            else:
                log.info("session %d declined shm offer; staying on wire",
                         sess.sid)
                self._shm_teardown(sess)
        elif isinstance(msg, wire.WireRequest):
            if sess.lo < 0:
                self._send(sess, wire.encode_error("request before HELLO"))
                return
            bad = self._validate_request(sess, msg)
            if bad is not None:
                # a geometry violation is a protocol breach: reject AND
                # drop, so a buggy client can never reach rows outside
                # its lease or crash the shared replay
                self._send(sess, wire.encode_error(bad))
                self._drop(sess)
                return
            self._pending.append((sess, msg, time.monotonic()))
        elif isinstance(msg, (wire.Attach, wire.Detach)):
            # slot-pool churn: one row of THIS session's lease turns over.
            # The client drains its pipeline before churning, so no
            # request of this session that references the row is queued;
            # other sessions cannot reference it at all (lease geometry).
            if sess.lo < 0:
                self._send(sess, wire.encode_error("churn before HELLO"))
                self._drop(sess)
                return
            if not 0 <= msg.slot < sess.batch:
                self._send(sess, wire.encode_error(
                    f"churn slot {msg.slot} outside lease batch "
                    f"({sess.batch},)"))
                self._drop(sess)
                return
            row = sess.lo + msg.slot
            self._reset_rows(row, row + 1)
            self.metrics.inc("attaches" if isinstance(msg, wire.Attach)
                             else "detaches")
        elif isinstance(msg, wire.Bye):
            self._flush(sess)
            self._drop(sess)
        elif isinstance(msg, wire.Error):
            self._drop(sess)
        # HelloAck / WireReply from a client are protocol violations;
        # drop silently rather than crash the loop
        else:
            self._drop(sess)

    def _validate_request(self, sess: Session,
                          req: wire.WireRequest) -> Optional[str]:
        """Geometry check against the session's lease — every index the
        replay will touch must be inside it.  Returns an error string, or
        None when the request is well-formed."""
        B = sess.batch
        if (req.triggered.shape != (B,) or req.server_pos.shape != (B,)
                or req.u.shape != (B,)):
            return (f"request vectors {req.triggered.shape}/"
                    f"{req.server_pos.shape}/{req.u.shape} != session "
                    f"batch ({B},)")
        if not 0 <= req.t < sess.max_len:
            return f"trigger step {req.t} outside [0, {sess.max_len})"
        if req.triggered.any():
            pos = req.server_pos[req.triggered]
            if (pos < 0).any() or (pos > req.t).any():
                return "server_pos outside [0, t] on a triggered stream"
        want = (int(req.backlog_lengths().sum()),) + self.tok_tail
        if req.tokens.shape != want:
            return f"token payload shape {req.tokens.shape} != {want}"
        return None

    # -- the replay core -----------------------------------------------------
    def _replay(self, group: List[Tuple[Session, wire.WireRequest, float]]
                ) -> None:
        """One masked catch-up over the union of the group's requests,
        then one reply per request (arrival order)."""
        S = self.slots
        trig = np.zeros(S, bool)
        pos = np.zeros(S, np.int32)
        tvec = np.zeros(S, np.int32)
        uvec = np.zeros(S, np.float32)
        for sess, req, _ in group:
            lengths = req.backlog_lengths()
            off = 0
            for i in np.flatnonzero(req.triggered):
                L = int(lengths[i])
                gi = sess.lo + int(i)
                p = int(req.server_pos[i])
                self._history[gi, p:req.t + 1] = req.tokens[off:off + L]
                off += L
                if trig[gi]:
                    pos[gi] = min(pos[gi], p)
                else:
                    pos[gi] = p
                trig[gi] = True
                if req.t >= tvec[gi]:
                    tvec[gi] = req.t
                    uvec[gi] = req.u[i]
        t0 = time.monotonic()
        cache, v, _ = self._eng._catchup(
            self._eng.params, self._cache, jnp.asarray(self._history),
            jnp.asarray(pos), jnp.asarray(tvec), jnp.asarray(trig),
            jnp.asarray(uvec))
        v = jax.block_until_ready(v)
        self._cache = cache
        dt = time.monotonic() - t0
        v_np = np.asarray(v)
        self.metrics.inc("replays")
        self.metrics.inc("requests", len(group))
        if len(group) > 1:
            self.metrics.inc("coalesced", len(group) - 1)
        hist = self.metrics.hists
        hist["replay_s"].observe(max(dt, 1e-9))
        hist["coalesce_width"].observe(len(group))
        if self.tracer is not None:
            self.tracer.add("server.replay", "server", t0, dt,
                            track="server", coalesced=len(group))
        now = time.monotonic()
        touched: Dict[int, Session] = {}
        for sess, req, arrived in group:
            # queue wait = arrival -> replay start: the duration-only v4
            # timing payload the client uses to split its measured RTT
            # into socket / queue / compute
            queue_s = max(t0 - arrived, 0.0)
            hist["queue_wait_s"].observe(max(queue_s, 1e-9))
            hist["turnaround_s"].observe(max(now - arrived, 1e-9))
            if self.tracer is not None:
                self.tracer.add("server.queue", "server", arrived, queue_s,
                                track="server", req_id=req.req_id)
            vi = v_np[sess.lo:sess.hi]
            fhat = np.asarray(self._fuse(jnp.asarray(req.u),
                                         jnp.asarray(vi),
                                         jnp.asarray(req.triggered)))
            self._send_reply(sess, wire.encode_reply(wire.WireReply(
                req.req_id, req.t, req.triggered, vi, fhat,
                server_time_s=dt / len(group), coalesced=len(group),
                queue_s=queue_s)))
            touched[sess.sid] = sess
        # ONE gathered flush per session for every reply this tick
        # queued (the micro-batching fix: k frames, one sendmsg)
        for sess in touched.values():
            if sess.conn in self._sessions and not sess.shm_live:
                self._flush(sess)

    def _process_pending(self) -> None:
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        group = [p for p in pending if p[0].coalesce]
        if group:
            self._replay(group)
        for p in pending:
            if not p[0].coalesce:
                self._replay([p])

    # -- loop ----------------------------------------------------------------
    def serve_tick(self, timeout: float = 0.001) -> None:
        if self._drain_req.is_set() and not self.draining:
            self.start_drain()
        for key, mask in self._sel.select(timeout):
            if key.data == "accept":
                self._accept()
                continue
            if isinstance(key.data, tuple) and key.data[0] == "shm":
                # a session doorbell: ring traffic (requests in, and/or
                # reply-ring space freed) — no socket involved
                self._shm_wake(key.data[1])
                continue
            sess = self._sessions.get(key.fileobj)
            if sess is None:
                continue
            if mask & selectors.EVENT_READ:
                self._read(sess)
            if mask & selectors.EVENT_WRITE and sess.conn in self._sessions:
                self._flush(sess)
        self._process_pending()

    def serve_forever(self, *, poll_s: float = 0.001,
                      stop: Optional[threading.Event] = None,
                      idle_exit_s: Optional[float] = None) -> None:
        """Run until ``stop`` is set (or forever).  ``idle_exit_s``: exit
        once a session has existed and none remain for that long — test
        and bench hygiene for subprocess servers."""
        try:
            self._serve_loop(poll_s, stop, idle_exit_s)
        except BaseException:
            # a reactor that dies must not leave its clients waiting on
            # open sockets: close them, as a crashed server process would
            self.close()
            raise

    def _serve_loop(self, poll_s: float, stop: Optional[threading.Event],
                    idle_exit_s: Optional[float]) -> None:
        idle_since: Optional[float] = None
        while stop is None or not stop.is_set():
            self.serve_tick(poll_s)
            if self.tracker is not None:
                now = time.monotonic()
                if now - self._last_stats_log >= self.stats_interval_s:
                    self._last_stats_log = now
                    self.tracker.log(self.stats_snapshot())
            # a drained server with no sessions left has nothing to do:
            # exit so the supervisor can reap it without a kill
            if self.draining and not self._sessions:
                return
            if idle_exit_s is not None:
                if self._sessions or self.stats["sessions"] == 0:
                    idle_since = None
                elif idle_since is None:
                    idle_since = time.monotonic()
                elif time.monotonic() - idle_since > idle_exit_s:
                    return

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for sess in list(self._sessions.values()):
            self._drop(sess)
        try:
            self._sel.unregister(self._listener)
        except (KeyError, ValueError):
            pass
        self._listener.close()
        self._sel.close()
        if self.tracker is not None:
            try:
                self.tracker.finish()
            except OSError:
                pass
        if self.uds is not None:
            import os
            try:
                os.unlink(self.uds)
            except OSError:
                pass
