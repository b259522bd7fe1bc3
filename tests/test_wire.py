"""Wire transport subsystem: codec round trips, the standalone correction
server (loopback bit-identity, multi-client isolation, request
coalescing), the transport registry's failure modes, and idempotent
teardown of workers/dispatchers."""
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import SPAWN_DEADLINE_S
from repro.configs.paper_synthetic import SERVING
from repro.core import decomposition as deco
from repro.data import tokens as tok
from repro.serving import SessionConfig, TransportSpec, async_rpc, wire
from repro.serving.collaborative import CollaborativeEngine

KEY = jax.random.PRNGKey(0)


def run_scan(eng, stream):
    return eng.session(SessionConfig(mode="scan")).run(stream)


def run_sync(eng, stream):
    return eng.session().run(stream)


def run_wire(eng, stream, *, address, max_staleness):
    cfg = SessionConfig(mode="async", max_staleness=max_staleness,
                        transport=TransportSpec("wire", address=address))
    with eng.session(cfg) as s:
        return s.run(stream)


def _cfg(threshold=0.1):
    return SERVING.replace(monitor=SERVING.monitor.__class__(
        **{**SERVING.monitor.__dict__, "threshold": threshold,
           "trigger_margin": 0.0}))


def _uds_path(tag):
    # mktemp-style: bind() creates the file, so the path must not exist
    return os.path.join(tempfile.mkdtemp(prefix=f"wire_{tag}_"), "s.sock")


# -- codec -------------------------------------------------------------------

class TestCodec:
    @settings(max_examples=20, deadline=None)
    @given(batch=st.integers(min_value=1, max_value=9),
           max_len=st.integers(min_value=2, max_value=33),
           t_frac=st.floats(min_value=0.0, max_value=1.0),
           k=st.sampled_from([0, 2]),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_request_round_trip(self, batch, max_len, t_frac, k, seed):
        """Arbitrary batch/length/codebook-tail geometry survives the
        frame: vectors bitwise, and the token payload is EXACTLY the
        concatenated triggered backlogs (never the full history)."""
        rng = np.random.default_rng(seed)
        t = int(round(t_frac * (max_len - 1)))
        triggered = rng.random(batch) < 0.5
        server_pos = rng.integers(0, t + 1, batch).astype(np.int32)
        u = rng.standard_normal(batch).astype(np.float32)
        tail = (k,) if k else ()
        history = rng.integers(0, 255, (batch, max_len) + tail,
                               dtype=np.int64).astype(np.int32)
        buf = wire.encode_request(7, t, triggered, server_pos, u, history)
        payloads = wire.FrameReader().feed(buf)
        assert len(payloads) == 1
        msg = wire.decode(payloads[0])
        assert isinstance(msg, wire.WireRequest)
        assert msg.req_id == 7 and msg.t == t
        np.testing.assert_array_equal(msg.triggered, triggered)
        np.testing.assert_array_equal(msg.server_pos, server_pos)
        np.testing.assert_array_equal(msg.u, u)
        rows = np.flatnonzero(triggered)
        if len(rows):
            want = np.concatenate(
                [history[i, server_pos[i]:t + 1] for i in rows], axis=0)
        else:
            want = np.zeros((0,) + tail, np.int32)
        np.testing.assert_array_equal(msg.tokens, want)
        np.testing.assert_array_equal(
            msg.backlog_lengths(),
            np.where(triggered, t + 1 - server_pos, 0))
        # backlog-proportional frames: payload ≈ tokens + per-stream
        # vectors, nowhere near the full (batch, max_len) history
        assert len(buf) < want.size * 4 + batch * 16 + 128

    @settings(max_examples=10, deadline=None)
    @given(batch=st.integers(min_value=1, max_value=17),
           seed=st.integers(min_value=0, max_value=2**31 - 1),
           coalesced=st.integers(min_value=1, max_value=64))
    def test_reply_round_trip(self, batch, seed, coalesced):
        rng = np.random.default_rng(seed)
        r = wire.WireReply(
            req_id=rng.integers(0, 2**63), t=int(rng.integers(0, 1000)),
            triggered=rng.random(batch) < 0.5,
            v=rng.standard_normal(batch).astype(np.float32),
            fhat=rng.standard_normal(batch).astype(np.float32),
            server_time_s=float(rng.random()), coalesced=coalesced)
        buf = wire.encode_reply(r)
        (payload,) = wire.FrameReader().feed(buf)
        got = wire.decode(payload)
        assert isinstance(got, wire.WireReply)
        assert got.req_id == r.req_id and got.t == r.t
        assert got.coalesced == coalesced
        assert got.server_time_s == pytest.approx(r.server_time_s)
        np.testing.assert_array_equal(got.triggered, r.triggered)
        np.testing.assert_array_equal(got.v, r.v)
        np.testing.assert_array_equal(got.fhat, r.fhat)

    def test_control_messages_round_trip(self):
        h = wire.Hello(batch=4, max_len=32, tok_tail=(8,), coalesce=False,
                       client="edge-7")
        (p,) = wire.FrameReader().feed(wire.encode_hello(h))
        assert wire.decode(p) == h
        a = wire.HelloAck(session_id=3, slot_lo=12, server_max_len=128)
        (p,) = wire.FrameReader().feed(wire.encode_hello_ack(a))
        assert wire.decode(p) == a
        (p,) = wire.FrameReader().feed(wire.encode_bye())
        assert isinstance(wire.decode(p), wire.Bye)
        (p,) = wire.FrameReader().feed(wire.encode_attach(3))
        assert wire.decode(p) == wire.Attach(3)
        (p,) = wire.FrameReader().feed(wire.encode_detach(7))
        assert wire.decode(p) == wire.Detach(7)
        (p,) = wire.FrameReader().feed(wire.encode_error("boom"))
        assert wire.decode(p) == wire.Error("boom")
        (p,) = wire.FrameReader().feed(wire.encode_redirect("/tmp/x.sock"))
        assert wire.decode(p) == wire.Redirect("/tmp/x.sock")
        (p,) = wire.FrameReader().feed(wire.encode_goaway())
        assert wire.decode(p) == wire.GoAway("draining")
        (p,) = wire.FrameReader().feed(wire.encode_goaway("rebalance"))
        assert wire.decode(p) == wire.GoAway("rebalance")

    def test_old_protocol_version_rejected_loudly(self):
        """Versions outside the ``[MIN_VERSION, VERSION]`` accept window
        must be rejected with an error NAMING both the version and the
        window — never silent misinterpretation of the old layout.  (v4
        and v5 are frame-compatible with v3 — the optional REPLY timing
        payload and the HELLO/HELLO_ACK shm tails are detected by
        presence — so v3 itself DECODES; see test_observability.py for
        that direction.)"""
        assert wire.VERSION == 5 and wire.MIN_VERSION == 3
        good = wire.FrameReader().feed(wire.encode_bye())[0]
        v1 = good[:2] + b"\x01" + good[3:]
        with pytest.raises(wire.WireError,
                           match=r"version 1.*supported \[3, 5\]"):
            wire.decode(v1)
        v6 = good[:2] + b"\x06" + good[3:]
        with pytest.raises(wire.WireError, match="version 6"):
            wire.decode(v6)

    def test_frame_reader_reassembles_any_fragmentation(self):
        frames = [wire.encode_bye(), wire.encode_error("x" * 300),
                  wire.encode_hello(wire.Hello(2, 8))]
        stream = b"".join(frames)
        rd = wire.FrameReader()
        got = []
        for i in range(len(stream)):           # worst case: 1 byte per read
            got.extend(rd.feed(stream[i:i + 1]))
        assert len(got) == 3
        assert isinstance(wire.decode(got[0]), wire.Bye)
        assert wire.decode(got[1]) == wire.Error("x" * 300)
        assert wire.decode(got[2]) == wire.Hello(2, 8)

    def test_malformed_frames_raise_wire_error(self):
        good = wire.FrameReader().feed(wire.encode_bye())[0]
        with pytest.raises(wire.WireError, match="magic"):
            wire.decode(b"\x00\x00" + good[2:])
        with pytest.raises(wire.WireError, match="version"):
            wire.decode(good[:2] + b"\x63" + good[3:])
        with pytest.raises(wire.WireError):
            wire.decode(good[:3])              # short frame
        req = wire.FrameReader().feed(wire.encode_request(
            1, 3, np.array([True]), np.array([0], np.int32),
            np.zeros(1, np.float32), np.zeros((1, 8), np.int32)))[0]
        with pytest.raises(wire.WireError):
            wire.decode(req[:-5])              # truncated array body
        with pytest.raises(wire.WireError, match="cap"):
            wire.FrameReader().feed(b"\xff\xff\xff\xff")
        # non-UTF8 string bytes must surface as WireError, nothing else
        err = wire.FrameReader().feed(wire.encode_error("ok"))[0]
        with pytest.raises(wire.WireError, match="string"):
            wire.decode(err[:-2] + b"\xff\xfe")


# -- transport registry / teardown satellites --------------------------------

def _dummy_worker_args():
    def fn(params, cache, history, server_pos, t, triggered, u):
        return cache, jnp.zeros_like(u), u
    return fn, None, jnp.zeros((2, 4))


class TestTransportRegistry:
    def test_unknown_transport_lists_valid_ones(self):
        fn, params, cache = _dummy_worker_args()
        with pytest.raises(ValueError) as ei:
            async_rpc.make_worker("carrier-pigeon", fn, params, cache)
        msg = str(ei.value)
        assert "carrier-pigeon" in msg
        for t in async_rpc.TRANSPORTS:
            assert repr(t) in msg, f"{t} missing from: {msg}"

    def test_wire_requires_address_and_rejects_latency(self):
        fn, params, cache = _dummy_worker_args()
        with pytest.raises(ValueError, match="address"):
            async_rpc.make_worker("wire", fn, params, cache)
        with pytest.raises(ValueError, match="measured"):
            async_rpc.make_worker("wire", fn, params, cache,
                                  latency_s=0.01,
                                  wire_opts={"address": "/nowhere"})

    @pytest.mark.parametrize("transport",
                             ["inproc", "stream", "thread", "mock_remote"])
    def test_close_is_idempotent(self, transport):
        fn, params, cache = _dummy_worker_args()
        w = async_rpc.make_worker(transport, fn, params, cache)
        w.close()
        w.close()  # must be a no-op, not a deadlock/error

    def test_finish_async_then_close_and_drain_reentrant(self):
        cfg = _cfg()
        params = deco.init_collab_lm(KEY, cfg)
        stream = next(tok.lm_batches(0, cfg, 2, 6))["tokens"]
        eng = CollaborativeEngine(params, cfg, batch=2, max_len=16)
        sess = eng.session(SessionConfig(mode="async", transport="inproc",
                                         max_staleness=2)).__enter__()
        disp, worker = eng._dispatcher, eng._worker
        for t in range(6):
            sess.step(jnp.asarray(stream[:, t]))
        sess.close()
        worker.close()            # second close (session close already did)
        worker.close()
        assert disp.drain() == [] # re-entrant after close
        assert disp.drain() == []


# -- the standalone correction server ----------------------------------------

@pytest.fixture(scope="module")
def wire_server():
    """One in-thread CorrectionServer shared by the loopback tests."""
    from repro.serving.server import CorrectionServer
    cfg = _cfg()
    params = deco.init_collab_lm(KEY, cfg)
    uds = _uds_path("srv")
    srv = CorrectionServer(cfg, params, slots=8, max_len=32, uds=uds)
    stop = threading.Event()
    th = threading.Thread(target=srv.serve_forever,
                          kwargs=dict(stop=stop), daemon=True)
    th.start()
    yield cfg, params, uds, srv
    stop.set()
    th.join(timeout=10)
    srv.close()


class TestWireLoopback:
    def test_sync_over_wire_matches_scan_and_run(self, wire_server):
        """Acceptance: the REAL boundary with max_staleness=0 reproduces
        the protocol — u/trigger bit-identical to run_scan, fhat and
        server positions matching the in-process sync engine, with RTT
        and bytes measured on the socket."""
        cfg, params, uds, srv = wire_server
        stream = next(tok.lm_batches(0, cfg, 3, 16))["tokens"]
        scan = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        rs = run_scan(scan, stream)
        sync = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        r1 = run_sync(sync, stream)
        a = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        r0 = run_wire(a, stream, address=uds, max_staleness=0)
        assert 0.0 < r0["triggered"].mean() < 1.0, "need mixed triggers"
        np.testing.assert_array_equal(r0["u"], rs["u"])
        np.testing.assert_array_equal(r0["triggered"], rs["triggered"])
        np.testing.assert_allclose(r0["fhat"], r1["fhat"], atol=1e-6)
        np.testing.assert_array_equal(a.server_pos, sync.server_pos)
        rep = r0["comms"]
        assert rep["bytes_sent"] == r1["comms"]["bytes_sent"]
        w = rep["wire"]
        assert w["replies"] == rep["async"]["requests"] > 0
        assert w["tx_bytes"] > 0 and w["rx_bytes"] > 0
        assert w["rtt_mean_s"] > 0.0

    def test_pipelined_over_wire_bytes_invariant_under_coalescing(
            self, wire_server):
        """Deep pipeline on the real boundary: the monitor path stays
        bit-identical, corrections only lower fhat, and the modeled byte
        accounting (each token ships once) survives server-side
        coalescing — bytes_sent is staleness- and coalescing-independent
        and <= baseline."""
        cfg, params, uds, srv = wire_server
        stream = next(tok.lm_batches(0, cfg, 3, 16))["tokens"]
        scan = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        rs = run_scan(scan, stream)
        sync = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        r1 = run_sync(sync, stream)
        a = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        ra = run_wire(a, stream, address=uds, max_staleness=4)
        np.testing.assert_array_equal(ra["u"], rs["u"])
        np.testing.assert_array_equal(ra["triggered"], rs["triggered"])
        assert bool(np.all(ra["fhat"] <= ra["u"] + 1e-6))
        rep = ra["comms"]
        assert rep["bytes_sent"] == r1["comms"]["bytes_sent"]
        assert rep["bytes_sent"] <= rep["bytes_baseline"]
        per = rep["per_stream"]
        assert (per["bytes_sent"] <= per["bytes_baseline"]).all()
        np.testing.assert_array_equal(a.server_pos, sync.server_pos)
        assert rep["async"]["inflight_now"] == 0

    def test_multi_client_session_isolation(self, wire_server):
        """Two engines on ONE server, stepped interleaved: the chatty
        client's triggers must not perturb the quiet client's traces,
        comms account, or server-side rows."""
        cfg, params, uds, srv = wire_server
        loud_cfg = _cfg(threshold=-1e9)   # every step triggers
        stream_a = next(tok.lm_batches(1, cfg, 2, 12))["tokens"]
        stream_b = next(tok.lm_batches(2, cfg, 2, 12))["tokens"]

        # local references, no wire
        ref_b = CollaborativeEngine(params, cfg, batch=2, max_len=32)
        rb_ref = run_sync(ref_b, stream_b)

        a = CollaborativeEngine(params, loud_cfg, batch=2, max_len=32)
        b = CollaborativeEngine(params, cfg, batch=2, max_len=32)
        wcfg = SessionConfig(mode="async", max_staleness=2,
                             transport=TransportSpec("wire", address=uds))
        sa = a.session(wcfg).__enter__()
        sb = b.session(wcfg).__enter__()
        outs_a, outs_b = [], []
        for t in range(12):
            outs_a.append(sa.step(jnp.asarray(stream_a[:, t])))
            outs_b.append(sb.step(jnp.asarray(stream_b[:, t])))
        sa.close()
        sb.close()
        res_b = {k: np.stack([o[k] for o in outs_b], 1)
                 for k in ("u", "fhat", "triggered")}
        res_a_trig = np.stack([o["triggered"] for o in outs_a], 1)
        assert res_a_trig.all(), "loud client must trigger every step"
        # B's protocol is exactly what it would be alone
        np.testing.assert_array_equal(res_b["u"], rb_ref["u"])
        np.testing.assert_array_equal(res_b["triggered"],
                                      rb_ref["triggered"])
        np.testing.assert_array_equal(b.server_pos, ref_b.server_pos)
        # and B's comms account only B's traffic
        assert (b.comms.report()["bytes_sent"]
                == rb_ref["comms"]["bytes_sent"])
        assert srv.stats["sessions"] >= 2

    def test_session_errors(self, wire_server):
        cfg, params, uds, srv = wire_server
        # more slots than the server owns -> Error frame, no crash
        sock = wire.connect(uds, timeout=10)
        try:
            sock.sendall(wire.encode_hello(wire.Hello(batch=999, max_len=16)))
            sock.settimeout(10.0)
            rd = wire.FrameReader()
            msgs = []
            while not msgs:
                data = sock.recv(1 << 16)
                assert data, "server closed without replying"
                msgs = [wire.decode(p) for p in rd.feed(data)]
            assert isinstance(msgs[0], wire.Error)
            assert "server full" in msgs[0].message
        finally:
            sock.close()
        # the client transport surfaces the refusal as a WireError
        with pytest.raises(wire.WireError, match="server full"):
            async_rpc.SocketWorker(cache=None, address=uds, batch=999,
                                   max_len=16)
        # an oversized max_len is refused before any slots are leased
        with pytest.raises(wire.WireError, match="max_len"):
            async_rpc.SocketWorker(cache=None, address=uds, batch=1,
                                   max_len=10_000)
        # a request whose vectors don't match the leased batch is refused
        # AND the session dropped — it can never reach foreign rows
        sock = wire.connect(uds, timeout=10)
        try:
            sock.settimeout(10.0)
            sock.sendall(wire.encode_hello(wire.Hello(batch=2, max_len=16)))
            rd = wire.FrameReader()
            msgs = []
            while not msgs:
                msgs = [wire.decode(p) for p in rd.feed(sock.recv(1 << 16))]
            assert isinstance(msgs[0], wire.HelloAck)
            bad = wire.WireRequest(
                req_id=0, t=3, triggered=np.ones(3, bool),
                server_pos=np.zeros(3, np.int32), u=np.zeros(3, np.float32),
                tokens=np.zeros(12, np.int32))
            sock.sendall(wire.encode_request_arrays(bad))
            msgs = []
            while not msgs:
                msgs = [wire.decode(p) for p in rd.feed(sock.recv(1 << 16))]
            assert isinstance(msgs[0], wire.Error)
            assert "session batch" in msgs[0].message
            assert sock.recv(1 << 16) == b"", "server must drop the session"
        finally:
            sock.close()
        # a v1 peer is rejected LOUDLY: the server answers an ERROR frame
        # naming both versions, then drops the connection
        sock = wire.connect(uds, timeout=10)
        try:
            sock.settimeout(10.0)
            hello = wire.encode_hello(wire.Hello(batch=1, max_len=16))
            v1 = hello[:6] + b"\x01" + hello[7:]  # patch the version byte
            sock.sendall(v1)
            rd = wire.FrameReader()
            msgs = []
            while not msgs:
                data = sock.recv(1 << 16)
                assert data, "server closed without replying"
                msgs = [wire.decode(p) for p in rd.feed(data)]
            assert isinstance(msgs[0], wire.Error)
            assert "version 1" in msgs[0].message
            assert "3" in msgs[0].message
        finally:
            sock.close()
        # churn frames are validated against the lease like requests
        sock = wire.connect(uds, timeout=10)
        try:
            sock.settimeout(10.0)
            sock.sendall(wire.encode_hello(wire.Hello(batch=2, max_len=16)))
            rd = wire.FrameReader()
            msgs = []
            while not msgs:
                msgs = [wire.decode(p) for p in rd.feed(sock.recv(1 << 16))]
            assert isinstance(msgs[0], wire.HelloAck)
            sock.sendall(wire.encode_attach(99))  # outside the lease
            msgs = []
            while not msgs:
                msgs = [wire.decode(p) for p in rd.feed(sock.recv(1 << 16))]
            assert isinstance(msgs[0], wire.Error)
            assert "lease" in msgs[0].message
        finally:
            sock.close()

    def test_engine_detached_after_wire_session(self, wire_server):
        """With a real boundary the server-side state dies with the
        session; the engine must refuse silent cold-cache serving after."""
        cfg, params, uds, srv = wire_server
        stream = next(tok.lm_batches(4, cfg, 2, 8))["tokens"]
        a = CollaborativeEngine(params, cfg, batch=2, max_len=32)
        run_wire(a, stream, address=uds, max_staleness=2)
        with pytest.raises(RuntimeError, match="remote correction server"):
            a.session().step(jnp.asarray(stream[:, 0]))
        with pytest.raises(RuntimeError, match="remote correction server"):
            a.session(SessionConfig(mode="async",
                                    transport="inproc")).__enter__()


class TestCoalescing:
    """Deterministic coalescing semantics via a manually-ticked server."""

    def _open(self, srv, uds, batch, coalesce):
        sock = wire.connect(uds, timeout=5)
        sock.sendall(wire.encode_hello(
            wire.Hello(batch=batch, max_len=16, coalesce=coalesce)))
        ack = self._collect(srv, sock, 1)[0]
        assert isinstance(ack, wire.HelloAck), ack
        return sock, ack

    def _collect(self, srv, sock, n, reader=None):
        reader = reader or wire.FrameReader()
        sock.settimeout(0.0)
        msgs = []
        deadline = time.monotonic() + 30
        while len(msgs) < n:
            srv.serve_tick(0.001)
            try:
                data = sock.recv(1 << 16)
            except (BlockingIOError, socket.timeout):
                continue
            assert data, "server closed"
            msgs.extend(wire.decode(p) for p in reader.feed(data))
            assert time.monotonic() < deadline
        return msgs

    def test_merged_replay_equals_per_request_replay(self):
        """Two queued requests (a deep pipeline: r2 re-triggers r1's row)
        merge into ONE replay — union of masks, min of positions, per-row
        latest t — and the replies match a per-request session replaying
        the same backlogs one by one."""
        from repro.serving.server import CorrectionServer
        cfg = _cfg()
        params = deco.init_collab_lm(KEY, cfg)
        srv = CorrectionServer(cfg, params, slots=2, max_len=16,
                               uds=_uds_path("coal"))
        try:
            rng = np.random.default_rng(0)
            hist = rng.integers(0, 255, (2, 16)).astype(np.int32)
            u1 = np.asarray([0.7, 0.0], np.float32)
            u2 = np.asarray([0.9, 0.4], np.float32)
            def reqs():
                # r1: row 0 triggers at t=2 (backlog 0..2)
                r1 = wire.encode_request(0, 2, np.array([True, False]),
                                         np.array([0, 0], np.int32), u1, hist)
                # r2: rows 0+1 trigger at t=5 (row0 backlog 3..5, row1 0..5)
                r2 = wire.encode_request(1, 5, np.array([True, True]),
                                         np.array([3, 0], np.int32), u2, hist)
                return r1, r2

            # coalescing session: both requests queued before one tick
            sock, _ = self._open(srv, srv.uds, 2, coalesce=True)
            r1, r2 = reqs()
            sock.sendall(r1 + r2)
            rep1, rep2 = self._collect(srv, sock, 2)
            assert rep1.req_id == 0 and rep2.req_id == 1, "FIFO per session"
            assert rep1.coalesced == 2 and rep2.coalesced == 2
            assert srv.stats["replays"] == 1 and srv.stats["coalesced"] == 1
            # merged semantics: row 0 replayed through t=5 once, so BOTH
            # replies carry the fresher corrector for row 0
            np.testing.assert_array_equal(rep1.v[0], rep2.v[0])
            sock.sendall(wire.encode_bye())
            sock.close()
            for _ in range(10):
                srv.serve_tick(0.001)
            assert not srv._sessions, "BYE must free the session"

            # per-request session (coalesce=False) on the SAME rows
            sock, ack = self._open(srv, srv.uds, 2, coalesce=False)
            assert ack.slot_lo == 0, "freed rows must be reused (and reset)"
            r1, r2 = reqs()
            sock.sendall(r1 + r2)
            p1, p2 = self._collect(srv, sock, 2)
            assert p1.coalesced == 1 and p2.coalesced == 1
            assert srv.stats["replays"] == 3, "per-request arm: one each"
            # after its full backlog both paths end at the same replay
            # state: r2's corrections agree bitwise
            np.testing.assert_array_equal(rep2.v, p2.v)
            np.testing.assert_array_equal(rep2.fhat, p2.fhat)
            # r1's reply in the per-request arm is the STALER t=2 v
            assert not np.array_equal(rep1.v[0], p1.v[0])
            sock.close()
        finally:
            srv.close()


class TestServerCrash:
    def test_reactor_crash_closes_client_sockets(self):
        """A server whose reactor dies (here: its replay raises) closes its
        sessions, so the client fails fast with a WireError instead of
        waiting on an open socket forever."""
        from repro.serving.server import CorrectionServer
        cfg = _cfg(threshold=-1e9)   # every step triggers
        params = deco.init_collab_lm(KEY, cfg)
        srv = CorrectionServer(cfg, params, slots=2, max_len=16,
                               uds=_uds_path("crash"))

        def boom(group):
            raise RuntimeError("replay failed")

        srv._replay = boom
        th = threading.Thread(target=srv.serve_forever, daemon=True)
        th.start()
        stream = next(tok.lm_batches(0, cfg, 2, 4))["tokens"]
        eng = CollaborativeEngine(params, cfg, batch=2, max_len=16)
        caught = []

        def client():
            try:
                run_wire(eng, stream, address=srv.address, max_staleness=0)
            except Exception as e:  # noqa: BLE001 - recorded for the assert
                caught.append(e)

        c = threading.Thread(target=client, daemon=True)
        c.start()
        c.join(timeout=120)
        th.join(timeout=10)
        assert not c.is_alive(), "client hung on a dead server"
        assert not th.is_alive()
        assert caught and isinstance(caught[0], wire.WireError), caught


class TestConnectHello:
    """Regression: the connect/handshake retry loop used to treat a
    refused handshake and a mid-handshake EOF identically — now a
    deliberate ERROR answer ("server full", "draining") surfaces as
    ``HandshakeRefused`` IMMEDIATELY (the fleet client tries a sibling),
    while a dead peer (refused connect, EOF mid-handshake) is retried
    until the deadline and then surfaces as ``PeerGone`` (the supervisor
    marks the server unhealthy)."""

    def test_deliberate_refusal_raises_immediately(self, wire_server):
        cfg, params, uds, srv = wire_server
        t0 = time.monotonic()
        with pytest.raises(wire.HandshakeRefused) as ei:
            # way over the 8-slot pool: the server answers ERROR
            wire.connect_hello(uds, wire.Hello(batch=100, max_len=32),
                               timeout=30.0)
        # refused != dead: no retry-until-deadline, and the server's
        # reason survives verbatim on .message
        assert time.monotonic() - t0 < 10.0
        assert "server full" in ei.value.message
        assert isinstance(ei.value, wire.WireError)

    def test_no_listener_is_peer_gone_after_retries(self):
        path = _uds_path("gone")  # directory exists, socket never bound
        t0 = time.monotonic()
        with pytest.raises(wire.PeerGone):
            wire.connect_hello(path, wire.Hello(batch=1, max_len=8),
                               timeout=0.6, retry_interval=0.05)
        assert time.monotonic() - t0 >= 0.5, "must retry until deadline"

    def test_mid_handshake_eof_is_peer_gone_not_refused(self):
        # a listener that accepts and instantly closes: the client sees
        # EOF before any ERROR frame — that is a dead peer, not a refusal
        path = _uds_path("eof")
        lst = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        lst.bind(path)
        lst.listen(8)
        accepts = []

        def slam():
            while True:
                try:
                    c, _ = lst.accept()
                except OSError:
                    return
                accepts.append(1)
                c.close()

        th = threading.Thread(target=slam, daemon=True)
        th.start()
        try:
            with pytest.raises(wire.PeerGone, match="handshake"):
                wire.connect_hello(path, wire.Hello(batch=1, max_len=8),
                                   timeout=0.6, retry_interval=0.05)
            assert len(accepts) >= 2, "EOF mid-handshake must be retried"
        finally:
            lst.close()

    def test_redirect_hop_is_followed(self, wire_server):
        cfg, params, uds, srv = wire_server
        # a fake router: answers any HELLO with REDIRECT to the real server
        path = _uds_path("rtr")
        lst = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        lst.bind(path)
        lst.listen(8)

        def router():
            try:
                c, _ = lst.accept()
            except OSError:
                return
            rd = wire.FrameReader()
            while not rd.feed(c.recv(1 << 16)):
                pass
            c.sendall(wire.encode_redirect(uds))
            c.close()

        th = threading.Thread(target=router, daemon=True)
        th.start()
        try:
            sock, ack, reader, tx, rx = wire.connect_hello(
                path, wire.Hello(batch=2, max_len=32), timeout=20.0)
            try:
                assert isinstance(ack, wire.HelloAck)
                assert tx > 0 and rx > 0
            finally:
                sock.sendall(wire.encode_bye())
                sock.close()
        finally:
            lst.close()


class TestTwoProcessSmoke:
    """CI tier-1: a real server SUBPROCESS + one engine over a UDS."""

    def test_two_process_loopback(self):
        cfg = _cfg()
        params = deco.init_collab_lm(KEY, cfg)
        stream = next(tok.lm_batches(0, cfg, 2, 10))["tokens"]
        tmp = tempfile.mkdtemp(prefix="wire_proc_")
        uds, ready = os.path.join(tmp, "s.sock"), os.path.join(tmp, "ready")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ)
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.server",
             "--arch", "paper-synthetic-serving", "--uds", uds,
             "--slots", "2", "--max-len", "24", "--ready-file", ready],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        try:
            deadline = time.monotonic() + SPAWN_DEADLINE_S
            while not os.path.exists(ready):
                assert proc.poll() is None, proc.stderr.read()[-3000:]
                assert time.monotonic() < deadline, "server startup timeout"
                time.sleep(0.05)
            eng = CollaborativeEngine(params, cfg, batch=2, max_len=24)
            res = run_wire(eng, stream, address=uds, max_staleness=2)
            scan = CollaborativeEngine(params, cfg, batch=2, max_len=24)
            rs = run_scan(scan, stream)
            np.testing.assert_array_equal(res["u"], rs["u"])
            np.testing.assert_array_equal(res["triggered"], rs["triggered"])
            assert bool(np.all(res["fhat"] <= res["u"] + 1e-6))
            w = res["comms"]["wire"]
            assert w["tx_bytes"] > 0 and w["rx_bytes"] > 0
            assert w["rtt_mean_s"] > 0.0, "RTT must be measured, not modeled"
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
