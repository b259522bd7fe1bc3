"""Correction-server fleet: supervisor health/routing, and the failover
guarantee — a SIGKILL'd (or drained) server is survived by re-HELLO +
full-history replay with per-row u/trigger/fhat BITWISE equal to an
uninterrupted run, the replay traffic charged to ``comms["failover"]``.

Fault injection comes from two primitives:

  * handle kills — ``ThreadServer.kill`` severs every session socket
    with no BYE/GOAWAY (what a SIGKILL looks like from the wire);
    ``SubprocessServer.kill`` IS a SIGKILL (the batch-64 acceptance
    test, name contains "subprocess" so CI's fast chaos selection can
    deselect it with ``-k "not subprocess"``);
  * ``tests/_chaos.py``'s ChaosProxy — byte-level faults a kill cannot
    express deterministically: torn frame + EOF, duplicated REPLY,
    delayed REPLY.

Determinism notes (why each assertion is safe to make bitwise):
strict-sync (max_staleness=0) traces are bitwise end-to-end INCLUDING
across failover, because every step blocks on its reply — pipeline depth
never varies.  Pipelined traces keep u/triggered bitwise (trigger
decisions depend only on u, which is edge-local) while fhat merge timing
is scheduling-dependent — so pipelined tests assert u/trigger bitwise
plus the safety invariant ``fhat <= u`` instead.
"""
import os
import threading
import time
from contextlib import contextmanager
from io import StringIO

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from _chaos import ChaosProxy
from repro.configs.paper_synthetic import SERVING
from repro.core import decomposition as deco
from repro.data import tokens as tok
from repro.serving import (InMemoryTracker, CompositeTracker, Histogram,
                           JsonFileTracker, SessionConfig, TransportSpec,
                           wire)
from repro.serving.collaborative import CollaborativeEngine
from repro.serving.fleet import (PENDING_TTL_S, FleetSupervisor,
                                 ServerHandle, resolve_route)
from repro.serving.tracker import read_stats

KEY = jax.random.PRNGKey(0)
BATCH, STEPS, MAX_LEN = 4, 24, 32


def _cfg(threshold=0.1):
    return SERVING.replace(monitor=SERVING.monitor.__class__(
        **{**SERVING.monitor.__dict__, "threshold": threshold,
           "trigger_margin": 0.0}))


@pytest.fixture(scope="module")
def world():
    cfg = _cfg()
    params = deco.init_collab_lm(KEY, cfg)
    stream = next(tok.lm_batches(0, cfg, BATCH, STEPS))["tokens"]
    return cfg, params, stream


@contextmanager
def fleet(cfg, params, *, n=2, slots=8, respawn=False, wrapper=None,
          shm=False):
    """A thread-backend fleet with a daemon supervisor loop ticking it."""
    sup = FleetSupervisor(backend="thread", n_servers=n, slots=slots,
                          max_len=MAX_LEN, cfg=cfg, params=params,
                          respawn=respawn, address_wrapper=wrapper,
                          shm=shm)
    sup.start()
    stop = threading.Event()
    t = threading.Thread(target=sup.run_forever, args=(stop,), daemon=True)
    t.start()
    try:
        yield sup
    finally:
        stop.set()
        t.join(timeout=10)
        sup.close()


def run_session(sup, params, cfg, stream, *, staleness, at=None,
                kind="wire"):
    """Serve ``stream`` step-by-step through the fleet router, firing
    ``at[i](sup, eng, sess)`` after step i.  Returns (stacked traces,
    comms report, engine)."""
    batch = stream.shape[0]
    eng = CollaborativeEngine(params, cfg, batch=batch, max_len=MAX_LEN)
    scfg = SessionConfig(
        mode="async", max_staleness=staleness,
        transport=TransportSpec(kind,
                                address="fleet:" + sup.router_address))
    out = []
    with eng.session(scfg) as s:
        for i in range(stream.shape[1]):
            out.append(s.step(stream[:, i]))
            if at and i in at:
                at[i](sup, eng, s)
        rep = s.report()
    res = {k: np.stack([np.asarray(o[k]) for o in out])
           for k in ("u", "fhat", "triggered")}
    return res, rep, eng


def victim_of(sup, eng):
    """The handle currently serving ``eng``'s worker."""
    return next(h for h in sup.servers.values()
                if h.address == eng._worker.server_address)


def wait_live(sup, n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while len(sup.live_servers()) < n:
        assert time.monotonic() < deadline, \
            f"fleet never reached {n} live: " \
            f"{[(h.name, h.state) for h in sup.servers.values()]}"
        time.sleep(0.02)


# -- trackers (the heartbeat/metrics surface) --------------------------------

class TestTracker:
    def test_histogram_summary_is_bounded_by_observations(self):
        h = Histogram(1e-4, 10.0)
        xs = [0.001, 0.01, 0.01, 0.5, 5.0]
        for x in xs:
            h.observe(x)
        s = h.summary()
        assert s["n"] == len(xs)
        assert s["max"] == max(xs)
        assert s["mean"] == pytest.approx(np.mean(xs))
        # approximate quantiles must stay inside the observed range
        assert 0 < s["p50"] <= s["max"]
        assert s["p50"] <= s["p99"] <= s["max"]
        # empty histogram: counts at zero, percentiles honestly absent
        # (None, not a fabricated 0.0 — see test_observability.py)
        assert Histogram(1e-4, 10.0).summary() == {
            "n": 0, "mean": 0.0, "max": 0.0, "p50": None, "p99": None}

    def test_json_file_tracker_heartbeat_round_trip(self, tmp_path):
        path = str(tmp_path / "hb" / "stats.json")
        t = JsonFileTracker(path)
        assert read_stats(path) is None, "no heartbeat before first log"
        t.log({"leased_rows": 3, "arr": np.arange(2)})
        rec = read_stats(path)
        assert rec["leased_rows"] == 3 and rec["arr"] == [0, 1]
        assert rec["ts"] > 0, "heartbeat must self-timestamp"
        t.log({"leased_rows": 5})
        assert read_stats(path)["leased_rows"] == 5, "log REPLACES the file"
        # a torn/garbage file is 'no heartbeat', never an exception
        with open(path, "w") as fh:
            fh.write('{"leased_rows": ')
        assert read_stats(path) is None
        t.finish()
        assert not os.path.exists(path), "finish() retires the heartbeat"

    def test_composite_tracker_fans_out(self):
        a, b = InMemoryTracker(), InMemoryTracker()
        buf = StringIO()
        from repro.serving.tracker import LogTracker
        c = CompositeTracker([a, LogTracker(buf, prefix="hb")])
        c.add(b)
        c.log({"x": 1}, step=7)
        c.log_summary({"done": True})
        assert a.records == b.records == [{"x": 1, "step": 7}]
        assert a.summary == {"done": True}
        assert buf.getvalue().startswith("hb[7] x=1")
        assert a.latest == {"x": 1, "step": 7}


# -- supervisor health state machine (no sockets, no jax) --------------------

class _FakeHandle(ServerHandle):
    def __init__(self, name="f", alive=True, rec=None):
        super().__init__(name)
        self._alive, self._rec = alive, rec

    def alive(self):
        return self._alive

    def scrape(self):
        return self._rec


class TestHealth:
    def test_starting_goes_live_on_first_heartbeat(self):
        h = _FakeHandle(rec=None)
        h.refresh(5.0)
        assert h.state == "starting", "no heartbeat yet: still starting"
        h._rec = {"ts": time.time(), "leased_rows": 2, "slots": 8,
                  "address": "/tmp/x.sock"}
        h.refresh(5.0)
        assert h.state == "live" and h.address == "/tmp/x.sock"
        assert h.load() == 2 and h.free_rows() == 6

    def test_stale_heartbeat_and_death_are_dead(self):
        h = _FakeHandle(rec={"ts": time.time(), "slots": 8})
        h.refresh(5.0)
        assert h.state == "live"
        h._rec = {"ts": time.time() - 60.0, "slots": 8}
        h.refresh(5.0)
        assert h.state == "dead", "stale heartbeat == hung server"
        h2 = _FakeHandle(rec={"ts": time.time(), "slots": 8})
        h2.refresh(5.0)
        h2._alive = False
        h2.refresh(5.0)
        assert h2.state == "dead"

    def test_draining_exit_is_a_clean_retire(self):
        h = _FakeHandle(rec={"ts": time.time(), "slots": 8})
        h.refresh(5.0)
        h._rec = {"ts": time.time(), "slots": 8, "draining": True}
        h.refresh(5.0)
        assert h.state == "draining"
        h._alive, h._rec = False, None
        h.refresh(5.0)
        assert h.state == "stopped", "drained exit is retire, not death"

    def test_pending_redirects_count_as_load_until_seen_or_expired(self):
        h = _FakeHandle(rec={"ts": time.time(), "leased_rows": 1, "slots": 8})
        h.refresh(5.0)
        h.pending.append((time.time(), 4))
        assert h.load() == 5, "an issued redirect is optimistic load"
        # a heartbeat NEWER than the redirect absorbs it (leased_rows now
        # reflects the session, or the client never came)
        h._rec = {"ts": time.time() + 0.001, "leased_rows": 5, "slots": 8}
        h.refresh(5.0)
        assert h.load() == 5
        h.pending.append((time.time() - 2 * PENDING_TTL_S, 4))
        assert h.load() == 5, "expired pending entries are dropped"


# -- routing -----------------------------------------------------------------

class TestRouting:
    def test_router_redirects_and_refuses(self, world):
        cfg, params, stream = world
        with fleet(cfg, params) as sup:
            wait_live(sup, 2)
            addrs = {h.address for h in sup.servers.values()}
            got = resolve_route(sup.router_address,
                                wire.Hello(batch=4, max_len=MAX_LEN))
            assert got in addrs
            # nothing fits 20 rows on 8-slot servers: ERROR, surfaced as
            # HandshakeRefused (try-elsewhere), not PeerGone (dead)
            with pytest.raises(wire.HandshakeRefused, match="no live"):
                resolve_route(sup.router_address,
                              wire.Hello(batch=20, max_len=MAX_LEN))
            assert sup.stats["routed"] >= 1
            assert sup.stats["refused"] >= 1

    def test_least_loaded_server_wins(self, world):
        cfg, params, _ = world
        stream5 = next(tok.lm_batches(1, cfg, 5, 4))["tokens"]
        with fleet(cfg, params) as sup:
            wait_live(sup, 2)
            eng = CollaborativeEngine(params, cfg, batch=5, max_len=MAX_LEN)
            scfg = SessionConfig(
                mode="async", max_staleness=0,
                transport=TransportSpec(
                    "wire", address="fleet:" + sup.router_address))
            with eng.session(scfg) as s:
                s.step(stream5[:, 0])
                busy = victim_of(sup, eng)
                # 5 of busy's 8 rows are leased: a 4-row session cannot
                # fit there, so the router MUST name the sibling
                got = resolve_route(sup.router_address,
                                    wire.Hello(batch=4, max_len=MAX_LEN))
                assert got != busy.address
            assert sup.stats["routed"] >= 2


# -- failover: kill / drain / retry-to-sibling (thread backend) --------------

class TestFailover:
    def test_kill_mid_flight_strict_sync_is_bitwise(self, world):
        """ISSUE acceptance (thread-scale): SIGKILL-equivalent mid-run,
        the client re-HELLOs, replays from position 0, and the whole
        per-row trace is bitwise identical to the uninterrupted run —
        with the replay charged to comms['failover'], not 'wire'."""
        cfg, params, stream = world
        with fleet(cfg, params) as sup:
            wait_live(sup, 2)
            ref, ref_rep, _ = run_session(sup, params, cfg, stream,
                                          staleness=0)
            kill = {10: lambda sup, eng, s: victim_of(sup, eng).kill()}
            got, rep, eng = run_session(sup, params, cfg, stream,
                                        staleness=0, at=kill)
            for k in ("u", "fhat", "triggered"):
                np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
            assert ref_rep.get("failover") is None, \
                "no failover bucket without a failover"
            fo = rep["failover"]
            assert fo["failovers"] == 1
            assert fo["tx_bytes"] > 0 and fo["replayed_tokens"] > 0
            assert fo["replay_requests"] >= 1
            # trigger decisions replayed masked: replay tokens can only
            # come from positions the dead server had already acked
            assert fo["replayed_tokens"] <= BATCH * STEPS
            # the uninterrupted run's wire bytes are a lower bound: the
            # wire bucket must NOT absorb the replay traffic
            assert rep["wire"]["tx_bytes"] <= ref_rep["wire"]["tx_bytes"]

    def test_kill_during_pipelined_flight_recovers(self, world):
        """Kill while replies are in flight (max_staleness=2): survivors'
        u/trigger stay bitwise (trigger logic is edge-local) and the
        merged corrections never break fhat <= u."""
        cfg, params, stream = world
        with fleet(cfg, params) as sup:
            wait_live(sup, 2)
            ref, _, _ = run_session(sup, params, cfg, stream, staleness=2)
            kill = {12: lambda sup, eng, s: victim_of(sup, eng).kill()}
            got, rep, _ = run_session(sup, params, cfg, stream,
                                      staleness=2, at=kill)
            np.testing.assert_array_equal(got["u"], ref["u"])
            np.testing.assert_array_equal(got["triggered"], ref["triggered"])
            assert bool(np.all(got["fhat"] <= got["u"] + 1e-6))
            fo = rep["failover"]
            assert fo["failovers"] == 1
            # pipelined kill strands unanswered real flights: they are
            # re-sent VERBATIM after the synthetic replay
            assert fo["resent_requests"] >= 1

    def test_drain_drops_zero_streams_and_retires(self, world):
        """Drain mid-run: the victim GOAWAYs, the client migrates, every
        stream finishes bitwise (zero drops), and the drained server
        exits as 'stopped' — retired, never respawned."""
        cfg, params, stream = world
        with fleet(cfg, params, respawn=True) as sup:
            wait_live(sup, 2)
            ref, _, _ = run_session(sup, params, cfg, stream, staleness=0)
            names = {}

            def drain(sup, eng, s):
                names["victim"] = victim_of(sup, eng).name
                sup.drain(names["victim"])

            got, rep, _ = run_session(sup, params, cfg, stream,
                                      staleness=0, at={8: drain})
            assert got["u"].shape == (STEPS, BATCH), "zero dropped streams"
            for k in ("u", "fhat", "triggered"):
                np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
            assert rep["failover"]["failovers"] == 1
            deadline = time.monotonic() + 20
            h = sup.servers[names["victim"]]
            while h.state != "stopped":
                assert time.monotonic() < deadline, \
                    f"drained server never retired (state={h.state})"
                time.sleep(0.02)
            assert sup.stats["retired"] >= 1
            assert sup.stats["respawns"] == 0, \
                "a drained server is retired, not replaced"

    def test_kill_during_hello_retries_to_sibling(self, world):
        """A redirect to a just-died server (the router's world-view is
        one heartbeat stale) must not strand the client: the dead-peer
        connect fails, the client re-asks the router, and lands on the
        sibling."""
        cfg, params, stream = world
        with fleet(cfg, params) as sup:
            wait_live(sup, 2)
            h0 = sup.servers["srv-0"]
            h0.kill()
            h0.state = "live"   # simulate the stale world-view window
            seen = {}
            spy = {0: lambda sup, eng, s:
                   seen.update(addr=eng._worker.server_address)}
            got, rep, eng = run_session(sup, params, cfg, stream,
                                        staleness=0, at=spy)
            assert seen["addr"] == sup.servers["srv-1"].address
            local = CollaborativeEngine(params, cfg, batch=BATCH,
                                        max_len=MAX_LEN)
            rs = local.session(SessionConfig(mode="scan")).run(stream)
            # scan traces are (batch, steps); stepped traces (steps, batch)
            np.testing.assert_array_equal(got["u"], np.asarray(rs["u"]).T)
            np.testing.assert_array_equal(got["triggered"],
                                          np.asarray(rs["triggered"]).T)
            # the bounce happened before any lease existed: nothing to
            # replay, so no failover is charged
            assert rep.get("failover") is None

    def test_dead_server_is_reaped_and_respawned(self, world):
        cfg, params, stream = world
        with fleet(cfg, params, respawn=True) as sup:
            wait_live(sup, 2)
            sup.kill("srv-0")
            wait_live(sup, 2)   # the replacement must come up live
            assert sup.servers["srv-0"].state == "dead"
            assert "srv-2" in sup.servers, "a fresh name, never reuse"
            assert sup.stats["reaped"] >= 1 and sup.stats["respawns"] >= 1
            got, rep, _ = run_session(sup, params, cfg, stream, staleness=0)
            assert rep.get("failover") is None, "post-respawn run is clean"


# -- byte-level chaos (proxy-injected) ---------------------------------------

class TestChaos:
    def test_duplicated_reply_is_dropped_not_merged(self, world):
        """A retransmitted REPLY must be discarded by the worker's
        head-of-flights check — merging it twice would corrupt acked
        positions and crash the Dispatcher's FIFO pairing."""
        cfg, params, stream = world
        proxy = ChaosProxy(seed=3)
        try:
            with fleet(cfg, params, wrapper=proxy.wrap) as sup:
                wait_live(sup, 2)
                ref, _, _ = run_session(sup, params, cfg, stream,
                                        staleness=0)
                arm = {5: lambda *_: proxy.dup_next_reply()}
                got, rep, _ = run_session(sup, params, cfg, stream,
                                          staleness=0, at=arm)
                assert proxy.stats["duplicated"] == 1
                for k in ("u", "fhat", "triggered"):
                    np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
                assert rep.get("failover") is None, \
                    "a duplicate is dropped in place, no migration"
        finally:
            proxy.close()

    def test_torn_frame_then_eof_triggers_failover(self, world):
        """Connection dropped mid-frame (half a REPLY, then EOF): the
        worker must treat it as a dead server — re-HELLO + replay —
        and still land bitwise on the uninterrupted trace."""
        cfg, params, stream = world
        proxy = ChaosProxy(seed=3)
        try:
            with fleet(cfg, params, wrapper=proxy.wrap) as sup:
                wait_live(sup, 2)
                ref, _, _ = run_session(sup, params, cfg, stream,
                                        staleness=0)
                arm = {6: lambda *_: proxy.drop_mid_frame()}
                got, rep, _ = run_session(sup, params, cfg, stream,
                                          staleness=0, at=arm)
                assert proxy.stats["dropped_mid_frame"] == 1
                for k in ("u", "fhat", "triggered"):
                    np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
                assert rep["failover"]["failovers"] >= 1
        finally:
            proxy.close()

    def test_delayed_reply_changes_nothing_but_time(self, world):
        cfg, params, stream = world
        proxy = ChaosProxy(seed=3)
        try:
            with fleet(cfg, params, wrapper=proxy.wrap) as sup:
                wait_live(sup, 2)
                ref, _, _ = run_session(sup, params, cfg, stream,
                                        staleness=0)
                arm = {4: lambda *_: proxy.delay_next_reply(0.4)}
                t0 = time.monotonic()
                got, rep, _ = run_session(sup, params, cfg, stream,
                                          staleness=0, at=arm)
                assert time.monotonic() - t0 >= 0.4
                assert proxy.stats["delayed"] == 1
                for k in ("u", "fhat", "triggered"):
                    np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
        finally:
            proxy.close()


# -- property: random schedules preserve safety + byte accounting ------------

class TestFailoverProperty:
    @settings(max_examples=3, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_random_schedule_keeps_fhat_safe_and_bytes_bounded(self, seed):
        """Random (kill step, staleness, churn step, stream) schedules:
        after any failover replay the merged correction still satisfies
        fhat <= u at EVERY step, and the measured wire + failover bytes
        stay within the replay-adjusted bound implied by the meter's own
        request/token counts (no unmetered traffic, no double charge)."""
        rng = np.random.default_rng(seed)
        steps = 16
        cfg = _cfg()
        params = deco.init_collab_lm(KEY, cfg)
        stream = next(tok.lm_batches(int(rng.integers(0, 1000)), cfg,
                                     BATCH, steps))["tokens"]
        staleness = int(rng.choice([0, 1, 2]))
        kill_at = int(rng.integers(3, steps - 3))
        churn_at = int(rng.integers(2, steps - 2))

        def kill(sup, eng, s):
            victim_of(sup, eng).kill()

        def churn(sup, eng, s):
            sid = s.streams[int(rng.integers(0, BATCH))]
            s.detach(sid)
            s.attach(("fresh", sid))

        at = {kill_at: kill}
        if churn_at != kill_at:
            at[churn_at] = churn
        with fleet(cfg, params, respawn=True) as sup:
            wait_live(sup, 2)
            got, rep, eng = run_session(sup, params, cfg,
                                        stream[:, :steps],
                                        staleness=staleness, at=at)
        assert bool(np.all(got["fhat"] <= got["u"] + 1e-6)), \
            f"fhat>u after failover (seed={seed})"
        fo = rep["failover"]
        assert fo["failovers"] >= 1
        comms = eng.comms
        n_req = (comms.dispatched + fo["replay_requests"]
                 + fo["resent_requests"])
        n_tok = comms.tokens_shipped + fo["replayed_tokens"]
        # per-connection handshake/churn/BYE cap + per-request framing
        # cap + 4 bytes per int32 token actually shipped
        bound = ((fo["failovers"] + 1) * (160 + 16 * BATCH)
                 + n_req * (64 + 16 * BATCH) + 4 * n_tok)
        total = rep["wire"]["tx_bytes"] + fo["tx_bytes"]
        assert 0 < total <= bound, \
            f"tx {total} outside replay-adjusted bound {bound} (seed={seed})"


# -- the full-fat acceptance: subprocess fleet, SIGKILL at batch 64 ----------

class TestSubprocessFleet:
    def test_subprocess_sigkill_batch64_recovers_bitwise(self):
        """ISSUE acceptance: two launch.server SUBPROCESSES behind the
        router, a batch-64 strict-sync client, a real SIGKILL mid-flight
        — recovery via re-HELLO + replay, per-row u/trigger/fhat bitwise
        vs the uninterrupted single-server reference (the no-kill routed
        run, which lives entirely on one server)."""
        cfg = _cfg()
        params = deco.init_collab_lm(KEY, cfg)
        batch, steps, max_len = 64, 20, 24
        stream = next(tok.lm_batches(0, cfg, batch, steps))["tokens"]
        sup = FleetSupervisor("paper-synthetic-serving", n_servers=2,
                              slots=batch, max_len=max_len,
                              backend="subprocess", respawn=False)
        stop = threading.Event()
        t = threading.Thread(target=sup.run_forever, args=(stop,),
                             daemon=True)
        try:
            sup.start(wait=True)
            t.start()
            wait_live(sup, 2, timeout=60.0)

            def run(at=None):
                eng = CollaborativeEngine(params, cfg, batch=batch,
                                          max_len=max_len)
                scfg = SessionConfig(
                    mode="async", max_staleness=0,
                    transport=TransportSpec(
                        "wire", address="fleet:" + sup.router_address))
                out = []
                with eng.session(scfg) as s:
                    for i in range(steps):
                        out.append(s.step(stream[:, i]))
                        if at and i in at:
                            at[i](eng)
                    rep = s.report()
                return ({k: np.stack([np.asarray(o[k]) for o in out])
                         for k in ("u", "fhat", "triggered")}, rep)

            ref, ref_rep = run()
            sigkill = {9: lambda eng: victim_of(sup, eng).kill()}
            got, rep = run(at=sigkill)
            for k in ("u", "fhat", "triggered"):
                np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
            assert 0.0 < got["triggered"].mean() < 1.0, "need mixed triggers"
            fo = rep["failover"]
            assert fo["failovers"] == 1 and fo["replayed_tokens"] > 0
            assert ref_rep.get("failover") is None
        finally:
            stop.set()
            t.join(timeout=10)
            sup.close()


class TestOneProcessPerChip:
    """Server processes never fight over an accelerator: spawning refuses
    where the parent holds the chip, or where several children would each
    ask for every chip."""

    def test_cpu_children_allowed(self, monkeypatch):
        from repro.launch.server import check_chip_free
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        check_chip_free(4)

    def test_parent_holding_tpu_refuses(self, monkeypatch):
        from jax._src import xla_bridge
        from repro.launch import server
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                            lambda: True)
        monkeypatch.setattr(server.jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="holds the tpu devices"):
            server.check_chip_free()
        with pytest.raises(RuntimeError, match="holds the tpu devices"):
            server.spawn_subprocess("paper-synthetic-serving",
                                    uds="/nonexistent.sock", slots=1,
                                    max_len=8, ready_file="/nonexistent")

    def test_parent_off_jax_starts_one(self, monkeypatch):
        from jax._src import xla_bridge
        from repro.launch.server import check_chip_free
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                            lambda: False)
        check_chip_free(1)
        with pytest.raises(RuntimeError, match="2 correction-server"):
            check_chip_free(2)
        with pytest.raises(RuntimeError, match="3 correction-server"):
            FleetSupervisor("paper-synthetic-serving", n_servers=3,
                            backend="subprocess")
