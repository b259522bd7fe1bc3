"""Observability subsystem: span tracer ring/export/schema, live spans
on the profiler's host plane, the unified metrics registry, histogram
percentile edge cases, the protocol-v4 REPLY timing payload (v3
compatibility both ways), the sync catch-up's counts and the named scopes
that split its compiled program, and the load-bearing contract that
tracing is FREE when off and INVISIBLE when on — traced sessions produce
bitwise-identical protocol outputs on every execution path (sync / scan /
async / wire)."""
import glob
import json
import os
import re
import struct
import tempfile
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_synthetic import SERVING
from repro.core import decomposition as deco
from repro.data import tokens as tok
from repro.observability import (MetricsRegistry, Tracer, breakdown,
                                 breakdown_table, flatten, load_trace,
                                 validate_chrome_trace)
from repro.observability import trace as trace_mod
from repro.serving import SessionConfig, TransportSpec, wire
from repro.serving.collaborative import TOKEN_BYTES, CollaborativeEngine
from repro.serving.policy import FixedPolicy
from repro.serving.tracker import Histogram, InMemoryTracker

KEY = jax.random.PRNGKey(0)


def _cfg(threshold=0.1):
    return SERVING.replace(monitor=SERVING.monitor.__class__(
        **{**SERVING.monitor.__dict__, "threshold": threshold,
           "trigger_margin": 0.0}))


def _uds_path(tag):
    return os.path.join(tempfile.mkdtemp(prefix=f"obs_{tag}_"), "s.sock")


# -- tracer ------------------------------------------------------------------

class TestTracer:
    def test_spans_record_and_clamp(self):
        tr = Tracer()
        t0 = tr.clock()
        tr.done("edge.decode", "edge", t0, track="edge", step=3)
        tr.add("server.queue", "server", 10.0, -0.5, track="server")
        spans = tr.spans()
        assert [s.name for s in spans] == ["edge.decode", "server.queue"]
        assert spans[0].dur >= 0 and spans[0].args["step"] == 3
        assert spans[1].dur == 0.0, "negative durations clamp to zero"

    def test_span_records_as_done(self):
        """A live span's ring entry is the one ``done`` records: name,
        category, track and args (late ones from ``set`` included), from
        entry to exit; a body that raises records nothing."""
        tr = Tracer()
        tr.done("edge.catchup", "edge", tr.clock(), track="wire", step=3)
        t_in = tr.clock()
        with tr.span("edge.catchup", "edge", track="wire", step=3) as sp:
            sp.set(rounds=2)
        t_out = tr.clock()
        by_done, by_span = tr.spans()
        for a in ("name", "cat", "track"):
            assert getattr(by_span, a) == getattr(by_done, a)
        assert by_span.args == {"step": 3, "rounds": 2}
        assert t_in <= by_span.ts and by_span.ts + by_span.dur <= t_out
        with pytest.raises(RuntimeError):
            with tr.span("edge.decode", "edge"):
                raise RuntimeError("body failed")
        assert len(tr) == 2

    def test_span_lands_on_profiler_host_plane(self, tmp_path):
        """Under a profiler trace a live span is a TraceMe on the host
        plane, on the device trace's clock, its args as event stats and
        nested as it was opened."""
        from jax.profiler import ProfileData
        tr = Tracer()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            with tr.span("edge.catchup", "edge", step=7, rounds=3) as sp:
                with tr.span("catchup.wait", "edge", step=7):
                    jnp.ones(8).sum().block_until_ready()
                sp.set(replayed=5)
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        evs = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in ("edge.catchup", "catchup.wait"):
                            evs[ev.name] = (line.name, ev.start_ns,
                                            ev.start_ns + ev.duration_ns,
                                            dict(ev.stats))
        outer, inner = evs["edge.catchup"], evs["catchup.wait"]
        assert outer[0] == inner[0], "one host thread"
        assert outer[1] <= inner[1] < inner[2] <= outer[2]
        assert outer[3] == {"step": 7, "rounds": 3, "replayed": 5}
        assert inner[3] == {"step": 7}
        assert [s.name for s in tr.spans()] == ["catchup.wait",
                                                "edge.catchup"]

    def test_ring_bound_and_dropped(self):
        tr = Tracer(capacity=4)
        for i in range(10):
            tr.add(f"s{i}", "edge", float(i), 0.1, track="edge")
        assert len(tr) == 4
        assert tr.dropped == 6
        assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]
        st = tr.stats()
        assert st["spans"] == 4 and st["dropped"] == 6

    def test_export_validate_round_trip(self, tmp_path):
        tr = Tracer()
        tr.add("wire.request", "wire", 1.0, 0.25, track="wire", req_id=7)
        tr.add("edge.decode", "edge", 1.0, 0.01, track="edge")
        path = str(tmp_path / "trace.json")
        assert tr.export(path) == 2
        obj = load_trace(path)  # validates on load
        xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == 2
        req = next(e for e in xs if e["name"] == "wire.request")
        assert req["dur"] == pytest.approx(0.25e6)  # microseconds
        assert req["args"]["req_id"] == 7
        # thread-name metadata makes Perfetto label the tracks
        metas = [e for e in obj["traceEvents"] if e["ph"] == "M"]
        names = {e["args"]["name"] for e in metas}
        assert {"edge", "wire", "server"} <= names

    def test_validate_rejects_malformed(self, tmp_path):
        with pytest.raises(ValueError):
            validate_chrome_trace({"no": "events"})
        with pytest.raises(ValueError):
            validate_chrome_trace({"traceEvents": []})  # no X events
        with pytest.raises(ValueError):
            validate_chrome_trace({"traceEvents": [
                {"ph": "X", "pid": 1, "tid": 0, "name": "a",
                 "ts": -1.0, "dur": 0.0}]})
        p = str(tmp_path / "garbage.json")
        with open(p, "w") as fh:
            json.dump({"traceEvents": [{"ph": "X"}]}, fh)
        with pytest.raises(ValueError):
            load_trace(p)

    def test_breakdown_over_spans_and_events(self, tmp_path):
        tr = Tracer()
        tr.add("wire.request", "wire", 0.0, 0.010, track="wire")
        tr.add("wire.encode", "wire", 0.0, 0.001, track="wire")
        tr.add("server.queue", "server", 0.0, 0.002, track="server")
        tr.add("server.catchup", "server", 0.0, 0.004, track="server")
        tr.add("wire.socket", "wire", 0.0, 0.003, track="wire")
        stats = breakdown(tr.spans())
        assert stats["rtt"]["p50_s"] == pytest.approx(0.010)
        assert stats["serialize"]["n"] == 1
        assert stats["compute"]["mean_s"] == pytest.approx(0.004)
        # identical numbers when computed from the exported JSON events
        path = str(tmp_path / "t.json")
        tr.export(path)
        stats2 = breakdown(load_trace(path)["traceEvents"])
        assert stats2["rtt"]["p50_s"] == pytest.approx(0.010)
        lines = breakdown_table(tr.spans())
        assert lines[1].split()[0] == "rtt", "RTT leads the table"


# -- metrics registry --------------------------------------------------------

class TestMetricsRegistry:
    def test_get_or_create_and_snapshot(self):
        reg = MetricsRegistry()
        assert reg.counter("requests") is reg.counter("requests")
        reg.inc("requests", 3)
        reg.gauge("load", fn=lambda: 0.5)
        reg.observe("lat_s", 0.2, lo=1e-4, hi=10.0)
        snap = reg.snapshot()
        assert snap["requests"] == 3
        assert snap["load"] == 0.5
        assert snap["lat_s_n"] == 1
        # single observation: percentiles are exactly the observation
        assert snap["lat_s_p50"] == snap["lat_s_p99"] == 0.2
        empty = MetricsRegistry()
        empty.histogram("h")
        s = empty.snapshot()
        assert s["h_n"] == 0 and s["h_p50"] is None and s["h_p99"] is None

    def test_flatten_nested(self):
        nested = {"a": 1, "wire": {"rtt_mean_s": 0.5, "deep": {"x": 2}},
                  "per_stream": [1, 2]}
        flat = flatten(nested, "comms")
        assert flat == {"comms/a": 1, "comms/wire/rtt_mean_s": 0.5,
                        "comms/wire/deep/x": 2, "comms/per_stream": [1, 2]}


# -- histogram percentile edge cases (satellite) -----------------------------

class TestHistogramEdgeCases:
    def test_empty_percentiles_are_none(self):
        s = Histogram(1e-4, 10.0).summary()
        assert s == {"n": 0, "mean": 0.0, "max": 0.0, "p50": None,
                     "p99": None}

    def test_single_observation_is_its_own_percentile(self):
        h = Histogram(1e-4, 10.0)
        h.observe(0.037)  # far from any bucket midpoint
        s = h.summary()
        assert s["p50"] == s["p99"] == 0.037
        assert s["n"] == 1 and s["max"] == 0.037

    def test_quantiles_clamped_to_observed_range(self):
        h = Histogram(1e-4, 10.0)
        for x in (0.02, 0.021, 0.022):
            h.observe(x)
        s = h.summary()
        assert 0.02 <= s["p50"] <= 0.022
        assert 0.02 <= s["p99"] <= 0.022


class TestInMemoryTrackerBound:
    def test_ring_evicts_oldest(self):
        t = InMemoryTracker(max_records=4)
        for i in range(10):
            t.log({"i": i})
        recs = t.records
        assert len(recs) == 4
        assert [r["i"] for r in recs] == [6, 7, 8, 9]
        assert t.latest == {"i": 9}

    def test_unbounded_keeps_everything(self):
        t = InMemoryTracker(max_records=None)
        for i in range(10):
            t.log({"i": i})
        assert len(t.records) == 10


# -- protocol v4 timing payload ----------------------------------------------

def _reply(queue_s):
    return wire.WireReply(
        req_id=9, t=5, triggered=np.array([True, False, True]),
        v=np.array([0.1, 0.0, 0.2], np.float32),
        fhat=np.array([0.5, 0.6, 0.7], np.float32),
        server_time_s=0.004, coalesced=2, queue_s=queue_s)


def _payload(buf):
    payloads = wire.FrameReader().feed(buf)
    assert len(payloads) == 1
    return payloads[0]


class TestWireV4Timing:
    def test_queue_s_round_trips(self):
        msg = wire.decode(_payload(wire.encode_reply(_reply(0.0025))))
        assert msg.queue_s == pytest.approx(0.0025)
        np.testing.assert_array_equal(msg.triggered, [True, False, True])
        assert msg.server_time_s == pytest.approx(0.004)

    def test_absent_payload_decodes_as_minus_one(self):
        short = _payload(wire.encode_reply(_reply(-1.0)))
        full = _payload(wire.encode_reply(_reply(0.0)))
        assert len(short) == len(full) - 8, "payload is exactly one <d"
        assert wire.decode(short).queue_s == -1.0

    def test_v3_frame_decodes_without_timing(self):
        # a v3 peer's REPLY: same body, no timing payload, version byte 3
        payload = bytearray(_payload(wire.encode_reply(_reply(-1.0))))
        assert payload[2] == wire.VERSION
        payload[2] = 3
        msg = wire.decode(bytes(payload))
        assert msg.queue_s == -1.0
        np.testing.assert_array_equal(msg.fhat, _reply(-1.0).fhat)

    def test_versions_outside_window_rejected(self):
        payload = bytearray(_payload(wire.encode_reply(_reply(0.5))))
        for bad in (wire.MIN_VERSION - 1, wire.VERSION + 1):
            payload[2] = bad
            with pytest.raises(wire.WireError, match="version"):
                wire.decode(bytes(payload))


# -- tracing is invisible: bitwise identity on every path --------------------

@pytest.fixture(scope="module")
def proto():
    cfg = _cfg()
    params = deco.init_collab_lm(KEY, cfg)
    stream = next(tok.lm_batches(0, cfg, 3, 14))["tokens"]
    return cfg, params, stream


def _run(cfg, params, stream, session_cfg):
    eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
    sess = eng.session(session_cfg)
    r = sess.run(stream)
    return r, sess


def _assert_bitwise(r_plain, r_traced):
    np.testing.assert_array_equal(r_plain["u"], r_traced["u"])
    np.testing.assert_array_equal(r_plain["triggered"], r_traced["triggered"])
    np.testing.assert_array_equal(r_plain["fhat"], r_traced["fhat"])


class TestTracedIdentity:
    def test_sync_bitwise(self, proto, tmp_path):
        cfg, params, stream = proto
        r0, _ = _run(cfg, params, stream, SessionConfig())
        r1, sess = _run(cfg, params, stream, SessionConfig(trace=True))
        _assert_bitwise(r0, r1)
        assert 0.0 < r1["triggered"].mean() < 1.0, "need mixed triggers"
        spans = sess.tracer.spans()
        names = {s.name for s in spans}
        assert {"edge.decode", "edge.trigger"} <= names
        assert "edge.catchup" in names, "triggered steps catch up in sync"
        path = str(tmp_path / "sync.json")
        assert sess.export_trace(path) == len(spans)
        load_trace(path)

    def test_sync_bitwise_with_step_spans(self, proto):
        """With a policy, the sync step's spans are all there —
        session.policy around the policy's two calls, catchup.dispatch
        and catchup.wait inside edge.catchup — and the protocol is
        bitwise that of the untraced session."""
        cfg, params, stream = proto
        r0, _ = _run(cfg, params, stream, SessionConfig(policy=FixedPolicy()))
        r1, sess = _run(cfg, params, stream,
                        SessionConfig(policy=FixedPolicy(), trace=True))
        _assert_bitwise(r0, r1)
        spans = sess.tracer.spans()
        S = stream.shape[1]
        n_catchup = int(r1["triggered"].any(axis=0).sum())
        count = lambda n: sum(s.name == n for s in spans)
        assert count("session.policy") == 2 * S
        assert count("edge.decode") == count("edge.trigger") == S
        assert (count("edge.catchup") == count("catchup.dispatch")
                == count("catchup.wait") == n_catchup)
        outer = [s for s in spans if s.name == "edge.catchup"]
        for name in ("catchup.dispatch", "catchup.wait"):
            kids = [s for s in spans if s.name == name]
            for o, k in zip(outer, kids):
                assert k.args["step"] == o.args["step"]
                assert o.ts <= k.ts and k.ts + k.dur <= o.ts + o.dur

    def test_sync_catchup_counts(self, proto):
        """Each edge.catchup span carries the call's rounds (the longest
        triggered backlog), rows (B) and replayed tokens (what comms
        charged), and the engine's counters sum them."""
        cfg, params, stream = proto
        r, sess = _run(cfg, params, stream, SessionConfig(trace=True))
        trig = r["triggered"]                       # (B, S)
        B, S = trig.shape
        want, server_pos = [], np.zeros(B, np.int64)
        for t in range(S):
            if trig[:, t].any():
                backlog = np.where(trig[:, t], t + 1 - server_pos, 0)
                want.append({"step": t, "rounds": int(backlog.max()),
                             "rows": B, "replayed": int(backlog.sum())})
                server_pos = np.where(trig[:, t], t + 1, server_pos)
        got = [{k: s.args[k] for k in ("step", "rounds", "rows", "replayed")}
               for s in sess.tracer.spans() if s.name == "edge.catchup"]
        assert got == want and any(w["rounds"] > 1 for w in want)
        snap = sess.metrics()
        assert snap["catchup.calls"] == len(want)
        assert snap["catchup.rounds"] == sum(w["rounds"] for w in want)
        assert "catchup.row_rounds" not in snap
        assert snap["catchup.replayed"] == sum(w["replayed"] for w in want)
        assert (snap["catchup.replayed"]
                == sess.report()["bytes_sent"] // TOKEN_BYTES)

    def test_scan_bitwise(self, proto):
        cfg, params, stream = proto
        r0, _ = _run(cfg, params, stream, SessionConfig(mode="scan"))
        r1, sess = _run(cfg, params, stream,
                        SessionConfig(mode="scan", trace=True))
        _assert_bitwise(r0, r1)
        assert {s.name for s in sess.tracer.spans()} == {"scan.run"}

    def test_async_bitwise(self, proto):
        cfg, params, stream = proto
        sc = SessionConfig(mode="async", max_staleness=2,
                           transport=TransportSpec("stream"))
        r0, _ = _run(cfg, params, stream, sc)
        r1, sess = _run(cfg, params, stream,
                        SessionConfig(mode="async", max_staleness=2,
                                      transport=TransportSpec("stream"),
                                      trace=True))
        _assert_bitwise(r0, r1)
        names = {s.name for s in sess.tracer.spans()}
        assert "edge.dispatch" in names and "edge.merge" in names

    def test_metrics_snapshot_shape(self, proto):
        cfg, params, stream = proto
        _, sess = _run(cfg, params, stream, SessionConfig(trace=True))
        snap = sess.metrics()
        assert snap["comms/trigger_rate"] > 0
        assert snap["trace/spans"] == len(sess.tracer.spans())
        # untraced sessions still get the registry + comms panes
        _, plain = _run(cfg, params, stream, SessionConfig())
        snap2 = plain.metrics()
        assert "comms/trigger_rate" in snap2
        assert not any(k.startswith("trace/") for k in snap2)

    def test_trace_ring_bound_respected_in_session(self, proto):
        cfg, params, stream = proto
        r1, sess = _run(cfg, params, stream,
                        SessionConfig(trace=True, trace_capacity=8))
        assert len(sess.tracer) == 8
        assert sess.tracer.dropped > 0
        r0, _ = _run(cfg, params, stream, SessionConfig())
        _assert_bitwise(r0, r1)  # dropping spans can't change the protocol


@pytest.fixture(scope="module")
def obs_wire_server(proto):
    """One in-thread CorrectionServer with its OWN tracer, shared by the
    wire identity tests."""
    from repro.serving.server import CorrectionServer
    cfg, params, _ = proto
    uds = _uds_path("srv")
    srv = CorrectionServer(cfg, params, slots=8, max_len=32, uds=uds,
                           tracer=Tracer())
    stop = threading.Event()
    th = threading.Thread(target=srv.serve_forever,
                          kwargs=dict(stop=stop), daemon=True)
    th.start()
    yield uds, srv
    stop.set()
    th.join(timeout=10)
    srv.close()


class TestTracedWire:
    def test_strict_sync_over_wire_bitwise(self, proto, obs_wire_server):
        """max_staleness=0 over the real socket: the fully deterministic
        boundary, so traced == untraced is bitwise INCLUDING fhat."""
        cfg, params, stream = proto
        uds, _ = obs_wire_server
        sc = dict(mode="sync", transport=TransportSpec("wire", address=uds))
        r0, _ = _run(cfg, params, stream, SessionConfig(**sc))
        r1, sess = _run(cfg, params, stream,
                        SessionConfig(**sc, trace=True))
        _assert_bitwise(r0, r1)
        names = {s.name for s in sess.tracer.spans()}
        assert {"wire.encode", "wire.request", "wire.socket",
                "server.queue", "server.catchup"} <= names

    def test_pipelined_over_wire_monitor_path_bitwise(self, proto,
                                                      obs_wire_server):
        """Pipelined over a real socket: merge timing is inherently
        nondeterministic (a reply lands at t+1 or t+2 run to run), so
        the contract is the monitor path — u and the trigger trace —
        bitwise, with corrections only ever lowering fhat."""
        cfg, params, stream = proto
        uds, srv = obs_wire_server
        sc = dict(mode="async", max_staleness=3,
                  transport=TransportSpec("wire", address=uds))
        r0, _ = _run(cfg, params, stream, SessionConfig(**sc))
        r1, sess = _run(cfg, params, stream,
                        SessionConfig(**sc, trace=True))
        np.testing.assert_array_equal(r0["u"], r1["u"])
        np.testing.assert_array_equal(r0["triggered"], r1["triggered"])
        assert np.all(r1["fhat"] <= r1["u"] + 1e-6)
        # the measured RTT breakdown reached the session registry
        snap = sess.metrics()
        assert snap["rtt_s_n"] > 0
        assert snap["rtt_queue_s_n"] > 0, "v4 timing payload present"
        assert snap["rtt_compute_s_p50"] is not None
        # and the server recorded its own half on its own tracer
        srv_names = {s.name for s in srv.tracer.spans()}
        assert {"server.queue", "server.replay"} <= srv_names
        assert srv.stats_snapshot()["queue_wait_s_n"] > 0


# -- the disabled path is actually disabled ----------------------------------

class TestDisabledPath:
    def test_untraced_session_never_touches_tracer(self, proto, monkeypatch):
        """No Tracer may be constructed or used when trace=False — the
        overhead guard behind the 'free when off' acceptance bullet."""
        def boom(*a, **k):
            raise AssertionError("tracer touched on the disabled path")
        monkeypatch.setattr(Tracer, "__init__", boom)
        monkeypatch.setattr(Tracer, "done", boom)
        monkeypatch.setattr(Tracer, "add", boom)
        monkeypatch.setattr(Tracer, "span", boom)
        # nor is a profiler annotation constructed
        monkeypatch.setattr(trace_mod, "TraceAnnotation", boom)
        cfg, params, stream = proto
        r, sess = _run(cfg, params, stream, SessionConfig())
        assert sess.tracer is None
        assert r["triggered"].any()
        # the policy's calls and the catch-up's children are span sites too
        r, sess = _run(cfg, params, stream,
                       SessionConfig(policy=FixedPolicy()))
        assert sess.tracer is None and r["triggered"].any()
        assert sess.metrics()["catchup.calls"] > 0

    def test_export_trace_refuses_when_off(self, proto):
        cfg, params, stream = proto
        _, sess = _run(cfg, params, stream, SessionConfig())
        with pytest.raises(RuntimeError, match="trace=True"):
            sess.export_trace("/tmp/never.json")

    def test_reused_engine_drops_stale_tracer(self, proto):
        """A traced session followed by an untraced one on the SAME
        engine must not inherit the old tracer."""
        cfg, params, stream = proto
        eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        s1 = eng.session(SessionConfig(trace=True))
        s1.run(stream)
        assert eng._tracer is not None
        s2 = eng.session(SessionConfig())
        s2._ensure_open()
        assert eng._tracer is None


# -- named scopes split the catch-up's compiled program ----------------------

def _computations(hlo: str) -> dict:
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) ", line)
        if m and line.rstrip().endswith("{"):
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line)
    return comps


def _called(comps: dict, root: str) -> set:
    """``root`` and every computation it runs, transitively: fusions,
    loops and branches (a reduction's scalar combiner, ``to_apply``,
    carries the bare op name and is left out)."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|body|condition)=%([\w.\-]+)", line)
            for group in re.findall(r"(?:calls|branch_computations)="
                                    r"\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def test_catchup_named_scopes_split_the_replay_loop(proto):
    """The contract the replay-loop reader of the device trace relies on:
    in ``_catchup_impl`` compiled, every operation in the body of the
    top-level while carries ``catchup.round`` (but the loop counter
    ``fori_loop`` adds), the masked cache write-back inside it
    ``decode.merge``, and the v head and the combine sit outside it."""
    cfg, params, _ = proto
    B = 3
    eng = CollaborativeEngine(params, cfg, batch=B, max_len=16)
    hlo = jax.jit(eng._catchup_impl).lower(
        params, eng.server.cache, eng._history, jnp.zeros(B, jnp.int32),
        jnp.asarray(4, jnp.int32), jnp.ones(B, bool),
        jnp.zeros(B, jnp.float32)).compile().as_text()
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo, re.M).group(1)
    loops = [line for line in comps[entry] if " while(" in line]
    assert len(loops) == 1, "one top-level loop: the replay rounds"
    body = re.search(r"body=%([\w.\-]+)", loops[0]).group(1)
    inside = _called(comps, body)
    names = lambda cs: [n for c in cs for line in comps[c]
                        for n in re.findall(r'op_name="([^"]*)"', line)]
    in_loop = names(inside)
    assert in_loop
    prefix = "jit(_catchup_impl)/while/body/"
    for n in in_loop:
        assert (n.startswith(prefix + "catchup.round/")
                or n == prefix + "add"), n
    assert any("/decode.merge/" in n for n in in_loop)
    outside = names(set(comps) - inside)
    for scope in ("catchup.v_head", "catchup.combine"):
        assert any(f"/{scope}/" in n for n in outside), scope
        assert not any(scope in n for n in in_loop), scope
