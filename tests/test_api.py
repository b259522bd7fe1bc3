"""The public serving API (serving/api.py): SessionConfig/TransportSpec
validation, the MonitorSession lifecycle, mode dispatch bit-identity
against the engine's three execution paths, and the deprecated engine
shims (run/run_scan/run_async) staying bit-identical to the session
path while warning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import decomposition as deco
from repro.data import tokens as tok
from repro.serving import MonitorSession, SessionConfig, TransportSpec
from repro.serving.collaborative import CollaborativeEngine

KEY = jax.random.PRNGKey(0)


def _setup(threshold=0.1, batch=3, length=16):
    cfg = registry.get_smoke("granite-8b")
    cfg = cfg.replace(monitor=cfg.monitor.__class__(
        **{**cfg.monitor.__dict__, "threshold": threshold,
           "trigger_margin": 0.0}))
    params = deco.init_collab_lm(KEY, cfg)
    stream = next(tok.lm_batches(0, cfg, batch, length))["tokens"]
    return cfg, params, stream


class TestTransportSpec:
    def test_parse_forms(self):
        assert TransportSpec.parse("stream") == TransportSpec("stream")
        w = TransportSpec.parse("wire:/tmp/corr.sock")
        assert w.kind == "wire" and w.address == "/tmp/corr.sock"
        w = TransportSpec.parse("wire:127.0.0.1:7431")
        assert w.address == "127.0.0.1:7431"
        spec = TransportSpec("thread", latency_s=0.01)
        assert TransportSpec.parse(spec) is spec

    def test_validation(self):
        with pytest.raises(ValueError, match="carrier-pigeon"):
            TransportSpec("carrier-pigeon")
        with pytest.raises(ValueError, match="address"):
            TransportSpec("wire")  # wire needs an address
        with pytest.raises(ValueError, match="no address"):
            TransportSpec("stream", address="/tmp/x")
        with pytest.raises(ValueError, match="latency"):
            TransportSpec("inproc", latency_s=0.01)
        with pytest.raises(ValueError, match="measured"):
            TransportSpec("wire", address="/tmp/x", latency_s=0.01)


class TestSessionConfig:
    def test_mode_and_staleness_validation(self):
        with pytest.raises(ValueError, match="walk"):
            SessionConfig(mode="walk")
        with pytest.raises(ValueError, match="max_staleness"):
            SessionConfig(mode="async", max_staleness=-1)
        with pytest.raises(ValueError, match="offline"):
            SessionConfig(mode="scan", transport="stream")

    def test_transport_string_is_parsed(self):
        c = SessionConfig(mode="async", transport="mock_remote")
        assert c.transport == TransportSpec("mock_remote")

    def test_needs_worker_and_effective_staleness(self):
        assert not SessionConfig(mode="sync").needs_worker
        assert not SessionConfig(mode="scan").needs_worker
        assert SessionConfig(mode="async").needs_worker
        wire = SessionConfig(mode="sync", transport=TransportSpec(
            "wire", address="/tmp/x"), max_staleness=8)
        assert wire.needs_worker
        assert wire.effective_staleness == 0, "sync over a transport is strict"
        assert SessionConfig(mode="async",
                             max_staleness=8).effective_staleness == 8

    def test_operating_point_mismatch_refused(self):
        cfg, params, _ = _setup()
        eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        with pytest.raises(ValueError, match="MonitorSession.open"):
            eng.session(SessionConfig(threshold=0.9))
        # a matching override is fine
        eng.session(SessionConfig(threshold=cfg.monitor.threshold))

    def test_open_applies_operating_point(self):
        cfg, params, stream = _setup(threshold=0.1)
        hi = MonitorSession.open(params, cfg, batch=3, max_len=32,
                                 config=SessionConfig(threshold=1e9))
        r = hi.run(stream)
        assert r["triggered"].sum() == 0, "override must silence triggers"
        assert hi.engine.m.threshold == 1e9


class TestLifecycle:
    def test_state_machine_and_context_manager(self):
        cfg, params, stream = _setup()
        eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        s = eng.session(SessionConfig(mode="async", transport="inproc"))
        assert s.state == "new"
        with s:
            assert s.state == "open"
            s.step(jnp.asarray(stream[:, 0]))
        assert s.state == "closed"
        s.close()  # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            s.step(jnp.asarray(stream[:, 1]))
        with pytest.raises(RuntimeError, match="closed"):
            s.attach("x")

    def test_run_closes_worker_backed_sessions(self):
        cfg, params, stream = _setup()
        eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        s = eng.session(SessionConfig(mode="async", transport="inproc"))
        s.run(stream)
        assert s.state == "closed"
        assert eng._dispatcher is None, "pipeline must be drained + closed"
        # plain sync sessions stay usable after run
        eng2 = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        s2 = eng2.session()
        s2.run(stream[:, :8])
        assert s2.state == "open"
        s2.step(jnp.asarray(stream[:, 8]))

    @pytest.mark.parametrize("transport", ["inproc", "stream"])
    def test_failed_dispatch_surfaces_its_own_error(self, transport):
        """A catch-up that raises at dispatch (a device out of memory)
        reaches the caller as itself: closing the session must not drain
        a request that was never sent."""
        cfg, params, stream = _setup(threshold=-1e9)   # every step triggers
        eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)

        def oom(*a):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

        eng._catchup = oom
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED") as e:
            with eng.session(SessionConfig(mode="async",
                                           transport=transport)) as s:
                s.step(jnp.asarray(stream[:, 0]))
        assert e.value.__context__ is None, "close raised over the error"
        assert eng._dispatcher is None or not eng._dispatcher.n_inflight

    def test_scan_sessions_are_offline_and_fixed(self):
        cfg, params, stream = _setup()
        eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        s = eng.session(SessionConfig(mode="scan"))
        with pytest.raises(RuntimeError, match="offline"):
            s.step(jnp.asarray(stream[:, 0]))
        with pytest.raises(RuntimeError, match="fixed membership"):
            s.attach("x")
        r = s.run(stream)
        assert "served" in r

    def test_step_token_forms_and_stream_iter(self):
        """Array tokens, dict tokens, and the stream() iterator agree."""
        cfg, params, stream = _setup()
        e1 = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        s1 = e1.session()
        r_arr = [s1.step(jnp.asarray(stream[:, t])) for t in range(6)]
        e2 = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        s2 = e2.session(streams=["a", "b", "c"])
        r_dict = [s2.step({"a": stream[0, t], "b": stream[1, t],
                           "c": stream[2, t]}) for t in range(6)]
        e3 = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        r_iter = list(e3.session().stream(
            stream[:, t] for t in range(6)))
        for ra, rd, ri in zip(r_arr, r_dict, r_iter):
            np.testing.assert_array_equal(ra["u"], rd["u"])
            np.testing.assert_array_equal(ra["u"], ri["u"])
            np.testing.assert_array_equal(ra["fhat"], rd["fhat"])
        assert r_dict[0]["streams"] == ("a", "b", "c")
        with pytest.raises(ValueError, match="mismatch"):
            s2.step({"a": stream[0, 6], "b": stream[1, 6]})

    def test_explicit_streams_on_used_engine_start_cold(self):
        """A second session with EXPLICIT stream ids on a used engine
        must honour the bit-cold guarantee (no inherited tenant state);
        default membership resumes (shim continuation semantics)."""
        cfg, params, stream = _setup()
        eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        eng.session().run(stream[:, :8])
        assert eng.edge_pos.max() == 8
        s2 = eng.session(streams=["x", "y", "z"])
        assert (eng.edge_pos == 0).all() and (eng.server_pos == 0).all()
        r = [s2.step({"x": stream[0, t], "y": stream[1, t],
                      "z": stream[2, t]}) for t in range(8)]
        fresh = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        ref = fresh.session().run(stream[:, :8])
        np.testing.assert_array_equal(
            np.stack([o["u"] for o in r], 1), ref["u"])
        # default membership on a used engine resumes instead
        eng2 = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        eng2.session().run(stream[:, :8])
        eng2.session()
        assert eng2.edge_pos.max() == 8, "streams=None must not reset"

    def test_one_async_session_at_a_time(self):
        cfg, params, stream = _setup()
        eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        s1 = eng.session(SessionConfig(mode="async", transport="inproc"))
        s1.__enter__()
        s2 = eng.session(SessionConfig(mode="async", transport="inproc"))
        with pytest.raises(RuntimeError, match="already open"):
            s2.__enter__()
        s1.close()


class TestModeBitIdentity:
    """MonitorSession dispatches to the same jitted paths: sync vs scan
    vs strict-async traces stay bit-identical (u/trigger) across modes,
    exactly as the pre-session engine methods were held to."""

    def test_three_modes_agree(self):
        cfg, params, stream = _setup()
        sync = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        r_sync = sync.session().run(stream)
        assert 0.0 < r_sync["triggered"].mean() < 1.0, "need mixed triggers"
        scan = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        r_scan = scan.session(SessionConfig(mode="scan")).run(stream)
        a = CollaborativeEngine(params, cfg, batch=3, max_len=32)
        r_async = a.session(SessionConfig(mode="async", transport="inproc",
                                          max_staleness=0)).run(stream)
        for r in (r_scan, r_async):
            np.testing.assert_array_equal(r_sync["u"], r["u"])
            np.testing.assert_array_equal(r_sync["triggered"], r["triggered"])
        np.testing.assert_array_equal(r_sync["fhat"], r_async["fhat"])
        np.testing.assert_allclose(r_sync["fhat"], r_scan["fhat"], atol=1e-6)


class TestDeprecatedShims:
    """Satellite: run/run_scan/run_async survive as DeprecationWarning
    shims whose output is bit-identical (u/trigger/fhat/comms) to the
    session path."""

    def _engines(self, cfg, params, n=2):
        return [CollaborativeEngine(params, cfg, batch=3, max_len=32)
                for _ in range(n)]

    def test_run_shim_bit_identical_and_warns(self):
        cfg, params, stream = _setup()
        shim_eng, sess_eng = self._engines(cfg, params)
        with pytest.warns(DeprecationWarning, match="MonitorSession"):
            r_shim = shim_eng.run(stream)
        r_sess = sess_eng.session().run(stream)
        assert 0.0 < r_sess["triggered"].mean() < 1.0
        self._assert_identical(r_shim, r_sess)

    def test_run_scan_shim_bit_identical_and_warns(self):
        cfg, params, stream = _setup()
        shim_eng, sess_eng = self._engines(cfg, params)
        with pytest.warns(DeprecationWarning, match="MonitorSession"):
            r_shim = shim_eng.run_scan(stream)
        r_sess = sess_eng.session(SessionConfig(mode="scan")).run(stream)
        self._assert_identical(r_shim, r_sess)
        np.testing.assert_array_equal(r_shim["served"], r_sess["served"])

    def test_run_async_shim_bit_identical_and_warns(self):
        cfg, params, stream = _setup()
        shim_eng, sess_eng = self._engines(cfg, params)
        with pytest.warns(DeprecationWarning, match="MonitorSession"):
            r_shim = shim_eng.run_async(stream, transport="inproc",
                                        max_staleness=2)
        with sess_eng.session(SessionConfig(mode="async", transport="inproc",
                                            max_staleness=2)) as s:
            r_sess = s.run(stream)
        self._assert_identical(r_shim, r_sess)

    @staticmethod
    def _assert_identical(r_shim, r_sess):
        np.testing.assert_array_equal(r_shim["u"], r_sess["u"])
        np.testing.assert_array_equal(r_shim["triggered"], r_sess["triggered"])
        np.testing.assert_array_equal(r_shim["fhat"], r_sess["fhat"])
        cs, cr = r_shim["comms"], r_sess["comms"]
        assert cs["bytes_sent"] == cr["bytes_sent"]
        assert cs["bytes_baseline"] == cr["bytes_baseline"]
        assert cs["trigger_rate"] == cr["trigger_rate"]
        if "per_stream" in cs:
            np.testing.assert_array_equal(cs["per_stream"]["bytes_sent"],
                                          cr["per_stream"]["bytes_sent"])


class TestPublicSurface:
    def test_serving_exports_the_session_api(self):
        import repro.serving as serving
        assert serving.MonitorSession is MonitorSession
        assert serving.SessionConfig is SessionConfig
        assert serving.TransportSpec is TransportSpec
        assert serving.CollaborativeEngine is CollaborativeEngine

    def test_engine_step_methods_are_private(self):
        """The pre-redesign per-step entrypoints are gone from the public
        surface; only construction, session(), and the deprecated run*
        shims remain."""
        for name in ("step", "step_async", "start_async", "finish_async"):
            assert not hasattr(CollaborativeEngine, name), name
        for name in ("session", "run", "run_scan", "run_async"):
            assert hasattr(CollaborativeEngine, name), name
