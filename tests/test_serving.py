"""Serving engine correctness + collaborative protocol accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.core import decomposition as deco
from repro.data import tokens as tok
from repro.serving import SessionConfig, TransportSpec
from repro.serving.collaborative import CollaborativeEngine
from repro.serving.engine import ServeEngine

KEY = jax.random.PRNGKey(0)


# every caller goes through the public MonitorSession API; the deprecated
# engine shims get their own dedicated test (tests/test_api.py)
def run_sync(eng, stream):
    return eng.session().run(stream)


def run_scan(eng, stream):
    return eng.session(SessionConfig(mode="scan")).run(stream)


def run_async(eng, stream, *, transport="stream", max_staleness=1,
              latency_s=None, address=None):
    spec = TransportSpec(transport, address=address, latency_s=latency_s)
    with eng.session(SessionConfig(mode="async", transport=spec,
                                   max_staleness=max_staleness)) as s:
        return s.run(stream)


class TestServeEngine:
    def test_prefill_matches_forward(self):
        """Cache-building prefill must reproduce the batched forward logits."""
        from repro.models import api as model_api
        cfg = registry.get_smoke("granite-8b")
        params = model_api.init_model(KEY, cfg)
        toks = next(tok.lm_batches(0, cfg, 2, 16))["tokens"]
        fwd = model_api.forward(params, cfg, {"tokens": jnp.asarray(toks)})
        eng = ServeEngine(params, cfg, batch=2, max_len=32)
        logits_last = eng.prefill(jnp.asarray(toks))
        np.testing.assert_allclose(np.asarray(logits_last),
                                   np.asarray(fwd["logits"][:, -1]),
                                   atol=2e-3, rtol=2e-3)

    def test_generate_is_deterministic_greedy(self):
        cfg = registry.get_smoke("xlstm-350m")
        from repro.models import api as model_api
        params = model_api.init_model(KEY, cfg)
        toks = jnp.asarray(next(tok.lm_batches(1, cfg, 2, 8))["tokens"])
        g1 = ServeEngine(params, cfg, 2, 32).generate(toks, 6)
        g2 = ServeEngine(params, cfg, 2, 32).generate(toks, 6)
        np.testing.assert_array_equal(g1, g2)

    def test_decode_at_per_element_positions_and_masking(self):
        """The per-element decode primitive: uniform vector positions match
        the batched decode; inactive elements' cache rows stay bit-frozen;
        heterogeneous positions decode each element at its own depth."""
        from repro.models import api as model_api
        cfg = registry.get_smoke("granite-8b")
        params = model_api.init_model(KEY, cfg)
        stream = jnp.asarray(next(tok.lm_batches(0, cfg, 3, 8))["tokens"])

        # uniform positions, all active == plain batched decode
        ref = ServeEngine(params, cfg, batch=3, max_len=16)
        per = ServeEngine(params, cfg, batch=3, max_len=16)
        for t in range(4):
            _, h_ref = ref.decode(stream[:, t])
            _, h_per = per.decode_at(stream[:, t], jnp.full((3,), t, jnp.int32),
                                     jnp.ones((3,), bool))
            np.testing.assert_allclose(np.asarray(h_per), np.asarray(h_ref),
                                       atol=2e-3, rtol=2e-3)

        # masking: inactive element's cache row is bit-untouched
        eng = ServeEngine(params, cfg, batch=3, max_len=16)
        before = np.asarray(eng.cache["blocks"].k).copy()
        eng.decode_at(stream[:, 0], jnp.zeros((3,), jnp.int32),
                      jnp.asarray([True, False, True]))
        after = np.asarray(eng.cache["blocks"].k)
        assert not np.array_equal(before[:, 0], after[:, 0])
        np.testing.assert_array_equal(before[:, 1], after[:, 1])

        # heterogeneous positions: element 1 held at pos 0 while element 0
        # advances; its eventual first decode matches a fresh engine's
        het = ServeEngine(params, cfg, batch=2, max_len=16)
        for t in range(3):
            het.decode_at(stream[:2, t], jnp.full((2,), t, jnp.int32),
                          jnp.asarray([True, False]))
        _, h = het.decode_at(jnp.stack([stream[0, 3], stream[1, 0]]),
                             jnp.asarray([3, 0], jnp.int32),
                             jnp.ones((2,), bool))
        fresh = ServeEngine(params, cfg, batch=2, max_len=16)
        _, h0 = fresh.decode(jnp.stack([stream[0, 0], stream[1, 0]]))
        np.testing.assert_allclose(np.asarray(h)[1], np.asarray(h0)[1],
                                   atol=2e-3, rtol=2e-3)


def _merged_step_at(cfg, axes):
    """The per-element step as it was before it wrote in place: every row
    decoded alone at its scalar position (vmap over the cache's batch axes,
    each row re-batched to size one, cache whole), then the whole cache
    merged under the row mask.  The reference for ``make_step_at``."""
    from repro.models import api as model_api

    def step(params, cache, tok, pos, active):
        def one(c, t, p):
            c1 = jax.tree.map(jnp.expand_dims, c, axes)
            _, h, nc = model_api.decode_step(params, cfg, c1, t[None], p,
                                             with_logits=False)
            return h[0], jax.tree.map(jnp.squeeze, nc, axes)

        hidden, new = jax.vmap(one, in_axes=(axes, 0, 0),
                               out_axes=(0, axes))(cache, tok, pos)

        def merge(n, o, ax):
            shape = [1] * n.ndim
            shape[ax] = active.shape[0]
            return jnp.where(jnp.reshape(active, shape), n, o)

        return hidden, jax.tree.map(merge, new, cache, axes)

    return jax.jit(step)


# the cache families the per-element step writes: dense KV, hybrid SSM
# state + shared-block KV, xLSTM memories, MLA latents (deepseek), a
# sliding-window ring (mixtral) and layer-flat self caches (vision)
PER_ROW_ARCHS = ["granite-8b", "zamba2-7b", "xlstm-350m", "deepseek-v3-671b",
                 "mixtral-8x22b", "llama-3.2-vision-11b"]


class TestPerRowDecode:
    B, L = 4, 16

    def _setup(self, arch):
        from repro.models import api as model_api
        from repro.serving.engine import cache_batch_axes, make_step_at
        cfg = registry.get_smoke(arch)
        params = model_api.init_model(KEY, cfg)
        toks = jnp.asarray(next(tok.lm_batches(0, cfg, self.B, self.L))["tokens"])
        new = jax.jit(make_step_at(cfg, with_logits=False))
        old = _merged_step_at(cfg, cache_batch_axes(cfg, self.B, self.L))
        return (params, toks, new, old, cache_batch_axes(cfg, self.B, self.L),
                model_api.init_cache(cfg, self.B, self.L))

    @pytest.mark.parametrize("arch", PER_ROW_ARCHS)
    def test_step_at_writes_in_place_under_the_row_mask(self, arch):
        """Rows at heterogeneous positions, inactive rows at clipped or
        out-of-range dummy positions: every leaf of an inactive row comes
        back bit-unchanged (K/V slots, recurrent state), and the cache and
        the active rows' hidden states are bitwise those of the per-row
        decode with a whole-cache merge."""
        params, toks, new, old, axes, cache = self._setup(arch)
        cold = cache
        base = np.array([0, 3, 6, 9])
        masks = [[1, 1, 1, 1], [1, 0, 1, 1], [0, 1, 1, 0], [1, 1, 0, 1],
                 [0, 0, 1, 1], [1, 0, 0, 0]]
        dummy = np.array([self.L - 1, self.L + 3, self.L - 1, 2 * self.L])
        for r, m in enumerate(masks):
            act = np.asarray(m, bool)
            pos = np.where(act, base + r, dummy).astype(np.int32)
            t = toks[np.arange(self.B), np.minimum(pos, self.L - 1)]
            _, h1, c1 = new(params, cache, t, jnp.asarray(pos), jnp.asarray(act))
            h0, c0 = old(params, cache, t, jnp.asarray(pos), jnp.asarray(act))
            for a, b, ax in zip(jax.tree.leaves(cache), jax.tree.leaves(c1),
                                jax.tree.leaves(axes)):
                np.testing.assert_array_equal(np.compress(~act, b, axis=ax),
                                              np.compress(~act, a, axis=ax))
            for a, b in zip(jax.tree.leaves(c0), jax.tree.leaves(c1)):
                np.testing.assert_array_equal(b, a)
            np.testing.assert_array_equal(np.asarray(h1)[act],
                                          np.asarray(h0)[act])
            cache = c1
        # the active rows did write: the replay is not a no-op
        assert any(not np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(cold), jax.tree.leaves(cache)))

    @pytest.mark.parametrize("arch", ["granite-8b", "zamba2-7b"])
    def test_step_at_moves_no_whole_cache(self, arch):
        """Structural guard on the per-element step's jaxpr: no select over
        a stack of cache layers (the whole-cache merge), no scan that
        slices a cache stack as ``xs`` or restacks one as ``ys``, and no
        dynamic slice or update that reads or writes more than one layer
        of a stack.  What remains are slot writes (scatter), one layer's
        read for attention and one layer's recurrent state."""
        params, toks, new, _, axes, cache = self._setup(arch)
        def shape(x):   # size-1 axes dropped: vmap inserts them
            return tuple(d for d in getattr(x, "aval", x).shape if d != 1)

        stacks = set()   # shapes of two or more layers of a cache leaf
        for leaf, ax in zip(jax.tree.leaves(cache), jax.tree.leaves(axes)):
            stacks.update(shape(jax.ShapeDtypeStruct(leaf.shape[j:], leaf.dtype))
                          for j in range(ax) if np.prod(leaf.shape[j:ax]) > 1)
        assert stacks
        pos = jnp.asarray([0, 3, 6, 9], jnp.int32)
        jaxpr = jax.make_jaxpr(new)(params, cache, toks[:, 0], pos,
                                    jnp.ones((self.B,), bool))
        seen, bad = set(), []

        def walk(j):
            for e in j.eqns:
                name = e.primitive.name
                seen.add(name)
                if name in ("select_n", "dynamic_slice"):
                    bad.extend((name, shape(v)) for v in e.outvars
                               if shape(v) in stacks)
                if name == "dynamic_update_slice" and shape(e.invars[1]) in stacks:
                    bad.append((name, shape(e.invars[1])))
                if name == "scan":
                    k = e.params["num_consts"] + e.params["num_carry"]
                    bad.extend(("scan xs", shape(v)) for v in e.invars[k:]
                               if shape(v) in stacks)
                    bad.extend(("scan ys", shape(v))
                               for v in e.outvars[e.params["num_carry"]:]
                               if shape(v) in stacks)
                for p in e.params.values():
                    for sub in (p if isinstance(p, (tuple, list)) else [p]):
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            walk(inner)

        walk(jaxpr.jaxpr)
        assert {"scan", "scatter"} <= seen, seen
        assert not bad, bad


class TestCollaborativeEngine:
    def _engine(self, threshold):
        cfg = registry.get_smoke("granite-8b")
        cfg = cfg.replace(monitor=cfg.monitor.__class__(
            **{**cfg.monitor.__dict__, "threshold": threshold,
               "trigger_margin": 0.0}))
        params = deco.init_collab_lm(KEY, cfg)
        return cfg, params

    def test_no_trigger_means_no_server_traffic(self):
        cfg, params = self._engine(threshold=1e9)  # unreachable
        eng = CollaborativeEngine(params, cfg, batch=2, max_len=64)
        stream = next(tok.lm_batches(0, cfg, 2, 12))["tokens"]
        res = run_sync(eng, stream)
        assert res["triggered"].sum() == 0
        assert res["comms"]["bytes_sent"] == 0
        assert eng.server.pos == 0, "server cache must stay cold"
        np.testing.assert_allclose(res["fhat"], res["u"])

    def test_always_trigger_matches_joint_model(self):
        """With threshold=-inf the engine must reproduce u - s*sigma(v) with
        the server fully caught up each step."""
        cfg, params = self._engine(threshold=-1e9)
        eng = CollaborativeEngine(params, cfg, batch=2, max_len=64)
        stream = next(tok.lm_batches(0, cfg, 2, 10))["tokens"]
        res = run_sync(eng, stream)
        assert res["triggered"].all()
        assert eng.server.pos == 10
        assert res["comms"]["reduction_x"] <= 1.0 + 1e-6
        assert bool(np.all(res["fhat"] <= res["u"] + 1e-6))

    def test_comms_reduction_under_selective_trigger(self):
        """Per-stream accounting: a quiet stream buys the full reduction —
        its tokens are NEVER shipped, regardless of what other streams do."""
        cfg, params = self._engine(threshold=0.5)
        eng = CollaborativeEngine(params, cfg, batch=2, max_len=128)
        # deterministic per-stream stub: stream 0 always pages, stream 1 never
        eng._u_head = jax.jit(
            lambda p, h: jnp.where(jnp.arange(h.shape[0]) == 0, 1.0, -1.0))
        stream = next(tok.lm_batches(3, cfg, 2, 40))["tokens"]
        res = run_sync(eng, stream)
        trig_rate = res["triggered"].mean()
        assert 0.0 < trig_rate < 1.0, "stub must produce mixed triggering"
        assert res["comms"]["bytes_sent"] < res["comms"]["bytes_baseline"]
        assert res["comms"]["reduction_x"] > 1.0
        per = res["comms"]["per_stream"]
        assert per["bytes_sent"][1] == 0, "quiet stream must ship nothing"
        assert per["bytes_sent"][0] == per["bytes_baseline"][0]

    def test_bytes_invariant_under_mixed_trigger(self):
        """Each token ships at most once => bytes_sent <= bytes_baseline,
        per stream and in aggregate (the seed charged
        triggered.sum() * backlog_len, which violates this)."""
        cfg, params = self._engine(threshold=0.5)
        eng = CollaborativeEngine(params, cfg, batch=2, max_len=128)
        eng._u_head = jax.jit(lambda p, h: jnp.tanh(10.0 * h[..., 0]))
        stream = next(tok.lm_batches(3, cfg, 2, 40))["tokens"]
        res = run_sync(eng, stream)
        assert 0.0 < res["triggered"].mean() < 1.0
        assert res["comms"]["bytes_sent"] <= res["comms"]["bytes_baseline"]
        per = res["comms"]["per_stream"]
        assert (per["bytes_sent"] <= per["bytes_baseline"]).all()
        # and the meter agrees with the raw trigger trace: shipped tokens on
        # stream i = index of its last trigger + 1
        for i in range(2):
            idx = np.where(res["triggered"][i])[0]
            want = (idx[-1] + 1) if len(idx) else 0
            assert per["bytes_sent"][i] == want * 8


class TestBatchedScanPath:
    def _setup(self, threshold=0.1, batch=3, length=20):
        cfg = registry.get_smoke("granite-8b")
        cfg = cfg.replace(monitor=cfg.monitor.__class__(
            **{**cfg.monitor.__dict__, "threshold": threshold,
               "trigger_margin": 0.0}))
        params = deco.init_collab_lm(KEY, cfg)
        stream = next(tok.lm_batches(0, cfg, batch, length))["tokens"]
        return cfg, params, stream

    def test_scan_bit_identical_to_per_step_reference(self):
        """The lax.scan fast path is pure machinery: identical ops to a
        per-step loop => bit-identical u/fhat/triggered traces."""
        from repro.core.gating import compact_correction
        from repro.models import api as model_api
        cfg, params, stream = self._setup()
        B, S = stream.shape[:2]
        eng = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        rs = run_scan(eng, stream)

        m, ecfg = cfg.monitor, deco.edge_arch(cfg)
        ecache = model_api.init_cache(ecfg, B, eng.max_len)
        scache = model_api.init_cache(cfg, B, eng.max_len)

        @jax.jit
        def ref_step(ecache, scache, tok_t, pos):
            _, eh, ecache = model_api.decode_step(
                params["edge"], ecfg, ecache, tok_t, pos)
            u = eng._u_head(params, eh)
            _, sh, scache = model_api.decode_step(
                params["server"], cfg, scache, tok_t, pos)

            def corrector(buf):
                return m.s * deco.sigma(eng._v_head(params, buf), m.sigma)

            fhat, _, _ = compact_correction(
                u, sh.astype(jnp.float32), corrector, m.threshold,
                m.trigger_margin, B)
            return ecache, scache, u, fhat, u > m.threshold - m.trigger_margin

        us, fhats, trigs = [], [], []
        for t in range(S):
            ecache, scache, u, fhat, trig = ref_step(
                ecache, scache, jnp.asarray(stream[:, t]),
                jnp.asarray(t, jnp.int32))
            us.append(np.asarray(u)); fhats.append(np.asarray(fhat))
            trigs.append(np.asarray(trig))
        np.testing.assert_array_equal(rs["u"], np.stack(us, 1))
        np.testing.assert_array_equal(rs["fhat"], np.stack(fhats, 1))
        np.testing.assert_array_equal(rs["triggered"], np.stack(trigs, 1))

    def test_scan_matches_lazy_online_engine(self):
        """Protocol equivalence: the lazily-catching-up online engine and
        the eager offline scan produce the same traces (u/trigger exact;
        fhat to vmap-vs-batch matmul rounding) and the SAME per-stream
        communication accounting."""
        cfg, params, stream = self._setup()
        B = stream.shape[0]
        lazy = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        r1 = run_sync(lazy, stream)
        scan = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        r2 = run_scan(scan, stream)
        assert 0.0 < r1["triggered"].mean() < 1.0, "need mixed triggers"
        np.testing.assert_array_equal(r1["u"], r2["u"])
        np.testing.assert_array_equal(r1["triggered"], r2["triggered"])
        np.testing.assert_allclose(r1["fhat"], r2["fhat"], atol=1e-6)
        np.testing.assert_array_equal(r1["comms"]["per_stream"]["bytes_sent"],
                                      r2["comms"]["per_stream"]["bytes_sent"])
        assert r1["comms"]["bytes_sent"] == r2["comms"]["bytes_sent"]
        assert r1["comms"]["trigger_rate"] == r2["comms"]["trigger_rate"]

    def test_per_element_backlog_isolation(self):
        """A trigger on stream 0 must not flush stream 1's backlog, advance
        its server position, or charge its comms account."""
        cfg, params, stream = self._setup(batch=2, length=12)
        eng = CollaborativeEngine(params, cfg, batch=2, max_len=32)
        eng._u_head = jax.jit(
            lambda p, h: jnp.where(jnp.arange(h.shape[0]) == 0, 1.0, -1.0))
        server_k_before = np.asarray(eng.server.cache["blocks"].k).copy()
        res = run_sync(eng, stream)
        assert res["triggered"][0].all() and not res["triggered"][1].any()
        # stream 0 caught up to the end; stream 1's server state untouched
        assert eng.server_pos[0] == 12 and eng.server_pos[1] == 0
        server_k = np.asarray(eng.server.cache["blocks"].k)
        assert not np.array_equal(server_k[:, 0], server_k_before[:, 0])
        np.testing.assert_array_equal(server_k[:, 1], server_k_before[:, 1])
        per = eng.comms.per_stream_report()
        assert per["bytes_sent"][0] > 0 and per["bytes_sent"][1] == 0
        # quiet stream's report is pure pass-through: fhat == u
        np.testing.assert_array_equal(res["fhat"][1], res["u"][1])

    def test_u_head_applies_truncation_mask(self):
        """Serving u must equal training u (monitor_score's Eq. 8
        truncation), not the full-basis head the seed served."""
        from repro.models import api as model_api
        cfg, params, stream = self._setup(batch=2, length=8)
        eng = CollaborativeEngine(params, cfg, batch=2, max_len=16,
                                  monitor_n=cfg.monitor.n_features // 2)
        res = run_sync(eng, stream)
        # training-side reference with the same truncation
        m = cfg.monitor
        from repro.nn.module import linear
        eout = model_api.forward(params["edge"], deco.edge_arch(cfg),
                                 {"tokens": jnp.asarray(stream)})
        feats = jnp.tanh(linear(params["u_head"]["w_feat"],
                                eout["hidden"].astype(jnp.float32)))
        mask = (jnp.arange(feats.shape[-1]) < m.n_features // 2).astype(jnp.float32)
        t = jax.nn.softplus(params["u_head"]["raw_t"])
        u_train = feats @ (params["u_head"]["a"] * mask) + t
        np.testing.assert_allclose(res["u"], np.asarray(u_train),
                                   atol=2e-3, rtol=2e-3)
        # and with a truncated n the serving scores differ from full-basis
        eng_full = CollaborativeEngine(params, cfg, batch=2, max_len=16)
        res_full = run_sync(eng_full, stream)
        assert not np.allclose(res["u"], res_full["u"])


class TestAsyncPipelinedEngine:
    """The pipelined online path (serving/async_rpc.py): strict-sync
    fallback bit-identity, staleness-independent monitor path, one-step-late
    merge semantics, and comms/server state consistency."""

    def _setup(self, threshold=0.1, batch=3, length=16):
        cfg = registry.get_smoke("granite-8b")
        cfg = cfg.replace(monitor=cfg.monitor.__class__(
            **{**cfg.monitor.__dict__, "threshold": threshold,
               "trigger_margin": 0.0}))
        params = deco.init_collab_lm(KEY, cfg)
        stream = next(tok.lm_batches(0, cfg, batch, length))["tokens"]
        return cfg, params, stream

    def test_sync_fallback_bit_identical_to_run(self):
        """max_staleness=0 is the strict synchronous engine: same traces,
        same comms, same server cache — bit for bit."""
        cfg, params, stream = self._setup()
        B = stream.shape[0]
        sync = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        r1 = run_sync(sync, stream)
        a = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        r0 = run_async(a, stream, transport="inproc", max_staleness=0)
        assert 0.0 < r1["triggered"].mean() < 1.0, "need mixed triggers"
        np.testing.assert_array_equal(r0["u"], r1["u"])
        np.testing.assert_array_equal(r0["fhat"], r1["fhat"])
        np.testing.assert_array_equal(r0["triggered"], r1["triggered"])
        assert r0["comms"]["bytes_sent"] == r1["comms"]["bytes_sent"]
        assert r0["comms"]["trigger_rate"] == r1["comms"]["trigger_rate"]
        np.testing.assert_array_equal(
            r0["comms"]["per_stream"]["bytes_sent"],
            r1["comms"]["per_stream"]["bytes_sent"])
        np.testing.assert_array_equal(a.server_pos, sync.server_pos)
        for x, y in zip(jax.tree.leaves(a.server.cache),
                        jax.tree.leaves(sync.server.cache)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_sync_fallback_matches_run_scan(self):
        """Acceptance: max_staleness=0 vs the offline scan — u/trigger
        bit-identical; fhat to vmap-vs-batch matmul rounding (the same
        tolerance the sync engine itself is held to vs the scan)."""
        cfg, params, stream = self._setup()
        B = stream.shape[0]
        scan = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        rs = run_scan(scan, stream)
        a = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        r0 = run_async(a, stream, transport="inproc", max_staleness=0)
        np.testing.assert_array_equal(r0["u"], rs["u"])
        np.testing.assert_array_equal(r0["triggered"], rs["triggered"])
        np.testing.assert_allclose(r0["fhat"], rs["fhat"], atol=1e-6)

    @settings(max_examples=5, deadline=None)
    @given(staleness=st.integers(min_value=0, max_value=3),
           threshold=st.floats(min_value=-0.3, max_value=0.3))
    def test_monitor_path_staleness_independent(self, staleness, threshold):
        """Property (safety): u and the trigger trace NEVER depend on the
        staleness window — the monitor path does not wait on the server —
        and corrections only ever lower fhat below u."""
        cfg, params, stream = self._setup(threshold=threshold, batch=2,
                                          length=8)
        scan = CollaborativeEngine(params, cfg, batch=2, max_len=16)
        rs = run_scan(scan, stream)
        a = CollaborativeEngine(params, cfg, batch=2, max_len=16)
        ra = run_async(a, stream, transport="inproc", max_staleness=staleness)
        np.testing.assert_array_equal(ra["u"], rs["u"])
        np.testing.assert_array_equal(ra["triggered"], rs["triggered"])
        assert bool(np.all(ra["fhat"] <= ra["u"] + 1e-6))

    def test_corrections_merge_one_step_late(self):
        """Pipelined semantics: with an always-triggering monitor the
        correction computed for step t lands in fhat at step t+1 (applied
        to step t+1's u); step 0 reports the uncorrected u."""
        cfg, params, stream = self._setup(threshold=0.5, batch=2, length=10)
        stub = jax.jit(lambda p, h: jnp.ones(h.shape[0], jnp.float32))
        sync = CollaborativeEngine(params, cfg, batch=2, max_len=16)
        sync._u_head = stub
        r1 = run_sync(sync, stream)
        assert r1["triggered"].all()
        corr_sync = r1["u"] - r1["fhat"]  # s*sigma(v_t) per step
        assert (corr_sync > 0).any(), "corrector must actually fire"

        a = CollaborativeEngine(params, cfg, batch=2, max_len=16)
        a._u_head = stub
        ra = run_async(a, stream, transport="inproc", max_staleness=2)
        assert ra["triggered"].all()
        # step 0: no reply merged yet -> monitor-only report
        np.testing.assert_array_equal(ra["fhat"][:, 0], ra["u"][:, 0])
        # step t>=1: yesterday's corrector applied to today's u
        np.testing.assert_allclose(
            ra["fhat"][:, 1:], ra["u"][:, 1:] - corr_sync[:, :-1], atol=1e-6)

    def test_async_transports_agree_and_comms_invariants(self):
        """stream/thread/mock_remote transports under simulated latency:
        identical monitor traces, identical shipped bytes (charged at
        dispatch, so staleness-independent), bytes invariant, clean
        in-flight teardown, and the final server cache matches the
        synchronous engine's."""
        cfg, params, stream = self._setup()
        B = stream.shape[0]
        sync = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        r1 = run_sync(sync, stream)
        for transport, latency in (("stream", 0.003), ("thread", 0.003),
                                   ("mock_remote", 0.003)):
            a = CollaborativeEngine(params, cfg, batch=B, max_len=32)
            ra = run_async(a, stream, transport=transport, latency_s=latency,
                           max_staleness=4)
            np.testing.assert_array_equal(ra["u"], r1["u"])
            np.testing.assert_array_equal(ra["triggered"], r1["triggered"])
            assert bool(np.all(ra["fhat"] <= ra["u"] + 1e-6))
            rep = ra["comms"]
            assert rep["bytes_sent"] == r1["comms"]["bytes_sent"]
            assert rep["bytes_sent"] <= rep["bytes_baseline"]
            per = rep["per_stream"]
            assert (per["bytes_sent"] <= per["bytes_baseline"]).all()
            assert rep["async"]["requests"] > 0
            assert rep["async"]["inflight_now"] == 0
            np.testing.assert_array_equal(a.server_pos, sync.server_pos)
            for x, y in zip(jax.tree.leaves(a.server.cache),
                            jax.tree.leaves(sync.server.cache)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_staleness_bound_is_enforced(self):
        """No reply merges later than max_staleness steps after its
        trigger, and in pipelined mode none merges in-step (ages 1..k)."""
        cfg, params, stream = self._setup(batch=2, length=12)
        for k in (1, 3):
            a = CollaborativeEngine(params, cfg, batch=2, max_len=16)
            ages = []
            orig = a.comms.record_merge
            a.comms.record_merge = lambda m, age: (ages.append(age),
                                                   orig(m, age))
            run_async(a, stream, transport="inproc", max_staleness=k)
            assert ages, "must have merged something"
            assert all(1 <= g <= k for g in ages)

    def test_no_trigger_means_no_async_traffic(self):
        cfg, params, stream = self._setup(threshold=1e9)
        B = stream.shape[0]
        a = CollaborativeEngine(params, cfg, batch=B, max_len=32)
        ra = run_async(a, stream, transport="stream", max_staleness=4)
        assert ra["triggered"].sum() == 0
        assert ra["comms"]["bytes_sent"] == 0
        assert "async" not in ra["comms"], "no requests -> no async section"
        assert a.server.pos == 0, "server cache must stay cold"
        np.testing.assert_allclose(ra["fhat"], ra["u"])
