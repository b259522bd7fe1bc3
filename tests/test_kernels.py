"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
kernels/ref.py, in the Pallas interpreter (``interpret=True``) on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.monitor_combine import monitor_combine
from repro.kernels.ssm_scan import ssd_scan
from repro.nn.attention import chunked_attention

KEY = jax.random.PRNGKey(0)


def rand(shape, dtype, k):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,Hq,Hkv,D,bq,bk,window", [
        (1, 128, 4, 4, 64, 64, 64, 0),       # MHA
        (2, 256, 8, 2, 64, 128, 64, 0),      # GQA
        (1, 256, 4, 1, 128, 64, 128, 0),     # MQA, wide head
        (2, 256, 4, 2, 32, 64, 64, 96),      # sliding window
        (1, 512, 2, 2, 64, 128, 128, 128),   # SWA block-aligned
    ])
    def test_vs_oracle(self, dtype, B, S, Hq, Hkv, D, bq, bk, window):
        q = rand((B, S, Hq, D), dtype, 1)
        k = rand((B, S, Hkv, D), dtype, 2)
        v = rand((B, S, Hkv, D), dtype, 3)
        out = flash_attention(q, k, v, causal=True, window=window, bq=bq, bk=bk,
                              interpret=True)
        ref = R.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=TOL[dtype], rtol=TOL[dtype])

    def test_chunked_xla_path_matches_oracle(self):
        q, k, v = (rand((2, 256, 8, 64), jnp.float32, i) for i in (1, 2, 3))
        out = chunked_attention(q, k, v, q_block=64, causal=True, window=100)
        ref = R.attention_ref(q, k, v, causal=True, window=100)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,Hq,Hkv,D,C,bk,pos,window", [
        (2, 8, 2, 64, 512, 128, 100, 0),
        (1, 4, 4, 128, 256, 256, 255, 0),
        (2, 8, 1, 64, 512, 64, 700, 512),   # ring buffer fully wrapped
        (1, 16, 2, 64, 1024, 256, 0, 0),    # first token
    ])
    def test_vs_oracle(self, dtype, B, Hq, Hkv, D, C, bk, pos, window):
        q = rand((B, Hq, D), dtype, 1)
        kc = rand((B, C, Hkv, D), dtype, 2)
        vc = rand((B, C, Hkv, D), dtype, 3)
        out = decode_attention(q, kc, vc, pos, window=window, bk=bk,
                               interpret=True)
        ref = R.decode_attention_ref(q, kc, vc, pos, window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=TOL[dtype], rtol=TOL[dtype])


class TestSSDScan:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (2, 256, 4, 32, 16, 64),
        (1, 128, 2, 64, 64, 128),   # single chunk
        (2, 512, 8, 16, 32, 32),    # many chunks
    ])
    def test_vs_sequential_oracle(self, B, S, H, P, N, chunk):
        x = 0.3 * rand((B, S, H, P), jnp.float32, 1)
        dt = jax.nn.softplus(rand((B, S, H), jnp.float32, 2))
        A = -jnp.exp(jnp.linspace(0.0, 1.0, H))
        Bm = 0.5 * rand((B, S, N), jnp.float32, 3)
        Cm = 0.5 * rand((B, S, N), jnp.float32, 4)
        xdt = x * dt[..., None]
        la = dt * A[None, None, :]
        out = ssd_scan(xdt, la, Bm, Cm, chunk=chunk, interpret=True)
        ref = R.ssd_ref(xdt, la, Bm, Cm)
        np.testing.assert_allclose(out, ref, atol=5e-5, rtol=5e-4)


class TestMonitorCombine:
    @given(n_blocks=st.integers(1, 4), s=st.floats(0.05, 2.0),
           thr=st.floats(-0.5, 0.5), seed=st.integers(0, 1000))
    @settings(max_examples=25, deadline=None)
    def test_vs_oracle(self, n_blocks, s, thr, seed):
        n = 256 * n_blocks
        k = jax.random.PRNGKey(seed)
        u, v, f = (jax.random.normal(jax.random.fold_in(k, i), (n,))
                   for i in range(3))
        fh, m, c = monitor_combine(u, v, f, s=s, threshold=thr, block=256,
                                   interpret=True)
        fr, mr, cr = R.monitor_combine_ref(u, v, f, s=s, threshold=thr)
        np.testing.assert_allclose(fh, fr, atol=1e-6)
        np.testing.assert_allclose(m, mr)
        np.testing.assert_allclose(c, cr)


class TestOpsDispatch:
    def test_xla_and_pallas_agree(self):
        from repro.kernels import ops
        q, k, v = (rand((1, 128, 4, 64), jnp.float32, i) for i in (1, 2, 3))
        ops.set_impl("xla")
        a = ops.flash_attention(q, k, v)
        ops.set_impl("pallas_interpret")
        b = ops.flash_attention(q, k, v)
        ops.set_impl(None)
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("arch", ["granite-8b", "zamba2-7b",
                                      "mixtral-8x22b"])
    def test_model_forward_same_under_kernel_impl(self, arch):
        """Whole-model forward: Pallas kernel path == XLA path."""
        from repro.configs import registry
        from repro.configs.base import ShapeConfig
        from repro.kernels import ops
        from repro.models import api as model_api
        cfg = registry.get_smoke(arch)
        params = model_api.init_model(KEY, cfg)
        batch = model_api.sample_batch(KEY, cfg,
                                       ShapeConfig("t", 32, 2, "train"))
        try:
            ops.set_impl("xla")
            a = model_api.forward(params, cfg, batch)["logits"]
            ops.set_impl("pallas_interpret")
            b = model_api.forward(params, cfg, batch)["logits"]
        finally:
            ops.set_impl(None)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


class TestKernelSelection:
    """The platform picks ``monitor_combine``'s kernel; interpret mode runs
    only on request, and a compiled kernel is never silently interpreted."""

    def test_combine_follows_platform(self):
        from repro.kernels import ops
        want = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert ops.combine_impl() == want
        assert ops.get_impl() == "xla"  # attention / SSD: XLA until A4

    def test_pallas_off_tpu_raises(self):
        from repro.kernels import ops
        u = jnp.zeros((8,), jnp.float32)
        try:
            ops.set_impl("pallas")
            if jax.default_backend() == "tpu":
                ops.monitor_combine(u, u, u, s=0.2)
            else:
                with pytest.raises(RuntimeError, match="pallas_interpret"):
                    ops.monitor_combine(u, u, u, s=0.2)
        finally:
            ops.set_impl(None)

    def test_interpret_on_request_matches_xla(self):
        from repro.kernels import ops
        k = jax.random.PRNGKey(3)
        u, v, f = (jax.random.normal(jax.random.fold_in(k, i), (300,))
                   for i in range(3))
        try:
            ops.set_impl("pallas_interpret")
            a = ops.monitor_combine(u, v, f, s=0.2)
        finally:
            ops.set_impl(None)
        b = R.monitor_combine_ref(u, v, f, s=0.2)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-6)

    def test_unknown_impl_refused(self):
        from repro.kernels import ops
        with pytest.raises(ValueError):
            ops.set_impl("cuda")
