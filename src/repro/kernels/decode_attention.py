"""Pallas TPU single-query (decode) attention over a KV cache.

This is the bandwidth-bound op of decode_32k / long_500k: every step streams
the whole (C, Hkv, D) cache from HBM through VMEM once.  Tiling: grid =
(B, Hkv, C/bk) with the cache axis sequential; all G query heads of a KV
group are processed together so the cache block is read once per group
(GQA's arithmetic-intensity advantage, made explicit).  Ring-buffer (SWA)
caches mask by slot validity instead of position order.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, bk: int, nk: int, G: int, scale: float, window: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)       # (G, D)
    kb = k_ref[0, :, 0].astype(jnp.float32)   # (bk, D)
    vb = v_ref[0, :, 0].astype(jnp.float32)   # (bk, Dv)
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ()))) * scale  # (G, bk)
    col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    C = nk * bk
    if window:  # ring buffer: slots < min(pos+1, C) hold real entries
        valid = col < jnp.minimum(pos + 1, C)
    else:
        valid = col <= pos
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ vb
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     pos, *, window: int = 0, bk: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, D); caches: (B, C, Hkv, D); pos: scalar -> (B, Hq, Dv)."""
    B, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    bk = min(bk, C)
    assert C % bk == 0
    nk = C // bk
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, bk=bk, nk=nk, G=G, scale=scale,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, ik: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, ik: (b, ik, h, 0)),
            pl.BlockSpec((1, bk, 1, Dv), lambda b, h, ik: (b, ik, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, Dv), jnp.float32),
                        pltpu.VMEM((G,), jnp.float32),
                        pltpu.VMEM((G,), jnp.float32)],
        interpret=interpret,
    )(pos_arr, qg, k_cache, v_cache)
    return out.reshape(B, Hq, Dv)
