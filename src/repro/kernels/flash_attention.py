"""Pallas TPU flash attention (prefill): online-softmax over KV blocks with
grid-sequential accumulation.

Tiling: grid = (B, Hq, S/bq, T/bk); the last (kv) axis is sequential, with
running (m, l, acc) carried in VMEM scratch.  Block shapes are 128-aligned
on the MXU contraction dims.  GQA is handled in the K/V index maps
(h -> h // group).  Causal + sliding-window masks are applied with global
row/col iota; KV blocks strictly above the causal diagonal are skipped
entirely (``pl.when``), halving work for causal prefill.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, nk: int, scale: float, causal: bool,
                  window: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip blocks strictly above the causal diagonal / outside the window
    row0, col0 = iq * bq, ik * bk
    needed = jnp.asarray(True)
    if causal:
        needed = needed & (col0 <= row0 + bq - 1)
    if window:
        needed = needed & (col0 + bk - 1 > row0 - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)   # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)   # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)   # (bk, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = mask & (col <= row)
        if window:
            mask = mask & (col > row - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # p must be explicitly re-masked: rows with no unmasked entry yet
        # have m_new == NEG_INF and exp(s - m_new) == 1 on masked entries.
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, Hq, D); k, v: (B, T, Hkv, D) -> (B, S, Hq, Dv)."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    bq, bk = min(bq, S), min(bk, T)
    assert S % bq == 0 and T % bk == 0, "seq lens must tile"
    nq, nk = S // bq, T // bk
    scale = 1.0 / math.sqrt(D)

    qt = q.transpose(0, 2, 1, 3)  # (B, Hq, S, D)
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, T, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                               causal=causal, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, Dv), q.dtype),
        scratch_shapes=[_vmem((bq, Dv)), _vmem((bq,)), _vmem((bq,))],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)
