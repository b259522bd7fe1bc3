"""Attention: GQA (llama/qwen/granite family), sliding-window, MLA (DeepSeek),
cross-attention (VLM), with separate prefill and single-token decode paths.

Prefill uses a query-block-chunked implementation (lax.scan over query
blocks) so the S x T score matrix is never materialised — this is the
XLA fallback matching the Pallas flash kernel in kernels/flash_attention.py
(dispatch happens in kernels/ops.py).

KV caches are fixed-capacity ring-free buffers: (B, S_max, n_kv, hd) with a
scalar fill pointer; decode writes at ``pos`` and masks entries >= pos+1.
Sliding-window decode uses a modular ring buffer of capacity ``window``.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.nn.module import Params, init_linear, linear
from repro.nn.rotary import apply_rope

NEG_INF = -1e30


import threading

_KV_SHARD = threading.local()  # prefill KV-sharding mode, set per block


class kv_shard_ctx:
    """Scope the prefill KV time-sharding mode ("none" | "time").

    §Perf D1 measured this lever as arch-dependent: it collapses
    qwen2.5-32b's pathological prefill collective 6.2× but REGRESSES archs
    whose propagation was already healthy (granite/llama-vision/mixtral:
    ~2× memory) — so it is opt-in per arch via cfg.prefill_kv_shard, and
    the paper's edge/monitor tower always runs "none" (device-local).
    """

    def __init__(self, mode: str):
        self.mode = mode

    def __enter__(self):
        self.prev = getattr(_KV_SHARD, "mode", "none")
        _KV_SHARD.mode = self.mode

    def __exit__(self, *a):
        _KV_SHARD.mode = self.prev


# backwards-compatible alias used by the monitor path
def kv_shard_optout():
    return kv_shard_ctx("none")


def _kv_time_shard(k: jnp.ndarray, v: jnp.ndarray):
    """§Perf D1: when kv-heads do NOT divide the 'model' axis, propagation
    shards K/V on head_dim and the score einsum contracts a sharded dim —
    SPMD then falls back to full rematerialisation (the same failure §Perf
    B1 fixed for decode).  Time-shard K/V instead: scores are local per
    time-shard; the softmax/output reductions are small.  No-op without an
    active mesh, with divisible kv-heads, or with an indivisible seq."""
    if getattr(_KV_SHARD, "mode", "none") != "time":
        return k, v
    try:
        mesh = jax.sharding.get_abstract_mesh()  # set by jax.set_mesh
        m = mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1
        if m <= 1 or k.shape[2] % m == 0 or k.shape[1] % m != 0:
            return k, v
        from jax.sharding import PartitionSpec as P
        daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        b = (daxes if len(daxes) > 1 else daxes[0]) if daxes else None
        spec = P(b, "model", None, None)
        return (jax.lax.with_sharding_constraint(k, spec),
                jax.lax.with_sharding_constraint(v, spec))
    except Exception:
        return k, v


# ---------------------------------------------------------------------------
# Core chunked attention (shared by prefill paths)
# ---------------------------------------------------------------------------


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      q_block: int = 1024, causal: bool = True,
                      window: int = 0, q_offset: int = 0,
                      unroll: bool = False) -> jnp.ndarray:
    """Blockwise attention. q:(B,S,Hq,D) k,v:(B,T,Hkv,D) -> (B,S,Hq,D).

    Scans over query blocks; each block computes scores against the full
    K/V (masked), so peak memory is O(q_block * T) instead of O(S * T).
    GQA is handled by grouping query heads over KV heads.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    k, v = _kv_time_shard(k, v)

    nblk = S // q_block if S % q_block == 0 else -1
    if nblk <= 0:  # odd sizes (tests): single block
        q_block, nblk = S, 1

    qb = q.reshape(B, nblk, q_block, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    col = jnp.arange(T)

    def body(_, inp):
        bi, qblk = inp  # qblk: (B, q_block, Hkv, G, D)
        row = q_offset + bi * q_block + jnp.arange(q_block)
        # bf16 operands + f32 accumulation (MXU-native); avoids materialising
        # f32 copies of K/V every scan iteration (§Perf hillclimb B2).
        s = jnp.einsum("bqkgd,btkd->bqkgt", qblk, k,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((q_block, T), bool)
        if causal:
            mask &= col[None, :] <= row[:, None]
        if window:
            mask &= col[None, :] > row[:, None] - window
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bqkgt,btkd->bqkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return None, o.astype(q.dtype)

    _, ob = jax.lax.scan(body, None, (jnp.arange(nblk), qb), unroll=unroll)
    return ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hq, Dv)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     pos: jnp.ndarray, *, window: int = 0) -> jnp.ndarray:
    """Single-query attention. q:(B,Hq,D), caches:(B,C,Hkv,D), pos scalar.

    Entries at index >= pos+1 (not yet written) are masked.  With a ring
    buffer (window > 0) every slot is valid once pos >= capacity.
    """
    B, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    # bf16 cache reads + f32 accumulation: no f32 cache copies (§Perf B2).
    s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(k_cache.dtype), k_cache,
                   preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(C)
    if window:
        valid = idx < jnp.minimum(pos + 1, C)
    else:
        valid = idx <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block (llama / qwen / granite / musicgen / zamba2-shared / mixtral)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, C, Hkv, D)
    v: jnp.ndarray


def init_gqa(key, d_model: int, n_heads: int, n_kv: int, head_dim: int, *,
             qkv_bias: bool = False, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d_model, n_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wk": init_linear(ks[1], d_model, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
        "wv": init_linear(ks[2], d_model, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
        "wo": init_linear(ks[3], n_heads * head_dim, d_model, bias=False, dtype=dtype),
    }


def gqa_prefill(p: Params, x: jnp.ndarray, *, n_heads: int, n_kv: int,
                head_dim: int, rope_theta: float = 1e4, window: int = 0,
                positions: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16, attn_fn=chunked_attention,
                return_kv: bool = False):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = linear(p["wq"], x, compute_dtype=compute_dtype).reshape(B, S, n_heads, head_dim)
    k = linear(p["wk"], x, compute_dtype=compute_dtype).reshape(B, S, n_kv, head_dim)
    v = linear(p["wv"], x, compute_dtype=compute_dtype).reshape(B, S, n_kv, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    o = attn_fn(q, k, v, causal=True, window=window)
    y = linear(p["wo"], o.reshape(B, S, n_heads * head_dim), compute_dtype=compute_dtype)
    if return_kv:
        return y, KVCache(k, v)
    return y


def gqa_decode(p: Params, x: jnp.ndarray, cache: KVCache, pos: jnp.ndarray, *,
               n_heads: int, n_kv: int, head_dim: int, rope_theta: float = 1e4,
               window: int = 0, compute_dtype=jnp.bfloat16):
    """x: (B, d_model) one token. Returns (y, new_cache)."""
    B = x.shape[0]
    C = cache.k.shape[1]
    q = linear(p["wq"], x, compute_dtype=compute_dtype).reshape(B, n_heads, head_dim)
    k = linear(p["wk"], x, compute_dtype=compute_dtype).reshape(B, n_kv, head_dim)
    v = linear(p["wv"], x, compute_dtype=compute_dtype).reshape(B, n_kv, head_dim)
    posb = jnp.full((B, 1), pos)
    q = apply_rope(q[:, None], posb, rope_theta)[:, 0]
    k = apply_rope(k[:, None], posb, rope_theta)[:, 0]
    slot = pos % C if window else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache.k, k[:, None].astype(cache.k.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache.v, v[:, None].astype(cache.v.dtype), slot, axis=1)
    o = decode_attention(q, k_cache, v_cache, pos, window=window)
    y = linear(p["wo"], o.reshape(B, n_heads * head_dim), compute_dtype=compute_dtype)
    return y, KVCache(k_cache, v_cache)


# ---------------------------------------------------------------------------
# Cross-attention (llama-3.2-vision image layers); no causal mask, no rope on kv
# ---------------------------------------------------------------------------


def cross_attn(p: Params, x: jnp.ndarray, kv_feats: jnp.ndarray, *,
               n_heads: int, n_kv: int, head_dim: int,
               compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    B, S, _ = x.shape
    T = kv_feats.shape[1]
    q = linear(p["wq"], x, compute_dtype=compute_dtype).reshape(B, S, n_heads, head_dim)
    k = linear(p["wk"], kv_feats, compute_dtype=compute_dtype).reshape(B, T, n_kv, head_dim)
    v = linear(p["wv"], kv_feats, compute_dtype=compute_dtype).reshape(B, T, n_kv, head_dim)
    o = chunked_attention(q, k, v, causal=False)
    return linear(p["wo"], o.reshape(B, S, n_heads * head_dim), compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V3).  The KV cache stores the
# compressed latent c_kv (kv_lora_rank) + decoupled rope key (qk_rope_dim):
# 576 floats/token instead of n_kv*head_dim*2 = 32768 — the paper-assigned
# arch's own long-context enabler.
# ---------------------------------------------------------------------------


class MLACache(NamedTuple):
    ckv: jnp.ndarray   # (B, C, kv_lora_rank)
    krope: jnp.ndarray  # (B, C, qk_rope_dim)


def init_mla(key, d_model: int, n_heads: int, *, q_lora: int, kv_lora: int,
             qk_nope: int, qk_rope: int, v_dim: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 6)
    return {
        "wq_a": init_linear(ks[0], d_model, q_lora, dtype=dtype),
        "wq_b": init_linear(ks[1], q_lora, n_heads * (qk_nope + qk_rope), dtype=dtype),
        "wkv_a": init_linear(ks[2], d_model, kv_lora + qk_rope, dtype=dtype),
        "wkv_b": init_linear(ks[3], kv_lora, n_heads * (qk_nope + v_dim), dtype=dtype),
        "wo": init_linear(ks[4], n_heads * v_dim, d_model, dtype=dtype),
    }


def _mla_qkv(p, x, *, n_heads, qk_nope, qk_rope, v_dim, positions, rope_theta,
             compute_dtype):
    B, S, _ = x.shape
    q = linear(p["wq_b"], linear(p["wq_a"], x, compute_dtype=compute_dtype),
               compute_dtype=compute_dtype).reshape(B, S, n_heads, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope, positions, rope_theta)
    kv_a = linear(p["wkv_a"], x, compute_dtype=compute_dtype)
    ckv, k_rope = kv_a[..., :-qk_rope], kv_a[..., -qk_rope:]
    k_rope = apply_rope(k_rope[:, :, None], positions, rope_theta)  # (B,S,1,r)
    kv = linear(p["wkv_b"], ckv, compute_dtype=compute_dtype).reshape(
        B, S, n_heads, qk_nope + v_dim)
    k_nope, v = kv[..., :qk_nope], kv[..., qk_nope:]
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, n_heads, qk_rope))], axis=-1)
    return q_full, k_full, v, ckv, k_rope[:, :, 0]


def mla_prefill(p: Params, x: jnp.ndarray, *, n_heads: int, qk_nope: int,
                qk_rope: int, v_dim: int, rope_theta: float = 1e4,
                positions: Optional[jnp.ndarray] = None, window: int = 0,
                compute_dtype=jnp.bfloat16, attn_fn=chunked_attention):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v, _, _ = _mla_qkv(p, x, n_heads=n_heads, qk_nope=qk_nope,
                             qk_rope=qk_rope, v_dim=v_dim, positions=positions,
                             rope_theta=rope_theta, compute_dtype=compute_dtype)
    o = attn_fn(q, k, v, causal=True, window=window)
    return linear(p["wo"], o.reshape(B, S, n_heads * v_dim), compute_dtype=compute_dtype)


def mla_decode(p: Params, x: jnp.ndarray, cache: MLACache, pos: jnp.ndarray, *,
               n_heads: int, qk_nope: int, qk_rope: int, v_dim: int,
               kv_lora: int, rope_theta: float = 1e4,
               compute_dtype=jnp.bfloat16):
    """Latent-cache decode: attention runs in the compressed space.

    Uses the absorbed-matmul trick: q_nope is mapped through W^kv_b's key half
    so scores are computed directly against the cached latents.
    """
    B = x.shape[0]
    C = cache.ckv.shape[1]
    posb = jnp.full((B, 1), pos)
    q = linear(p["wq_b"], linear(p["wq_a"], x, compute_dtype=compute_dtype),
               compute_dtype=compute_dtype).reshape(B, n_heads, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope[:, None], posb, rope_theta)[:, 0]
    kv_a = linear(p["wkv_a"], x, compute_dtype=compute_dtype)
    ckv_t, krope_t = kv_a[..., :-qk_rope], kv_a[..., -qk_rope:]
    krope_t = apply_rope(krope_t[:, None, None], posb, rope_theta)[:, 0, 0]
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache.ckv, ckv_t[:, None].astype(cache.ckv.dtype), pos, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache.krope, krope_t[:, None].astype(cache.krope.dtype), pos, axis=1)
    # Absorb: W^kv_b = [W_k (kv_lora -> H*qk_nope); W_v (kv_lora -> H*v_dim)]
    wkv = p["wkv_b"]["w"].astype(compute_dtype).reshape(kv_lora, n_heads, qk_nope + v_dim)
    wk, wv = wkv[..., :qk_nope], wkv[..., qk_nope:]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(jnp.float32),
                       wk.astype(jnp.float32))  # (B,H,kv_lora)
    scale = 1.0 / math.sqrt(qk_nope + qk_rope)
    # bf16 latent-cache reads + f32 accumulation (§Perf B2): never
    # materialise an f32 copy of the (B, C, kv_lora) cache.
    s = (jnp.einsum("bhr,btr->bht", q_lat.astype(ckv.dtype), ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,btr->bht", q_rope.astype(krope.dtype), krope,
                      preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(C) <= pos
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bht,btr->bhr", prob.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)  # (B,H,r)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wv.astype(jnp.float32))
    y = linear(p["wo"], o.reshape(B, n_heads * v_dim).astype(compute_dtype),
               compute_dtype=compute_dtype)
    return y, MLACache(ckv, krope)
