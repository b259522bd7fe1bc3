"""Shared backbone scaffolding: transformer blocks, stacked-layer scans.

Every backbone is a pair of pure functions over a param tree; layer stacks
are ``lax.scan`` over parameters stacked on a leading layer axis (keeps the
HLO size O(1 layer) — essential for the 40-pair dry-run) with optional
``jax.checkpoint`` remat.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.nn.attention import (KVCache, MLACache, cross_attn, gqa_decode,
                                gqa_prefill, init_gqa, init_mla, mla_decode,
                                mla_prefill)
from repro.nn.mlp import init_swiglu, swiglu
from repro.nn.moe import init_moe, moe_dispatch
from repro.nn.module import Params
from repro.nn.norms import init_rmsnorm, rmsnorm


def cdt(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def pdt(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def stack_init(init_fn: Callable, key, n: int) -> Params:
    return jax.vmap(init_fn)(jax.random.split(key, n))


def scan_layers(body: Callable, h, stacked: Params, *, remat: bool = True,
                init_aux=None, unroll: bool = False):
    """body(layer_params, h, aux) -> (h, aux); scans over the layer axis.
    ``unroll=True`` (dry-run accounting mode) fully unrolls the loop so
    cost_analysis counts every layer (it counts a while body once)."""
    f = jax.checkpoint(body) if remat else body

    def step(carry, lp):
        h, aux = carry
        h, aux = f(lp, h, aux)
        return (h, aux), None

    (h, aux), _ = jax.lax.scan(step, (h, init_aux), stacked, unroll=unroll)
    return h, aux


def scan_layers_decode(body: Callable, h_t, stacked: Params, caches, pos,
                       unroll: bool = False):
    """body(layer_params, h_t, cache, pos) -> (h_t, new_cache)."""

    def step(h, xs):
        lp, cache = xs
        h, new_cache = body(lp, h, cache, pos)
        return h, new_cache

    return jax.lax.scan(step, h_t, (stacked, caches), unroll=unroll)


# ---------------------------------------------------------------------------
# Sequence parallelism (§Perf C1): the residual stream between the matmul
# regions is replicated over 'model' by default; constraining its SEQUENCE
# axis onto 'model' divides all norm/elementwise (and their backward/remat)
# HBM traffic by the model-axis size.  XLA inserts the all-gather before the
# attention/SSM mixers and turns the row-parallel all-reduce into
# reduce-scatter — equal collective volume.
# ---------------------------------------------------------------------------


def _sp_mesh(cfg: ArchConfig, h):
    if not cfg.seq_parallel or h.ndim != 3:
        return None
    try:
        mesh = jax.sharding.get_abstract_mesh()  # set by jax.set_mesh
        if "model" not in mesh.axis_names or mesh.shape["model"] <= 1:
            return None
        if h.shape[1] % mesh.shape["model"] != 0:
            return None
        return mesh
    except Exception:
        return None


def _batch_axes(mesh):
    ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not ax:
        return None
    return ax if len(ax) > 1 else ax[0]


def seq_shard(h: jnp.ndarray, cfg: ArchConfig):
    """Constrain (B, S, d) h to (batch_axes, 'model', None): seq axis onto
    'model', batch staying on the data axes.  A mixer OUTPUT constrained
    this way turns the megatron row-parallel all-reduce into a
    reduce-scatter."""
    mesh = _sp_mesh(cfg, h)
    if mesh is None:
        return h
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        h, P(_batch_axes(mesh), "model", None))


def seq_unshard(h: jnp.ndarray, cfg: ArchConfig):
    """All-gather of the seq axis (batch sharding preserved) before a mixer
    (attention / SSM scan) that needs the full sequence.  Without this the
    partitioner tries to run the mixer with a sharded seq axis (for the SSD
    chunk recurrence that degenerates badly)."""
    mesh = _sp_mesh(cfg, h)
    if mesh is None:
        return h
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        h, P(_batch_axes(mesh), None, None))


# ---------------------------------------------------------------------------
# Transformer block (attention + FFN); FFN is SwiGLU or MoE.
# ---------------------------------------------------------------------------


def init_attn(key, cfg: ArchConfig) -> Params:
    if cfg.use_mla:
        return init_mla(key, cfg.d_model, cfg.n_heads, q_lora=cfg.q_lora_rank,
                        kv_lora=cfg.kv_lora_rank, qk_nope=cfg.qk_nope_dim,
                        qk_rope=cfg.qk_rope_dim, v_dim=cfg.v_head_dim,
                        dtype=pdt(cfg))
    return init_gqa(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias, dtype=pdt(cfg))


def init_block(key, cfg: ArchConfig, *, moe: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "ln_attn": init_rmsnorm(cfg.d_model, pdt(cfg)),
        "attn": init_attn(ks[0], cfg),
        "ln_mlp": init_rmsnorm(cfg.d_model, pdt(cfg)),
    }
    if moe:
        p["moe"] = init_moe(ks[1], cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                            n_shared=cfg.n_shared_experts, dtype=pdt(cfg))
    else:
        p["mlp"] = init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dtype=pdt(cfg))
    return p


def _attn_prefill(p, h, cfg: ArchConfig, window: int, attn_fn=None):
    if attn_fn is None:
        if cfg.scan_unroll:
            import functools
            from repro.nn.attention import chunked_attention
            attn_fn = functools.partial(chunked_attention, unroll=True)
        else:
            from repro.kernels import ops
            if ops.get_impl() != "xla":  # Pallas flash kernel path
                attn_fn = ops.flash_attention
    kw = {} if attn_fn is None else {"attn_fn": attn_fn}
    from repro.nn.attention import kv_shard_ctx
    with kv_shard_ctx(cfg.prefill_kv_shard):
        if cfg.use_mla:
            return mla_prefill(p, h, n_heads=cfg.n_heads,
                               qk_nope=cfg.qk_nope_dim,
                               qk_rope=cfg.qk_rope_dim, v_dim=cfg.v_head_dim,
                               rope_theta=cfg.rope_theta, window=window,
                               compute_dtype=cdt(cfg), **kw)
        return gqa_prefill(p, h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                           head_dim=cfg.resolved_head_dim,
                           rope_theta=cfg.rope_theta,
                           window=window, compute_dtype=cdt(cfg), **kw)


def block_prefill(p: Params, h: jnp.ndarray, cfg: ArchConfig, *,
                  moe: bool = False, window: int = 0,
                  attn_fn=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (h, aux_loss)."""
    h = seq_shard(h, cfg)
    hn = seq_unshard(rmsnorm(p["ln_attn"], h, cfg.norm_eps), cfg)
    a = _attn_prefill(p["attn"], hn, cfg, window, attn_fn)
    h = h + seq_shard(a, cfg)
    hn = seq_unshard(rmsnorm(p["ln_mlp"], h, cfg.norm_eps), cfg)
    if moe:
        m, aux = moe_dispatch(p["moe"], hn, n_experts=cfg.n_experts,
                              top_k=cfg.top_k,
                              capacity_factor=cfg.capacity_factor,
                              compute_dtype=cdt(cfg), impl=cfg.moe_impl)
    else:
        m, aux = swiglu(p["mlp"], hn, compute_dtype=cdt(cfg)), jnp.zeros((), jnp.float32)
    return h + seq_shard(m, cfg), aux


def block_decode(p: Params, h: jnp.ndarray, cache, pos, cfg: ArchConfig, *,
                 moe: bool = False, window: int = 0):
    hn = rmsnorm(p["ln_attn"], h, cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = mla_decode(p["attn"], hn, cache, pos, n_heads=cfg.n_heads,
                                  qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim,
                                  v_dim=cfg.v_head_dim, kv_lora=cfg.kv_lora_rank,
                                  rope_theta=cfg.rope_theta, compute_dtype=cdt(cfg))
    else:
        a, new_cache = gqa_decode(p["attn"], hn, cache, pos, n_heads=cfg.n_heads,
                                  n_kv=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
                                  rope_theta=cfg.rope_theta, window=window,
                                  compute_dtype=cdt(cfg))
    h = h + a
    hn = rmsnorm(p["ln_mlp"], h, cfg.norm_eps)
    if moe:
        m, _ = moe_dispatch(p["moe"], hn[:, None, :], n_experts=cfg.n_experts,
                            top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                            compute_dtype=cdt(cfg), impl=cfg.moe_impl)
        m = m[:, 0]
    else:
        m = swiglu(p["mlp"], hn, compute_dtype=cdt(cfg))
    return h + m, new_cache


def init_kv_cache(cfg: ArchConfig, batch: int, capacity: int):
    if cfg.use_mla:
        return MLACache(
            ckv=jnp.zeros((batch, capacity, cfg.kv_lora_rank), cdt(cfg)),
            krope=jnp.zeros((batch, capacity, cfg.qk_rope_dim), cdt(cfg)))
    return KVCache(
        k=jnp.zeros((batch, capacity, cfg.n_kv_heads, cfg.resolved_head_dim), cdt(cfg)),
        v=jnp.zeros((batch, capacity, cfg.n_kv_heads, cfg.resolved_head_dim), cdt(cfg)))


LONG_CONTEXT_THRESHOLD = 65_536  # beyond this, full-attention archs switch
                                 # to their swa-variant ring cache (DESIGN.md §5)


def decode_capacity(cfg: ArchConfig, seq_len: int) -> int:
    """Cache capacity for a decode shape: the long_500k swa-variant caps the
    window for full-attention archs (DESIGN.md §5)."""
    if cfg.sliding_window:
        return min(seq_len, cfg.sliding_window)
    if cfg.long_context_window and seq_len > LONG_CONTEXT_THRESHOLD:
        return cfg.long_context_window
    return seq_len


def decode_window(cfg: ArchConfig, seq_len: int) -> int:
    cap = decode_capacity(cfg, seq_len)
    return cap if cap < seq_len else (cfg.sliding_window or 0)
