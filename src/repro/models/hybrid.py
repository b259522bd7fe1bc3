"""Zamba2-style hybrid backbone (arXiv:2411.15242): a stack of Mamba2 blocks
with ONE shared attention+MLP transformer block applied every
``shared_attn_every`` Mamba2 blocks (parameters reused at every invocation —
the arch's signature trick; we omit the per-invocation LoRA deltas and note
this in DESIGN.md).

Layout: n_super = n_layers // k super-blocks of (k mamba layers + shared
block invocation), then (n_layers mod k) tail mamba layers.  Each shared
invocation keeps its own KV cache at decode time (params shared, state not).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.base import (block_decode, block_prefill, cdt, decode_capacity,
                               init_block, init_kv_cache, pdt, recurrent_step,
                               scan_layers, scan_layers_decode, stack_init)
from repro.nn.embedding import embed, init_embedding, unembed
from repro.nn.module import Params, rowwise
from repro.nn.norms import init_rmsnorm, rmsnorm
from repro.nn.ssm import (init_mamba2, init_ssm_cache, mamba2_decode,
                          mamba2_prefill)


def _layout(cfg: ArchConfig) -> Tuple[int, int, int]:
    k = cfg.shared_attn_every or cfg.n_layers
    n_super = cfg.n_layers // k
    tail = cfg.n_layers - n_super * k
    return n_super, k, tail


def _init_mamba_block(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 2)
    return {"ln": init_rmsnorm(cfg.d_model, pdt(cfg)),
            "mamba": init_mamba2(ks[0], cfg.d_model, expand=cfg.ssm_expand,
                                 state=cfg.ssm_state, conv_k=cfg.ssm_conv,
                                 dtype=pdt(cfg))}


def init_lm(key, cfg: ArchConfig) -> Params:
    n_super, k, tail = _layout(cfg)
    ks = jax.random.split(key, 6)
    p: Params = {
        "embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model, pdt(cfg)),
        "ln_f": init_rmsnorm(cfg.d_model, pdt(cfg)),
        "mamba_blocks": stack_init(
            lambda kk: stack_init(lambda k2: _init_mamba_block(k2, cfg), kk, k),
            ks[1], n_super),
        "shared": init_block(ks[2], cfg),  # ONE param set, reused n_super times
    }
    if not cfg.tie_embeddings:
        p["unembed"] = init_embedding(ks[3], cfg.vocab_size, cfg.d_model, pdt(cfg))
    if tail:
        p["tail"] = stack_init(lambda k2: _init_mamba_block(k2, cfg), ks[4], tail)
    return p


def _mamba_fwd(lp, h, cfg: ArchConfig, scan_fn=None):
    if scan_fn is None:
        if cfg.scan_unroll:
            import functools
            from repro.nn.ssm import ssd_chunked
            scan_fn = functools.partial(ssd_chunked, unroll=True)
        else:
            from repro.kernels import ops
            if ops.get_impl() != "xla":  # Pallas SSD kernel path
                scan_fn = ops.ssd_scan
    kw = {} if scan_fn is None else {"scan_fn": scan_fn}
    from repro.models.base import seq_shard, seq_unshard
    h = seq_shard(h, cfg)
    hn = seq_unshard(rmsnorm(lp["ln"], h, cfg.norm_eps), cfg)
    y = mamba2_prefill(lp["mamba"], hn, expand=cfg.ssm_expand,
                       state=cfg.ssm_state, conv_k=cfg.ssm_conv,
                       chunk=cfg.ssm_chunk, compute_dtype=cdt(cfg), **kw)
    return h + seq_shard(y, cfg)


def forward(params: Params, cfg: ArchConfig, batch: Dict, *,
            attn_fn=None, ssm_scan_fn=None) -> Dict[str, jnp.ndarray]:
    n_super, k, tail = _layout(cfg)
    h = embed(params["embed"], batch["tokens"], cdt(cfg))
    shared = params["shared"]

    def super_body(lp, h, aux):
        def inner(mlp_, h, aux):
            return _mamba_fwd(mlp_, h, cfg, ssm_scan_fn), aux
        h, aux = scan_layers(inner, h, lp, remat=False, init_aux=aux,
                             unroll=cfg.scan_unroll)
        h, a = block_prefill(shared, h, cfg, attn_fn=attn_fn)
        return h, aux + a

    aux0 = jnp.zeros((), jnp.float32)
    h, aux = scan_layers(super_body, h, params["mamba_blocks"],
                         remat=cfg.remat, init_aux=aux0,
                         unroll=cfg.scan_unroll)
    if tail:
        def body(lp, h, aux):
            return _mamba_fwd(lp, h, cfg, ssm_scan_fn), aux
        h, aux = scan_layers(body, h, params["tail"], remat=cfg.remat,
                             init_aux=aux, unroll=cfg.scan_unroll)

    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    tab = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return {"hidden": h, "logits": unembed(tab, h, cdt(cfg)), "aux_loss": aux}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, seq_len: int, image_tokens: int = 0):
    n_super, k, tail = _layout(cfg)
    cap = decode_capacity(cfg, seq_len)
    ssm = init_ssm_cache(batch, cfg.d_model, expand=cfg.ssm_expand,
                         state=cfg.ssm_state, conv_k=cfg.ssm_conv)

    def stack(n, tree):
        return jax.tree.map(lambda l: jnp.broadcast_to(l, (n,) + l.shape), tree)

    return {
        "ssm": stack(n_super, stack(k, ssm)),
        "ssm_tail": stack(tail, ssm) if tail else None,
        "attn": stack(n_super, init_kv_cache(cfg, batch, cap)),
    }


def decode_step(params: Params, cfg: ArchConfig, cache, tokens_t, pos, *,
                active=None, with_logits: bool = True):
    """One decode step; ``pos`` and ``active`` as in
    ``transformer.decode_step``."""
    n_super, k, tail = _layout(cfg)
    h = rowwise(lambda _, t: embed(params["embed"], t, cdt(cfg)), pos)(tokens_t)
    shared = params["shared"]
    cap = cache["attn"].k.shape[2]
    win = cap if cfg.long_context_window else 0

    def mamba_body(lp, h, caches, at, pos):
        def step(_, h, c):
            y, nc = mamba2_decode(lp["mamba"], rmsnorm(lp["ln"], h, cfg.norm_eps),
                                  c, expand=cfg.ssm_expand, state=cfg.ssm_state,
                                  conv_k=cfg.ssm_conv, compute_dtype=cdt(cfg))
            return h + y, nc

        return recurrent_step(step, h, caches, at, pos, active)

    def super_body(carry, xs):
        h, ssm, attn = carry
        lp, s = xs
        h, ssm = scan_layers_decode(mamba_body, h, lp, ssm, pos, at=(s,),
                                    unroll=cfg.scan_unroll)
        h, attn = block_decode(shared, h, attn, pos, cfg, window=win, at=(s,),
                               active=active)
        return (h, ssm, attn), None

    (h, new_ssm, new_attn), _ = jax.lax.scan(
        super_body, (h, cache["ssm"], cache["attn"]),
        (params["mamba_blocks"], jnp.arange(n_super)), unroll=cfg.scan_unroll)
    new_tail = None
    if tail:
        h, new_tail = scan_layers_decode(mamba_body, h, params["tail"],
                                         cache["ssm_tail"], pos,
                                         unroll=cfg.scan_unroll)
    def head(_, h):
        h = rmsnorm(params["ln_f"], h[:, None], cfg.norm_eps)[:, 0]
        tab = params["embed"] if cfg.tie_embeddings else params["unembed"]
        return (unembed(tab, h, cdt(cfg)) if with_logits else None), h

    logits, h = rowwise(head, pos)(h)
    return logits, h, {"ssm": new_ssm, "ssm_tail": new_tail, "attn": new_attn}
