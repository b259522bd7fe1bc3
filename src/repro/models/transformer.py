"""Generic decoder-only backbone covering the dense / moe / vlm / audio
families (granite, qwen*, deepseek-v3, mixtral, llama-3.2-vision, musicgen).

Structure per family:
  dense  : embed -> scan(L x block) -> norm -> unembed
  moe    : embed -> scan(first_dense x dense block) -> scan(rest x moe block)
           [-> MTP head if cfg.mtp_depth > 0 (DeepSeek-V3)]
  vlm    : embed -> scan(n_super x [per_super self blocks + 1 cross block])
           cross blocks attend to stub-provided image patch embeddings
  audio  : sum-of-codebook embed -> dense stack -> per-codebook heads
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.base import (block_decode, block_prefill, cdt, decode_window,
                               init_block, init_kv_cache, pdt, scan_layers,
                               scan_layers_decode, stack_init)
from repro.nn.attention import cross_attn, init_gqa
from repro.nn.embedding import (codebook_embed, codebook_unembed, embed,
                                init_codebook_embedding, init_embedding,
                                unembed)
from repro.nn.module import Params, init_linear, linear, rowwise
from repro.nn.norms import init_rmsnorm, rmsnorm


def _layer_layout(cfg: ArchConfig) -> Dict[str, int]:
    if cfg.family == "vlm" and cfg.cross_attn_every:
        n_super = cfg.n_layers // cfg.cross_attn_every
        tail = cfg.n_layers - n_super * cfg.cross_attn_every
        return {"kind": "vlm", "n_super": n_super,
                "per_super": cfg.cross_attn_every, "tail": tail}
    if cfg.is_moe:
        return {"kind": "moe", "dense": cfg.first_dense_layers,
                "moe": cfg.n_layers - cfg.first_dense_layers}
    return {"kind": "dense", "dense": cfg.n_layers}


def init_lm(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 8)
    lay = _layer_layout(cfg)
    p: Params = {"ln_f": init_rmsnorm(cfg.d_model, pdt(cfg))}
    if cfg.family == "audio":
        p["embed"] = init_codebook_embedding(ks[0], cfg.n_codebooks,
                                             cfg.vocab_size, cfg.d_model, pdt(cfg))
    else:
        p["embed"] = init_embedding(ks[0], cfg.vocab_size, cfg.d_model, pdt(cfg))
        if not cfg.tie_embeddings:
            p["unembed"] = init_embedding(ks[1], cfg.vocab_size, cfg.d_model, pdt(cfg))

    if lay["kind"] == "vlm":
        p["blocks"] = stack_init(
            lambda k: stack_init(lambda k2: init_block(k2, cfg), k, lay["per_super"]),
            ks[2], lay["n_super"])
        p["cross"] = stack_init(
            lambda k: {
                "ln": init_rmsnorm(cfg.d_model, pdt(cfg)),
                "attn": init_gqa(k, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.resolved_head_dim, dtype=pdt(cfg)),
                "gate": jnp.zeros((1,), jnp.float32),  # tanh-gated (llama-3.2)
            }, ks[3], lay["n_super"])
        if lay["tail"]:
            p["tail"] = stack_init(lambda k: init_block(k, cfg), ks[4], lay["tail"])
    elif lay["kind"] == "moe":
        if lay["dense"]:
            p["blocks_dense"] = stack_init(
                lambda k: init_block(k, cfg.replace(d_ff=cfg.d_ff or cfg.moe_d_ff)),
                ks[2], lay["dense"])
        p["blocks_moe"] = stack_init(lambda k: init_block(k, cfg, moe=True),
                                     ks[3], lay["moe"])
        if cfg.mtp_depth:
            p["mtp"] = {
                "proj": init_linear(ks[5], 2 * cfg.d_model, cfg.d_model, dtype=pdt(cfg)),
                "ln_h": init_rmsnorm(cfg.d_model, pdt(cfg)),
                "ln_e": init_rmsnorm(cfg.d_model, pdt(cfg)),
                "block": init_block(ks[6], cfg, moe=True),
                "ln_f": init_rmsnorm(cfg.d_model, pdt(cfg)),
            }
    else:
        p["blocks"] = stack_init(lambda k: init_block(k, cfg), ks[2], lay["dense"])
    return p


def _embed_in(p: Params, cfg: ArchConfig, batch: Dict) -> jnp.ndarray:
    if cfg.family == "audio":
        return codebook_embed(p["embed"], batch["tokens"], cdt(cfg))
    return embed(p["embed"], batch["tokens"], cdt(cfg))


def _logits(p: Params, cfg: ArchConfig, h: jnp.ndarray) -> jnp.ndarray:
    if cfg.family == "audio":
        return codebook_unembed(p["embed"], h, cdt(cfg))
    tab = p["embed"] if cfg.tie_embeddings else p["unembed"]
    return unembed(tab, h, cdt(cfg))


def forward(params: Params, cfg: ArchConfig, batch: Dict, *,
            attn_fn=None) -> Dict[str, jnp.ndarray]:
    """Prefill/training forward. batch: tokens (B,S[,K]) [+ image_embeds]."""
    lay = _layer_layout(cfg)
    h = _embed_in(params, cfg, batch)
    aux0 = jnp.zeros((), jnp.float32)
    window = cfg.sliding_window

    if lay["kind"] == "vlm":
        img = batch["image_embeds"].astype(cdt(cfg))

        def super_body(lp, h, aux):
            def self_body(slp, h, aux):
                h, a = block_prefill(slp, h, cfg, window=window, attn_fn=attn_fn)
                return h, aux + a
            h, aux = scan_layers(self_body, h, lp["blocks"], remat=False,
                                 init_aux=aux, unroll=cfg.scan_unroll)
            cp = lp["cross"]
            c = cross_attn(cp["attn"], rmsnorm(cp["ln"], h, cfg.norm_eps), img,
                           n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                           head_dim=cfg.resolved_head_dim, compute_dtype=cdt(cfg))
            h = h + jnp.tanh(cp["gate"].astype(jnp.float32)) * c.astype(jnp.float32)
            return h.astype(cdt(cfg)), aux

        stacked = {"blocks": params["blocks"], "cross": params["cross"]}
        h, aux = scan_layers(super_body, h, stacked, remat=cfg.remat,
                             init_aux=aux0, unroll=cfg.scan_unroll)
        if lay.get("tail"):
            def body(lp, h, aux):
                h, a = block_prefill(lp, h, cfg, window=window, attn_fn=attn_fn)
                return h, aux + a
            h, aux = scan_layers(body, h, params["tail"], remat=cfg.remat,
                                 init_aux=aux, unroll=cfg.scan_unroll)
    elif lay["kind"] == "moe":
        def dense_body(lp, h, aux):
            h, a = block_prefill(lp, h, cfg.replace(d_ff=cfg.d_ff or cfg.moe_d_ff),
                                 window=window, attn_fn=attn_fn)
            return h, aux + a

        def moe_body(lp, h, aux):
            h, a = block_prefill(lp, h, cfg, moe=True, window=window, attn_fn=attn_fn)
            return h, aux + a

        aux = aux0
        if lay["dense"]:
            h, aux = scan_layers(dense_body, h, params["blocks_dense"],
                                 remat=cfg.remat, init_aux=aux,
                                 unroll=cfg.scan_unroll)
        h, aux = scan_layers(moe_body, h, params["blocks_moe"],
                             remat=cfg.remat, init_aux=aux,
                             unroll=cfg.scan_unroll)
    else:
        def body(lp, h, aux):
            h, a = block_prefill(lp, h, cfg, window=window, attn_fn=attn_fn)
            return h, aux + a
        h, aux = scan_layers(body, h, params["blocks"], remat=cfg.remat,
                             init_aux=aux0, unroll=cfg.scan_unroll)

    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    out = {"hidden": h, "logits": _logits(params, cfg, h), "aux_loss": aux}

    if cfg.is_moe and cfg.mtp_depth and "mtp" in params:
        # DeepSeek-V3 MTP (depth 1): combine h_t with emb(tok_{t+1}) to
        # predict tok_{t+2}; trained alongside the main head.
        mp = params["mtp"]
        emb_next = jnp.roll(_embed_in(params, cfg, batch), -1, axis=1)
        z = jnp.concatenate([rmsnorm(mp["ln_h"], h, cfg.norm_eps),
                             rmsnorm(mp["ln_e"], emb_next, cfg.norm_eps)], axis=-1)
        z = linear(mp["proj"], z, compute_dtype=cdt(cfg))
        z, _ = block_prefill(mp["block"], z, cfg, moe=True, window=window,
                             attn_fn=attn_fn)
        out["mtp_logits"] = _logits(params, cfg, rmsnorm(mp["ln_f"], z, cfg.norm_eps))
    return out


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, seq_len: int,
               image_tokens: int = 0):
    lay = _layer_layout(cfg)
    from repro.models.base import decode_capacity
    cap = decode_capacity(cfg, seq_len)

    def stack_cache(n):
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l, (n,) + l.shape), init_kv_cache(cfg, batch, cap))

    if lay["kind"] == "vlm":
        cache = {"self": stack_cache(lay["n_super"] * lay["per_super"] + lay.get("tail", 0)),
                 # cross-KV computed once at prefill; stub zeros at dry-run
                 "cross_k": jnp.zeros((lay["n_super"], batch, image_tokens or cfg.n_image_tokens,
                                       cfg.n_kv_heads, cfg.resolved_head_dim), cdt(cfg)),
                 "cross_v": jnp.zeros((lay["n_super"], batch, image_tokens or cfg.n_image_tokens,
                                       cfg.n_kv_heads, cfg.resolved_head_dim), cdt(cfg))}
        return cache
    if lay["kind"] == "moe":
        return {"dense": stack_cache(lay["dense"]) if lay["dense"] else None,
                "moe": stack_cache(lay["moe"])}
    return {"blocks": stack_cache(lay["dense"])}


def decode_step(params: Params, cfg: ArchConfig, cache, tokens_t: jnp.ndarray,
                pos, *, active=None, with_logits: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
    """One decode step. tokens_t: (B,[K]) -> (logits, hidden_t, new_cache).
    with_logits=False returns logits=None (monitoring-only decode).
    ``pos``: a scalar, or (B,) for a position per row (each row decoded
    alone, ``rowwise``).  ``active`` ((B,) bool or None): rows where it is
    False get their cache back bit-unchanged (``nn.attention.write_slot``);
    their hidden state and logits are garbage."""
    lay = _layer_layout(cfg)
    # Window handling: the cache was sized by decode_capacity; if it is
    # smaller than the logical context we run it as a ring buffer (SWA).
    def embed_in(_, t):
        if cfg.family == "audio":
            return codebook_embed(params["embed"], t[:, None], cdt(cfg))[:, 0]
        return embed(params["embed"], t, cdt(cfg))

    h = rowwise(embed_in, pos)(tokens_t)

    if lay["kind"] == "vlm":
        n_sup, per = lay["n_super"], lay["per_super"]
        cap = cache["self"].k.shape[2]
        win = cap if cfg.sliding_window or cfg.long_context_window else 0

        def self_body(first):
            # the self caches stack every layer flat: (n_super*per + tail, ...)
            def body(lp, h, c, at, pos):
                return block_decode(lp, h, c, pos, cfg, window=win,
                                    at=(first + at[0],), active=active)
            return body

        def super_body(carry, xs):
            h, selfc = carry
            lp, ck, cv, s = xs
            h, selfc = scan_layers_decode(self_body(s * per), h, lp["blocks"],
                                          selfc, pos, unroll=cfg.scan_unroll)
            cp = lp["cross"]

            def cross(_, h, ck, cv):
                hn = rmsnorm(cp["ln"], h[:, None], cfg.norm_eps)
                catt = cross_attn_decode(cp["attn"], hn[:, 0], ck, cv, cfg)
                return (h.astype(jnp.float32) + jnp.tanh(cp["gate"].astype(jnp.float32))
                        * catt.astype(jnp.float32)).astype(cdt(cfg))

            return (rowwise(cross, pos)(h, ck, cv), selfc), None

        stacked = {"blocks": params["blocks"], "cross": params["cross"]}
        (h, selfc), _ = jax.lax.scan(
            super_body, (h, cache["self"]),
            (stacked, cache["cross_k"], cache["cross_v"], jnp.arange(n_sup)),
            unroll=cfg.scan_unroll)
        if lay.get("tail"):
            h, selfc = scan_layers_decode(self_body(n_sup * per), h,
                                          params["tail"], selfc, pos,
                                          unroll=cfg.scan_unroll)
        new_cache = {"self": selfc, "cross_k": cache["cross_k"],
                     "cross_v": cache["cross_v"]}
    elif lay["kind"] == "moe":
        capc = cache["moe"].ckv if cfg.use_mla else cache["moe"].k
        cap = capc.shape[2]
        win = cap if cfg.long_context_window else 0

        def dense_body(lp, h, c, at, pos):
            return block_decode(lp, h, c, pos,
                                cfg.replace(d_ff=cfg.d_ff or cfg.moe_d_ff),
                                window=win, at=at, active=active)

        def moe_body(lp, h, c, at, pos):
            return block_decode(lp, h, c, pos, cfg, moe=True, window=win,
                                at=at, active=active)

        new_dense = None
        if lay["dense"]:
            h, new_dense = scan_layers_decode(dense_body, h, params["blocks_dense"],
                                              cache["dense"], pos,
                                              unroll=cfg.scan_unroll)
        h, new_moe = scan_layers_decode(moe_body, h, params["blocks_moe"],
                                        cache["moe"], pos,
                                        unroll=cfg.scan_unroll)
        new_cache = {"dense": new_dense, "moe": new_moe}
    else:
        cap = cache["blocks"].k.shape[2]
        win = cap if (cfg.sliding_window or cfg.long_context_window) else 0

        def body(lp, h, c, at, pos):
            return block_decode(lp, h, c, pos, cfg, window=win, at=at,
                                active=active)

        h, new_blocks = scan_layers_decode(body, h, params["blocks"],
                                           cache["blocks"], pos,
                                           unroll=cfg.scan_unroll)
        new_cache = {"blocks": new_blocks}

    def head(_, h):
        h = rmsnorm(params["ln_f"], h[:, None], cfg.norm_eps)[:, 0]
        return (_logits(params, cfg, h) if with_logits else None), h

    logits, h = rowwise(head, pos)(h)
    return logits, h, new_cache


def cross_attn_decode(p: Params, x: jnp.ndarray, k_img: jnp.ndarray,
                      v_img: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Single-token cross attention against precomputed image KV."""
    from repro.nn.attention import decode_attention
    B = x.shape[0]
    q = linear(p["wq"], x, compute_dtype=cdt(cfg)).reshape(
        B, cfg.n_heads, cfg.resolved_head_dim)
    T = k_img.shape[1]
    o = decode_attention(q, k_img, v_img, jnp.asarray(T - 1))
    return linear(p["wo"], o.reshape(B, -1), compute_dtype=cdt(cfg))
