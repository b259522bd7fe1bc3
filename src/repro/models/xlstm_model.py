"""xLSTM-350m backbone (arXiv:2405.04517): mLSTM blocks with one sLSTM block
every ``slstm_every`` layers (the paper's xLSTM[a:b] notation).  d_ff=0 in
the assigned config: blocks carry their own internal projections.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.base import (cdt, pdt, recurrent_step, scan_layers,
                               scan_layers_decode, stack_init)
from repro.nn.embedding import embed, init_embedding, unembed
from repro.nn.module import Params, rowwise
from repro.nn.norms import init_rmsnorm, rmsnorm
from repro.nn.xlstm import (MLSTMState, SLSTMState, init_mlstm, init_mlstm_state,
                            init_slstm, init_slstm_state, mlstm_decode,
                            mlstm_parallel, slstm_scan, slstm_step)


def _layout(cfg: ArchConfig):
    k = cfg.slstm_every or cfg.n_layers + 1
    if cfg.slstm_every:
        n_super = cfg.n_layers // k
        per = k - 1  # per super-block: (k-1) mLSTM + 1 sLSTM
        tail = cfg.n_layers - n_super * k  # trailing mLSTM layers
    else:
        n_super, per, tail = 0, 0, cfg.n_layers
    return n_super, per, tail


def init_lm(key, cfg: ArchConfig) -> Params:
    n_super, per, tail = _layout(cfg)
    ks = jax.random.split(key, 6)

    def init_m(k2):
        return {"ln": init_rmsnorm(cfg.d_model, pdt(cfg)),
                "cell": init_mlstm(k2, cfg.d_model, cfg.n_heads, dtype=pdt(cfg))}

    def init_s(k2):
        return {"ln": init_rmsnorm(cfg.d_model, pdt(cfg)),
                "cell": init_slstm(k2, cfg.d_model, cfg.n_heads, dtype=pdt(cfg))}

    p: Params = {
        "embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model, pdt(cfg)),
        "ln_f": init_rmsnorm(cfg.d_model, pdt(cfg)),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = init_embedding(ks[1], cfg.vocab_size, cfg.d_model, pdt(cfg))
    if n_super:
        p["super"] = {
            "mlstm": stack_init(lambda kk: stack_init(init_m, kk, per), ks[2], n_super),
            "slstm": stack_init(init_s, ks[3], n_super),
        }
    if tail:
        p["tail"] = stack_init(init_m, ks[4], tail)
    return p


def forward(params: Params, cfg: ArchConfig, batch: Dict, *,
            attn_fn=None, ssm_scan_fn=None) -> Dict[str, jnp.ndarray]:
    n_super, per, tail = _layout(cfg)
    h = embed(params["embed"], batch["tokens"], cdt(cfg))
    aux0 = jnp.zeros((), jnp.float32)

    def m_body(lp, h, aux):
        y = mlstm_parallel(lp["cell"], rmsnorm(lp["ln"], h, cfg.norm_eps),
                           cfg.n_heads, compute_dtype=cdt(cfg))
        return h + y, aux

    aux = aux0
    if n_super:
        def super_body(lp, h, aux):
            h, aux = scan_layers(m_body, h, lp["mlstm"], remat=False, init_aux=aux,
                                 unroll=cfg.scan_unroll)
            y, _ = slstm_scan(lp["slstm"]["cell"],
                              rmsnorm(lp["slstm"]["ln"], h, cfg.norm_eps),
                              cfg.n_heads, compute_dtype=cdt(cfg))
            return h + y, aux
        h, aux = scan_layers(super_body, h, params["super"], remat=cfg.remat,
                             init_aux=aux, unroll=cfg.scan_unroll)
    if tail:
        h, aux = scan_layers(m_body, h, params["tail"], remat=cfg.remat,
                             init_aux=aux, unroll=cfg.scan_unroll)

    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    tab = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return {"hidden": h, "logits": unembed(tab, h, cdt(cfg)), "aux_loss": aux}


def init_cache(cfg: ArchConfig, batch: int, seq_len: int, image_tokens: int = 0):
    n_super, per, tail = _layout(cfg)
    m = init_mlstm_state(batch, cfg.d_model, cfg.n_heads)
    s = init_slstm_state(batch, cfg.d_model)

    def stack(n, tree):
        return jax.tree.map(lambda l: jnp.broadcast_to(l, (n,) + l.shape), tree)

    return {
        "mlstm": stack(n_super, stack(per, m)) if n_super else None,
        "slstm": stack(n_super, s) if n_super else None,
        "tail": stack(tail, m) if tail else None,
    }


def decode_step(params: Params, cfg: ArchConfig, cache, tokens_t, pos, *,
                active=None, with_logits: bool = True):
    """One decode step; ``pos`` and ``active`` as in
    ``transformer.decode_step``."""
    n_super, per, tail = _layout(cfg)
    h = rowwise(lambda _, t: embed(params["embed"], t, cdt(cfg)), pos)(tokens_t)

    def m_body(lp, h, caches, at, pos):
        def step(_, h, c):
            y, nc = mlstm_decode(lp["cell"],
                                 rmsnorm(lp["ln"], h[:, None], cfg.norm_eps)[:, 0],
                                 c, cfg.n_heads, compute_dtype=cdt(cfg))
            return h + y, nc

        return recurrent_step(step, h, caches, at, pos, active)

    new_cache = {"mlstm": None, "slstm": None, "tail": None}
    if n_super:
        def super_body(carry, xs):
            h, mc, sc = carry
            lp, s = xs
            h, mc = scan_layers_decode(m_body, h, lp["mlstm"], mc, pos, at=(s,),
                                       unroll=cfg.scan_unroll)

            def s_step(_, h, c):
                hn = rmsnorm(lp["slstm"]["ln"], h[:, None], cfg.norm_eps)[:, 0]
                y, nc = slstm_step(lp["slstm"]["cell"], hn, c, cfg.n_heads)
                y = y * lp["slstm"]["cell"]["norm_scale"].astype(jnp.float32)[None, :]
                return (h.astype(jnp.float32) + y).astype(cdt(cfg)), nc

            h, sc = recurrent_step(s_step, h, sc, (s,), pos, active)
            return (h, mc, sc), None

        (h, new_m, new_s), _ = jax.lax.scan(
            super_body, (h, cache["mlstm"], cache["slstm"]),
            (params["super"], jnp.arange(n_super)), unroll=cfg.scan_unroll)
        new_cache["mlstm"], new_cache["slstm"] = new_m, new_s
    if tail:
        h, new_t = scan_layers_decode(m_body, h, params["tail"], cache["tail"], pos,
                                      unroll=cfg.scan_unroll)
        new_cache["tail"] = new_t

    def head(_, h):
        h = rmsnorm(params["ln_f"], h[:, None], cfg.norm_eps)[:, 0]
        tab = params["embed"] if cfg.tie_embeddings else params["unembed"]
        return (unembed(tab, h, cdt(cfg)) if with_logits else None), h

    logits, h = rowwise(head, pos)(h)
    return logits, h, new_cache
