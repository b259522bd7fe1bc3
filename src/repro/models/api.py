"""Uniform backbone API — the rest of the framework (core/, launch/,
serving/, training/) only talks to these five functions:

    init_model(key, cfg)                         -> params
    forward(params, cfg, batch)                  -> {hidden, logits, aux_loss, ...}
    init_cache(cfg, batch, seq_len)              -> cache pytree
    decode_step(params, cfg, cache, tokens, pos) -> (logits, hidden_t, cache)
    input_specs(cfg, shape)                      -> ShapeDtypeStruct batch
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import hybrid, transformer, xlstm_model
from repro.models.base import cdt

_FAMILY = {
    "dense": transformer, "moe": transformer, "vlm": transformer,
    "audio": transformer, "hybrid": hybrid, "ssm": xlstm_model,
}


def _impl(cfg: ArchConfig):
    return _FAMILY[cfg.family]


def init_model(key, cfg: ArchConfig):
    return _impl(cfg).init_lm(key, cfg)


def forward(params, cfg: ArchConfig, batch: Dict, **kw):
    return _impl(cfg).forward(params, cfg, batch, **kw)


def init_cache(cfg: ArchConfig, batch: int, seq_len: int):
    return _impl(cfg).init_cache(cfg, batch, seq_len)


def decode_step(params, cfg: ArchConfig, cache, tokens_t, pos, *,
                active=None, with_logits: bool = True):
    """with_logits=False skips the unembed projection (monitoring-only
    decode: the collaborative protocol consumes hidden scores, not
    next-token logits — the tokens come from the monitored stream).

    ``active`` ((B,) bool, or None for every row): the row mask of the
    cache writes.  A row where it is False gets every cache leaf back
    bit-unchanged — its K/V slot rewritten with the slot's own value, its
    recurrent state with the state it read — while its hidden state and
    logits are garbage (decoded densely, discarded by the caller)."""
    return _impl(cfg).decode_step(params, cfg, cache, tokens_t, pos,
                                  active=active, with_logits=with_logits)


# ---------------------------------------------------------------------------
# Shape-only input stand-ins (dry-run; modality frontends are stubs per brief)
# ---------------------------------------------------------------------------


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def tok(shp):
        return jax.ShapeDtypeStruct(shp, i32)

    if shape.kind == "train" or shape.kind == "prefill":
        if cfg.family == "audio":
            batch = {"tokens": tok((B, S, cfg.n_codebooks))}
        else:
            batch = {"tokens": tok((B, S))}
        if cfg.family == "vlm":
            batch["image_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_image_tokens, cfg.d_model), cdt(cfg))
        if shape.kind == "train":
            if cfg.family == "audio":
                batch["labels"] = tok((B, S, cfg.n_codebooks))
            else:
                batch["labels"] = tok((B, S))
            # monitoring target for the collaborative head (paper technique)
            batch["monitor_target"] = jax.ShapeDtypeStruct((B, S), jnp.float32)
        return batch

    # decode: one new token + a cache filled to seq_len
    if cfg.family == "audio":
        tokens = tok((B, cfg.n_codebooks))
    else:
        tokens = tok((B,))
    cache = jax.eval_shape(lambda: init_cache(cfg, B, S))
    return {"tokens": tokens, "cache": cache,
            "pos": jax.ShapeDtypeStruct((), i32)}


def sample_batch(key, cfg: ArchConfig, shape: ShapeConfig) -> Dict:
    """Concrete random batch matching input_specs (smoke tests / examples)."""
    specs = input_specs(cfg, shape)
    out = {}
    for name, spec in specs.items():
        key = jax.random.fold_in(key, hash(name) % (2**31))
        if name == "cache":
            out[name] = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        elif spec.dtype == jnp.int32 and name != "pos":
            out[name] = jax.random.randint(key, spec.shape, 0, cfg.vocab_size, jnp.int32)
        elif name == "pos":
            out[name] = jnp.asarray(shape.seq_len - 1, jnp.int32)
        else:
            out[name] = jax.random.normal(key, spec.shape, jnp.float32).astype(spec.dtype)
    return out
