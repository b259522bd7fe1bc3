"""The plain float32 reference of the collaborative system: the edge tower
and its u head, the server tower's final hidden state and the v head.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, a full causal forward over
whole streams, no cache, no kernel, nothing imported from the program.  It
reads the weights the benchmark made (``bench/weights.py``) by their names.
Each configuration's server tower is in ``bench/configs/<name>.py`` and is
built from the layers here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def mm(x, w):
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HI)


def linear(p, x):
    y = mm(x, p["w"])
    return y + p["b"].astype(F32) if "b" in p else y


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, theta):
    """Half-split rotary embedding of x (B, T, H, D) at positions 0..T-1."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv          # (T, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int = 0, q_block: int = 256):
    """Causal softmax attention, q (B,T,Hq,D), k/v (B,T,Hkv,D); query
    heads grouped over KV heads; ``window`` > 0 keeps the last ``window``
    positions.  Computed a block of queries at a time so that the scores
    fit; every block sees every key."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    qb = min(q_block, T)
    qg = q.reshape(B, T, Hkv, Hq // Hkv, D)
    cols = jnp.arange(T)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * qb, qb, axis=1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qi, k, precision=HI) \
            / math.sqrt(D)
        rows = i * qb + jnp.arange(qb)
        keep = cols[None, :] <= rows[:, None]
        if window:
            keep &= cols[None, :] > rows[:, None] - window
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(T // qb))           # (nb,B,qb,..)
    return jnp.moveaxis(o, 0, 1).reshape(B, T, Hq, D)


def swiglu(p, x):
    g = linear(p["w_gate"], x)
    return linear(p["w_down"], jax.nn.silu(g) * linear(p["w_up"], x))


def dense_block(p, h, *, n_heads, n_kv, head_dim, theta, eps, window=0):
    """Pre-norm attention + SwiGLU block (llama / granite layout)."""
    B, T, _ = h.shape
    x = rmsnorm(h, p["ln_attn"]["scale"], eps)
    a = p["attn"]
    q = rope(linear(a["wq"], x).reshape(B, T, n_heads, head_dim), theta)
    k = rope(linear(a["wk"], x).reshape(B, T, n_kv, head_dim), theta)
    v = linear(a["wv"], x).reshape(B, T, n_kv, head_dim)
    o = attention(q, k, v, window)
    h = h + linear(a["wo"], o.reshape(B, T, n_heads * head_dim))
    return h + swiglu(p["mlp"], rmsnorm(h, p["ln_mlp"]["scale"], eps))


def layer(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def n_stacked(stacked) -> int:
    return jax.tree.leaves(stacked)[0].shape[0]


# --- the edge tower and the heads -------------------------------------------

EDGE_WINDOW = 1024  # the edge tower's attention window (its ring cache)


def edge_u(params, tokens, mon: dict, *, theta: float = 1e4,
           eps: float = 1e-5):
    """u at every position of tokens (B, T): the edge decoder (tied
    embedding, dense blocks over a 1024-token window, final norm), then
    the truncated-basis head u = tanh(h W) . (a * [i < n]) + softplus(t)."""
    e = params["edge"]
    d, nh = mon["d_model"], mon["n_heads"]
    h = e["embed"]["table"].astype(F32)[tokens]
    for i in range(n_stacked(e["blocks"])):
        h = dense_block(layer(e["blocks"], i), h, n_heads=nh, n_kv=nh,
                        head_dim=d // nh, theta=theta, eps=eps,
                        window=EDGE_WINDOW)
    h = rmsnorm(h, e["ln_f"]["scale"], eps)
    hd = params["u_head"]
    feats = jnp.tanh(linear(hd["w_feat"], h))
    mask = (jnp.arange(feats.shape[-1]) < mon["n_features"]).astype(F32)
    return jnp.sum(feats * (hd["a"] * mask), -1) \
        + jax.nn.softplus(hd["raw_t"].astype(F32))


def v_head(params, hidden):
    hd = params["v_head"]
    return jnp.sum(hidden.astype(F32) * hd["w"][:, 0].astype(F32), -1) \
        + hd["b"][0].astype(F32)


def by_blocks(fn, args_rows, rows: int):
    """Run ``fn(*row_block_args)`` over blocks of ``rows`` rows (the last
    block padded by repeating its first row) and stack the numpy results:
    the reference of a whole pool, a block at a time, so that it fits."""
    n = args_rows[0].shape[0]
    outs = []
    for i in range(0, n, rows):
        blk = [a[i:i + rows] for a in args_rows]
        m = blk[0].shape[0]
        if m < rows:
            blk = [np.concatenate([b, np.repeat(b[:1], rows - m, 0)])
                   for b in blk]
        outs.append(np.asarray(fn(*blk))[:m])
    return np.concatenate(outs)


def round_weights(params, dtype=jnp.float8_e4m3fn):
    """The control's weights: every floating leaf rounded to ``dtype`` and
    widened back to float32.  The rounded copy is made in one program and
    widened in another: inside one program XLA on the TPU drops the
    narrowing convert pair, and the control would be the reference."""
    narrow = jax.jit(lambda p: jax.tree.map(lambda a: a.astype(dtype), p))(
        params)
    jax.block_until_ready(narrow)
    return jax.jit(lambda p: jax.tree.map(lambda a: a.astype(F32), p))(
        narrow)
