"""zamba2-7b-chip: the plain reference of the server tower, and the least
work of one replayed token, from the sizes in ``zamba2-7b-chip.json``.

The server tower as the repository states it (the departures from the
published model are listed in the JSON): tied embedding; per period,
``shared_attn_every`` Mamba2 layers (pre-norm, residual) and then one
invocation of the single shared attention + SwiGLU block; final RMSNorm.
Mamba2 here is the plain recurrence, one token at a time:
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  y_t = C_t h_t + D x_t.
"""
import jax
import jax.numpy as jnp

import reference as ref

HEAD_P = 64  # mamba_headdim


def _dims(sz):
    d_in = sz["mamba_expand"] * sz["hidden_size"]
    return d_in, d_in // HEAD_P, HEAD_P, sz["mamba_d_state"]


def _conv(v, c):
    """Depthwise causal conv of v (B, T, C) with w (K, C) and bias, silu."""
    w = c["w"].astype(ref.F32)
    K, T = w.shape[0], v.shape[1]
    vp = jnp.pad(v, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(vp[:, i:i + T] * w[i] for i in range(K))
    return jax.nn.silu(y + c["b"].astype(ref.F32))


def mamba2(p, x, sz):
    B, T, _ = x.shape
    d_in, H, P, N = _dims(sz)
    z = ref.linear(p["w_z"], x)
    xi = _conv(ref.linear(p["w_x"], x), p["conv_x"]).reshape(B, T, H, P)
    Bm = _conv(ref.linear(p["w_B"], x), p["conv_B"])
    Cm = _conv(ref.linear(p["w_C"], x), p["conv_C"])
    dt = jax.nn.softplus(ref.linear(p["w_dt"], x) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(ref.F32))

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = h * jnp.exp(dt_t * A)[:, :, None, None] + jnp.einsum(
            "bn,bhp->bhpn", b_t, x_t * dt_t[..., None], precision=ref.HI)
        return h, jnp.einsum("bn,bhpn->bhp", c_t, h, precision=ref.HI)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (xi, Bm, Cm, dt))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), ref.F32), xs)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xi
    y = y.reshape(B, T, d_in) * jax.nn.silu(z) * p["norm_scale"]
    return ref.linear(p["out_proj"], y)


def server_hidden(params, tokens, sz):
    """Final hidden state (B, T, d) of the server tower over tokens (B, T)."""
    s = params["server"]
    eps = sz["rms_norm_eps"]
    nh = sz["num_attention_heads"]
    h = s["embed"]["table"].astype(ref.F32)[tokens]

    def mamba_layer(lp, h):
        return h + mamba2(lp["mamba"], ref.rmsnorm(h, lp["ln"]["scale"], eps),
                          sz)

    for i in range(ref.n_stacked(s["mamba_blocks"])):
        period = ref.layer(s["mamba_blocks"], i)
        for j in range(ref.n_stacked(period)):
            h = mamba_layer(ref.layer(period, j), h)
        h = ref.dense_block(s["shared"], h, n_heads=nh,
                            n_kv=sz["num_key_value_heads"],
                            head_dim=sz["hidden_size"] // nh,
                            theta=sz["rope_theta"], eps=eps)
    if "tail" in s:
        for j in range(ref.n_stacked(s["tail"])):
            h = mamba_layer(ref.layer(s["tail"], j), h)
    return ref.rmsnorm(h, s["ln_f"]["scale"], eps)


# --- least work --------------------------------------------------------------

def _layout(sz):
    k = sz["program"]["set"]["shared_attn_every"]
    L = sz["num_hidden_layers"]
    return L, L // k                           # mamba layers, invocations


def _mamba_params(sz) -> int:
    d = sz["hidden_size"]
    d_in, H, P, N = _dims(sz)
    K = sz["mamba_d_conv"]
    return (2 * d * d_in + 2 * d * N + d * H + d_in * d      # projections
            + (K + 1) * (d_in + 2 * N) + 3 * H + d_in + d)    # conv, consts


def _shared_params(sz) -> int:
    d, nh, nkv = (sz["hidden_size"], sz["num_attention_heads"],
                  sz["num_key_value_heads"])
    hd = d // nh
    return d * hd * (nh + 2 * nkv) + nh * hd * d \
        + 3 * d * sz["ffn_hidden_size"] + 2 * d


def body_params(sz) -> int:
    """Distinct parameters outside the embedding (the shared block once)."""
    L, _ = _layout(sz)
    return L * _mamba_params(sz) + _shared_params(sz) + sz["hidden_size"]


def matmul_params(sz) -> int:
    """Parameters a token passes: the shared block once per invocation."""
    L, n_inv = _layout(sz)
    return L * _mamba_params(sz) + n_inv * _shared_params(sz) \
        + sz["hidden_size"]


def attn_flops(sz, ctx: int) -> int:
    """Shared-attention scores and values over ``ctx`` positions, plus the
    state update and readout of every Mamba2 layer (5 H P N a token)."""
    L, n_inv = _layout(sz)
    nh = sz["num_attention_heads"]
    hd = sz["hidden_size"] // nh
    _, H, P, N = _dims(sz)
    return n_inv * 4 * nh * hd * ctx + L * 5 * H * P * N


def state_bytes(sz, ctx: int) -> int:
    """bf16 KV of the shared block's invocations over ``ctx`` positions."""
    _, n_inv = _layout(sz)
    nh = sz["num_attention_heads"]
    hd = sz["hidden_size"] // nh
    return n_inv * 2 * sz["num_key_value_heads"] * hd * 2 * ctx


def token_write_bytes(sz) -> int:
    return state_bytes(sz, 1)


def fixed_state_bytes(sz) -> int:
    """A row's f32 recurrent state, read once and written once a call:
    the SSM state and the conv tails of every Mamba2 layer."""
    L, _ = _layout(sz)
    d_in, H, P, N = _dims(sz)
    K = sz["mamba_d_conv"]
    return L * 4 * (H * P * N + (K - 1) * (d_in + 2 * N))
