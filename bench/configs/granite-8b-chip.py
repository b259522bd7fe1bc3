"""granite-8b-chip: the plain reference of the server tower, and the least
work of one replayed token, from the sizes in ``granite-8b-chip.json``.

The server tower is a llama-layout decoder: tied embedding, pre-norm
blocks of grouped-query attention with half-split rotary embeddings and a
SwiGLU MLP, final RMSNorm.  Its last hidden state feeds the v head.
"""
import reference as ref


def server_hidden(params, tokens, sz):
    """Final hidden state (B, T, d) of the server tower over tokens (B, T)."""
    s = params["server"]
    h = s["embed"]["table"].astype(ref.F32)[tokens]
    nh = sz["num_attention_heads"]
    for i in range(ref.n_stacked(s["blocks"])):
        h = ref.dense_block(ref.layer(s["blocks"], i), h, n_heads=nh,
                            n_kv=sz["num_key_value_heads"],
                            head_dim=sz["hidden_size"] // nh,
                            theta=sz["rope_theta"], eps=sz["rms_norm_eps"])
    return ref.rmsnorm(h, s["ln_f"]["scale"], sz["rms_norm_eps"])


def body_params(sz) -> int:
    """Parameters outside the embedding: the matrices a token passes."""
    d, nh, nkv = (sz["hidden_size"], sz["num_attention_heads"],
                  sz["num_key_value_heads"])
    hd = d // nh
    attn = d * hd * (nh + 2 * nkv) + nh * hd * d
    mlp = 3 * d * sz["intermediate_size"]
    return sz["num_hidden_layers"] * (attn + mlp + 2 * d) + d


def attn_flops(sz, ctx: int) -> int:
    """Score and value products of one token over ``ctx`` positions."""
    nh = sz["num_attention_heads"]
    hd = sz["hidden_size"] // nh
    return sz["num_hidden_layers"] * 4 * nh * hd * ctx


def state_bytes(sz, ctx: int) -> int:
    """Per-row state the tower reads at context ``ctx``: its bf16 KV."""
    nh = sz["num_attention_heads"]
    hd = sz["hidden_size"] // nh
    return sz["num_hidden_layers"] * 2 * sz["num_key_value_heads"] * hd \
        * 2 * ctx


def token_write_bytes(sz) -> int:
    """What one new token writes: its KV slot in every layer."""
    return state_bytes(sz, 1)


def fixed_state_bytes(sz) -> int:
    """Per-row state read and written whatever the context: none here."""
    return 0
