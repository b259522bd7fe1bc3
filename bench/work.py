"""Least work of the monitoring path, counted from a configuration's shapes.

This is the work any implementation has to do, whatever does it, so no
change to the program can raise a share of a peak by doing the same job
another way:

- weights are read once per step that does the work, in the compute type
  (bfloat16, 2 bytes); the float32 master copy is not counted;
- attention reads each row's KV up to its own position, not the cache's
  length; each new token writes its own KV slot (or SSM state) once;
- only triggered rows' backlog tokens are replayed, each counted once;
- the edge records B tokens, not the (B, max_len) history;
- useful FLOPs are 2 x matrix parameters per token plus attention over
  the token's context; the edge tower's unused logits are excluded.

The server tower's pieces come from the configuration's module
(``bench/configs/<name>.py``); the edge tower is the configuration's
``monitor`` group.
"""
from __future__ import annotations

from reference import EDGE_WINDOW

BF16 = 2


def _edge_dims(mon):
    d, nh = mon["d_model"], mon["n_heads"]
    return d, nh, d // nh


def edge_body_params(mon) -> int:
    """Edge matrices a token passes (dense blocks, KV heads = heads) plus
    the u head's features."""
    d, nh, hd = _edge_dims(mon)
    per_layer = 4 * d * d + 3 * d * mon["d_ff"] + 2 * d
    return mon["n_layers"] * per_layer + d + d * mon["n_features"] \
        + mon["n_features"]


def edge_step(mon, positions):
    """(flops, bytes) of one edge step: every attached row decodes one
    token at its position (a sequence of ints), records it, scores u."""
    d, nh, hd = _edge_dims(mon)
    L = mon["n_layers"]
    n = len(positions)
    body = edge_body_params(mon)
    kv_tok = L * 2 * nh * hd * BF16          # one position's KV, all layers
    flops = 2 * body * n
    nbytes = body * BF16 + n * (d * BF16 + 4 + kv_tok)  # weights, embed
    for p in positions:                      # row, recorded token, KV write
        ctx = min(p + 1, EDGE_WINDOW)
        flops += L * 4 * nh * hd * ctx
        nbytes += (ctx - 1) * kv_tok
    return flops, nbytes


def catchup(mod, sz, replays):
    """(flops, bytes) of one catch-up call.  ``replays``: per triggered
    row, the (first, last) positions it replays.  Weights once; each
    row's earlier KV read once and each replayed token's KV written once;
    a row's fixed recurrent state read and written once; the v head on
    each row's last token."""
    if not replays:
        return 0, 0
    d = sz["hidden_size"]
    mat = getattr(mod, "matmul_params", mod.body_params)(sz)
    flops = 0
    nbytes = mod.body_params(sz) * BF16
    for p0, p1 in replays:
        n_tok = p1 - p0 + 1
        flops += 2 * mat * n_tok + 2 * d
        flops += sum(mod.attn_flops(sz, p + 1) for p in range(p0, p1 + 1))
        nbytes += mod.state_bytes(sz, p0) + n_tok * (
            mod.token_write_bytes(sz) + d * BF16 + 4)
        nbytes += 2 * mod.fixed_state_bytes(sz)
    return flops, nbytes
