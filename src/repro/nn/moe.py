"""Mixture-of-Experts: top-k router + sort-based capacity dispatch.

TPU adaptation note (DESIGN.md §3/§4): the canonical GPU MoE uses ragged
grouped-GEMM; on TPU we use the static-capacity idiom — tokens are ranked
per expert, the first ``capacity`` survive, and expert compute is one
stacked einsum on the MXU.  Dropped tokens fall through on the residual
stream (standard Switch behaviour).  The same static-capacity trick is what
``core/gating.py`` reuses for the paper's trigger-gated corrector dispatch.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn.module import Params, init_linear, linear, normal_init


def init_moe(key, d_model: int, moe_d_ff: int, n_experts: int, *,
             n_shared: int = 0, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    sd_in = 1.0 / math.sqrt(d_model)
    sd_out = 1.0 / math.sqrt(moe_d_ff)
    p: Params = {
        "router": init_linear(ks[0], d_model, n_experts, dtype=jnp.float32),
        "w_gate": normal_init(ks[1], (n_experts, d_model, moe_d_ff), dtype, sd_in),
        "w_up": normal_init(ks[2], (n_experts, d_model, moe_d_ff), dtype, sd_in),
        "w_down": normal_init(ks[3], (n_experts, moe_d_ff, d_model), dtype, sd_out),
    }
    if n_shared:
        p["shared"] = {
            "w_gate": normal_init(ks[4], (d_model, n_shared * moe_d_ff), dtype, sd_in),
            "w_up": normal_init(jax.random.fold_in(ks[4], 1),
                                (d_model, n_shared * moe_d_ff), dtype, sd_in),
            "w_down": normal_init(jax.random.fold_in(ks[4], 2),
                                  (n_shared * moe_d_ff, d_model), dtype, sd_out),
        }
    return p


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    c = int(math.ceil(n_tokens * top_k / n_experts * capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


def _route(p: Params, xf: jnp.ndarray, n_experts: int, top_k: int):
    """Router in f32 -> (top_p, top_i, aux)."""
    T = xf.shape[0]
    logits = linear(p["router"], xf.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_p, top_i = jax.lax.top_k(probs, top_k)  # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # Load-balance auxiliary loss (Switch/GShard form).
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((n_experts,), jnp.float32).at[top_i.reshape(-1)].add(
        1.0 / (T * top_k))
    aux = n_experts * jnp.sum(me * ce)
    return top_p, top_i, aux


def _slot_table(top_i, top_p, *, n_experts: int, top_k: int, C: int,
                e_lo: int = 0, e_sel: Optional[int] = None):
    """Compact slot table: (slot_tok (E_sel*C,), w_slot (E_sel*C,)).

    slot_tok[s] = token id filling slot s (sentinel T when empty/dropped);
    w_slot[s]   = routing weight of that assignment (0 when empty).
    All intermediates here are over index/weight VECTORS (never the d-wide
    activations) — §Perf A2: the activation gathers/scatters downstream run
    over E_sel*C kept slots, not T*k candidate slots.
    """
    T = top_i.shape[0]
    e_sel = n_experts if e_sel is None else e_sel
    flat_e = top_i.reshape(T * top_k)
    flat_w = top_p.reshape(T * top_k).astype(jnp.float32)
    order = jnp.argsort(flat_e)  # stable
    se = flat_e[order]
    stok = order // top_k
    sw = flat_w[order]
    counts = jnp.zeros((n_experts,), jnp.int32).at[se].add(1)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * top_k) - offsets[se]
    mine = (se >= e_lo) & (se < e_lo + e_sel)
    keep = (pos < C) & mine
    my_e = jnp.where(mine, se - e_lo, 0)
    slot = jnp.where(keep, my_e * C + jnp.minimum(pos, C - 1), e_sel * C)
    slot_tok = jnp.full((e_sel * C + 1,), T, jnp.int32).at[slot].set(
        jnp.where(keep, stok, T))
    w_slot = jnp.zeros((e_sel * C + 1,), jnp.float32).at[slot].set(sw * keep)
    return slot_tok[: e_sel * C], w_slot[: e_sel * C]


def _expert_ffn(xf, slot_tok, w_slot, wg, wu, wd, *, e_sel: int, C: int,
                compute_dtype):
    """Gather kept tokens -> stacked expert SwiGLU -> weighted scatter-add."""
    T, d = xf.shape
    xf_pad = jnp.concatenate(
        [xf.astype(compute_dtype), jnp.zeros((1, d), compute_dtype)], axis=0)
    h = xf_pad[slot_tok].reshape(e_sel, C, d)
    g = jnp.einsum("ecd,edf->ecf", h, wg.astype(compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", h, wu.astype(compute_dtype))
    eo = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                    wd.astype(compute_dtype))
    contrib = eo.reshape(e_sel * C, d) * w_slot[:, None].astype(eo.dtype)
    y = jnp.zeros((T + 1, d), jnp.float32).at[slot_tok].add(
        contrib.astype(jnp.float32))
    return y[:T]


def _shared_ffn(p: Params, xf, compute_dtype):
    sp = p["shared"]
    gs = linear({"w": sp["w_gate"]}, xf, compute_dtype=compute_dtype)
    us = linear({"w": sp["w_up"]}, xf, compute_dtype=compute_dtype)
    return linear({"w": sp["w_down"]}, jax.nn.silu(gs) * us,
                  compute_dtype=compute_dtype).astype(jnp.float32)


def moe_apply(p: Params, x: jnp.ndarray, *, n_experts: int, top_k: int,
              capacity_factor: float = 1.25,
              compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss).  Sort-based static-capacity dispatch."""
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    top_p, top_i, aux = _route(p, xf, n_experts, top_k)
    C = expert_capacity(T, n_experts, top_k, capacity_factor)
    slot_tok, w_slot = _slot_table(top_i, top_p, n_experts=n_experts,
                                   top_k=top_k, C=C)
    y = _expert_ffn(xf, slot_tok, w_slot, p["w_gate"], p["w_up"], p["w_down"],
                    e_sel=n_experts, C=C, compute_dtype=compute_dtype)
    if "shared" in p:
        y = y + _shared_ffn(p, xf, compute_dtype)
    return y.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map) — §Perf hillclimb A.
#
# Under plain jit-SPMD the sort/scatter dispatch above is GLOBAL: XLA must
# all-gather the token activations to run one argsort over B*S*k slots and
# materialise an (E, C_global, d) buffer — for deepseek-v3 train_4k that is a
# ~150 GB tensor and ~2000 s of ICI time per step.  Here each (pod,data) shard
# routes only its LOCAL tokens (activations are replicated over 'model'
# between blocks, megatron-style, so no token exchange is needed at all);
# each 'model' shard keeps its E/model_size experts, applies them at local
# capacity, and the partial outputs combine with ONE psum over 'model' per
# layer — the same collective class as the row-parallel matmul all-reduce
# that is already on the dense path.
# ---------------------------------------------------------------------------


def _current_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def ep_applicable(n_experts: int) -> bool:
    """True when a mesh with a 'model' axis (>1) is active.  E % model == 0
    selects expert-parallel; otherwise the ff dim is tensor-sharded — both
    run the dispatch locally per data shard inside shard_map."""
    mesh = _current_mesh()
    return (mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] > 1)


def moe_apply_ep(p: Params, x: jnp.ndarray, *, n_experts: int, top_k: int,
                 capacity_factor: float = 1.25,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Locally-dispatched moe_apply (shard_map): identical math to
    moe_apply, but routing/sort/scatter run per (pod,data) shard.

    - E % model == 0 (deepseek: 256 % 16): EXPERT-parallel — each model
      shard holds E/model experts and its partial outputs psum-combine.
    - else (mixtral: 8 on a 16-way axis): experts replicated, their ff dim
      TENSOR-sharded; the w_down contraction psum-combines.

    Either way there is exactly ONE psum over 'model' per layer and no
    global sort/gather.  Shared experts stay on the dense megatron path.
    """
    mesh = _current_mesh()
    B, S, d = x.shape
    ep = int(mesh.shape["model"])
    expert_parallel = n_experts % ep == 0
    e_sel = n_experts // ep if expert_parallel else n_experts
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dtotal = 1
    for a in daxes:
        dtotal *= int(mesh.shape[a])
    if B % dtotal != 0:  # batch not shardable over data: fall back
        return moe_apply(p, x, n_experts=n_experts, top_k=top_k,
                         capacity_factor=capacity_factor,
                         compute_dtype=compute_dtype)
    t_loc = (B // dtotal) * S
    C = expert_capacity(t_loc, n_experts, top_k, capacity_factor)
    bspec = P(daxes if len(daxes) > 1 else daxes[0], None, None)
    wspec = (P("model", None, None) if expert_parallel
             else P(None, None, "model"))
    wdspec = (P("model", None, None) if expert_parallel
              else P(None, "model", None))

    def local(xl, rw, rb, wg, wu, wd):
        xf = xl.reshape(t_loc, d)
        logits = xf.astype(jnp.float32) @ rw + rb  # router in f32
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((n_experts,), jnp.float32).at[top_i.reshape(-1)].add(
            1.0 / (t_loc * top_k))
        aux = n_experts * jnp.sum(me * ce)
        if daxes:
            aux = jax.lax.pmean(aux, daxes)

        e_lo = (jax.lax.axis_index("model") * e_sel) if expert_parallel else 0
        slot_tok, w_slot = _slot_table(top_i, top_p, n_experts=n_experts,
                                       top_k=top_k, C=C, e_lo=e_lo,
                                       e_sel=e_sel)
        y = _expert_ffn(xf, slot_tok, w_slot, wg, wu, wd, e_sel=e_sel, C=C,
                        compute_dtype=compute_dtype)
        y = jax.lax.psum(y, "model")  # combine expert/ff-shard partials
        return y.reshape(xl.shape[0], S, d).astype(xl.dtype), aux

    rb = p["router"].get("b")
    if rb is None:
        rb = jnp.zeros((n_experts,), jnp.float32)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(bspec, P(), P(), wspec, wspec, wdspec),
        out_specs=(bspec, P()),
        check_vma=False)
    y, aux = fn(x, p["router"]["w"].astype(jnp.float32), rb,
                p["w_gate"], p["w_up"], p["w_down"])

    if "shared" in p:
        ys = _shared_ffn(p, x.reshape(B * S, d), compute_dtype)
        y = y + ys.reshape(B, S, d).astype(y.dtype)
    return y, aux


def moe_dispatch(p: Params, x: jnp.ndarray, *, n_experts: int, top_k: int,
                 capacity_factor: float = 1.25, compute_dtype=jnp.bfloat16,
                 impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """impl: 'dense' (jit-SPMD global dispatch, the recorded baseline),
    'ep' (locally-dispatched shard_map), 'auto' (ep when applicable)."""
    if impl == "ep" or (impl == "auto" and ep_applicable(n_experts)):
        return moe_apply_ep(p, x, n_experts=n_experts, top_k=top_k,
                            capacity_factor=capacity_factor,
                            compute_dtype=compute_dtype)
    return moe_apply(p, x, n_experts=n_experts, top_k=top_k,
                     capacity_factor=capacity_factor,
                     compute_dtype=compute_dtype)
