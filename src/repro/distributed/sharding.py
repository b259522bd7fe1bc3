"""Partition-rule engine: regex over param-tree key paths -> PartitionSpec.

Rules give *candidate* specs aligned to the TRAILING dims of each leaf
(stacked-layer leading axes are padded with None automatically).  The first
candidate whose named axes divide the corresponding dims is chosen;
otherwise the leaf replicates.  This one mechanism covers all 10 assigned
families — e.g. MoE experts shard expert-parallel where E % model == 0
(deepseek, 256) and fall back to d_ff tensor-parallel where not (mixtral, 8).

The monitor tower ('edge', 'u_head', 'v_head' subtrees) is ALWAYS
replicated over 'model' — the paper's device-locality requirement: the edge
path must not require model-axis collectives (asserted in tests by parsing
the lowered HLO of monitor_step).
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from repro.nn.module import map_with_path


def abstract_mesh(axis_sizes: Sequence[int],
                  axis_names: Sequence[str]) -> AbstractMesh:
    """A device-free mesh for the partition-rule checks."""
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))

# Candidate trailing-dim specs per path regex (first match wins; within a
# match, first divisible candidate wins).  "model" is the tensor axis;
# batch axes are handled by activation/batch specs, not these rules.
_RULES: List[Tuple[str, List[Tuple[Optional[str], ...]]]] = [
    # --- paper monitor tower: strictly replicated ---------------------------
    (r"(^|/)(edge|u_head|v_head)(/|$)", [()]),
    # --- embeddings ----------------------------------------------------------
    (r"embed/table$", [("model", None), (None, "model", None)]),
    # --- MoE (E, d, ff) / (E, ff, d): expert-parallel first, else TP on ff ---
    (r"moe/w_(gate|up)$", [("model", None, None), (None, None, "model")]),
    (r"moe/w_down$", [("model", None, None), (None, "model", None)]),
    (r"moe/shared/w_(gate|up)$", [(None, "model")]),
    (r"moe/shared/w_down$", [("model", None)]),
    (r"moe/router/", [()]),
    # --- attention: column-parallel in, row-parallel out ---------------------
    (r"(wq|wk|wv|wq_a|wq_b|wkv_b)/w$", [(None, "model")]),
    (r"(wq|wk|wv)/b$", [("model",)]),
    (r"wkv_a/w$", [()]),  # MLA latent proj output is tiny (kv_lora+rope)
    (r"(wo|w_out)/w$", [("model", None)]),
    # --- dense MLP -----------------------------------------------------------
    (r"mlp/w_(gate|up)/w$", [(None, "model")]),
    (r"mlp/w_down/w$", [("model", None)]),
    # --- Mamba2 split streams -------------------------------------------------
    (r"(w_z|w_x)/w$", [(None, "model")]),
    (r"(w_B|w_C)/w$", [()]),
    (r"w_dt/w$", [(None, "model")]),
    (r"conv_x/w$", [(None, "model")]),
    (r"conv_x/b$", [("model",)]),
    (r"conv_[BC]/", [()]),
    (r"(A_log|D|dt_bias)$", [("model",)]),
    (r"mamba/norm_scale$", [("model",)]),
    (r"out_proj/w$", [("model", None)]),
    # --- xLSTM ----------------------------------------------------------------
    (r"(w_i|w_f|w_o|w_z)/w$", [(None, "model")]),
    (r"r_[zifo]$", [()]),
    # --- everything else (norms, gates, scalars): replicate --------------------
    (r".*", [()]),
]


def _choose(shape: Tuple[int, ...], candidates, mesh: Mesh) -> P:
    for cand in candidates:
        if len(cand) > len(shape):
            continue
        if not any(ax is not None for ax in cand):
            return P()  # canonical replication
        spec = (None,) * (len(shape) - len(cand)) + tuple(cand)
        ok = True
        for dim, ax in zip(shape, spec):
            if ax is not None and dim % mesh.shape[ax] != 0:
                ok = False
                break
        if ok:
            return P(*spec)
    return P()


def param_specs(tree: Any, mesh: Mesh) -> Any:
    """Param tree (of arrays or ShapeDtypeStructs) -> PartitionSpec tree."""

    def assign(path: str, leaf) -> P:
        if leaf is None or not hasattr(leaf, "shape"):
            return P()
        for pattern, candidates in _RULES:
            if re.search(pattern, path):
                return _choose(leaf.shape, candidates, mesh)
        return P()

    return map_with_path(assign, tree)


def param_shardings(tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs(tree, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Batch / cache / activation specs
# ---------------------------------------------------------------------------


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_spec(mesh: Mesh, leaf_shape: Tuple[int, ...], batch_size: int) -> P:
    """Shard dim0 (batch) over pod+data where divisible; else replicate."""
    axes = data_axes(mesh)
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    if batch_size % total == 0 and axes:
        first = axes if len(axes) > 1 else axes[0]
        return P(first, *((None,) * (len(leaf_shape) - 1)))
    # fall back to data-only or replication (long_500k: batch 1)
    if "data" in mesh.shape and batch_size % mesh.shape["data"] == 0:
        return P("data", *((None,) * (len(leaf_shape) - 1)))
    return P()


def batch_shardings(batch_tree: Any, mesh: Mesh) -> Any:
    def assign(leaf):
        if not hasattr(leaf, "shape") or not leaf.shape:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, batch_spec(mesh, leaf.shape, leaf.shape[0]))
    return jax.tree.map(assign, batch_tree)


def cache_specs(cache_tree: Any, mesh: Mesh, batch: int, *,
                use_model: bool = True, mode: str = "heads") -> Any:
    """KV/SSM cache sharding.  Cache leaves are layer-stacked (sometimes
    doubly: super-blocks x inner layers): (..., B, C, n_kv, hd) /
    (..., B, H, P, N).  The batch axis (first axis whose size == ``batch``)
    shards over data; the model axis goes to one of:

    - mode="heads" (baseline): the last trailing dim (>= 2 past the batch
      axis, so the cache-time axis indexed by dynamic_update_slice stays
      unsharded) divisible by the model axis — kv-heads where divisible,
      else head_dim, else replicated (DESIGN.md §6).
    - mode="time" (flash-decode, §Perf hillclimb B): the cache TIME axis
      (batch axis + 1) shards over model; each model shard scores its slice
      of the context locally and the softmax/output reductions become small
      cross-shard collectives.  The dynamic_update_slice at ``pos`` lowers
      to a masked per-shard update.
    """
    model = mesh.shape.get("model", 1)
    # a mesh without a model axis (serving data-parallel meshes, e.g.
    # "data:8") must never emit a "model" spec entry — model % 1 == 0
    # would otherwise qualify every trailing dim
    use_model = use_model and "model" in mesh.shape
    daxes = data_axes(mesh)
    dtotal = 1
    for a in daxes:
        dtotal *= mesh.shape[a]

    def assign(leaf):
        if leaf is None or not hasattr(leaf, "shape") or leaf.ndim < 2:
            return P()
        spec: List = [None] * leaf.ndim
        try:
            baxis = next(i for i in range(leaf.ndim - 1)
                         if leaf.shape[i] == batch)
        except StopIteration:
            return P()
        if batch % dtotal == 0 and daxes:
            spec[baxis] = daxes if len(daxes) > 1 else daxes[0]
        elif "data" in mesh.shape and batch % mesh.shape["data"] == 0:
            spec[baxis] = "data"
        if use_model and mode == "time":
            taxis = baxis + 1
            if (taxis < leaf.ndim
                    and leaf.shape[taxis] % model == 0
                    and leaf.shape[taxis] >= model):
                spec[taxis] = "model"
            return P(*spec)
        if use_model:
            for ax in range(leaf.ndim - 1, baxis + 1, -1):
                if leaf.shape[ax] % model == 0 and leaf.shape[ax] >= model:
                    spec[ax] = "model"
                    break
        return P(*spec)

    return jax.tree.map(assign, cache_tree)


def cache_shardings(cache_tree: Any, mesh: Mesh, batch: int, *,
                    use_model: bool = True, mode: str = "heads") -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        cache_specs(cache_tree, mesh, batch,
                                    use_model=use_model, mode=mode),
                        is_leaf=lambda x: isinstance(x, P))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def opt_specs(tree: Any, mesh: Mesh, *, zero1: bool = False) -> Any:
    """Optimizer-moment PartitionSpecs.  zero1=False: mirror the params
    (the recorded baseline).  zero1=True (§Perf A3): additionally shard each
    moment leaf over the data axes on its first free divisible dim —
    ZeRO-1-style state partitioning (the update step reshards once per step,
    amortised over the whole layer stack)."""
    specs = param_specs(tree, mesh)
    if not zero1:
        return specs
    daxes = data_axes(mesh)
    dtotal = 1
    for a in daxes:
        dtotal *= mesh.shape[a]
    dname = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    if dname is None or dtotal == 1:
        return specs

    def widen(leaf, spec: P) -> P:
        if leaf is None or not hasattr(leaf, "shape") or leaf.ndim == 0:
            return spec
        s = list(spec) + [None] * (leaf.ndim - len(spec))
        for ax in range(leaf.ndim):
            if s[ax] is None and leaf.shape[ax] % dtotal == 0 \
                    and leaf.shape[ax] >= dtotal:
                s[ax] = dname
                return P(*s)
        return spec

    flat_p, treedef = jax.tree.flatten(tree)
    flat_s = treedef.flatten_up_to(specs)
    return jax.tree.unflatten(
        treedef, [widen(l, s) for l, s in zip(flat_p, flat_s)])


def opt_shardings(tree: Any, mesh: Mesh, *, zero1: bool = False) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        opt_specs(tree, mesh, zero1=zero1),
                        is_leaf=lambda x: isinstance(x, P))
