"""Minimal functional module substrate.

No flax/haiku in the environment, so parameters are plain nested dicts of
``jnp.ndarray`` ("param trees").  Every layer exposes

    init_<layer>(key, ...) -> params          (pure, shape-only logic)
    <layer>(params, x, ...) -> y              (pure apply)

Path utilities flatten the tree into "/"-joined string paths; the sharding
rule engine (distributed/sharding.py) matches regexes against those paths.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Iterator, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def normal_init(key, shape, dtype, stddev: float = 0.02):
    return (stddev * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def lecun_init(key, shape, dtype, fan_in: int | None = None):
    fan = fan_in if fan_in is not None else shape[0]
    return normal_init(key, shape, dtype, stddev=1.0 / math.sqrt(max(fan, 1)))


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Linear
# ---------------------------------------------------------------------------


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32, stddev: float | None = None) -> Params:
    kw, _ = jax.random.split(key)
    sd = stddev if stddev is not None else 1.0 / math.sqrt(d_in)
    p: Params = {"w": normal_init(kw, (d_in, d_out), dtype, sd)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jnp.ndarray, *, compute_dtype=None) -> jnp.ndarray:
    w = p["w"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Param tree utilities
# ---------------------------------------------------------------------------


def _is_container(tree) -> bool:
    """list/tuple nodes to descend into.  PartitionSpec subclasses tuple but
    is a LEAF (a spec per array), as is any NamedTuple-style cache record —
    descending into them mangles spec trees (e.g. 'embed/table/0')."""
    if not isinstance(tree, (list, tuple)):
        return False
    from jax.sharding import PartitionSpec
    return not (isinstance(tree, PartitionSpec) or hasattr(tree, "_fields"))


def iter_paths(tree: Params, prefix: str = "") -> Iterator[Tuple[str, jnp.ndarray]]:
    """Yield ("a/b/c", leaf) pairs in deterministic order."""
    if isinstance(tree, dict):
        for k in sorted(tree.keys()):
            yield from iter_paths(tree[k], f"{prefix}/{k}" if prefix else str(k))
    elif _is_container(tree):
        for i, v in enumerate(tree):
            yield from iter_paths(v, f"{prefix}/{i}" if prefix else str(i))
    else:
        yield prefix, tree


def map_with_path(fn: Callable[[str, Any], Any], tree: Params, prefix: str = ""):
    """Map ``fn(path, leaf)`` over the tree, preserving structure."""
    if isinstance(tree, dict):
        return {k: map_with_path(fn, v, f"{prefix}/{k}" if prefix else str(k))
                for k, v in tree.items()}
    if _is_container(tree):
        t = type(tree)
        return t(map_with_path(fn, v, f"{prefix}/{i}" if prefix else str(i))
                 for i, v in enumerate(tree))
    return fn(prefix, tree)


def param_count(tree: Params) -> int:
    return sum(int(l.size) for _, l in iter_paths(tree) if hasattr(l, "size"))


def param_bytes(tree: Params) -> int:
    return sum(int(l.size) * l.dtype.itemsize
               for _, l in iter_paths(tree) if hasattr(l, "size"))


def cast_tree(tree: Params, dtype) -> Params:
    return jax.tree.map(
        lambda l: l.astype(dtype) if jnp.issubdtype(l.dtype, jnp.floating) else l,
        tree)


# ---------------------------------------------------------------------------
# Per-row decode
# ---------------------------------------------------------------------------


def rowwise(fn: Callable, pos) -> Callable:
    """``fn(pos, *rows)`` over a batch of rows (each arg a tree of arrays
    with the batch on axis 0), at one position or at a position per row.

    With a scalar ``pos`` it is ``fn`` itself: the rows decode as one
    batch.  With a (B,) ``pos`` every row runs alone at its own position,
    as a batch of one (``vmap`` over the rows, each re-batched to size 1),
    so a row's numerics depend neither on the rows beside it nor on how
    the batch is sharded.  Callers keep cache reads and writes outside
    ``fn``: those stay batch-level, in place on the stacked cache."""
    if jnp.ndim(pos) == 0:
        return lambda *rows: fn(pos, *rows)

    def one(p, *rows):
        out = fn(p, *jax.tree.map(lambda a: a[None], rows))
        return jax.tree.map(lambda a: a[0], out)

    return lambda *rows: jax.vmap(one)(pos, *rows)
