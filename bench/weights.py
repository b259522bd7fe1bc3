"""Seeded weights for the system under test, made by the benchmark.

The program's tree of parameter shapes is read with ``jax.eval_shape`` (no
values); every leaf is then filled from the seed by a rule on its name, in
one jitted call on the device, in the type it is stored in.  The same
arrays feed the program and the plain reference, so neither takes weights
the other made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _path(kp) -> str:
    out = []
    for k in kp:
        out.append(str(getattr(k, "key", getattr(k, "idx", getattr(
            k, "name", k)))))
    return "/".join(out)


def _leaf(key, path: str, shape, dtype, t_init: float):
    """One leaf's values by its name.  Linear and embedding weights are
    normal with the usual fan-in scale; norm scales and SSM constants sit
    near their trained ranges, so every leaf the forward reads matters."""
    name = path.split("/")[-1]
    parent = path.split("/")[-2] if "/" in path else ""
    f32 = jnp.float32
    if name == "table":                        # token embedding (tied)
        v = 0.02 * jax.random.normal(key, shape, f32)
    elif name == "w" and parent.startswith("conv_"):   # depthwise conv (K,C)
        v = jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
    elif name == "w":                          # linear (.., d_in, d_out)
        v = jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
    elif name == "b":
        v = 0.02 * jax.random.normal(key, shape, f32)
    elif name in ("scale", "norm_scale"):
        v = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif name == "A_log":                      # decay rates in [1, 16]
        v = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif name == "D":
        v = jax.random.uniform(key, shape, f32, 0.5, 1.5)
    elif name == "dt_bias":
        # softplus^-1 of a step log-uniform in [1e-3, 1e-1] (Mamba2's
        # time_step_min / time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "a":                          # truncated-basis weights
        v = 0.1 * jax.random.normal(key, shape, f32)
    elif name == "raw_t":                      # softplus^-1(t_init)
        v = jnp.full(shape, math.log(math.expm1(t_init)), f32)
    else:
        raise KeyError(f"no weight rule for parameter {path!r}")
    return v.astype(dtype)


def make(shapes, seed: int, *, t_init: float, out_shardings=None):
    """Fill the tree ``shapes`` (ShapeDtypeStructs) from ``seed`` in one
    jitted call on the device; with ``out_shardings`` (a replicated
    ``NamedSharding`` on a mesh) every device of the mesh makes the same
    values in that call, with no copy made first on one device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path(kp) for kp, _ in flat]

    def init(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(keys[i], paths[i], s.shape, s.dtype, t_init)
            for i, (_, s) in enumerate(flat)])

    if out_shardings is None:
        return jax.jit(init)(jax.random.PRNGKey(seed))
    return jax.jit(init, out_shardings=out_shardings)(
        jax.random.PRNGKey(seed))
