"""Expert-parallel (shard_map) MoE vs the dense-dispatch oracle.

Runs in a subprocess with 8 placeholder devices (mesh 2x4) so the session's
single-device tests are unaffected (same pattern as test_dryrun_subprocess).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.nn.moe import init_moe, moe_apply, moe_apply_ep, ep_applicable

E, K, D, F = 8, 2, 64, 128
B, S = 4, 32
key = jax.random.PRNGKey(0)
p = init_moe(key, D, F, E, n_shared=1)
x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, D), jnp.float32)

y_ref, aux_ref = moe_apply(p, x, n_experts=E, top_k=K, compute_dtype=jnp.float32)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    assert ep_applicable(E), "ep must be applicable on 2x4 mesh with E=8"
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    y_ep, aux_ep = jax.jit(lambda p, x: moe_apply_ep(
        p, x, n_experts=E, top_k=K, compute_dtype=jnp.float32))(p, xs)

# Same capacity semantics only when no tokens are dropped in either scheme;
# with cf=1.25 a few drops can differ (global vs per-shard ranking), so
# compare with a tolerance on the overwhelming majority of positions.
y_ref, y_ep = np.asarray(y_ref), np.asarray(y_ep)
close = np.isclose(y_ref, y_ep, rtol=2e-4, atol=2e-4)
frac = close.mean()
assert frac > 0.97, f"only {frac:.4f} of outputs match"
assert abs(float(aux_ref) - float(aux_ep)) < 5e-2, (aux_ref, aux_ep)

# gradient flows through the ep path
def loss(p, x):
    y, aux = moe_apply_ep(p, x, n_experts=E, top_k=K, compute_dtype=jnp.float32)
    return jnp.sum(y ** 2) + aux
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(p, xs)
for leaf in jax.tree.leaves(g):
    assert np.isfinite(np.asarray(leaf)).all()
print("EP_OK", frac)

# --- TP-ff variant: E=6 not divisible by model=4 -> ff tensor-sharded ------
E2 = 6
p2 = init_moe(jax.random.fold_in(key, 7), D, F, E2)
y2_ref, aux2_ref = moe_apply(p2, x, n_experts=E2, top_k=K,
                             compute_dtype=jnp.float32)
with jax.set_mesh(mesh):
    y2_ep, aux2_ep = jax.jit(lambda p, x: moe_apply_ep(
        p, x, n_experts=E2, top_k=K, compute_dtype=jnp.float32))(p2, xs)
y2_ref, y2_ep = np.asarray(y2_ref), np.asarray(y2_ep)
frac2 = np.isclose(y2_ref, y2_ep, rtol=2e-4, atol=2e-4).mean()
assert frac2 > 0.97, f"tp-ff: only {frac2:.4f} match"
print("TP_OK", frac2)
"""


@pytest.mark.slow
def test_moe_ep_matches_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "EP_OK" in r.stdout
