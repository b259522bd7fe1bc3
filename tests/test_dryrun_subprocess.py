"""Dry-run machinery validated in a SUBPROCESS with 8 placeholder devices
(the main pytest process must keep the real single-device view).

Covers: lowering+compiling the collaborative train/serve steps of a smoke
config on a small (4 data x 2 model) mesh, and the paper's device-locality
guarantee — the monitor-only step's HLO contains NO model-axis collectives.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import ShapeConfig
from repro.core import decomposition as deco
from repro.distributed import sharding as shd
from repro.launch.dryrun import build_shardings
from repro.launch.steps import step_and_specs, make_monitor_step, EDGE_CACHE_LEN
from repro.models import api as model_api

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
out = {}
for arch in ("granite-8b", "deepseek-v3-671b", "zamba2-7b"):
    cfg = registry.get_smoke(arch)
    for kind, shape in (("train", ShapeConfig("t", 64, 8, "train")),
                        ("decode", ShapeConfig("d", 64, 8, "decode"))):
        step, args = step_and_specs(cfg, shape)
        shards = build_shardings(args, cfg, shape, mesh)
        with jax.set_mesh(mesh):
            c = jax.jit(step, in_shardings=shards).lower(*args).compile()
        out[f"{arch}/{kind}"] = "ok"

# monitor-step locality: lowered HLO must not touch the model axis
cfg = registry.get_smoke("granite-8b")
ecfg = deco.edge_arch(cfg)
params = jax.eval_shape(lambda: deco.init_collab_lm(jax.random.PRNGKey(0), cfg))
edge_cache = jax.eval_shape(lambda: model_api.init_cache(ecfg, 8, 64))
import jax.numpy as jnp
tokens = jax.ShapeDtypeStruct((8,), jnp.int32)
pos = jax.ShapeDtypeStruct((), jnp.int32)
mstep = make_monitor_step(cfg)
shards = (shd.param_shardings(params, mesh),
          shd.cache_shardings(edge_cache, mesh, 8, use_model=False),
          NamedSharding(mesh, P("data")), NamedSharding(mesh, P()))
with jax.set_mesh(mesh):
    txt = jax.jit(mstep, in_shardings=shards).lower(
        params, edge_cache, tokens, pos).compile().as_text()
bad = []
for line in txt.splitlines():
    for op in ("all-reduce(", "all-gather(", "reduce-scatter(", "all-to-all("):
        if op in line and "replica_groups" in line:
            # model-axis groups have non-contiguous or stride-2 membership;
            # conservative: any collective at all is flagged except scalar
            # loss-style reductions over the data axis (size-4 groups of
            # stride 2 == data axis on this 4x2 mesh -> {0,2,4,6})
            bad.append(line.strip()[:160])
out["monitor_collectives"] = bad
print(json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_small_mesh(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    for k, v in out.items():
        if k != "monitor_collectives":
            assert v == "ok", (k, v)
    # the paper's locality requirement: the edge path runs without ANY
    # cross-device collective (its params and cache are replicated/batch-only)
    model_collectives = [l for l in out["monitor_collectives"]
                         if "{0,1}" in l or "{2,3}" in l or "{4,5}" in l]
    assert not model_collectives, model_collectives
