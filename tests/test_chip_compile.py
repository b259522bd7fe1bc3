"""The serving path's programs compile for a TPU v5e at the CHIP preset's
published widths, here on the CPU, against a described (not attached) chip.

Nothing runs: these catch what the TPU compiler refuses (tiling, fast-memory
limits, programs that do not fit) before any chip time is spent.  The
topology is described only inside the module fixture: describing it loads
libtpu, which one process at a time may hold, so it must happen in the test
process that was handed this file, never at import or collection.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.models import api as model_api

B, MAX_LEN = 32, 2048  # chip_smoke.py's serving batch and cache length


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def cfg():
    return registry.get_preset("granite-8b", "chip")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


def test_monitor_combine_compiles_to_kernel(one_chip):
    from repro.kernels.monitor_combine import monitor_combine
    v = _spec((B,), jnp.float32, one_chip)
    txt = jax.jit(lambda u, v, f: monitor_combine(
        u, v, f, s=0.2, interpret=False)).lower(v, v, v).compile().as_text()
    assert "tpu_custom_call" in txt


def test_decode_attention_at_preset_shapes(one_chip, cfg):
    from repro.nn.attention import decode_attention
    h = cfg.resolved_head_dim
    q = _spec((B, cfg.n_heads, h), jnp.bfloat16, one_chip)
    kv = _spec((B, MAX_LEN, cfg.n_kv_heads, h), jnp.bfloat16, one_chip)
    pos = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(decode_attention).lower(q, kv, kv, pos).compile()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == B * cfg.n_heads * h * 2  # (B, heads, head_dim) bf16


@pytest.mark.parametrize("per_row", [False, True],
                         ids=["batched", "catchup_per_row"])
def test_preset_decode_layer(one_chip, cfg, per_row):
    """One layer of the preset's decode step at batch 32 x 2048: the batched
    decode (edge/scan paths) and the catch-up's per-row masked decode."""
    from repro.serving.engine import make_step_at
    c1 = cfg.replace(n_layers=1)
    params = _specs(jax.eval_shape(
        lambda: model_api.init_model(jax.random.PRNGKey(0), c1)), one_chip)
    cache = _specs(jax.eval_shape(
        lambda: model_api.init_cache(c1, B, MAX_LEN)), one_chip)
    toks = _spec((B,), jnp.int32, one_chip)
    if per_row:
        step = make_step_at(c1, with_logits=False)
        args = (params, cache, toks, toks, _spec((B,), jnp.bool_, one_chip))
    else:
        def step(p, c, t, pos):
            return model_api.decode_step(p, c1, c, t, pos, with_logits=False)
        args = (params, cache, toks, _spec((), jnp.int32, one_chip))
    m = jax.jit(step).lower(*args).compile().memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < 16e9, f"one layer needs {total / 1e9:.2f} GB"

