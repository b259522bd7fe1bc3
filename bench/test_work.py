"""The least-work functions against hand counts at small shapes, and
against the parameter counts of the program's own trees."""
import jax
import pytest

import harness
import work
from conftest import TINY, tiny_overrides
from peaks import least_seconds, peaks_for


def _sizes(config):
    sz, mod = harness.load_config(config)
    sz.update(tiny_overrides(config)["config"])
    return sz, mod


def _count(tree, skip=()):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return sum(a.size for kp, a in leaves
               if not any(s in jax.tree_util.keystr(kp) for s in skip))


@pytest.mark.parametrize("config", sorted(TINY))
def test_body_params_match_program(config):
    from repro.models import api as model_api
    sz, mod = _sizes(config)
    cfg = harness.arch_config(sz)
    shapes = jax.eval_shape(lambda k: model_api.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    assert mod.body_params(sz) == _count(shapes, skip=("'embed'",))


def test_edge_params_match_program():
    from repro.core import decomposition as deco
    sz, _ = _sizes("granite-8b-chip")
    cfg = harness.arch_config(sz)
    p = jax.eval_shape(lambda k: deco.init_collab_lm(k, cfg),
                       jax.random.PRNGKey(0))
    edge = _count(p["edge"], skip=("'embed'",)) + _count(p["u_head"]) - 1
    assert work.edge_body_params(sz["monitor"]) == edge   # less raw_t


def test_edge_step_hand_count():
    # 1 layer, d 32, 2 heads of 16, d_ff 64, 16 features; rows at 0 and 5
    mon = TINY["granite-8b-chip"]["monitor"]
    body = (4 * 32 * 32 + 3 * 32 * 64 + 2 * 32) + 32 + 32 * 16 + 16
    assert body == 10864
    kv_tok = 1 * 2 * 2 * 16 * 2                 # layers x K,V x heads x hd x 2B
    flops = 2 * body * 2 + 4 * 2 * 16 * (1 + 6)
    nbytes = body * 2 + 2 * (32 * 2 + 4 + kv_tok) + (0 + 5) * kv_tok
    assert work.edge_step(mon, [0, 5]) == (flops, nbytes)


def test_edge_step_reads_the_window_only():
    mon = TINY["granite-8b-chip"]["monitor"]
    assert work.edge_step(mon, [5000]) == work.edge_step(mon, [1023])


def test_catchup_hand_count_dense():
    sz, mod = _sizes("granite-8b-chip")
    # 2 layers, d 64, 4 heads x 16 (2 KV), d_ff 128
    body = 2 * (64 * 16 * (4 + 4) + 4 * 16 * 64 + 3 * 64 * 128 + 2 * 64) + 64
    assert mod.body_params(sz) == body == 74048
    attn = lambda ctx: 2 * 4 * 4 * 16 * ctx      # L x 4 x heads x hd x ctx
    kv = lambda ctx: 2 * 2 * 2 * 16 * 2 * ctx    # L x K,V x kv x hd x 2B
    flops = (2 * body + 2 * 64 + attn(1)) \
        + (2 * body * 2 + 2 * 64 + attn(4) + attn(5))
    nbytes = body * 2 + (kv(0) + 1 * (kv(1) + 64 * 2 + 4)) \
        + (kv(3) + 2 * (kv(1) + 64 * 2 + 4))
    assert work.catchup(mod, sz, [(0, 0), (3, 4)]) == (flops, nbytes)
    assert work.catchup(mod, sz, []) == (0, 0)


def test_catchup_hand_count_hybrid():
    sz, mod = _sizes("zamba2-7b-chip")
    # 2 Mamba2 layers (d 64 -> d_in 128: 2 heads of 64, state 16, conv 4),
    # one invocation of the shared block (4 heads x 16, d_ff 128)
    mamba = (2 * 64 * 128 + 2 * 64 * 16 + 64 * 2 + 128 * 64
             + 5 * (128 + 32) + 3 * 2 + 128 + 64)
    shared = 64 * 16 * 12 + 4 * 16 * 64 + 3 * 64 * 128 + 2 * 64
    body = 2 * mamba + shared + 64
    assert mod.body_params(sz) == mod.matmul_params(sz) == body
    state = 2 * 4 * (2 * 64 * 16 + 3 * (128 + 32))  # f32 SSM state + conv
    assert mod.fixed_state_bytes(sz) == state
    kv = lambda ctx: 1 * 2 * 4 * 16 * 2 * ctx
    attn = lambda ctx: 4 * 4 * 16 * ctx + 2 * 5 * 2 * 64 * 16
    flops = 2 * body * 2 + 2 * 64 + attn(3) + attn(4)
    nbytes = body * 2 + kv(2) + 2 * (kv(1) + 64 * 2 + 4) + 2 * state
    assert work.catchup(mod, sz, [(2, 3)]) == (flops, nbytes)


def test_peaks_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert (p["flops_bf16"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert least_seconds(197e12, 0, p) == (1.0, "compute")
    assert least_seconds(0, 819e9, p) == (1.0, "memory")
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
