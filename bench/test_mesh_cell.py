"""The four-chip cell at a small size on four virtual CPU devices, past
the harness's look for a chip: the session runs on its ``data:4`` mesh,
the weights are made replicated with the values one device makes, a sound
run comes out correct, and with the timed path broken underneath
``correct`` comes out false, once for each fault the cell can have.

The runs share one child process, started with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (JAX reads it at
start-up): ``python bench/test_mesh_cell.py`` prints their results as one
JSON line."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "granite-8b.storm.data4"
SEED = 2**31 + 29
FAULTS = ("state_unchanged", "half_batch", "token_altered",
          "answer_altered", "exchange_left_out")


def _exchange_left_out(eng):
    """The answers are not gathered across the chips: every chip's block
    of rows reads the first chip's fhat.  (Leaving out the catch-up's one
    collective, the all-reduce of its round count, is no fault: each
    chip's own count covers its rows.)"""
    import jax.numpy as jnp
    fn = eng._catchup
    n = eng.mesh.devices.size

    def broken(*a):
        cache, v, fhat = fn(*a)
        return cache, v, jnp.tile(fhat[:fhat.shape[0] // n], n)
    eng._catchup = broken


def _child() -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import harness
    import test_faults
    import weights
    from conftest import tiny_overrides
    from repro.core import decomposition as deco

    ov = tiny_overrides("granite-8b-chip")
    ov["traffic"]["streams"] = 8                  # 2 rows a device

    def run(patch=None):
        return harness.run_cell(CELL, SEED, 30.0, False, allow_cpu=True,
                                overrides=ov, max_steps=20, patch=patch)

    sound = run()
    out = {"sound": {"correct": sound["correct"],
                     "count": sound["device"]["count"],
                     "metrics": sorted(sound["metrics"])}}
    patches = {"state_unchanged": test_faults._state_unchanged,
               "half_batch": test_faults._half_batch,
               "token_altered": test_faults._token_altered,
               "answer_altered": test_faults._answer_altered,
               "exchange_left_out": _exchange_left_out}
    for name in FAULTS:
        out[name] = run(patches[name])["correct"]

    sz, _ = harness.load_config("granite-8b-chip")
    sz.update(ov["config"])
    cfg = harness.arch_config(sz)
    shapes = jax.eval_shape(lambda k: deco.init_collab_lm(k, cfg),
                            jax.random.PRNGKey(0))
    one = weights.make(shapes, 7, t_init=0.1)
    repl = NamedSharding(Mesh(np.asarray(jax.devices()[:4]), ("data",)),
                         PartitionSpec())
    four = weights.make(shapes, 7, t_init=0.1, out_shardings=repl)
    leaves = list(zip(jax.tree.leaves(one), jax.tree.leaves(four)))
    out["weights"] = {
        "equal": all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in leaves),
        "replicated": all(b.sharding == repl for _, b in leaves),
        "leaves": len(leaves)}
    return out


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__)], cwd=HERE,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_on_four_devices(child):
    got = child["sound"]
    assert got["correct"] and got["count"] == 4
    assert got["metrics"] == ["setup_s", "tokens_per_s"]


def test_weights_are_replicated_with_one_devices_values(child):
    got = child["weights"]
    assert got["leaves"] > 10 and got["equal"] and got["replicated"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught_on_four_devices(child, fault):
    assert child[fault] is False


if __name__ == "__main__":
    sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]
    print(json.dumps(_child()), flush=True)
