"""The benchmark's own tests (``python -m pytest bench/``) run on the CPU
at small sizes; the benchmark and the system under test are importable."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.join(os.path.dirname(HERE), "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import copy  # noqa: E402
import json  # noqa: E402

import pytest  # noqa: E402

# the cells' configurations cut to a size the CPU runs in seconds; the
# traffic keeps its mix and loop, with 4 streams of 24 tokens
TINY_MONITOR = {"n_layers": 1, "d_model": 32, "n_heads": 2, "d_ff": 64,
                "n_features": 16, "t_init": 0.1, "s": 0.2}
TINY = {
    "granite-8b-chip": {"num_hidden_layers": 2, "hidden_size": 64,
                        "num_attention_heads": 4, "num_key_value_heads": 2,
                        "intermediate_size": 128, "vocab_size": 256,
                        "monitor": TINY_MONITOR},
    "zamba2-7b-chip": {"num_hidden_layers": 2, "hidden_size": 64,
                       "num_attention_heads": 4, "num_key_value_heads": 4,
                       "ffn_hidden_size": 128, "mamba_d_state": 16,
                       "vocab_size": 256, "monitor": TINY_MONITOR},
}
TINY_TRAFFIC = {"streams": 4, "max_len": 24}


def tiny_overrides(config: str) -> dict:
    cfg = copy.deepcopy(TINY[config])
    if config.startswith("zamba2"):
        with open(os.path.join(HERE, "configs", f"{config}.json")) as f:
            prog = json.load(f)["program"]
        prog["set"]["shared_attn_every"] = 2
        cfg["program"] = prog
    return {"config": cfg, "traffic": dict(TINY_TRAFFIC)}


@pytest.fixture(autouse=True, scope="session")
def _cpu_compile_cache(tmp_path_factory):
    """Keep the tests' CPU programs out of the checkout's compile cache."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    yield
