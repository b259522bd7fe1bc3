#!/usr/bin/env python3
"""Read the control of a cell's comparison: the plain reference on weights
rounded to fp8 e4m3 (the precision below the configuration's bfloat16) put
in the program's place, compared with the float32 reference exactly as a
run's window is, over the tokens a window of the cell serves.

    python bench/control.py --workload granite-8b.storm --steps 1024 \
        --seeds 11 12 13

Prints one JSON line per seed with the numbers ``check.compare`` limits;
each has to exceed its limit for the comparison to have power.  The
benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]


def readings(workload: str, seed: int, steps: int, *, allow_cpu=False,
             overrides=None) -> dict:
    import jax
    import check
    import harness
    import loadgen
    import weights
    from repro.core import decomposition as deco
    cell = {c["name"]: c for c in harness.load_benchmark()["workloads"]}[
        workload]
    sz, mod = harness.load_config(cell["config"])
    traffic = loadgen.load_traffic(cell["traffic"])
    overrides = overrides or {}
    sz.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))
    harness.find_devices(1, allow_cpu)
    harness.enable_compile_cache()
    cfg = harness.arch_config(sz)
    shapes = jax.eval_shape(lambda k: deco.init_collab_lm(k, cfg),
                            jax.random.PRNGKey(0))
    params = weights.make(shapes, loadgen.jax_seed(seed, loadgen.WEIGHTS),
                          t_init=sz["monitor"]["t_init"])
    T = check.padded(steps, traffic["max_len"])
    toks = loadgen.streams(traffic, cfg.vocab_size, seed, loadgen.COHORT)
    trig = loadgen.triggers(traffic, seed, loadgen.COHORT).T
    got = check.control(check.Reference(sz, mod), params, toks[:, :T],
                        trig[:, :T])
    return {"workload": workload, "seed": seed, "steps": T,
            **got, "limits": {k: check.LIMITS[k] for k in got}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.steps)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
