"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production mesh, with ShapeDtypeStruct inputs (no allocation), and
extract the roofline terms.

MUST set the placeholder device count before ANY other import — jax locks
the device count at first initialisation.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import INPUT_SHAPES, SHAPES_BY_NAME  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.core import decomposition as deco  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import roofline as rf  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import step_and_specs  # noqa: E402
from repro.nn.module import iter_paths  # noqa: E402
from repro.training.optimizer import AdamState  # noqa: E402


def _active_params(params_shapes, cfg) -> float:
    """Exact ACTIVE server-param count from the eval_shape tree: routed
    expert weights are scaled by top_k/n_experts."""
    total = routed = 0
    for path, leaf in iter_paths(params_shapes["server"]):
        if leaf is None or not hasattr(leaf, "size"):
            continue
        total += int(leaf.size)
        if "/moe/w_" in ("/" + path) or path.split("/")[-2:-1] == ["moe"]:
            if "/shared/" not in "/" + path and "/router" not in "/" + path:
                routed += int(leaf.size)
    if cfg.is_moe and routed:
        active = total - routed + routed * cfg.top_k / cfg.n_experts
    else:
        active = total
    return float(active), float(total)


def _model_flops(cfg, shape, params_shapes) -> float:
    active, _ = _active_params(params_shapes, cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        return 2.0 * active * shape.global_batch * shape.seq_len
    return 2.0 * active * shape.global_batch  # decode: one token per stream


def build_shardings(step_args, cfg, shape, mesh):
    """Shardings matching step_and_specs arg order for each step kind."""
    params = step_args[0]
    pshard = shd.param_shardings(params, mesh)
    rep = shd.replicated(mesh)
    if shape.kind == "train":
        _, opt_state, batch = step_args
        oshard = shd.opt_shardings(params, mesh, zero1=cfg.zero1)
        opt_shard = AdamState(count=rep, m=oshard, v=oshard)
        return (pshard, opt_shard, shd.batch_shardings(batch, mesh))
    if shape.kind == "prefill":
        _, batch = step_args
        return (pshard, shd.batch_shardings(batch, mesh))
    _, server_cache, edge_cache, tokens, pos = step_args
    B = shape.global_batch
    return (pshard,
            shd.cache_shardings(server_cache, mesh, B,
                                mode=cfg.decode_cache_shard),
            shd.cache_shardings(edge_cache, mesh, B, use_model=False),
            shd.batch_shardings({"t": tokens}, mesh)["t"],
            rep)


def _compile(cfg, shape, mesh):
    step_fn, args = step_and_specs(cfg, shape)
    in_shardings = build_shardings(args, cfg, shape, mesh)
    # NOTE (§Perf B3, refuted): donating the KV caches (in-place update) is
    # the deployment-correct choice on TPU, but the CPU backend inserts
    # extra copies under donation+sharding and the cost model penalises it
    # (+12% memory term, +10 GiB args+temp) — so the dry-run measures the
    # undonated form.
    with jax.set_mesh(mesh):
        return (jax.jit(step_fn, in_shardings=in_shardings)
                .lower(*args).compile(), args)


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True, overrides: Optional[Dict] = None,
             skip_probes: bool = False) -> Dict:
    """One (arch x shape x mesh) dry-run record.

    1) FULL production program (rolled scans) lowered+compiled on the mesh —
       proves sharding coherence and yields memory_analysis.
    2) Small UNROLLED probe compiles (launch/layer_costs.py) -> faithful
       per-device FLOPs / bytes / collective bytes, linear in layer counts.
    """
    from repro.launch import layer_costs as lc

    cfg = registry.get_full(arch).replace(**(overrides or {}))
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_desc = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size

    t0 = time.time()
    compiled_full, args = _compile(cfg, shape, mesh)
    t_full = time.time() - t0
    mem = compiled_full.memory_analysis()
    params_shapes = args[0]

    if skip_probes:
        costs = rf.cost_dict(compiled_full)
    else:
        full_counts, probes = lc.probe_plan(cfg)
        probe_costs = {}
        for name, counts in probes:
            cfg_p = lc.with_counts(cfg, counts).replace(scan_unroll=True)
            compiled_p, _ = _compile(cfg_p, shape, mesh)
            probe_costs[name] = rf.cost_dict(compiled_p)
        costs = lc.extrapolate(full_counts, probe_costs)
    t_probes = time.time() - t0 - t_full

    mf = _model_flops(cfg, shape, params_shapes)
    roof = rf.analyze_costs(costs, arch=arch, shape=shape_name,
                            mesh_desc=mesh_desc, chips=chips, model_flops=mf)
    rec = roof.as_dict()
    rec.update({
        "chips": chips,
        "compile_full_s": round(t_full, 1), "compile_probes_s": round(t_probes, 1),
        "memory_analysis": {
            k: int(getattr(mem, k, 0)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
        } if mem is not None else None,
    })
    if verbose:
        print(rf.fmt_row(roof), flush=True)
        if mem is not None:
            gb = (rec["memory_analysis"]["argument_size_in_bytes"]
                  + rec["memory_analysis"]["temp_size_in_bytes"]) / 2**30
            print(f"    args+temp per device: {gb:.2f} GiB   "
                  f"full-compile {t_full:.0f}s probes {t_probes:.0f}s", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else registry.names()
    shapes = [args.shape] if args.shape else [s.name for s in INPUT_SHAPES]
    results = []
    for a in archs:
        for s in shapes:
            try:
                rec = run_pair(a, s, multi_pod=args.multi_pod)
                rec["status"] = "ok"
            except Exception as e:  # a failure here is a sharding bug
                traceback.print_exc()
                rec = {"arch": a, "shape": s, "status": "FAIL",
                       "error": repr(e)}
            results.append(rec)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(json.dumps(rec) + "\n")
    n_ok = sum(r["status"] == "ok" for r in results)
    print(f"\n{n_ok}/{len(results)} pairs lowered+compiled OK")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
