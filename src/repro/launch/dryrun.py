"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: the SPMD
partitioner must accept every sharding, the compiled per-device program's
memory_analysis must fit a v5e (16 GB), and cost/collective analysis feeds
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b \
      --shapes train_4k,prefill_32k --mesh both --out experiments/dryrun
"""
# The host platform must present 512 placeholder devices BEFORE jax (or
# anything importing jax) initializes — these two lines must stay first.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import ALL_ARCHS, SHAPES, TrainConfig, get_config, shapes_for  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs  # noqa: E402
from repro.models.transformer import decode_step, init_defs, prefill_logits  # noqa: E402
from repro.optim.adamw import adamw_init  # noqa: E402
from repro.parallel.sharding import (  # noqa: E402
    batch_specs,
    cache_specs,
    make_plan,
    make_sharder,
    param_shardings,
)
from repro.parallel.spec import abstract  # noqa: E402
from repro.roofline.analysis import (  # noqa: E402
    HW,
    V5E,
    collective_bytes,
    hw_for,
    model_flops,
    roofline_report,
)
from repro.train.loop import make_train_step  # noqa: E402

V5E_HBM = 16 * 1024**3

# per-(arch, shape) gradient-accumulation overrides (memory fit, §Perf log)
MICROBATCHES: dict[tuple[str, str], int] = {}


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_cell(cfg, shape, mesh, force_big=None):
    """Returns (jitted_fn, abstract_args) for one dry-run cell."""
    plan = make_plan(
        cfg, mesh, force_big=force_big, inference=shape.kind != "train"
    )
    sh = make_sharder(cfg, mesh, plan, shape.kind, shape.global_batch)
    pspecs = param_shardings(cfg, mesh, plan)
    specs = input_specs(cfg, shape)
    bspecs = batch_specs(cfg, plan, shape.kind, shape.global_batch)
    rep = NamedSharding(mesh, P())

    if shape.kind == "train":
        params_abs = abstract(init_defs(cfg), jnp.float32)
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        p_sh = _named(mesh, pspecs)
        o_sh = {"m": p_sh, "v": p_sh, "count": rep}
        mb = MICROBATCHES.get((cfg.name, shape.name), 1)
        tcfg = TrainConfig(remat=True, microbatches=mb)
        step = make_train_step(cfg, tcfg, sh=sh, grad_shardings=p_sh)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, _named(mesh, bspecs), rep),
            out_shardings=(p_sh, o_sh, rep),
            donate_argnums=(0, 1),
        )
        args = (params_abs, opt_abs, specs["batch"], jax.ShapeDtypeStruct((), jnp.int32))
        return fn, args

    params_abs = abstract(init_defs(cfg), jnp.bfloat16)
    p_sh = _named(mesh, pspecs)
    if shape.kind == "prefill":
        fn = jax.jit(
            lambda p, b: prefill_logits(p, b, cfg, sh=sh),
            in_shardings=(p_sh, _named(mesh, bspecs)),
            out_shardings=rep,
        )
        return fn, (params_abs, specs["batch"])

    # decode: serve_step over the full cache
    cache_abs = specs["cache"]
    cspecs = cache_specs(cfg, plan, cache_abs, shape.global_batch)
    c_sh = _named(mesh, cspecs)
    fn = jax.jit(
        lambda p, c, b, pos: decode_step(p, c, b, pos, cfg, sh=sh),
        in_shardings=(p_sh, c_sh, _named(mesh, bspecs), rep),
        out_shardings=(rep, c_sh),
        donate_argnums=(1,),
    )
    return fn, (params_abs, cache_abs, specs["batch"], jax.ShapeDtypeStruct((), jnp.int32))


def _cost_numbers(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    byt = float(
        cost.get("bytes accessed", 0.0)
        or sum(v for k, v in cost.items() if k.startswith("bytes accessed"))
    )
    return {"flops": flops, "bytes": byt, "coll": coll}


def calibrated_costs(cfg, shape, mesh, force_big: bool) -> dict:
    """Exact per-device costs via two *unrolled* small-depth compiles.

    XLA's cost_analysis counts while-loop (lax.scan) bodies once, so the
    scanned production compile undercounts per-layer flops/bytes/collectives
    by ~n_superblocks x.  Costs are linear in layer count L, so we compile
    unrolled models at L=p and L=2p (p = pattern length, full widths) and
    extrapolate: cost(L) = overhead + per_layer * L.
    """
    import dataclasses

    p = len(cfg.attn_pattern)
    nums = []
    for L in (p, 2 * p):
        c = dataclasses.replace(cfg, n_layers=L, scan_layers=False)
        fn, args = build_cell(c, shape, mesh, force_big=force_big)
        nums.append(_cost_numbers(fn.lower(*args).compile()))
    L1, L2, L = p, 2 * p, cfg.n_layers

    def lin(v1, v2):
        slope = (v2 - v1) / (L2 - L1)
        return max(v1 + slope * (L - L1), 0.0)

    out = {
        "flops": lin(nums[0]["flops"], nums[1]["flops"]),
        "bytes": lin(nums[0]["bytes"], nums[1]["bytes"]),
        "coll": {
            k: lin(nums[0]["coll"][k], nums[1]["coll"][k])
            for k in nums[0]["coll"]
            if k != "counts"
        },
    }
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             hw: HW = hw_for(V5E)) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    force_big = cfg.param_count() > 8e9
    t0 = time.time()
    fn, args = build_cell(cfg, shape, mesh)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    raw = _cost_numbers(compiled)
    cal = calibrated_costs(cfg, shape, mesh, force_big)

    flops_dev = cal["flops"]
    bytes_dev = cal["bytes"]
    coll = cal["coll"]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mflops = model_flops(cfg, shape.kind, tokens)
    report = roofline_report(flops_dev, bytes_dev, coll["total"], hw=hw)

    out = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev,
        "hbm_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll["total"],
        "collectives": {k: v for k, v in coll.items() if k != "counts"},
        "collective_counts": raw["coll"]["counts"],
        "raw_scanned": {
            "flops": raw["flops"],
            "bytes": raw["bytes"],
            "coll_total": raw["coll"]["total"],
        },
        "model_flops_total": mflops,
        "model_flops_per_device": mflops / n_dev,
        "useful_flops_ratio": (mflops / n_dev) / flops_dev if flops_dev else 0.0,
        "roofline": report,
    }
    if mem is not None:
        out["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
        arg_b = out["memory"]["argument_bytes"] or 0
        tmp_b = out["memory"]["temp_bytes"] or 0
        out["fits_v5e"] = bool(arg_b + tmp_b < V5E_HBM)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shapes", default="assigned")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args()

    archs = list(ALL_ARCHS) if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for arch in archs:
        shape_names = (
            [s.name for s in shapes_for(arch)]
            if args.shapes == "assigned"
            else args.shapes.split(",")
        )
        for shape_name in shape_names:
            for mp in meshes:
                tag = f"{arch}_{shape_name}_{'mp' if mp else 'sp'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip] {tag} (exists)")
                    continue
                try:
                    res = run_cell(arch, shape_name, mp)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    r = res["roofline"]
                    print(
                        f"[ok] {tag}: compile {res['t_compile_s']}s "
                        f"comp={r['compute_s']:.3e}s mem={r['memory_s']:.3e}s "
                        f"coll={r['collective_s']:.3e}s dom={r['dominant']} "
                        f"useful={res['useful_flops_ratio']:.2f}",
                        flush=True,
                    )
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e}", flush=True)
                    traceback.print_exc()
                    if args.fail_fast:
                        raise
    if failures:
        print(f"\n{len(failures)} failures:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
