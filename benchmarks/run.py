"""Benchmark harness: one module per paper table/figure (+ beyond-paper).

Default mode prints ``name,us_per_call,derived`` CSV.  --scale scales stream
sizes (default 0.25 for CI speed; 1.0 ~ 1% of the paper's stream sizes with
matched m/K ratios and p1; --scale 100 approaches the original sizes).

--ci-set instead runs the canonical quick-bench list (CI_SET below — the
JSON benches the regression gate covers) through each module's own
bench_main, writing one BENCH_<name>.json per bench under --out.  This list
is THE definition of what bench-quick runs; ci.yml calls

    python benchmarks/run.py --quick --ci-set --out bench-out/

and then merges/gates bench-out/BENCH_*.json with check_regression.py.
Each bench's --quick scale comes from its own QUICK_SCALE constant, so
adding a bench to CI is: give it collect() + QUICK_SCALE, list it here,
regenerate the baseline.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

from benchmarks import (
    bench_batched_fidelity,
    bench_drift,
    bench_failover_serving,
    bench_heavy_hitters,
    bench_hetero_elastic,
    bench_fig4,
    bench_fig5,
    bench_fig6,
    bench_fig7,
    bench_fig8,
    bench_fig9,
    bench_kernels,
    bench_moe_balance,
    bench_moe_train,
    bench_scale_choices,
    bench_serving,
    bench_sharded_router,
    bench_storm_sim,
    bench_table2,
    bench_theory,
    bench_trace_scale,
)
from benchmarks.common import bench_main
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("table2", bench_table2),
    ("fig4", bench_fig4),
    ("fig5", bench_fig5),
    ("fig6", bench_fig6),
    ("fig7", bench_fig7),
    ("fig8", bench_fig8),
    ("fig9", bench_fig9),
    ("storm_sim", bench_storm_sim),
    ("theory", bench_theory),
    ("heavy_hitters", bench_heavy_hitters),
    ("moe_balance", bench_moe_balance),
    ("moe_train", bench_moe_train),
    ("batched_fidelity", bench_batched_fidelity),
    ("kernels", bench_kernels),
    ("scale_choices", bench_scale_choices),
    ("drift", bench_drift),
    ("serving", bench_serving),
    ("failover_serving", bench_failover_serving),
    ("hetero_elastic", bench_hetero_elastic),
    ("sharded_router", bench_sharded_router),
    ("trace_scale", bench_trace_scale),
]

# The canonical CI quick-bench list: every JSON bench check_regression.py
# gates.  Order matters only for log readability.
CI_SET = [
    ("scale_choices", bench_scale_choices),
    ("drift", bench_drift),
    ("kernels", bench_kernels),
    ("serving", bench_serving),
    ("moe_balance", bench_moe_balance),
    ("moe_train", bench_moe_train),
    ("failover_serving", bench_failover_serving),
    ("hetero_elastic", bench_hetero_elastic),
    ("sharded_router", bench_sharded_router),
    ("trace_scale", bench_trace_scale),
]


def run_ci_set(out_dir: str, *, quick: bool, scale: float, seed: int,
               only=None) -> list[Path]:
    """Run every CI_SET bench via its bench_main, one JSON report each."""
    paths = []
    for name, mod in CI_SET:
        if only and name not in only:
            continue
        out = Path(out_dir) / f"BENCH_{name}.json"
        argv = ["--scale", str(scale), "--seed", str(seed),
                "--out", str(out)]
        if quick:
            argv.append("--quick")
        t0 = time.time()
        bench_main(name, mod.collect,
                   quick_scale=getattr(mod, "QUICK_SCALE", 0.05), argv=argv)
        print(f"# {name} done in {time.time()-t0:.1f}s",
              file=sys.stderr, flush=True)
        paths.append(out)
    return paths


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=None,
                    help="stream-size scale (CSV default 0.25, --ci-set 1.0)")
    ap.add_argument("--only", default=None, help="comma-separated module names")
    ap.add_argument("--quick", action="store_true",
                    help="with --ci-set: clamp each bench to its QUICK_SCALE")
    ap.add_argument("--ci-set", action="store_true",
                    help="run the canonical JSON quick-bench list instead of CSV")
    ap.add_argument("--out", default="bench-out",
                    help="with --ci-set: directory for BENCH_<name>.json reports")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()

    if args.ci_set:
        run_ci_set(args.out, quick=args.quick,
                   scale=1.0 if args.scale is None else args.scale,
                   seed=args.seed, only=only)
        return

    scale = 0.25 if args.scale is None else args.scale
    print("name,us_per_call,derived")
    for name, mod in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        rows = mod.run(scale=scale)
        for row in rows:
            print(row.csv(), flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
