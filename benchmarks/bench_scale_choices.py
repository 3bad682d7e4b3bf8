"""Beyond-paper: the imbalance-vs-scale crossover (arXiv 1510.05714 Fig 4-5).

Plain d=2 PKG balances only while p1 <= d/W.  Sweeping the large-deployment
scenarios (W in {50, 100}, Zipf z in [1.4, 2.0]) shows PKG's imbalance
exploding past that bound while D-Choices (skew-adaptive d) and W-Choices
(head keys go anywhere) hold near-perfect balance.  Also verifies that the
one-shard sharded scan with per-key candidate counts matches its JAX oracle
bit-exactly.

`PYTHONPATH=src:. python benchmarks/bench_scale_choices.py [--scale S]
[--quick] [--out PATH]` writes the JSON report via the benchmarks/common.py
convention (default ./BENCH_scale_choices.json, or $BENCH_DIR); `run(scale)`
yields the usual CSV rows for benchmarks/run.py.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, bench_main, route
from repro.core import SCALE_SCENARIOS, avg_imbalance_fraction
from repro.core.streams import zipf_stream
from repro.kernels import ref
from repro.parallel.sharded_router import ref_sharded_route

METHODS = ("pkg", "d_choices", "w_choices")


def kernel_bit_exact(d_max: int = 8, n_workers: int = 100) -> bool:
    """One-shard sharded scan (per-key candidate counts) vs the ref.py
    oracle on a skewed stream."""
    n = 4096
    keys = jnp.asarray(zipf_stream(n, 1000, 1.8, seed=9))
    nc = jnp.asarray(
        np.random.default_rng(9).integers(1, d_max + 1, n, dtype=np.int32)
    )
    a_k, l_k = ref_sharded_route(keys, nc, n_workers, d_max=d_max)
    a_r, l_r = ref.ref_adaptive_route(keys, nc, n_workers, d_max=d_max, chunk=n)
    return bool(
        (np.asarray(a_k) == np.asarray(a_r)).all()
        and (np.asarray(l_k) == np.asarray(l_r[-1])).all()
    )


def collect(scale: float = 1.0, seed: int = 0) -> dict:
    """Full sweep as a JSON-serialisable report with acceptance checks."""
    scenarios = {}
    for name, sc in sorted(SCALE_SCENARIOS.items()):
        keys = sc.generate(seed=seed, scale=scale)
        entry = {"n_workers": sc.n_workers, "z": sc.z, "p1": sc.head_fraction(),
                 "n_msgs": len(keys), "imbalance": {}, "us_per_msg": {}}
        for method in METHODS:
            a, dt = route(method, keys, sc.n_workers)
            entry["imbalance"][method] = avg_imbalance_fraction(a, sc.n_workers)
            entry["us_per_msg"][method] = dt / len(keys) * 1e6
        scenarios[name] = entry

    hard = scenarios["W100_z2.0"]["imbalance"]
    report = {
        "scenarios": scenarios,
        "checks": {
            "d_choices_below_pkg_at_W100_z2.0": hard["d_choices"] < hard["pkg"],
            "w_choices_below_pkg_at_W100_z2.0": hard["w_choices"] < hard["pkg"],
            "adaptive_kernel_bit_exact": kernel_bit_exact(),
        },
    }
    return report


def run(scale: float = 1.0) -> list[Row]:
    rows = []
    report = collect(scale=scale)
    for name, entry in report["scenarios"].items():
        for method in METHODS:
            rows.append(
                Row(
                    f"scale_choices/{name}/{method}",
                    entry["us_per_msg"][method],
                    f"{entry['imbalance'][method]:.3e}",
                )
            )
    ok = all(report["checks"].values())
    rows.append(Row("scale_choices/checks", 0.0, "pass" if ok else "FAIL"))
    return rows


# CI quick scale, shared with benchmarks/run.py --ci-set.
QUICK_SCALE = 0.05

if __name__ == "__main__":
    bench_main("scale_choices", collect, quick_scale=QUICK_SCALE)
