"""Shared benchmark helpers: timed partitioner runs, row collection, and the
single JSON-report convention.

Every bench module exposes run(scale: float) -> list[Row]; run.py prints
``name,us_per_call,derived`` CSV (us_per_call = wall time per routed message,
derived = the paper's metric for that table/figure).

JSON-emitting benches route ALL file output through write_report/bench_main:
reports land at ``--out PATH`` when given, else ``$BENCH_DIR/BENCH_<name>.json``
(BENCH_DIR defaults to cwd), so local runs and CI artifacts use identical
paths and the regression gate (benchmarks/check_regression.py) can diff them.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import (
    PARTITIONERS,
    avg_imbalance_fraction,
    d_choices_partition,
    hash_partition,
    off_greedy_partition,
    on_greedy_partition,
    online_d_choices_partition,
    online_w_choices_partition,
    pkg_partition,
    pkg_partition_batched,
    potc_static_partition,
    shuffle_partition,
    simulate_sources,
    w_choices_partition,
    w_choices_sharded_partition,
)


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.4f},{self.derived}"


def route(method: str, keys: np.ndarray, n_workers: int, n_keys: Optional[int] = None,
          d: int = 2, seed: int = 0, **kw) -> tuple[np.ndarray, float]:
    """Run a partitioner; returns (assignment, seconds). JIT warm-up excluded.

    Extra keyword args (capacity, decay_period, theta, ...) pass through to
    the adaptive partitioners, so every bench measures a configuration via
    this one dispatch (no per-bench re-implementations to drift apart).
    """
    ks = jnp.asarray(keys, jnp.int32)
    n_keys = int(n_keys or (int(keys.max()) + 1))

    def call():
        if method == "kg":
            return hash_partition(ks, n_workers, seed=seed)
        if method == "sg":
            return shuffle_partition(ks, n_workers)
        if method == "pkg":
            return pkg_partition(ks, n_workers, d=d, seed=seed)
        if method == "pkg_batched":
            return pkg_partition_batched(ks, n_workers, d=d, seed=seed)
        if method == "potc":
            return potc_static_partition(ks, n_workers, n_keys, d=d, seed=seed)
        if method == "on_greedy":
            return on_greedy_partition(ks, n_workers, n_keys)
        if method == "off_greedy":
            return off_greedy_partition(ks, n_workers, n_keys)
        if method == "d_choices":
            return d_choices_partition(keys, n_workers, d=d, seed=seed, **kw)
        if method == "w_choices":
            return w_choices_partition(keys, n_workers, d=d, seed=seed, **kw)
        if method == "w_choices_sharded":
            return w_choices_sharded_partition(
                keys, n_workers, d=d, seed=seed, n_shards=1, sync_period=1, **kw
            )
        if method == "d_choices_online":
            return online_d_choices_partition(ks, n_workers, d=d, seed=seed, **kw)
        if method == "w_choices_online":
            return online_w_choices_partition(ks, n_workers, d=d, seed=seed, **kw)
        raise ValueError(method)

    a = np.asarray(call())  # warm-up/compile
    t0 = time.perf_counter()
    a = np.asarray(call())
    dt = time.perf_counter() - t0
    return a, dt


def imbalance_row(tag: str, method: str, keys: np.ndarray, n_workers: int,
                  n_keys: Optional[int] = None, d: int = 2) -> Row:
    a, dt = route(method, keys, n_workers, n_keys=n_keys, d=d)
    frac = avg_imbalance_fraction(a, n_workers)
    return Row(tag, dt / len(keys) * 1e6, f"{frac:.3e}")


def sources_row(tag: str, keys: np.ndarray, n_workers: int, n_sources: int,
                mode: str, probe_period: int = 0,
                source_keys: Optional[np.ndarray] = None) -> Row:
    t0 = time.perf_counter()
    a = simulate_sources(keys, n_workers, n_sources, mode=mode,
                         probe_period=probe_period, source_keys=source_keys)
    dt = time.perf_counter() - t0
    frac = avg_imbalance_fraction(a, n_workers)
    return Row(tag, dt / len(keys) * 1e6, f"{frac:.3e}")


# ---------------------------------------------------------------------------
# JSON report convention (the single output path for local runs and CI).
# ---------------------------------------------------------------------------


def report_path(name: str, out: Optional[str] = None) -> Path:
    """Canonical location of a bench report: --out wins, else
    $BENCH_DIR/BENCH_<name>.json (BENCH_DIR defaults to the cwd)."""
    if out:
        return Path(out)
    return Path(os.environ.get("BENCH_DIR", ".")) / f"BENCH_{name}.json"


def write_report(name: str, report: dict, out: Optional[str] = None) -> Path:
    path = report_path(name, out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return path


def bench_main(
    name: str,
    collect: Callable[..., dict],
    quick_scale: float = 0.05,
    argv: Optional[list[str]] = None,
) -> dict:
    """Shared __main__ for JSON benches: --scale/--seed/--out/--quick.

    Runs collect(scale=..., seed=...), stamps bench metadata, writes the
    report via write_report (the one sanctioned output path), and prints it
    to stdout.  --quick clamps the scale for CI's bench-quick job.
    """
    ap = argparse.ArgumentParser(description=f"bench_{name}")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="report path (default BENCH_<name>.json)")
    ap.add_argument("--quick", action="store_true",
                    help=f"reduced-size CI mode (scale <= {quick_scale})")
    args = ap.parse_args(argv)
    scale = min(args.scale, quick_scale) if args.quick else args.scale
    t0 = time.time()
    report = collect(scale=scale, seed=args.seed)
    report.update(bench=name, scale=scale, seed=args.seed,
                  seconds=round(time.time() - t0, 2))
    path = write_report(name, report, args.out)
    print(json.dumps(report, indent=2, sort_keys=True))
    print(f"# wrote {path}", file=sys.stderr)
    return report
