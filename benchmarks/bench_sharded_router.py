"""Multi-device sharded-router sweep: keys/sec, scaling efficiency, and the
imbalance-vs-sync-period tradeoff (DESIGN.md §6.1), on the BENCH_* JSON
convention.

Sweeps shards in {1, 2, 8} x sync_period in {1, 16} x methods {pkg, d, w}
over a skewed zipf stream, plus a heterogeneous-shard tradeoff curve (stream
sorted so the hot keys concentrate on one shard — the regime where load-sync
staleness genuinely costs balance) and a roofline report on the compiled
routed step (flops / HBM bytes vs the memory-bandwidth bound, per-epoch
collective bytes of the psum).

Standalone runs force 8 CPU host devices via XLA_FLAGS before importing jax;
under benchmarks/run.py --ci-set the flag comes from the environment
(ci.yml).  When fewer devices exist, shard counts above the device count run
on the bit-exact single-device emulation (ref_sharded_route) and the entry
is marked "emulated" — assignments and imbalance are identical, wall time is
not a scaling measurement.

Gating (check_regression.py): "imbalance" (up), "imbalance_ratio" vs the
single-core router (up), "keys_per_sec" (down) and "scaling_efficiency"
(down).  The gated keys_per_sec is RELATIVE to the same run's single-core
PKG throughput, so the CPU CI gates the ratios, not the machine-dependent
absolute number; the absolute keys/sec headline ships un-gated under
"abs_keys_per_sec" (the >= 1e8 target is a compiled-TPU number).
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import Row, bench_main  # noqa: E402
from repro.core import avg_imbalance_fraction, zipf_stream  # noqa: E402
from repro.core.estimation import W_SENTINEL  # noqa: E402
from repro.core.partitioners import _adaptive_n_cand, _head_flags  # noqa: E402
from repro.parallel.sharded_router import (  # noqa: E402
    ref_sharded_route,
    routed_step_roofline,
    sharded_route,
)
from repro.roofline.analysis import V5E, hw_for  # noqa: E402

QUICK_SCALE = 0.1

W = 32
BLOCK = 128
D_MAX = 8  # D-Choices candidate cap
SHARDS = (1, 2, 8)
SYNCS = (1, 16)
TRADEOFF_SYNCS = (1, 4, 16)
GRID = 8 * 16 * BLOCK  # one N serves every (shards, sync) combination


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _methods(keys_np: np.ndarray, n_workers: int):
    """method -> (n_cand or None, d_max, w_mode); pre-passes excluded from
    all timings (the routed step is what shards)."""
    nc_d = _adaptive_n_cand(keys_np, n_workers, 2, D_MAX, None, 1024, 2.0, 8)
    flags = _head_flags(keys_np, n_workers, 2, None, 1024, 8)
    nc_w = np.where(flags != 0, np.int32(W_SENTINEL), np.int32(2)).astype(np.int32)
    return {
        "pkg": (None, 2, False),
        "d": (nc_d, D_MAX, False),
        "w": (nc_w, 2, True),
    }


def _route(keys, nc, n_workers, *, d_max, n_shards, sync_period, w_mode,
           emulated: bool):
    fn = ref_sharded_route if emulated else sharded_route
    return fn(
        keys, nc, n_workers, d_max=d_max, n_shards=n_shards,
        sync_period=sync_period, block=BLOCK, w_mode=w_mode,
    )


def bit_exact_check(seed: int) -> bool:
    """sharded(n_shards=1, sync_period=1) vs the ref.py one-estimator
    oracles — the differential also in tests/test_sharded_router.py."""
    from repro.kernels import ref

    n = 2048
    keys = jnp.asarray(zipf_stream(n, 500, 1.6, seed=seed))
    ok = True
    for name, (nc, d_max, w_mode) in _methods(np.asarray(keys), W).items():
        ncj = None if nc is None else jnp.asarray(nc)
        full = jnp.full((n,), 2, jnp.int32) if ncj is None else ncj
        a_s, l_s = ref_sharded_route(
            keys, ncj, W, d_max=d_max, n_shards=1, sync_period=1,
            block=BLOCK, w_mode=w_mode,
        )
        if w_mode:
            flags = (np.asarray(full) == int(W_SENTINEL)).astype(np.int32)
            a_k, l_k = ref.ref_w_route(keys, jnp.asarray(flags), W, d=d_max,
                                       chunk=n, block=BLOCK)
        else:
            a_k, l_k = ref.ref_adaptive_route(keys, full, W, d_max=d_max,
                                              chunk=n, block=BLOCK)
        ok = ok and bool(
            (np.asarray(a_s) == np.asarray(a_k)).all()
            and (np.asarray(l_s) == np.asarray(l_k[-1])).all()
        )
    return ok


def collect(scale: float = 1.0, seed: int = 0) -> dict:
    n = max(int(262_144 * scale) // GRID, 1) * GRID
    n_dev = jax.local_device_count()
    keys_np = zipf_stream(n, 1_000, 1.8, seed=seed)
    keys = jnp.asarray(keys_np)
    methods = _methods(keys_np, W)

    # single-core reference: 1 shard, sync_period=1 on the jitted oracle path
    single = {}
    for name, (nc, d_max, w_mode) in methods.items():
        ncj = None if nc is None else jnp.asarray(nc)
        a, _ = ref_sharded_route(keys, ncj, W, d_max=d_max, n_shards=1,
                                 sync_period=1, block=BLOCK, w_mode=w_mode)
        dt = _time(lambda k=keys, c=ncj, dm=d_max, wm=w_mode: ref_sharded_route(
            k, c, W, d_max=dm, n_shards=1, sync_period=1, block=BLOCK, w_mode=wm))
        single[name] = {
            "imbalance": avg_imbalance_fraction(np.asarray(a), W),
            "keys_per_sec": n / dt,
        }
    pkg_single_thru = single["pkg"]["keys_per_sec"]

    scenarios = {}
    conservation_ok = True
    for s in SHARDS:
        emulated = s > n_dev
        for p in SYNCS:
            entry = {
                "n_shards": s, "sync_period": p, "n_workers": W, "n_msgs": n,
                "z": 1.8, "emulated": emulated,
                "imbalance": {}, "imbalance_ratio": {}, "keys_per_sec": {},
                "scaling_efficiency": {}, "abs_keys_per_sec": {},
            }
            for name, (nc, d_max, w_mode) in methods.items():
                ncj = None if nc is None else jnp.asarray(nc)
                a, loads = _route(keys, ncj, W, d_max=d_max, n_shards=s,
                                  sync_period=p, w_mode=w_mode,
                                  emulated=emulated)
                a_np = np.asarray(a)
                hist = np.bincount(a_np, minlength=W).astype(np.float32)
                conservation_ok = conservation_ok and bool(
                    (np.asarray(loads) == hist).all()
                )
                dt = _time(lambda: _route(
                    keys, ncj, W, d_max=d_max, n_shards=s, sync_period=p,
                    w_mode=w_mode, emulated=emulated))
                thru = n / dt
                imb = avg_imbalance_fraction(a_np, W)
                entry["imbalance"][name] = imb
                entry["imbalance_ratio"][name] = imb / max(
                    single[name]["imbalance"], 1e-4
                )
                entry["keys_per_sec"][name] = thru / pkg_single_thru
                entry["scaling_efficiency"][name] = (
                    thru / single[name]["keys_per_sec"] / s
                )
                entry["abs_keys_per_sec"][name] = thru
            scenarios[f"zipf_s{s}_p{p}"] = entry

    # imbalance-vs-sync-period tradeoff on heterogeneous shards: sorted keys
    # concentrate the head on one shard, so stale views genuinely cost
    # balance and the curve is monotone in sync_period.
    keys_sorted = np.sort(keys_np)
    flags_sorted = _head_flags(keys_sorted, W, 2, None, 1024, 8)
    nc_sorted = jnp.asarray(np.where(
        flags_sorted != 0, np.int32(W_SENTINEL), np.int32(2)
    ).astype(np.int32))
    ks = jnp.asarray(keys_sorted)
    hetero_emulated = 8 > n_dev
    tradeoff = {}
    for p in TRADEOFF_SYNCS:
        a, _ = _route(ks, nc_sorted, W, d_max=2, n_shards=8, sync_period=p,
                      w_mode=True, emulated=hetero_emulated)
        h = np.bincount(np.asarray(a), minlength=W)
        tradeoff[p] = float(h.max() - h.mean()) / n
        scenarios[f"hetero_w_p{p}"] = {
            "n_shards": 8, "sync_period": p, "n_workers": W, "n_msgs": n,
            "emulated": hetero_emulated,
            "imbalance": {"w": tradeoff[p]},
        }

    # the roofline terms are modelled for the v5e target, whatever device
    # compiled the program: its peaks are named, not assumed
    roofline = routed_step_roofline(
        W, n_shards=min(8, n_dev), sync_period=16, n_epochs=4, block=BLOCK,
        d_max=2, w_mode=True, hw=hw_for(V5E),
    )
    roofline["peaks_of"] = V5E

    return {
        "n_devices": n_dev,
        "single_core": single,
        "scenarios": scenarios,
        "roofline": roofline,
        "checks": {
            "one_shard_sync1_bit_exact": bit_exact_check(seed + 3),
            "load_sync_conservation": conservation_ok,
            "w_tradeoff_monotone_in_sync_period":
                tradeoff[TRADEOFF_SYNCS[0]]
                <= tradeoff[TRADEOFF_SYNCS[-1]] * 1.05,
            "w_beats_pkg_sharded": all(
                e["imbalance"]["w"] < e["imbalance"]["pkg"]
                for name, e in scenarios.items() if name.startswith("zipf_")
            ),
        },
    }


def run(scale: float = 1.0) -> list[Row]:
    report = collect(scale=scale)
    rows = []
    for name, entry in sorted(report["scenarios"].items()):
        for method, thru in sorted(entry.get("abs_keys_per_sec", {}).items()):
            rows.append(Row(
                f"sharded/{name}/{method}",
                1e6 / thru,
                f"{entry['imbalance'][method]:.3e}",
            ))
        if "abs_keys_per_sec" not in entry:
            for method, imb in sorted(entry["imbalance"].items()):
                rows.append(Row(f"sharded/{name}/{method}", 0.0, f"{imb:.3e}"))
    ok = all(report["checks"].values())
    rows.append(Row("sharded/checks", 0.0, "pass" if ok else "FAIL"))
    return rows


if __name__ == "__main__":
    bench_main("sharded_router", collect, quick_scale=QUICK_SCALE)
