"""Kernel microbench + W-router sweep, on the BENCH_* JSON convention.

Wall time is measured on the jitted XLA oracle paths (the CPU production
path; Pallas interpret mode is a correctness tool, not a timing target), with
one interpret-mode run per kernel as a sanity check.

The W-router sweep measures the on-device W-Choices semantics (DESIGN.md SS3.3
"On-device W-Choices"): per-block head tables emitted with any_worker=True
route head keys through the global-argmin water-fill, the d_max-capped tables
(any_worker=False) are the pre-PR-4 router, and plain PKG anchors the bottom.
W in {8, 50, 100} x tail d in {2, 4} x {stationary, drift} streams; imbalance
entries feed CI's regression gate (check_regression.py), us_per_msg is
reported but never gated.

`PYTHONPATH=src:. python benchmarks/bench_kernels.py [--scale S] [--quick]
[--out PATH]` writes BENCH_kernels.json via benchmarks/common.py; `run(scale)`
yields CSV rows for benchmarks/run.py.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, bench_main
from repro.core import avg_imbalance_fraction, drift_stream, online_head_tables, zipf_stream
from repro.kernels import ref
from repro.parallel.chunked_driver import ChunkedRouter

W_SWEEP = (8, 50, 100)
D_SWEEP = (2, 4)
CAPACITY = 128
CHUNK, BLOCK = 1024, 128
D_CAP = 4  # d_max of the capped (pre-W) router baseline


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _sweep_streams(n: int, seed: int) -> dict[str, np.ndarray]:
    return {
        "stationary": zipf_stream(n, 1_000, 1.8, seed=seed),
        "drift": drift_stream(
            n, 1_000, 1.8, half_life=max(n // 4, 256), seed=seed + 1
        ),
    }


def _tables(keys, n_workers: int, d: int, d_max: int, any_worker: bool):
    return online_head_tables(
        keys, block=BLOCK, capacity=CAPACITY, n_workers=n_workers,
        d=d, d_max=d_max, any_worker=any_worker,
    )


def _routers(n_workers: int):
    """method name -> (jitted oracle route fn, table spec or None)."""
    routers = {
        "pkg": (
            jax.jit(functools.partial(
                ref.ref_pkg_route, n_workers=n_workers, d=2,
                chunk=CHUNK, block=BLOCK,
            )),
            None,
        ),
        "d_router": (
            jax.jit(functools.partial(
                ref.ref_adaptive_route_online, n_workers=n_workers,
                d_base=2, d_max=D_CAP, chunk=CHUNK, block=BLOCK,
            )),  # w_mode default False: the pre-W router, no water-fill
            (2, D_CAP, False),
        ),
    }
    for d in D_SWEEP:
        routers[f"w_router_d{d}"] = (
            jax.jit(functools.partial(
                ref.ref_w_route_online, n_workers=n_workers,
                d_base=d, d_max=d, chunk=CHUNK, block=BLOCK,
            )),
            (d, d, True),
        )
    return routers


def w_router_bit_exact(n: int = 2048, seed: int = 3) -> bool:
    """W-Choices chunk step (ChunkedRouter) vs oracle: its carried summary's
    sentinel tables against online_head_tables', assign+loads.

    Covers W=100 under drift and W=50 (not a power of two) stationary.
    """
    ok = True
    cases = [
        (100, jnp.asarray(drift_stream(n, 500, 1.8, half_life=n // 2, seed=seed))),
        (50, jnp.asarray(zipf_stream(n, 500, 1.8, seed=seed))),
    ]
    for W, keys in cases:
        tk, tn = _tables(keys, W, d=2, d_max=2, any_worker=True)
        router = ChunkedRouter(W, "w_choices", d=2, chunk=CHUNK, block=BLOCK,
                               ss_capacity=CAPACITY)
        a_k = router.route_stream(np.asarray(keys))
        a_r, l_r = ref.ref_w_route_online(
            keys, tk, tn, W, d_base=2, d_max=2, chunk=n, block=BLOCK
        )
        ok = ok and bool(
            (a_k == np.asarray(a_r)).all()
            and (router.loads == np.asarray(l_r[-1])).all()
        )
    return ok


def collect(scale: float = 1.0, seed: int = 0) -> dict:
    """W-router sweep -> JSON report with imbalance/us_per_msg + checks."""
    n = max(int(32_768 * scale) // CHUNK, 2) * CHUNK
    scenarios = {}
    routers = {W: _routers(W) for W in W_SWEEP}  # one jit cache per (W, d)
    for kind, keys_np in _sweep_streams(n, seed).items():
        keys = jnp.asarray(keys_np)
        for W in W_SWEEP:
            entry = {
                "kind": kind, "n_workers": W, "n_msgs": n, "z": 1.8,
                "imbalance": {}, "us_per_msg": {},
            }
            for method, (fn, spec) in routers[W].items():
                if spec is None:
                    args = (keys,)
                else:
                    d, d_max, any_worker = spec
                    args = (keys, *_tables(keys, W, d, d_max, any_worker))
                assign, _ = fn(*args)
                entry["imbalance"][method] = avg_imbalance_fraction(
                    np.asarray(assign), W
                )
                entry["us_per_msg"][method] = _time(fn, *args) / n * 1e6
            scenarios[f"{kind}_w{W}"] = entry

    s100 = scenarios["stationary_w100"]["imbalance"]
    report = {
        "scenarios": scenarios,
        "checks": {
            # the tentpole claim: on-device W-Choices makes the device path
            # the best-balanced one where d_max-capped routing gives out
            "w_router_beats_capped_at_w100":
                s100["w_router_d2"] < s100["d_router"],
            "w_router_beats_pkg_everywhere": all(
                e["imbalance"]["w_router_d2"] < e["imbalance"]["pkg"]
                for e in scenarios.values()
            ),
            "w_router_bit_exact": w_router_bit_exact(seed=seed + 3),
        },
    }
    return report


def run(scale: float = 1.0) -> list[Row]:
    rows = []
    key = jax.random.PRNGKey(0)

    # PKG oracle (jitted scan)
    n = max(int(131_072 * scale) // 1024, 2) * 1024  # chunk-divisible
    keys = jnp.asarray(zipf_stream(n, 10_000, 1.1, seed=1))
    f = jax.jit(lambda k: ref.ref_pkg_route(k, 32, chunk=1024, block=128))
    dt = _time(f, keys)
    rows.append(Row("kernel/pkg_route_ref", dt / len(keys) * 1e6, f"keys={len(keys)}"))

    # moe dispatch oracle
    T = max(int(16_384 * scale) // 512, 1) * 512
    E, k = 64, 8
    probs = jax.nn.softmax(jax.random.normal(key, (T, E)), -1)
    tv, ti = jax.lax.top_k(probs, 2 * k)
    cand = ti.reshape(-1, k, 2).astype(jnp.int32)
    cg = tv.reshape(-1, k, 2)
    f = jax.jit(lambda c, g: ref.ref_moe_pkg_dispatch(c, g, E, block=256))
    dt = _time(f, cand, cg)
    rows.append(Row("kernel/moe_dispatch_ref", dt / cand.shape[0] * 1e6, f"T={cand.shape[0]}"))

    # flash attention oracle vs naive full-logits timing
    B, S, H, hd = 1, int(1024 * max(scale, 0.25)), 8, 64
    q = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
    kk = jax.random.normal(key, (B, S, 2, hd), jnp.bfloat16)
    vv = jax.random.normal(key, (B, S, 2, hd), jnp.bfloat16)
    f = jax.jit(lambda a, b, c: ref.ref_flash_attention(a, b, c))
    dt = _time(f, q, kk, vv)
    rows.append(Row("kernel/attention_ref", dt / S * 1e6, f"S={S}"))

    # rmsnorm
    x = jax.random.normal(key, (4096, 2048), jnp.bfloat16)
    w = jax.random.normal(key, (2048,)) * 0.1
    f = jax.jit(lambda a, b: ref.ref_rmsnorm(a, b))
    dt = _time(f, x, w)
    rows.append(Row("kernel/rmsnorm_ref", dt / 4096 * 1e6, "rows=4096"))

    # interpret-mode sanity (correctness path exists end-to-end)
    from repro.kernels.rmsnorm import rmsnorm

    dt = _time(lambda a, b: rmsnorm(a, b), x[:256], w, reps=1)
    rows.append(Row("kernel/rmsnorm_pallas_interpret", dt / 256 * 1e6, "rows=256"))

    # W-router sweep (imbalance + oracle wallclock per configuration)
    report = collect(scale=scale)
    for name, entry in sorted(report["scenarios"].items()):
        for method in sorted(entry["imbalance"]):
            rows.append(
                Row(
                    f"kernel/w_sweep/{name}/{method}",
                    entry["us_per_msg"][method],
                    f"{entry['imbalance'][method]:.3e}",
                )
            )
    ok = all(report["checks"].values())
    rows.append(Row("kernel/w_sweep/checks", 0.0, "pass" if ok else "FAIL"))
    return rows


# CI quick scale, shared with benchmarks/run.py --ci-set.
QUICK_SCALE = 0.1

if __name__ == "__main__":
    bench_main("kernels", collect, quick_scale=QUICK_SCALE)
