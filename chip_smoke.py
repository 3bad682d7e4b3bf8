#!/usr/bin/env python3
"""Chip smoke test: the stream router's main path on a TPU, at the paper's
Wikipedia page-visit deployment (arXiv 1510.07623 Table 1, WP: 22,000,000
events over 2,900,000 keys, head probability 9.32%) routed over W = 100
workers.  The stream is generated from --seed; nothing is downloaded.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded_route over a 4-chip mesh

One chip, in order:

1. Prefix checks on the first --prefix events.  The chunked router
   (ChunkedRouter) runs on the chip for pkg, d_choices and w_choices, and
   its assignments must equal, bit for bit, the one-shot oracles of
   kernels/ref.py run on the host CPU device of this process.
2. The main path: WP chunks from StreamSpec.stream_chunks into
   ChunkedRouter.route_stream with a histogram sink.  pkg routes --events
   events, d_choices and w_choices --adaptive-events.  The histogram must sum
   to the events routed and no assignment may fall outside [0, W).
3. MoE W-Choices dispatch: moe_adaptive_dispatch(w_mode=True) compiled on
   the chip at the router widths of Mixtral-8x7B (k=2 of E=8 experts) and
   OLMoE-1B-7B (k=8 of E=64), one 8,192-token sequence with a hot expert,
   must equal ref_moe_adaptive_dispatch on the host CPU bit for bit (expert
   ids, gates and loads).

--four-chips runs only sharded_route over make_stream_mesh(4), one shard per
chip, at sync_period 1 and 16 over 4 x 1,048,576 WP events, and compares it
with ref_sharded_route on one device.  It also checks that the compiled
program holds one all-reduce of 4*W bytes per load-sync epoch and that the
result is spread over all four chips.

Each phase prints one JSON line.  The last line is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}} and is
printed only when every check passed.  The script exits non-zero, without
that line, on a failed check or when JAX finds no TPU.  Every phase runs in
this one process, which holds the chip(s).  It times nothing: speed is the
benchmark's (BENCHMARK.json, benchmarks/chip/run_cell.py).
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

W = 100
CHUNK = 8192
BLOCK = 128
POLICIES = ("pkg", "d_choices", "w_choices")
FOUR_CHIP_EVENTS = 4 * 1_048_576
SYNC_PERIODS = (1, 16)
# Space-Saving head tracker of d_choices / w_choices: the ChunkedRouter
# defaults, named here so the one-shot reference builds the same tables
SS_CAPACITY, MIN_COUNT, SLACK, D_MAX = 256, 8, 2.0, 8
# MoE router widths (name, experts E, slots per token k); one 8k-token
# sequence in blocks of 256 tokens, so a block holds 256*k routing lanes
MOE_WIDTHS = (("mixtral", 8, 2), ("olmoe", 64, 8))
MOE_TOKENS, MOE_BLOCK = 8192, 256


class SmokeFailure(Exception):
    pass


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def wp_prefix(n: int, seed: int) -> np.ndarray:
    """The first n events of the WP stream (n a multiple of CHUNK)."""
    from repro.core.streams import PAPER_DATASETS

    chunks = PAPER_DATASETS["WP"].stream_chunks(CHUNK, seed=seed)
    return np.concatenate(list(itertools.islice(chunks, n // CHUNK)))


def make_router(policy: str, seed: int):
    from repro.parallel.chunked_driver import ChunkedRouter

    return ChunkedRouter(
        W, policy, d=2, d_max=D_MAX, chunk=CHUNK, block=BLOCK, seed=seed,
        ss_capacity=SS_CAPACITY, min_count=MIN_COUNT, slack=SLACK,
    )


def first_diff(a: np.ndarray, b: np.ndarray):
    """Index of the first event two assignment arrays disagree on, or None."""
    if a.shape != b.shape:
        return 0
    bad = np.flatnonzero(a != b)
    return int(bad[0]) if len(bad) else None


# -- one chip -----------------------------------------------------------------


def prefix_checks(n: int, seed: int) -> None:
    """Chunked router on the chip == ref oracle on the host CPU."""
    import jax

    from repro.core.estimation import online_head_tables
    from repro.kernels import ref

    keys = wp_prefix(n, seed)
    cpu = jax.devices("cpu")[0]
    keys_cpu = jax.device_put(keys, cpu)
    for policy in POLICIES:
        router = make_router(policy, seed)
        chunked = router.route_stream(keys)
        w_mode = policy == "w_choices"
        if policy == "pkg":
            oracle = ref.ref_pkg_route(
                keys_cpu, W, d=2, seed=seed, chunk=n, block=BLOCK
            )[0]
        else:
            tk, tn = online_head_tables(
                keys_cpu, BLOCK, SS_CAPACITY, W, d=router.d,
                d_max=router.d_max, slack=SLACK, min_count=MIN_COUNT,
                any_worker=w_mode,
            )
            oracle = ref.ref_adaptive_route_online(
                keys_cpu, tk, tn, W, d_base=router.d, d_max=router.d_max,
                seed=seed, chunk=n, block=BLOCK, w_mode=w_mode,
            )[0]
        oracle = np.asarray(oracle)
        chunked_eq_ref = bool(np.array_equal(chunked, oracle))
        emit(
            phase=f"prefix/{policy}", events=n,
            chunked_eq_ref=chunked_eq_ref,
            first_diff_chunked_ref=first_diff(chunked, oracle),
        )
        check(chunked_eq_ref, f"{policy}: chunked router on chip != ref oracle")


def stream_phase(policy: str, events: int, seed: int) -> None:
    """WP chunks -> ChunkedRouter.route_stream -> histogram sink."""
    from repro.core.streams import PAPER_DATASETS

    router = make_router(policy, seed)
    hist = np.zeros(W, np.int64)
    bad = [0]

    def sink(a: np.ndarray) -> None:
        bad[0] += int(((a < 0) | (a >= W)).sum())
        hist[:] += np.bincount(np.clip(a, 0, W - 1), minlength=W)

    chunks = PAPER_DATASETS["WP"].stream_chunks(CHUNK, seed=seed)
    chunks = itertools.islice(chunks, -(-events // CHUNK))
    n = router.route_stream(chunks, on_chunk=sink)
    full = PAPER_DATASETS["WP"].n_msgs
    emit(
        phase=f"stream/{policy}", events=n, stream_events=full,
        cut=None if n >= full else f"first {n} of {full} events",
        final_imbalance=float(hist.max() - hist.mean()) / n,
        max_load=int(hist.max()), min_load=int(hist.min()),
        hist_sum=int(hist.sum()), out_of_range=bad[0],
    )
    check(n == min(events, full), f"{policy}: routed {n} events")
    check(int(hist.sum()) == n, f"{policy}: histogram sums to {hist.sum()}")
    check(bad[0] == 0, f"{policy}: {bad[0]} assignments outside [0, {W})")
    check(
        np.array_equal(router.loads, hist.astype(np.float32)),
        f"{policy}: carried loads row != histogram",
    )


def moe_candidates(E: int, k: int, seed: int):
    """Router-ranked (T, k, 2) candidates and gates of a softmax router
    whose expert 0 is hot, so the W-Choices head tables flag it."""
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((MOE_TOKENS, E)).astype(np.float32)
    logits[:, 0] += 3.0
    probs = np.exp(logits - logits.max(axis=1, keepdims=True))
    probs /= probs.sum(axis=1, keepdims=True)
    order = np.argsort(-probs, axis=1, kind="stable")[:, : 2 * k]
    gates = np.take_along_axis(probs, order, axis=1)
    return (order.reshape(MOE_TOKENS, k, 2).astype(np.int32),
            gates.reshape(MOE_TOKENS, k, 2).astype(np.float32))


def moe_phase(seed: int, dev) -> None:
    """moe_adaptive_dispatch(w_mode=True) on the chip == its host oracle."""
    import jax

    from repro.core.estimation import W_SENTINEL
    from repro.kernels import ref
    from repro.kernels.moe_pkg_dispatch import moe_adaptive_dispatch
    from repro.models.moe import expert_head_tables

    cpu = jax.devices("cpu")[0]
    for name, E, k in MOE_WIDTHS:
        cand, gates = moe_candidates(E, k, seed)
        with jax.default_device(cpu):
            tk, tn = expert_head_tables(
                jax.numpy.asarray(cand[:, 0, 0]), E, MOE_BLOCK, d_base=2,
                d_max=2, any_worker=True,
            )
            oracle = ref.ref_moe_adaptive_dispatch(
                cand, gates, tk, tn, E, d_base=2, d_max=2, block=MOE_BLOCK,
                w_mode=True,
            )
        args = jax.device_put((cand, gates, tk, tn), dev)
        kernel = moe_adaptive_dispatch(
            *args, E, d_base=2, d_max=2, block=MOE_BLOCK, interpret=False,
            w_mode=True,
        )
        heads = np.asarray(tk)[np.asarray(tn) == int(W_SENTINEL)]
        spilled = int(np.isin(cand[:, 0, 0], heads).sum()) * k
        equal = [bool(np.array_equal(np.asarray(a), np.asarray(b)))
                 for a, b in zip(kernel, oracle)]
        emit(
            phase=f"moe/{name}", tokens=MOE_TOKENS, experts=E, k=k,
            lanes_per_block=MOE_BLOCK * k, head_lanes_at_most=spilled,
            idx_eq_ref=equal[0], gates_eq_ref=equal[1], loads_eq_ref=equal[2],
        )
        check(spilled > 0, f"moe/{name}: no head tokens, water-fill unused")
        check(all(equal), f"moe/{name}: moe_adaptive_dispatch != ref")


# -- four chips ---------------------------------------------------------------


def four_chip_phase(seed: int) -> None:
    import jax.numpy as jnp

    from repro.core.estimation import (
        W_SENTINEL,
        SpaceSavingTracker,
        head_threshold,
    )
    from repro.launch.mesh import make_stream_mesh
    from repro.parallel.sharded_router import (
        ref_sharded_route,
        routed_step_roofline,
        sharded_route,
    )

    mesh = make_stream_mesh(4)  # raises with fewer than 4 devices
    keys = wp_prefix(FOUR_CHIP_EVENTS, seed)
    # offline W-Choices head set from the repo's Space-Saving tracker over
    # the first 2^18 events (the WP head keys hold > d/W of the stream)
    tracker = SpaceSavingTracker(1024)
    tracker.update(keys[: 1 << 18])
    head_ids, _, _ = tracker.head_counts(head_threshold(W, 2), 8)
    n_cand = np.where(
        np.isin(keys, head_ids), np.int32(W_SENTINEL), np.int32(2)
    ).astype(np.int32)
    keys_j, nc_j = jnp.asarray(keys), jnp.asarray(n_cand)
    for sync in SYNC_PERIODS:
        for w_mode in (False, True):
            nc = nc_j if w_mode else None
            kw = dict(d_max=2, seed=seed, n_shards=4, sync_period=sync,
                      block=BLOCK, w_mode=w_mode)
            a_s, l_s = sharded_route(keys_j, nc, W, mesh=mesh, **kw)
            a_r, l_r = ref_sharded_route(keys_j, nc, W, **kw)
            shard_devices = {s.device.id for s in a_s.addressable_shards}
            a_s_np = np.asarray(a_s)
            hist = np.bincount(a_s_np, minlength=W)
            bit_exact = bool(
                np.array_equal(a_s_np, np.asarray(a_r))
                and np.array_equal(np.asarray(l_s), np.asarray(l_r))
            )
            name = "w_choices" if w_mode else "pkg"
            emit(
                phase=f"four_chip/{name}/sync{sync}", events=len(keys),
                n_heads=len(head_ids), shard_devices=sorted(shard_devices),
                final_imbalance=float(hist.max() - hist.mean()) / len(keys),
                sharded_eq_ref=bit_exact,
            )
            check(bit_exact, f"{name} sync={sync}: sharded_route != ref")
            check(len(shard_devices) == 4, f"shards on {shard_devices}")
            check(
                np.array_equal(np.asarray(l_s), hist.astype(np.float32)),
                f"{name} sync={sync}: synced loads != histogram",
            )
        roof = routed_step_roofline(
            W, n_shards=4, sync_period=sync, n_epochs=4, block=BLOCK,
            d_max=2, w_mode=True, mesh=mesh,
        )
        n_allreduce = roof["collective_counts"]["all-reduce"]
        emit(
            phase=f"four_chip/collectives/sync{sync}",
            all_reduce_count=n_allreduce,
            collective_bytes_per_epoch=roof["collective_bytes_per_epoch"],
        )
        check(
            n_allreduce == 1 and roof["collective_bytes_per_epoch"] == 4 * W,
            f"sync={sync}: expected one {4 * W}-byte all-reduce per epoch",
        )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded_route phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--events", type=int, default=22_000_000,
                    help="events pkg routes (default: the whole WP stream)")
    ap.add_argument("--adaptive-events", type=int, default=2_097_152,
                    help="events d_choices and w_choices route")
    ap.add_argument("--prefix", type=int, default=262_144,
                    help="events of the bit-exactness checks (multiple of "
                         f"{CHUNK})")
    args = ap.parse_args()
    if args.prefix % CHUNK:
        ap.error(f"--prefix must be a multiple of {CHUNK}")

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repo's src/repro ({e}); run "
              "it from a checkout of the repository", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default platform is "
              f"{dev.platform!r}; no result", file=sys.stderr)
        return 2
    n_used = 4 if args.four_chips else 1
    emit(phase="device", platform=dev.platform, kind=dev.device_kind,
         devices=len(jax.devices()), used=n_used, jax=jax.__version__,
         compile_cache=cache_dir)

    try:
        if args.four_chips:
            four_chip_phase(args.seed)
        else:
            prefix_checks(args.prefix, args.seed)
            stream_phase("pkg", args.events, args.seed)
            for policy in ("d_choices", "w_choices"):
                stream_phase(policy, args.adaptive_events, args.seed)
            moe_phase(args.seed, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_used,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
