"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.estimation import W_SENTINEL, online_head_tables
from repro.core.streams import drift_stream, zipf_stream
from repro.kernels import ref
from repro.kernels.adaptive_route import (
    adaptive_route,
    adaptive_route_online,
    w_route,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_pkg_dispatch import moe_adaptive_dispatch, moe_pkg_dispatch
from repro.kernels.pkg_route import pkg_route
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.route_core import (
    MASK,
    waterfill_assign,
    waterfill_levels,
    waterfill_picks,
)
from repro.models.moe import _pkg_choose, expert_head_tables


@pytest.mark.parametrize("n_workers", [5, 16, 50, 100])
@pytest.mark.parametrize("d", [2, 3])
def test_pkg_route_matches_ref(n_workers, d):
    keys = jnp.asarray(zipf_stream(4096, 777, 1.1, seed=n_workers))
    a_k, l_k = pkg_route(keys, n_workers, d=d, chunk=1024, block=128)
    a_r, l_r = ref.ref_pkg_route(keys, n_workers, d=d, chunk=1024, block=128)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r))


@pytest.mark.parametrize("chunk,block", [(512, 64), (2048, 256), (1024, 1024)])
def test_pkg_route_chunk_block_sweep(chunk, block):
    keys = jnp.asarray(zipf_stream(4096, 333, 1.4, seed=1))
    a_k, _ = pkg_route(keys, 12, chunk=chunk, block=block)
    a_r, _ = ref.ref_pkg_route(keys, 12, chunk=chunk, block=block)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))


@pytest.mark.parametrize("n_workers", [16, 50, 100])
@pytest.mark.parametrize("d_max", [2, 4, 8])
def test_adaptive_route_matches_ref(n_workers, d_max):
    keys = jnp.asarray(zipf_stream(4096, 777, 1.6, seed=d_max))
    nc = jnp.asarray(
        np.random.default_rng(n_workers).integers(1, d_max + 1, 4096, dtype=np.int32)
    )
    a_k, l_k = adaptive_route(keys, nc, n_workers, d_max=d_max)
    a_r, l_r = ref.ref_adaptive_route(keys, nc, n_workers, d_max=d_max)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


@pytest.mark.parametrize("chunk,block", [(512, 64), (2048, 256), (1024, 1024)])
def test_adaptive_route_chunk_block_sweep(chunk, block):
    keys = jnp.asarray(zipf_stream(4096, 333, 1.4, seed=1))
    nc = jnp.asarray(np.random.default_rng(2).integers(1, 5, 4096, dtype=np.int32))
    a_k, _ = adaptive_route(keys, nc, 12, d_max=4, chunk=chunk, block=block)
    a_r, _ = ref.ref_adaptive_route(keys, nc, 12, d_max=4, chunk=chunk, block=block)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))


@pytest.mark.parametrize("n_workers", [16, 100])
@pytest.mark.parametrize("capacity", [32, 64])
def test_adaptive_route_online_matches_ref(n_workers, capacity):
    """Head-table kernel vs oracle, tables from the real online tracker."""
    keys = jnp.asarray(zipf_stream(4096, 777, 1.8, seed=capacity))
    tk, tn = online_head_tables(
        keys, block=128, capacity=capacity, n_workers=n_workers, d_max=8
    )
    a_k, l_k = adaptive_route_online(keys, tk, tn, n_workers, d_max=8)
    a_r, l_r = ref.ref_adaptive_route_online(keys, tk, tn, n_workers, d_max=8)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


def test_adaptive_route_online_drift_decay_matches_ref():
    """Same contract under drift with the windowed (decayed) tracker."""
    keys = jnp.asarray(drift_stream(8192, 2_000, 1.8, half_life=2_048, seed=3))
    tk, tn = online_head_tables(
        keys, block=128, capacity=64, n_workers=100, d_max=8, decay_period=2_048
    )
    a_k, _ = adaptive_route_online(keys, tk, tn, 100, d_max=8)
    a_r, _ = ref.ref_adaptive_route_online(keys, tk, tn, 100, d_max=8)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))


def test_adaptive_route_online_empty_table_is_pkg_route():
    """All-miss head tables (staleness degenerate case) reduce to plain PKG:
    a lookup miss yields d_base candidates and the seed family is prefix-
    stable, so assignments match pkg_route bit-exactly."""
    keys = jnp.asarray(zipf_stream(4096, 500, 1.2, seed=3))
    nblk = 4096 // 128
    tk = jnp.full((nblk, 32), -1, jnp.int32)
    tn = jnp.zeros((nblk, 32), jnp.int32)
    a_o, l_o = adaptive_route_online(keys, tk, tn, 16, d_base=2, d_max=4)
    a_p, l_p = pkg_route(keys, 16, d=2)
    np.testing.assert_array_equal(np.asarray(a_o), np.asarray(a_p))
    np.testing.assert_array_equal(np.asarray(l_o), np.asarray(l_p))


def test_adaptive_route_all_two_choices_is_pkg_route():
    """n_cand == 2 everywhere reduces to the plain PKG router bit-exactly."""
    keys = jnp.asarray(zipf_stream(4096, 500, 1.2, seed=3))
    nc = jnp.full(4096, 2, jnp.int32)
    a_a, l_a = adaptive_route(keys, nc, 16, d_max=4)
    a_p, l_p = pkg_route(keys, 16, d=2)
    np.testing.assert_array_equal(np.asarray(a_a), np.asarray(a_p))
    np.testing.assert_array_equal(np.asarray(l_a), np.asarray(l_p))


# ---------------------------------------------------------------------------
# W-Choices global-argmin path (DESIGN.md SS3.3 "In-kernel W-Choices")
# ---------------------------------------------------------------------------


def _sequential_waterfill(loads, n, inv_cap=None):
    """The definition: n times, take the (capacity-normalized) argmin of
    the running loads and add one there."""
    icap = np.ones_like(loads) if inv_cap is None else inv_cap
    sim, cur = [], loads.copy()
    for _ in range(n):
        j = int(np.argmin(cur * icap))
        sim.append(j)
        cur[j] += 1.0
    return sim


@pytest.mark.parametrize("n_workers", [7, 100, 150, 200])
def test_waterfill_picks_equal_sequential_argmin(n_workers):
    """The loop-free water-fill must reproduce 'argmin, add one' exactly —
    including lowest-index ties — for W not a power of two and W > the VPU
    lane width the reduction pads to."""
    rng = np.random.default_rng(n_workers)
    loads = rng.integers(0, 40, n_workers).astype(np.float32)
    picks = np.asarray(
        waterfill_picks(jnp.asarray(loads)[None, :], n_workers=n_workers, block=96)
    )
    assert picks.tolist() == _sequential_waterfill(loads, 96)


def _waterfill_case(case, n_workers, rng, V=128):
    """(loads, head flags) of one water-fill case over a V-lane block."""
    loads = rng.integers(0, 40, n_workers).astype(np.float32)
    if case == "equal":
        loads[:] = 17
    elif case == "one_low":  # more than V below the rest: it takes all V picks
        loads[:] = 3 * V
        loads[rng.integers(n_workers)] = 2 * V - 1
    elif case == "apart":  # loads V apart
        loads = (rng.permutation(n_workers) * V).astype(np.float32)
    elif case == "killed":  # masked lanes, as ChunkedRouter.kill leaves them
        loads[rng.random(n_workers) < 0.5] = MASK
        loads[rng.integers(n_workers)] = 5
    elif case == "masked":  # every lane masked: the lowest index wins
        loads[:] = MASK
    is_w = rng.random(V) < 0.4
    if case in ("all_heads", "one_low"):
        is_w[:] = True
    elif case == "no_head":
        is_w[:] = False
    return loads, is_w


WATERFILL_ROWS = ("equal", "one_low", "apart", "killed", "masked",
                  "all_heads", "no_head")
WATERFILL_CASES = [
    pytest.param("assign", "random", weighted, n, id=f"{weighted}-{n}")
    for weighted in (False, True) for n in (7, 100, 128, 150, 200)
] + [
    pytest.param(fill, case, False, n, id=f"{fill}-{case}-{n}")
    for fill in ("assign", "levels")
    for case in ("random",) * (fill == "levels") + WATERFILL_ROWS
    for n in (7, 100, 128, 150, 200)
]


@pytest.mark.parametrize("fill,case,weighted,n_workers", WATERFILL_CASES)
def test_waterfill_assign_equals_sequential_argmin(fill, case, weighted,
                                                   n_workers):
    """The kernels' water-fills give the r-th head lane of a block the r-th
    pick of the definition — head lanes scattered over the block, ties to
    the lowest worker, capacity-normalized values included — and equal the
    oracle's loop-free picks; lanes that are not heads read 0.  `assign` is
    the sequential loop (the only one with capacities), `levels` the
    loop-free level count route_block runs without them; the rows include
    all loads equal, one worker more than V below the rest, loads V apart,
    masked workers, and blocks of all heads or none."""
    rng = np.random.default_rng(n_workers + weighted)
    loads, is_w = _waterfill_case(case, n_workers, rng)
    icap = (
        (1.0 / rng.integers(1, 5, n_workers)).astype(np.float32)
        if weighted else None
    )
    icap_row = None if icap is None else jnp.asarray(icap)[None, :]
    row = jnp.asarray(loads)[None, :]
    if fill == "levels":
        got = waterfill_levels(row, jnp.asarray(is_w), n_workers=n_workers)
    else:
        got = waterfill_assign(
            row, jnp.asarray(is_w), n_workers=n_workers, inv_cap=icap_row
        )
    got = np.asarray(got)
    n = int(is_w.sum())
    assert got[is_w].tolist() == _sequential_waterfill(loads, n, icap)
    assert not got[~is_w].any()
    picks = np.asarray(waterfill_picks(
        row, n_workers=n_workers, block=128, inv_cap=icap_row,
    ))
    assert got[is_w].tolist() == picks[:n].tolist()


@pytest.mark.parametrize("n_workers", [7, 50, 100, 200])
@pytest.mark.parametrize("d", [2, 4])
def test_w_route_matches_ref(n_workers, d):
    """Kernel vs oracle with random head flags: assignments AND loads bit-
    equal, across W not a power of two and W above the 128-lane block."""
    keys = jnp.asarray(zipf_stream(2048, 500, 1.6, seed=n_workers))
    flags = jnp.asarray(
        (np.random.default_rng(d).random(2048) < 0.25).astype(np.int32)
    )
    a_k, l_k = w_route(keys, flags, n_workers, d=d, chunk=1024, block=128)
    a_r, l_r = ref.ref_w_route(keys, flags, n_workers, d=d, chunk=1024, block=128)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


def test_w_route_all_tail_is_pkg_route():
    """No head flags -> the sentinel path is never taken and the W router
    IS the plain PKG router, message for message."""
    keys = jnp.asarray(zipf_stream(2048, 500, 1.2, seed=3))
    flags = jnp.zeros(2048, jnp.int32)
    a_w, l_w = w_route(keys, flags, 16, d=2)
    a_p, l_p = pkg_route(keys, 16, d=2)
    np.testing.assert_array_equal(np.asarray(a_w), np.asarray(a_p))
    np.testing.assert_array_equal(np.asarray(l_w), np.asarray(l_p))


@pytest.mark.parametrize("n_workers", [13, 100])
def test_w_route_all_head_waterfills_perfectly(n_workers):
    """Every message head-flagged -> the whole chunk is one global water-fill:
    worker loads differ by at most 1, and the kernel still matches its
    oracle bit-exactly."""
    keys = jnp.asarray(zipf_stream(1024, 50, 1.5, seed=9))
    flags = jnp.ones(1024, jnp.int32)
    a_k, _ = w_route(keys, flags, n_workers, chunk=1024, block=128)
    a_r, _ = ref.ref_w_route(keys, flags, n_workers, chunk=1024, block=128)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    loads = np.bincount(np.asarray(a_k), minlength=n_workers)
    assert loads.max() - loads.min() <= 1


def test_w_route_tie_break_deterministic_at_equal_loads():
    """From an all-zero loads row, the water-fill must cycle workers in
    ascending index order (argmin's first-index rule at every level) — the
    tie-break contract shared with w_choices_partition."""
    W = 16
    keys = jnp.asarray(zipf_stream(1024, 50, 1.5, seed=1))
    flags = jnp.ones(1024, jnp.int32)
    a, _ = w_route(keys, flags, W, chunk=1024, block=128)
    np.testing.assert_array_equal(
        np.asarray(a)[:128], np.arange(128, dtype=np.int32) % W
    )


def test_w_route_block1_equals_w_choices_partition():
    """THE differential contract: with block=1 (no staleness) and a single
    chunk, the in-kernel W-Choices path reproduces the sequential
    w_choices_partition bit-exactly given the same head set."""
    from repro.core.estimation import SpaceSavingTracker, head_threshold
    from repro.core.partitioners import _head_lookup, w_choices_partition

    W, cap = 100, 256
    keys_np = zipf_stream(2048, 500, 1.8, seed=5).astype(np.int32)
    tracker = SpaceSavingTracker(cap)
    tracker.update(keys_np)
    head_ids, _, _ = tracker.head_counts(head_threshold(W, 2), 8)
    assert len(head_ids) > 0, "stream must actually have head keys"
    flags = _head_lookup(
        keys_np.astype(np.int64), head_ids, np.ones(len(head_ids), np.int32), 0
    )
    a_seq = np.asarray(w_choices_partition(keys_np, W, capacity=cap))
    a_krn, _ = w_route(
        jnp.asarray(keys_np), jnp.asarray(flags), W, chunk=2048, block=1
    )
    np.testing.assert_array_equal(a_seq, np.asarray(a_krn))


def test_w_choices_kernel_partition_registered_and_bit_exact_at_block1():
    """The registered partitioner wraps the same contract end to end (its own
    tracker pre-pass included) and is reachable through PARTITIONERS."""
    from repro.core.partitioners import PARTITIONERS, w_choices_partition

    assert PARTITIONERS["w_choices_kernel"] is not None
    W, cap = 100, 256
    keys_np = zipf_stream(1500, 400, 1.8, seed=7).astype(np.int32)  # ragged m
    a_seq = np.asarray(w_choices_partition(keys_np, W, capacity=cap))
    a_krn = np.asarray(
        PARTITIONERS["w_choices_kernel"](
            keys_np, W, capacity=cap, chunk=1536, block=1
        )
    )
    np.testing.assert_array_equal(a_seq, a_krn)


@pytest.mark.parametrize("n_workers", [50, 100])
def test_adaptive_route_online_any_worker_matches_ref(n_workers):
    """Online W-Choices: sentinel head tables flow through head_table_ncand
    unclipped and the kernel matches ref_w_route_online bit-exactly."""
    keys = jnp.asarray(drift_stream(4096, 800, 1.8, half_life=2048, seed=2))
    tk, tn = online_head_tables(
        keys, block=128, capacity=64, n_workers=n_workers, d=2, d_max=2,
        any_worker=True,
    )
    assert (np.asarray(tn) == int(W_SENTINEL)).any(), "no head slot emitted"
    a_k, l_k = adaptive_route_online(
        keys, tk, tn, n_workers, d_base=2, d_max=2, w_mode=True
    )
    a_r, l_r = ref.ref_w_route_online(keys, tk, tn, n_workers, d_base=2, d_max=2)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


def test_w_mode_off_matches_on_without_sentinels():
    """w_mode is a perf switch, not a semantics switch: sentinel-free
    candidate counts route bit-identically with the W path compiled out,
    kernel and oracle both."""
    keys = jnp.asarray(zipf_stream(2048, 500, 1.4, seed=4))
    nc = jnp.asarray(np.random.default_rng(0).integers(1, 5, 2048, dtype=np.int32))
    a_on, l_on = adaptive_route(keys, nc, 32, d_max=4, w_mode=True)
    a_off, l_off = adaptive_route(keys, nc, 32, d_max=4, w_mode=False)
    np.testing.assert_array_equal(np.asarray(a_on), np.asarray(a_off))
    np.testing.assert_array_equal(np.asarray(l_on), np.asarray(l_off))
    r_on, _ = ref.ref_adaptive_route(keys, nc, 32, d_max=4, w_mode=True)
    r_off, _ = ref.ref_adaptive_route(keys, nc, 32, d_max=4, w_mode=False)
    np.testing.assert_array_equal(np.asarray(r_on), np.asarray(r_off))


@pytest.mark.parametrize("T,k,E,block", [(512, 1, 8, 128), (1024, 2, 16, 256), (2048, 8, 64, 512)])
def test_moe_pkg_dispatch_matches_ref(T, k, E, block):
    key = jax.random.PRNGKey(T + k)
    probs = jax.nn.softmax(jax.random.normal(key, (T, E)), -1)
    tv, ti = jax.lax.top_k(probs, 2 * k)
    cand = ti.reshape(T, k, 2).astype(jnp.int32)
    cg = tv.reshape(T, k, 2)
    i_k, g_k, l_k = moe_pkg_dispatch(cand, cg, E, block=block)
    i_r, g_r, l_r = ref.ref_moe_pkg_dispatch(cand, cg, E, block=block)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r))


def test_moe_dispatch_balance_property():
    """PKG dispatch keeps the max-expert load near the mean."""
    key = jax.random.PRNGKey(0)
    T, E, k = 4096, 16, 2
    # adversarially skewed router: one expert dominates logits
    logits = jax.random.normal(key, (T, E)).at[:, 0].add(3.0)
    probs = jax.nn.softmax(logits, -1)
    tv, ti = jax.lax.top_k(probs, 2 * k)
    idx, _, loads = moe_pkg_dispatch(
        ti.reshape(T, k, 2).astype(jnp.int32), tv.reshape(T, k, 2), E
    )
    assert float(loads.max()) / (T * k / E) < 1.7
    naive = jnp.zeros(E).at[ti[:, :k].reshape(-1)].add(1.0)
    assert float(loads.max()) < float(naive.max())


def _moe_cands(key, T, E, k, width, skew=3.0):
    """Router-ranked candidates/gates (T, k, width) with a hot expert 0."""
    logits = jax.random.normal(key, (T, E)).at[:, 0].add(skew)
    probs = jax.nn.softmax(logits, -1)
    tv, ti = jax.lax.top_k(probs, width * k)
    return ti.reshape(T, k, width).astype(jnp.int32), tv.reshape(T, k, width)


@pytest.mark.parametrize(
    "T,k,E,block", [(512, 1, 8, 128), (1024, 2, 16, 256), (1024, 4, 64, 512)]
)
@pytest.mark.parametrize("w_mode,d_max", [(False, 4), (True, 2)])
def test_moe_adaptive_dispatch_matches_ref(T, k, E, block, w_mode, d_max):
    """Pallas adaptive dispatch vs the shared-core oracle, with REAL head
    tables from the preferred-expert stream: capped-count tables (d mode)
    and sentinel tables (w mode), idx + gates + loads bit-equal."""
    key = jax.random.PRNGKey(T + k + d_max)
    cand, cg = _moe_cands(key, T, E, k, d_max)
    tk, tn = expert_head_tables(
        cand[:, 0, 0], E, block, d_base=2, d_max=d_max, any_worker=w_mode
    )
    out_k = moe_adaptive_dispatch(
        cand, cg, tk, tn, E, d_base=2, d_max=d_max, block=block, w_mode=w_mode
    )
    out_r = ref.ref_moe_adaptive_dispatch(
        cand, cg, tk, tn, E, d_base=2, d_max=d_max, block=block, w_mode=w_mode
    )
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_dispatch_block1_matches_model_pkg_choose():
    """With block=1 (no staleness) the kernel, its oracle, and the model
    layer's _pkg_choose are the same sequential PoTC, token for token —
    the contract tying models/moe.py to the kernel substrate."""
    T, k, E = 256, 2, 8
    cand, cg = _moe_cands(jax.random.PRNGKey(9), T, E, k, 2)
    i_m, g_m = _pkg_choose(cand, cg, E, block=1)
    i_r, g_r, l_r = ref.ref_moe_pkg_dispatch(cand, cg, E, block=1)
    i_k, g_k, l_k = moe_pkg_dispatch(cand, cg, E, block=1)
    np.testing.assert_array_equal(np.asarray(i_m), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_k))
    np.testing.assert_array_equal(np.asarray(g_m), np.asarray(g_r))
    np.testing.assert_array_equal(np.asarray(g_r), np.asarray(g_k))
    np.testing.assert_array_equal(np.asarray(l_r), np.asarray(l_k))
    # the model layer's int32 histogram is the f32 loads, exactly
    counts = np.bincount(np.asarray(i_m).reshape(-1), minlength=E)
    np.testing.assert_array_equal(counts, np.asarray(l_k).astype(np.int64))


def test_moe_adaptive_all_miss_table_is_pkg_dispatch():
    """All-miss head tables (the all-tail block): every token keeps its
    d_base=2 rank pair, so the W-mode adaptive dispatch IS plain PKG-PoTC
    dispatch bit-exactly — kernel and oracle both."""
    T, k, E, block = 1024, 2, 16, 256
    cand, cg = _moe_cands(jax.random.PRNGKey(4), T, E, k, 2)
    tk = jnp.full((T // block, E), -1, jnp.int32)
    tn = jnp.zeros((T // block, E), jnp.int32)
    out_a = moe_adaptive_dispatch(
        cand, cg, tk, tn, E, d_base=2, d_max=2, block=block, w_mode=True
    )
    out_p = moe_pkg_dispatch(cand, cg, E, block=block)
    out_r = ref.ref_moe_pkg_dispatch(cand, cg, E, block=block)
    for a, p, r in zip(out_a, out_p, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(p))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_moe_adaptive_all_head_waterfills_and_ties_ascend():
    """Every token prefers a sentinel-flagged expert -> the whole stream
    water-fills: final expert loads within 1 of each other; from zero loads
    the first block's picks cycle experts in ascending id order (argmin's
    first-index tie-break — tie-break determinism); spilled lanes keep
    their slot's top-ranked gate."""
    T, k, E, block = 512, 2, 8, 128
    key = jax.random.PRNGKey(6)
    runner = jax.random.randint(key, (T, k, 1), 0, E, jnp.int32)
    cand = jnp.concatenate([jnp.zeros((T, k, 1), jnp.int32), runner], -1)
    cg = jax.nn.softmax(jax.random.normal(key, (T, k, 2)), -1)
    tk = jnp.full((T // block, E), -1, jnp.int32).at[:, 0].set(0)
    tn = jnp.zeros((T // block, E), jnp.int32).at[:, 0].set(int(W_SENTINEL))
    idx, gates, loads = moe_adaptive_dispatch(
        cand, cg, tk, tn, E, d_base=2, d_max=2, block=block, w_mode=True
    )
    i_r, g_r, l_r = ref.ref_moe_adaptive_dispatch(
        cand, cg, tk, tn, E, d_base=2, d_max=2, block=block, w_mode=True
    )
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(g_r))
    loads = np.asarray(loads)
    assert loads.sum() == T * k
    assert loads.max() - loads.min() <= 1
    np.testing.assert_array_equal(
        np.asarray(idx).reshape(-1)[: block * k],
        np.arange(block * k, dtype=np.int32) % E,
    )
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(cg[:, :, 0]))


@pytest.mark.parametrize(
    "B,S,T,H,Kv,hd,causal,window",
    [
        (2, 256, 256, 4, 2, 64, True, 0),
        (1, 128, 384, 8, 8, 64, True, 128),
        (2, 256, 256, 4, 1, 32, False, 0),
        (1, 256, 256, 6, 2, 80, True, 0),  # danube-like hd=80
        (1, 128, 512, 4, 4, 128, True, 256),
    ],
)
def test_flash_attention_matches_ref(B, S, T, H, Kv, hd, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(S + T), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Kv, hd), jnp.float32)
    o_k = flash_attention(q, k, v, causal=causal, window=window)
    o_r = ref.ref_flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 256, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 256, 2, 64), jnp.bfloat16)
    o_k = flash_attention(q, k, v).astype(jnp.float32)
    o_r = ref.ref_flash_attention(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=3e-2)


@pytest.mark.parametrize("shape", [(8, 128), (3, 77, 256), (2, 4, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(shape[-1]), 2)
    x = jax.random.normal(ks[0], shape, dtype)
    w = jax.random.normal(ks[1], (shape[-1],), jnp.float32) * 0.2
    o_k = rmsnorm(x, w).astype(jnp.float32)
    o_r = ref.ref_rmsnorm(x, w).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5 if dtype == jnp.float32 else 2e-2)
