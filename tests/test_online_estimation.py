"""Online (scan-carry) SPACESAVING: error bounds, decay, and the
offline-vs-online agreement regressions (DESIGN.md SS3.3 "Online estimation").

The hypothesis property test checks the classic SPACESAVING guarantees hold
for the array-state implementation on *drifting* streams: estimates are upper
bounds, over-estimation never exceeds total/capacity (the m/k bound), and the
error-corrected count is a lower bound.

adaptive_d_counts' int32 path is exact up to total < 2**31; the summary's own
int32 counts and total wrap at 2**31 events, the int32 limit that remains.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import (
    SpaceSavingTracker,
    adaptive_d,
    adaptive_d_counts,
    d_choices_partition,
    drift_stream,
    head_threshold,
    online_d_choices_partition,
    online_head_tables,
    online_ss_estimate,
    online_ss_from_tracker,
    online_ss_init,
    online_ss_update,
    zipf_stream,
)
from repro.core.metrics import avg_imbalance_fraction


def _run_tracker(keys, capacity):
    state = online_ss_init(capacity)
    return lax.scan(
        lambda s, k: (online_ss_update(s, k), None), state,
        jnp.asarray(keys, jnp.int32),
    )[0]


def _assert_ss_bounds(state, keys, capacity):
    true = np.bincount(np.asarray(keys), minlength=int(np.max(keys)) + 1)
    ks = np.asarray(state.keys)
    counts = np.asarray(state.counts)
    errors = np.asarray(state.errors)
    total = int(state.total)
    assert total == len(keys)
    live = counts > 0
    assert live.sum() <= capacity
    est = counts[live]
    tc = true[ks[live]]
    assert (est >= tc).all(), "estimates must be upper bounds"
    assert (est - tc <= total / capacity).all(), "m/k over-estimation bound"
    assert (est - errors[live] <= tc).all(), "error-corrected count is a lower bound"


@pytest.mark.parametrize("capacity", [8, 64])
@pytest.mark.parametrize("z", [0.8, 1.8])
def test_online_ss_bounds_on_drifting_streams(capacity, z):
    keys = drift_stream(3_000, 300, z, half_life=500, seed=z > 1)
    _assert_ss_bounds(_run_tracker(keys, capacity), keys, capacity)


def test_online_ss_bounds_property():
    """Hypothesis sweep over stream shapes (drift rate, skew, capacity)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=15, deadline=None)
    @hyp.given(
        n_keys=st.integers(5, 200),
        z=st.floats(0.0, 2.5),
        half_life=st.integers(50, 2_000),
        capacity=st.integers(2, 48),
        seed=st.integers(0, 5),
    )
    def check(n_keys, z, half_life, capacity, seed):
        keys = drift_stream(
            800, n_keys, z, half_life=half_life,
            rotate_top=min(8, n_keys), seed=seed,
        )
        _assert_ss_bounds(_run_tracker(keys, capacity), keys, capacity)

    check()


def test_online_matches_python_tracker_totals():
    """Array state and dict tracker agree on totals and on clear head keys."""
    keys = zipf_stream(20_000, 2_000, 1.6, seed=4)
    state = _run_tracker(keys, 128)
    tracker = SpaceSavingTracker(128)
    for k in keys:  # element-wise: identical offer schedule to the scan
        tracker.offer(int(k))
    assert int(state.total) == tracker.total
    ids, _ = tracker.head_keys(0.02)
    # a dict-tracker head key's true count is >= (theta - 1/cap) * m, and the
    # array state's estimate upper-bounds the true count
    floor = (0.02 - 1.0 / 128) * len(keys)
    for k in ids:
        assert int(online_ss_estimate(state, int(k))) >= floor


def test_online_ss_decay_tracks_rotating_head():
    """With windowed decay the head table follows the drift; without, the
    stale head lingers.  Checked via the per-block tables the kernel consumes."""
    m, n_keys, W = 16_384, 2_000, 100
    rng = np.random.default_rng(0)
    half = m // 2
    a = np.where(rng.random(half) < 0.4, 7, rng.integers(0, n_keys, half))
    b = np.where(rng.random(half) < 0.4, 1_313, rng.integers(0, n_keys, half))
    keys = jnp.asarray(np.concatenate([a, b]), jnp.int32)
    tk, tn = online_head_tables(
        keys, block=128, capacity=64, n_workers=W, d_max=16,
        decay_period=1_024,
    )
    last_k, last_n = np.asarray(tk[-1]), np.asarray(tn[-1])
    head_now = set(last_k[last_n > 2].tolist())
    assert 1_313 in head_now, "new head must be detected online"
    assert 7 not in head_now, "decayed summary must forget the old head"
    # without decay the old head's accumulated mass keeps it flagged
    tk2, tn2 = online_head_tables(keys, block=128, capacity=64, n_workers=W, d_max=16)
    stale_k, stale_n = np.asarray(tk2[-1]), np.asarray(tn2[-1])
    assert 7 in set(stale_k[stale_n > 2].tolist())


def test_adaptive_d_counts_integer_exact():
    """A ceil boundary where float64 and integer arithmetic disagree:
    p = 350/10000 = 0.035 -> slack*p*W = 7 exactly, so d(k) = 7 — but 0.035
    is not binary-representable and the float path rounds the product just
    above 7, giving ceil = 8.  The online and offline variants both must use
    the integer rule or frozen-carry differential equality breaks."""
    assert int(adaptive_d_counts(np.asarray([350]), 10_000, 100)[0]) == 7
    assert int(adaptive_d(np.asarray([350 / 10_000.0]), 100)[0]) == 8  # the trap
    # jnp and numpy paths agree everywhere
    counts = np.arange(0, 2_000, 7, dtype=np.int64)
    a = adaptive_d_counts(counts, 20_000, 100, d_base=2, d_max=16)
    b = adaptive_d_counts(jnp.asarray(counts, jnp.int32), jnp.int32(20_000), 100,
                          d_base=2, d_max=16)
    np.testing.assert_array_equal(a, np.asarray(b))


@pytest.mark.parametrize("W", [100, 1_000])
@pytest.mark.parametrize("slack", [2.0, 1.5])
def test_adaptive_d_counts_int32_matches_int64(W, slack):
    """The jnp (int32) path gives the numpy (int64) path's d(k) for counts
    <= total < 2**31: around 2**31 / (s_num * W), where slack_num * W *
    count first leaves int32, and up to 2**31 - 1.  A product formed in
    int32 wraps there (at W = 100 and slack 2 a key at 9.3% of 118 M events
    read d = 2 in place of 19)."""
    s_num = 3 if slack == 1.5 else 2
    edge = (1 << 31) // (s_num * W)
    rng = np.random.default_rng(W)
    for total in [edge, 2 * edge + 1, 118_000_000, (1 << 31) - 12_345, (1 << 31) - 1]:
        counts = np.concatenate([
            np.arange(edge - 3, edge + 4),
            np.arange(total - 3, total + 1),
            rng.integers(0, total + 1, 500),
        ])
        counts = counts[(counts >= 0) & (counts <= total)]
        for d_max in (16, W):
            want = adaptive_d_counts(counts, total, W, d_max=d_max, slack=slack)
            got = adaptive_d_counts(
                jnp.asarray(counts, jnp.int32), jnp.int32(total), W,
                d_max=d_max, slack=slack,
            )
            np.testing.assert_array_equal(np.asarray(got), want)
    assert int(adaptive_d_counts(
        jnp.int32(11_000_000), jnp.int32(118_000_000), 100, d_max=100
    )) == 19


def test_offline_and_online_d_choices_agree_on_stationary_streams():
    """Satellite regression: same stream, no drift -> the online variant's
    balance matches the offline pre-pass (and bit-exactly so when the carry
    is warm-started and frozen; see test_partitioner_invariants)."""
    W = 100
    keys = zipf_stream(25_000, 5_000, 1.8, seed=11)
    off = avg_imbalance_fraction(
        np.asarray(d_choices_partition(keys, W, capacity=256)), W
    )
    on = avg_imbalance_fraction(
        np.asarray(online_d_choices_partition(keys, W, capacity=256)), W
    )
    assert on <= 1.2 * off + 1e-4, (on, off)


def test_online_ss_from_tracker_roundtrip():
    keys = zipf_stream(10_000, 1_000, 1.5, seed=2)
    tracker = SpaceSavingTracker(64)
    tracker.update(keys)
    state = online_ss_from_tracker(tracker, 64)
    assert int(state.total) == tracker.total
    for k, c in tracker._ss.counts.items():
        assert int(online_ss_estimate(state, k)) == c


def test_tracker_decay_windowed_mode():
    tracker = SpaceSavingTracker(32)
    tracker.update(np.full(1_000, 5, np.int64))
    assert tracker.is_head(5, theta=0.5)
    tracker.decay(0.5)
    assert tracker.total == 500
    assert tracker._ss.counts[5] == 500
    # decay keeps fractions, so head status is unchanged on a stable stream
    assert tracker.is_head(5, theta=0.5)
    # a one-element tail entry decays away entirely
    tracker.offer(9)
    tracker.decay(0.5)
    assert 9 not in tracker._ss.counts


def test_head_threshold_is_balanceability_bound():
    assert head_threshold(100, 2) == pytest.approx(0.02)
