"""Differential partitioner invariants (deterministic; always in tier-1).

Covers the cross-implementation contracts that must hold exactly:
  (a) pkg_partition_batched(block=1) == pkg_partition, message for message
  (b) every PKG assignment lies in the key's hash_choices candidate set
  (c) shuffle imbalance <= 1
  (d) D-/W-Choices imbalance <= PKG on Zipf z >= 1.5 at n_workers = 100
  (e) the fully-online adaptive variants vs their offline pre-pass twins:
      frozen-carry online == offline bit-exactly (two very different code
      paths computing the same decisions), tail-only streams == PKG, and the
      decayed online tracker wins under head-key drift
plus the adaptive partitioners' tail-key contract: with no head keys they
reproduce PKG bit-exactly (same candidates, same tie-breaking).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SCALE_SCENARIOS,
    SpaceSavingTracker,
    adaptive_d,
    d_choices_partition,
    drift_stream,
    hash_choices,
    head_threshold,
    online_d_choices_partition,
    online_ss_from_tracker,
    online_w_choices_partition,
    pkg_partition,
    pkg_partition_batched,
    shuffle_partition,
    w_choices_partition,
    w_choices_sharded_partition,
    zipf_stream,
)
from repro.core.metrics import avg_imbalance_fraction, final_imbalance_fraction


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("z", [0.8, 1.4])
def test_batched_block1_equals_sequential(seed, z):
    """(a) A block of one key is exactly the sequential greedy scan."""
    keys = jnp.asarray(zipf_stream(3_000, 400, z, seed=seed))
    a_seq = np.asarray(pkg_partition(keys, 12))
    a_b1 = np.asarray(pkg_partition_batched(keys, 12, block=1))
    np.testing.assert_array_equal(a_seq, a_b1)


@pytest.mark.parametrize("d", [2, 3, 4])
@pytest.mark.parametrize("n_workers", [5, 16, 100])
def test_pkg_assignment_within_candidates(d, n_workers):
    """(b) PKG only ever routes to one of the key's d hash candidates."""
    keys = jnp.asarray(zipf_stream(4_000, 600, 1.2, seed=d))
    a = np.asarray(pkg_partition(keys, n_workers, d=d))
    cand = np.asarray(hash_choices(keys, n_workers, d=d))
    assert (a[:, None] == cand).any(axis=1).all()


@pytest.mark.parametrize("m,n_workers", [(1, 2), (97, 10), (10_000, 64)])
def test_shuffle_imbalance_at_most_one(m, n_workers):
    """(c) Round-robin is perfectly balanced up to integrality."""
    a = np.asarray(shuffle_partition(jnp.zeros(m, jnp.int32), n_workers))
    loads = np.bincount(a, minlength=n_workers)
    assert loads.max() - loads.min() <= 1


@pytest.mark.parametrize("name", ["W100_z1.6", "W100_z2.0", "W50_z1.8"])
def test_adaptive_beats_pkg_on_scale_scenarios(name):
    """(d) In the large-deployment regime the adaptive variants dominate."""
    sc = SCALE_SCENARIOS[name]
    keys = sc.generate(seed=11, scale=0.25)
    W = sc.n_workers
    assert sc.head_fraction() > head_threshold(W), "scenario must be PKG-hard"
    pkg = final_imbalance_fraction(np.asarray(pkg_partition(jnp.asarray(keys), W)), W)
    dch = final_imbalance_fraction(np.asarray(d_choices_partition(keys, W)), W)
    wch = final_imbalance_fraction(np.asarray(w_choices_partition(keys, W)), W)
    assert dch < pkg, (name, dch, pkg)
    assert wch < pkg, (name, wch, pkg)
    assert wch < 1e-3, (name, wch)  # head-anywhere restores near-perfection


def test_adaptive_equals_pkg_without_head_keys():
    """Tail keys keep PKG's exact routing: below-threshold streams match."""
    keys = zipf_stream(20_000, 5_000, 0.5, seed=3)  # p1 << d/W
    a_pkg = np.asarray(pkg_partition(jnp.asarray(keys), 10))
    np.testing.assert_array_equal(a_pkg, np.asarray(d_choices_partition(keys, 10)))
    np.testing.assert_array_equal(a_pkg, np.asarray(w_choices_partition(keys, 10)))


@pytest.mark.parametrize("name", ["W100_z1.6", "W100_z2.0"])
def test_w_choices_kernel_near_perfect_on_scale_scenarios(name):
    """(d) for the device path: the one-shard W router (default block=128,
    global-argmin water-fill) keeps the near-perfect balance of the
    sequential W-Choices where PKG explodes — the gap ROADMAP open item 1
    existed for."""
    sc = SCALE_SCENARIOS[name]
    keys = sc.generate(seed=11, scale=0.25)
    W = sc.n_workers
    pkg = final_imbalance_fraction(np.asarray(pkg_partition(jnp.asarray(keys), W)), W)
    wk = final_imbalance_fraction(
        np.asarray(w_choices_sharded_partition(keys, W)), W
    )
    assert wk < pkg / 10, (name, wk, pkg)
    assert wk < 5e-3, (name, wk)


def test_d_choices_candidates_extend_pkg_candidates():
    """d(k) >= 2 candidates always include PKG's two (seed-prefix property)."""
    keys = jnp.asarray(zipf_stream(1_000, 100, 1.0, seed=0))
    c2 = np.asarray(hash_choices(keys, 32, d=2))
    c8 = np.asarray(hash_choices(keys, 32, d=8))
    np.testing.assert_array_equal(c2, c8[:, :2])


@pytest.mark.parametrize("z", [1.4, 1.8])
def test_online_frozen_equals_offline_differentially(z):
    """(e) The online scan with a warm frozen carry must reproduce the offline
    pre-pass variants bit-exactly: the offline path computes head sets and
    d(k) in numpy (searchsorted lookup, int64), the online path recomputes
    them per element inside the lax.scan carry (int32 table probes) — any
    divergence in threshold/tie-breaking/integer-ceil logic shows up here."""
    W, cap = 100, 256
    keys = zipf_stream(20_000, 5_000, z, seed=int(z * 10))
    tracker = SpaceSavingTracker(cap)
    tracker.update(np.asarray(keys, np.int32))
    state = online_ss_from_tracker(tracker, cap)
    a_off = np.asarray(d_choices_partition(keys, W, capacity=cap))
    a_on = np.asarray(
        online_d_choices_partition(
            keys, W, capacity=cap, init_state=state, update_tracker=False
        )
    )
    np.testing.assert_array_equal(a_off, a_on)
    w_off = np.asarray(w_choices_partition(keys, W, capacity=cap))
    w_on = np.asarray(
        online_w_choices_partition(
            keys, W, capacity=cap, init_state=state, update_tracker=False
        )
    )
    np.testing.assert_array_equal(w_off, w_on)


def test_online_frozen_equals_offline_adversarial_small_stream():
    """(e) Boundary regression: a key seen 7 times in 300 messages clears
    theta = 0.02 by fraction (7/300) but not the min_count floor — offline
    and frozen-carry online must make the SAME call (both use the canonical
    head_test with min_count), or the differential contract breaks exactly
    where estimates are noisiest."""
    W, cap = 100, 64
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 200, 300).astype(np.int32)
    keys[rng.choice(300, 7, replace=False)] = 777  # 7/300 >= theta, < min_count
    tracker = SpaceSavingTracker(cap)
    tracker.update(keys)
    state = online_ss_from_tracker(tracker, cap)
    a_off = np.asarray(d_choices_partition(keys, W, capacity=cap))
    a_on = np.asarray(
        online_d_choices_partition(
            keys, W, capacity=cap, init_state=state, update_tracker=False
        )
    )
    np.testing.assert_array_equal(a_off, a_on)
    w_off = np.asarray(w_choices_partition(keys, W, capacity=cap))
    w_on = np.asarray(
        online_w_choices_partition(
            keys, W, capacity=cap, init_state=state, update_tracker=False
        )
    )
    np.testing.assert_array_equal(w_off, w_on)


def test_online_equals_pkg_without_head_keys():
    """(e) Cold-start online on a below-threshold stream is PKG bit-exactly —
    live tracker updates included, no key ever clears theta."""
    keys = zipf_stream(20_000, 5_000, 0.5, seed=3)  # p1 << d/W
    a_pkg = np.asarray(pkg_partition(jnp.asarray(keys), 10))
    np.testing.assert_array_equal(
        a_pkg, np.asarray(online_d_choices_partition(keys, 10))
    )
    np.testing.assert_array_equal(
        a_pkg, np.asarray(online_w_choices_partition(keys, 10))
    )


def test_online_matches_offline_on_stationary_stream():
    """(e) Live (cold-start, updating) online lands on the offline variant's
    balance once the head set is stable."""
    W = 100
    keys = zipf_stream(30_000, 5_000, 1.8, seed=11)
    d_off = final_imbalance_fraction(
        np.asarray(d_choices_partition(keys, W, capacity=256)), W
    )
    d_on = final_imbalance_fraction(
        np.asarray(online_d_choices_partition(keys, W, capacity=256)), W
    )
    assert d_on <= 1.2 * d_off + 1e-4, (d_on, d_off)
    w_off = final_imbalance_fraction(
        np.asarray(w_choices_partition(keys, W, capacity=256)), W
    )
    w_on = final_imbalance_fraction(
        np.asarray(online_w_choices_partition(keys, W, capacity=256)), W
    )
    assert w_on <= 2.0 * w_off + 1e-4, (w_on, w_off)


def test_online_decayed_beats_offline_under_drift():
    """(e) The tentpole claim, in-suite at reduced size: when the head set
    churns, the whole-stream pre-pass dilutes below theta while the decayed
    online tracker follows the rotation."""
    W, m = 100, 40_000
    keys = drift_stream(m, 5_000, 1.8, half_life=m // 8, seed=5)
    decay = m // 16
    w_off = avg_imbalance_fraction(
        np.asarray(w_choices_partition(keys, W, capacity=256)), W
    )
    w_on = avg_imbalance_fraction(
        np.asarray(
            online_w_choices_partition(keys, W, capacity=256, decay_period=decay)
        ),
        W,
    )
    assert w_on < w_off, (w_on, w_off)


def test_space_saving_tracker_finds_true_head():
    keys = zipf_stream(50_000, 5_000, 1.8, seed=7)
    tracker = SpaceSavingTracker(capacity=512)
    tracker.update(keys)
    counts = np.bincount(keys)
    true_head = set(np.flatnonzero(counts / len(keys) >= 0.02).tolist())
    ids, p_hat = tracker.head_keys(0.02)
    assert true_head <= set(ids.tolist())  # no false negatives
    # overestimation is bounded by total/capacity
    for k, p in zip(ids, p_hat):
        assert p <= counts[k] / len(keys) + 1.0 / 512 + 1e-12


def test_adaptive_d_rule():
    p = np.array([0.001, 0.02, 0.3, 0.9])
    d = adaptive_d(p, n_workers=100, d_base=2, d_max=16)
    assert d.tolist() == [2, 4, 16, 16]
    assert (adaptive_d(p, n_workers=4, d_base=2, d_max=4) <= 4).all()
