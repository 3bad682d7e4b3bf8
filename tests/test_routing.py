"""The routing-policy substrate: hash unification, ledger accounting, the
per-request-adapter == route_batch differential, and the W-Choices edge
policy's cold/hot contracts (ISSUE 5 satellites)."""
import numpy as np
import pytest

from repro.core.hashing import hash_choices, hash_choices_np
from repro.core.routing import (
    ROUTING_POLICIES,
    LoadLedger,
    PoTCPolicy,
    RoundRobinPolicy,
    WChoicesPolicy,
    make_policy,
)
from repro.core.streams import multi_tenant_stream, zipf_stream
from repro.serving import PolicyScheduler

HOST_POLICIES = ["kg", "rr", "potc", "w_choices"]


# --- hash unification -------------------------------------------------------


def test_numpy_hash_bit_identical_to_device_hash():
    """hash_choices_np is the schedulers' hash; it must equal the device
    family exactly or edge and core disagree on candidate replicas."""
    keys = zipf_stream(4096, 1000, 1.2, seed=3)
    for d, seed, n in [(1, 0, 7), (2, 4, 16), (5, 99, 101)]:
        np.testing.assert_array_equal(
            hash_choices_np(keys, n, d=d, seed=seed),
            np.asarray(hash_choices(keys, n, d=d, seed=seed)),
        )


def test_scalar_hash_matches_vector_hash():
    got = [int(hash_choices_np(k, 16, d=1, seed=5)[0]) for k in range(64)]
    want = hash_choices_np(np.arange(64), 16, d=1, seed=5)[:, 0].tolist()
    assert got == want


# --- LoadLedger -------------------------------------------------------------


def test_ledger_acquire_release_clamps():
    led = LoadLedger(4)
    led.acquire(1, 5.0)
    led.acquire(1, 2.0)
    assert led.loads[1] == 7.0
    led.release(1, 3.0)
    assert led.loads[1] == 4.0
    led.release(1, 99.0)  # over-release clamps at zero
    assert led.loads[1] == 0.0
    assert (led.loads >= 0).all()


def test_ledger_imbalance_fraction():
    led = LoadLedger(4)
    for r, c in [(0, 8.0), (1, 4.0), (2, 2.0), (3, 2.0)]:
        led.acquire(r, c)
    assert led.imbalance() == pytest.approx(8.0 - 4.0)
    assert led.imbalance_fraction() == pytest.approx(4.0 / 16.0)


# --- differential: per-request adapter == route_batch ------------------------


@pytest.mark.parametrize("name", HOST_POLICIES)
def test_adapter_bit_identical_to_route_batch(name):
    """ISSUE satellite: a fresh PolicyScheduler driven request by request
    (no completions) must reproduce route_batch exactly — same policy code,
    same ledger arithmetic, same stream."""
    keys, _ = multi_tenant_stream(6_000, n_tenants=3, n_keys=400, z=1.5, seed=2)
    batch = make_policy(name, 24, d=2, seed=7).route_batch(keys)
    sched = PolicyScheduler(make_policy(name, 24, d=2, seed=7))
    per_request = np.array([sched.route(int(k)) for k in keys], np.int32)
    np.testing.assert_array_equal(batch, per_request)
    np.testing.assert_allclose(
        sched.loads, np.bincount(batch, minlength=24).astype(np.float64)
    )


@pytest.mark.parametrize("name", ["potc", "w_choices"])
def test_adapter_differential_with_costs(name):
    keys = zipf_stream(3_000, 300, 1.3, seed=5)
    costs = np.random.default_rng(0).lognormal(0.0, 0.5, len(keys))
    batch = make_policy(name, 10, d=2, seed=1).route_batch(keys, costs)
    sched = PolicyScheduler(make_policy(name, 10, d=2, seed=1))
    per = np.array(
        [sched.route(int(k), float(c)) for k, c in zip(keys, costs)], np.int32
    )
    np.testing.assert_array_equal(batch, per)


def test_route_batch_is_deterministic_across_calls():
    """route_batch resets estimator state: two calls, identical output."""
    keys = zipf_stream(2_000, 100, 1.5, seed=1)
    pol = make_policy("w_choices", 16, d=2, seed=0)
    a, b = pol.route_batch(keys), pol.route_batch(keys)
    np.testing.assert_array_equal(a, b)
    rr = make_policy("rr", 5, seed=3)
    np.testing.assert_array_equal(rr.route_batch(keys), rr.route_batch(keys))


# --- individual policy contracts --------------------------------------------


def test_kg_matches_single_choice_hash():
    keys = zipf_stream(1_000, 200, 1.0, seed=0)
    out = make_policy("kg", 13, seed=2).route_batch(keys)
    np.testing.assert_array_equal(
        out, hash_choices_np(keys, 13, d=1, seed=2)[:, 0]
    )


def test_rr_uniform_and_seed_offsets():
    out = make_policy("rr", 5, seed=0).route_batch(np.zeros(100, np.int32))
    counts = np.bincount(out, minlength=5)
    assert counts.max() - counts.min() <= 1
    # the seed is honored as a start offset: different seeds, shifted cycles
    a = RoundRobinPolicy(7, seed=1).route_batch(np.zeros(14, np.int32))
    b = RoundRobinPolicy(7, seed=2).route_batch(np.zeros(14, np.int32))
    assert a[0] != b[0] or not np.array_equal(a, b)
    assert (np.diff(a) % 7 == 1).all()  # still cyclic


def test_potc_fanout_bounded_by_d():
    keys = zipf_stream(5_000, 60, 1.0, seed=4)
    for d in (2, 3):
        out = PoTCPolicy(16, d=d, seed=0).route_batch(keys)
        fan = {}
        for k, r in zip(keys, out):
            fan.setdefault(int(k), set()).add(int(r))
        assert max(len(v) for v in fan.values()) <= d


# --- W-Choices edge policy (ISSUE satellite: cold->hot transition) ----------


def test_w_choices_cold_to_hot_transition_routes_globally():
    """A key starts cold (PoTC candidates only) and, once its tracked
    fraction clears theta, routes to the globally least-loaded replica."""
    n = 16
    pol = WChoicesPolicy(n, d=2, seed=0, min_count=8)
    pol.reset()
    led = LoadLedger(n)
    hot = 7
    cand = set(int(c) for c in pol.candidates(hot))
    # interleave the hot key with uniform cold traffic
    rng = np.random.default_rng(0)
    replicas_seen = []
    was_hot = []
    for i in range(4_000):
        k = hot if rng.random() < 0.6 else int(rng.integers(100, 5000))
        c = pol.decide(k, led.loads)
        led.acquire(c, 1.0)
        if k == hot:
            replicas_seen.append(c)
            was_hot.append(pol.is_hot(hot))
    # cold phase: only the two hash candidates; hot phase: global argmin
    first_hot = was_hot.index(True)
    assert first_hot > 0, "key must start cold (min_count floor)"
    assert set(replicas_seen[:first_hot]) <= cand
    assert len(set(replicas_seen)) > 2, "hot key escaped its candidates"


def test_w_choices_cold_keys_stay_within_d_replicas():
    rng = np.random.default_rng(1)
    keys = np.where(rng.random(10_000) < 0.5, 3, rng.integers(10, 500, 10_000))
    out = WChoicesPolicy(16, d=2, seed=0).route_batch(keys)
    fan = {}
    for k, r in zip(keys, out):
        fan.setdefault(int(k), set()).add(int(r))
    assert max(len(v) for k, v in fan.items() if k != 3) <= 2
    assert len(fan[3]) > 2


def test_w_choices_batch_beats_potc_past_balanceability_limit():
    rng = np.random.default_rng(0)
    keys = np.where(rng.random(20_000) < 0.6, 7, rng.integers(100, 5000, 20_000))

    def frac(assign, n):
        loads = np.bincount(assign, minlength=n).astype(float)
        return (loads.max() - loads.mean()) / loads.sum()

    f_w = frac(WChoicesPolicy(16, d=2, seed=0).route_batch(keys), 16)
    f_p = frac(PoTCPolicy(16, d=2, seed=0).route_batch(keys), 16)
    assert f_w < f_p / 5
    assert f_w < 0.01


# --- registry / device-backed policies --------------------------------------


def test_make_policy_unknown_name_raises():
    with pytest.raises(ValueError, match="unknown routing policy"):
        make_policy("nope", 4)


def test_registry_names_match_classes():
    for name, cls in ROUTING_POLICIES.items():
        assert cls.name == name


def test_device_policy_rejects_per_request_and_costs():
    pol = make_policy("w_choices_kernel", 8)
    with pytest.raises(NotImplementedError):
        pol.decide(1, np.zeros(8))
    with pytest.raises(ValueError, match="batch-only"):
        PolicyScheduler(pol)
    with pytest.raises(ValueError, match="unit-cost"):
        pol.route_batch(np.arange(8), costs=np.full(8, 2.0))


def test_device_w_policy_matches_host_w_partitioner():
    """The registered device-backed W policy rides the one-shard sharded
    scan; at block=1 it is bit-exact to w_choices_partition, which shares
    its head set with the host batch path."""
    from repro.core.partitioners import w_choices_partition

    keys = zipf_stream(1_024, 200, 1.6, seed=0)
    dev = make_policy(
        "w_choices_kernel", 50, d=2, seed=0, block=1, capacity=1024
    )
    np.testing.assert_array_equal(
        dev.route_batch(keys),
        np.asarray(w_choices_partition(keys, 50, d=2, seed=0, capacity=1024)),
    )


def test_device_d_policy_matches_host_d_partitioner():
    """The one-shard sharded scan at block=1 == d_choices_partition (same
    pre-pass)."""
    from repro.core.partitioners import d_choices_partition

    keys = zipf_stream(1_024, 200, 1.6, seed=1)
    dev = make_policy(
        "d_choices_kernel", 50, d=2, seed=0, d_max=8, block=1, capacity=1024
    )
    np.testing.assert_array_equal(
        dev.route_batch(keys),
        np.asarray(
            d_choices_partition(keys, 50, d=2, d_max=8, seed=0, capacity=1024)
        ),
    )
