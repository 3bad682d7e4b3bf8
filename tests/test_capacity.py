"""Per-worker capacity weighting: the cross-cutting contracts (PR 9).

The capacity extension (arXiv 1705.09073) touches every routing layer —
LoadLedger, all registered policies, all registered partitioners, the chunk
step, the sharded router — and its safety story is a single invariant:

  *capacities=None and uniform capacities are BIT-EXACT to the unweighted
  path, on every registered entry point.*

That is what makes the feature free to adopt: turning it on with a uniform
vector changes nothing, and the weighted path only ever reroutes when the
vector says workers genuinely differ.  This module sweeps the registries so
a future capacity-aware implementation cannot register itself without
inheriting the differentials, and pins the two boundary semantics:

  * zero capacity == dead for host policies and the ledger (a worker that
    can do no work never wins an argmin), while device-backed policies
    REJECT non-positive capacities (the kernels divide by them);
  * elastic rescale (serving.sim.Autoscaler) conserves work — every request
    is completed or shed, and the ledger drains to exactly zero.
"""
import inspect

import numpy as np
import pytest

from repro.core import (
    PARTITIONERS,
    ROUTING_POLICIES,
    LoadLedger,
    capacity_imbalance_fraction,
    make_policy,
    zipf_stream,
)
from repro.serving import Autoscaler, PoTCScheduler, simulate_serving

N = 8
CAPS = np.array([1.0, 2.0, 4.0, 1.0, 2.0, 4.0, 1.0, 2.0])


def _keys(m=3_000, seed=0):
    return zipf_stream(m, 300, 1.3, seed=seed)


def _capacity_partitioners():
    """Registered partitioners that accept a capacities vector."""
    return [
        (name, fn) for name, fn in PARTITIONERS.items()
        if "capacities" in inspect.signature(fn).parameters
    ]


def _partition(fn, keys, **kw):
    sig = inspect.signature(fn).parameters
    if "emulate" in sig:  # sharded variants: force the 1-device ref path
        kw.setdefault("emulate", True)
    if "n_keys" in sig:  # potc_static_partition sizes its key table up front
        kw.setdefault("n_keys", int(np.max(keys)) + 1)
    return np.asarray(fn(keys, N, **kw))


# ---------------------------------------------------------------------------
# uniform capacities are bit-exact to the unweighted path, everywhere
# ---------------------------------------------------------------------------

def test_every_registered_partitioner_is_capacity_aware():
    """The registry sweep below must cover the full registry: any
    partitioner registered without a capacities parameter is a hole in the
    capacity story (kg/sg route capacity-blind by *algorithm* — they still
    take and ignore-or-use the argument uniformly)."""
    missing = [n for n, f in PARTITIONERS.items()
               if "capacities" not in inspect.signature(f).parameters]
    assert missing == ["kg", "sg"], missing


@pytest.mark.parametrize("name,fn", _capacity_partitioners())
def test_partitioner_uniform_capacity_bit_exact(name, fn):
    keys = _keys()
    base = _partition(fn, keys)
    unif = _partition(fn, keys, capacities=np.full(N, 1.0))
    np.testing.assert_array_equal(base, unif, err_msg=name)


@pytest.mark.parametrize("name,fn", _capacity_partitioners())
def test_partitioner_heterogeneous_capacity_valid(name, fn):
    """Weighted assignments stay in range and the capacity vector reaches
    the argmin: on a skewed pool some messages must move."""
    keys = _keys()
    base = _partition(fn, keys)
    het = _partition(fn, keys, capacities=CAPS)
    assert het.min() >= 0 and het.max() < N
    if name != "potc":  # potc samples d random candidates; loads only
        assert (het != base).any(), f"{name}: capacities had no effect"


@pytest.mark.parametrize("pname", sorted(ROUTING_POLICIES))
def test_policy_uniform_capacity_bit_exact(pname):
    keys = _keys(2_000)
    base = np.asarray(make_policy(pname, N).route_batch(keys))
    unif = np.asarray(
        make_policy(pname, N).route_batch(keys, capacities=np.full(N, 2.0))
    )
    np.testing.assert_array_equal(base, unif, err_msg=pname)


# ---------------------------------------------------------------------------
# zero capacity == dead (host), rejected (device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pname", sorted(ROUTING_POLICIES))
def test_zero_capacity_worker_gets_no_traffic(pname):
    caps = CAPS.copy()
    caps[3] = 0.0
    policy = make_policy(pname, N)
    if not policy.per_request:  # device-backed: kernels divide by capacity
        with pytest.raises(ValueError, match="strictly positive"):
            policy.route_batch(_keys(512), capacities=caps)
        return
    a = np.asarray(policy.route_batch(_keys(2_000), capacities=caps))
    assert not (a == 3).any(), f"{pname} routed to a zero-capacity worker"


def test_ledger_zero_capacity_is_dead():
    led = LoadLedger(4, capacities=[1.0, 0.0, 2.0, 1.0])
    assert list(led.live_mask()) == [True, False, True, True]
    led.kill(0)
    assert list(led.live_mask()) == [False, False, True, True]
    led.revive(0)
    assert list(led.live_mask()) == [True, False, True, True]


@pytest.mark.parametrize("bad", [
    [1.0, 2.0],                   # wrong shape
    [1.0, -1.0, 1.0, 1.0],        # negative
    [1.0, float("nan"), 1.0, 1.0],
    [1.0, float("inf"), 1.0, 1.0],
])
def test_ledger_rejects_malformed_capacities(bad):
    with pytest.raises(ValueError):
        LoadLedger(4, capacities=bad)


def test_ledger_normalized_loads_and_imbalance():
    led = LoadLedger(3, capacities=[1.0, 2.0, 4.0])
    for r, c in ((0, 1.0), (1, 2.0), (2, 4.0)):  # exactly proportional
        led.acquire(r, c)
    np.testing.assert_allclose(led.normalized_loads(), [1.0, 1.0, 1.0])
    assert led.imbalance() == pytest.approx(0.0)
    led.acquire(0, 1.0)  # overload the slow worker
    assert led.imbalance() > 0.0


# ---------------------------------------------------------------------------
# the metric
# ---------------------------------------------------------------------------

def test_capacity_imbalance_zero_iff_proportional():
    assign = np.repeat(np.arange(3), [100, 200, 400])
    assert capacity_imbalance_fraction(
        assign, [1.0, 2.0, 4.0]) == pytest.approx(0.0)
    assert capacity_imbalance_fraction(assign, [1.0, 1.0, 1.0]) > 0.0


def test_capacity_imbalance_uniform_matches_relative_imbalance():
    rng = np.random.default_rng(0)
    assign = rng.integers(0, 5, size=4_000)
    loads = np.bincount(assign, minlength=5)
    expect = (loads.max() - loads.mean()) / loads.mean()
    got = capacity_imbalance_fraction(assign, np.ones(5))
    assert got == pytest.approx(expect)


# ---------------------------------------------------------------------------
# elastic rescale conserves work and drains clean
# ---------------------------------------------------------------------------

def _wave_costs(m):
    costs = np.ones(m)
    costs[m // 3: 2 * m // 3] = 2.5
    return costs


def test_autoscaler_rescale_conserves_and_drains():
    m = 6_000
    asc = Autoscaler(min_replicas=3, max_replicas=N, initial=3,
                     high=3.0, low=0.5, check_every=m // 100,
                     cooldown=m // 40)
    sched = PoTCScheduler(N, seed=0)
    res = simulate_serving(sched, _keys(m, seed=1), costs=_wave_costs(m),
                           utilization=0.85, autoscaler=asc)
    # conservation: nothing lost across every kill/revive transition
    assert res.completed + res.shed == m
    # the strict ledger drains to exactly zero after the tail drain
    np.testing.assert_array_equal(sched.ledger.loads, np.zeros(N))
    # the wave actually exercised both directions
    ups = [e for e in res.scale_events if e[1] == 1]
    downs = [e for e in res.scale_events if e[1] == -1]
    assert ups and downs
    # pool size stays within the configured band at every event
    size = asc.initial
    for _, d, _ in res.scale_events:
        size += d
        assert asc.min_replicas <= size <= asc.max_replicas
    # every request completed on a replica that existed
    assert res.assign.min() >= 0 and res.assign.max() < N


def test_autoscaler_with_heterogeneous_capacities():
    m = 4_000
    caps = CAPS.copy()
    asc = Autoscaler(min_replicas=2, max_replicas=N, initial=2,
                     high=3.0, low=0.5, check_every=m // 100,
                     cooldown=m // 50)
    sched = PoTCScheduler(N, seed=0, capacities=caps)
    res = simulate_serving(sched, _keys(m, seed=2), costs=_wave_costs(m),
                           utilization=0.85, autoscaler=asc)
    assert res.completed + res.shed == m
    np.testing.assert_array_equal(sched.ledger.loads, np.zeros(N))


def test_autoscaler_never_revives_zero_capacity_replica():
    m = 3_000
    caps = np.array([1.0, 1.0, 1.0, 0.0, 1.0, 1.0, 1.0, 1.0])
    asc = Autoscaler(min_replicas=2, max_replicas=7, initial=2,
                     high=2.0, low=0.5, check_every=m // 100,
                     cooldown=m // 50)
    sched = PoTCScheduler(N, seed=0, capacities=caps)
    res = simulate_serving(sched, _keys(m, seed=3), costs=_wave_costs(m),
                           utilization=0.9, autoscaler=asc)
    assert res.completed + res.shed == m
    assert not (res.assign == 3).any()
    assert all(r != 3 for _, _, r in res.scale_events)


def test_autoscaler_max_replicas_bounded_by_eligible():
    caps = np.array([1.0, 1.0, 0.0, 1.0])
    sched = PoTCScheduler(4, seed=0, capacities=caps)
    asc = Autoscaler(min_replicas=1, max_replicas=4, initial=1)
    with pytest.raises(ValueError, match="positive-capacity"):
        simulate_serving(sched, _keys(500), autoscaler=asc)


@pytest.mark.parametrize("kw", [
    dict(min_replicas=0, max_replicas=4),
    dict(min_replicas=3, max_replicas=2),
    dict(min_replicas=1, max_replicas=4, initial=5),
    dict(min_replicas=1, max_replicas=4, high=1.0, low=1.0),
    dict(min_replicas=1, max_replicas=4, check_every=0),
    dict(min_replicas=1, max_replicas=4, cooldown=-1),
])
def test_autoscaler_rejects_malformed_config(kw):
    with pytest.raises(ValueError):
        Autoscaler(**kw)


def test_uniform_capacity_serving_bit_exact():
    """The whole serving stack — scheduler, ledger, simulator service rates,
    sampling — reproduces the unweighted run exactly at uniform capacity."""
    m = 3_000
    keys = _keys(m, seed=4)
    base = simulate_serving(PoTCScheduler(N, seed=0), keys)
    unif = simulate_serving(
        PoTCScheduler(N, seed=0, capacities=np.full(N, 1.0)), keys)
    np.testing.assert_array_equal(base.assign, unif.assign)
    np.testing.assert_array_equal(base.latency, unif.latency)
    np.testing.assert_array_equal(base.sample_imbalance,
                                  unif.sample_imbalance)
    assert base.makespan == unif.makespan
