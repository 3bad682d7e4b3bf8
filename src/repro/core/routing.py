"""Unified routing-policy substrate (DESIGN.md §8).

A RoutingPolicy is the paper's algorithm written ONCE and consumed three ways:

1. **Batch** — ``route_batch(keys, costs) -> assignments`` routes a whole
   stream on the host (candidate hashing vectorized via hash_choices_np, the
   load-dependent greedy step a tight numpy loop).  This is what simulations
   and benchmarks call.
2. **Per-request** — ``decide(key, loads)`` is one routing decision over a
   LoadLedger snapshot.  serving.scheduler.PolicyScheduler wraps (policy,
   ledger) into the classic ``route/complete`` scheduler interface; driving a
   fresh adapter over a stream with no completions is bit-identical to
   ``route_batch`` on the same stream (the differential contract in
   tests/test_routing.py).
3. **Device** — batch-only policies backed by the sharded router
   (parallel.sharded_router; the ``*_kernel`` names run it at one shard),
   so a benchmark sweep can put the device path on the same axis as the
   host policies.

Load accounting lives in exactly one place: LoadLedger.  Policies never
mutate loads themselves — ``decide`` reads a loads vector; the caller
(route_batch's internal ledger, or the serving adapter's shared one) acquires
and releases.  Estimator state (the W-Choices SPACESAVING tracker, the
round-robin cursor) lives on the policy and is cleared by ``reset()``;
``route_batch`` always routes from a fresh state so repeated calls are
deterministic.

All candidates come from core.hashing's SplitMix32 family (hash_choices_np is
bit-identical to the device hash_choices), so the serving edge, the host
simulation and the kernels agree on the candidate replicas of every key.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.estimation import SpaceSavingTracker, head_threshold
from repro.core.hashing import derive_seeds_np, hash_choices_np, splitmix32_np


def _hash_key_np(key: int, seeds: np.ndarray, n_workers: int) -> np.ndarray:
    """Scalar fast path of hash_choices_np with precomputed per-choice seeds
    (bit-identical; ``seeds = derive_seeds_np(seed, d)``).  decide() runs
    once per request, so re-deriving the seed family there would dominate
    the serving adapter's hot path."""
    with np.errstate(over="ignore"):
        h = splitmix32_np(np.uint32(int(key) & 0xFFFFFFFF) ^ seeds)
        return (h % np.uint32(n_workers)).astype(np.int32)

def _cap_alive(alive: Optional[np.ndarray],
               capacities: Optional[np.ndarray]) -> Optional[np.ndarray]:
    """Fold zero-capacity workers into the alive mask: a worker with c_i == 0
    can absorb no work, so every policy treats it exactly like a dead replica
    (same rehash-chain / skip / live-argmin failover paths)."""
    if capacities is None:
        return alive
    pos = capacities > 0
    if pos.all():
        return alive
    return pos if alive is None else (alive & pos)


def _cap_loads(loads: np.ndarray,
               capacities: Optional[np.ndarray]) -> np.ndarray:
    """Capacity-normalized loads ``load_i / c_i`` (inf where c_i == 0, so a
    zero-capacity worker never wins an argmin).  ``capacities=None`` returns
    ``loads`` unchanged — the pre-capacity fast path, bit-identical."""
    if capacities is None:
        return loads
    out = np.full(len(loads), np.inf, dtype=np.float64)
    np.divide(loads, capacities, out=out, where=capacities > 0)
    return out


def _check_capacities(n: int, capacities) -> Optional[np.ndarray]:
    """Validate and canonicalize a capacities vector (None passes through)."""
    if capacities is None:
        return None
    cap = np.asarray(capacities, dtype=np.float64).reshape(-1)
    if cap.shape != (n,):
        raise ValueError(f"capacities shape {cap.shape} != ({n},)")
    if not np.isfinite(cap).all() or (cap < 0).any():
        raise ValueError("capacities must be finite and >= 0")
    if not (cap > 0).any():
        raise ValueError("at least one capacity must be positive")
    return cap


__all__ = [
    "LoadLedger",
    "RoutingPolicy",
    "KGPolicy",
    "RoundRobinPolicy",
    "PoTCPolicy",
    "WChoicesPolicy",
    "DeviceWChoicesPolicy",
    "DeviceDChoicesPolicy",
    "ShardedWChoicesPolicy",
    "ROUTING_POLICIES",
    "DEFAULT_SCHEDULER",
    "host_policy_names",
    "scheduler_sweep_names",
    "make_policy",
]


class LoadLedger:
    """THE outstanding-work account: one float64 vector, acquire/release.

    Every consumer of a policy talks to loads through this class, so the
    "route adds exactly cost, complete releases it, never negative" contract
    is written once instead of per scheduler class.

    Two robustness extensions ride on the same account:

    * ``strict`` — release() normally clamps at zero, which silently masks
      double-``complete()`` bugs; strict mode raises on over-release instead
      (beyond a float-accumulation epsilon).  The serving simulator enables
      it, so its "ledger drains to exactly zero" invariant is enforced, not
      assumed.
    * a **live-replica mask** — ``alive`` is a bool vector; ``kill()`` /
      ``revive()`` flip it, and policies consult it through ``decide`` so a
      dead replica's keys are drained and redistributed (DESIGN.md §8).
      ``imbalance()`` is computed over live replicas only: a dead replica's
      zero load is capacity removed from the cluster, not spare headroom.
    * **per-worker capacities** (arXiv 1705.09073) — an optional weights
      vector ``c``; imbalance and every load comparison downstream work on
      the capacity-normalized loads ``load_i / c_i``, so a 4x-speed worker
      legitimately carries 4x the outstanding work.  ``capacities=None``
      keeps the uniform-cluster code path bit-identical to before; a
      zero-capacity worker is folded into the live mask (it behaves exactly
      like a dead replica).
    """

    __slots__ = ("loads", "alive", "strict", "capacities", "_n_dead", "_cap_mask")

    _EPS = 1e-6  # float accumulation tolerance for strict over-release

    def __init__(self, n_replicas: int, strict: bool = False, capacities=None):
        self.loads = np.zeros(n_replicas, dtype=np.float64)
        self.alive = np.ones(n_replicas, dtype=bool)
        self.strict = strict
        self._n_dead = 0
        self.capacities = None
        self._cap_mask = None
        if capacities is not None:
            self.set_capacities(capacities)

    @property
    def n(self) -> int:
        return len(self.loads)

    @property
    def any_dead(self) -> bool:
        return self._n_dead > 0

    def set_capacities(self, capacities) -> None:
        """Install (or clear, with None) the per-worker capacity vector."""
        cap = _check_capacities(self.n, capacities)
        self.capacities = cap
        if cap is None or (cap > 0).all():
            self._cap_mask = None
        else:
            self._cap_mask = cap > 0

    def normalized_loads(self) -> np.ndarray:
        """``load_i / c_i`` (inf at zero capacity); ``loads`` itself when no
        capacities are set."""
        return _cap_loads(self.loads, self.capacities)

    def live_mask(self) -> Optional[np.ndarray]:
        """The alive vector when any replica is dead, else None — the exact
        argument ``RoutingPolicy.decide`` takes (None keeps the all-alive
        fast path bit-identical to the pre-failover code).  Zero-capacity
        workers are merged in as dead."""
        if self._cap_mask is not None:
            return (self.alive & self._cap_mask) if self._n_dead else self._cap_mask
        return self.alive if self._n_dead else None

    def kill(self, replica: int) -> None:
        """Mark a replica dead; it stops receiving routes until revive()."""
        if self.alive[replica]:
            if self._n_dead == self.n - 1:
                raise ValueError("cannot kill the last live replica")
            self.alive[replica] = False
            self._n_dead += 1

    def revive(self, replica: int) -> None:
        if not self.alive[replica]:
            self.alive[replica] = True
            self._n_dead -= 1

    def acquire(self, replica: int, cost: float = 1.0) -> None:
        self.loads[replica] += cost

    def release(self, replica: int, cost: float = 1.0) -> None:
        """Completion event; clamps at zero unless ``strict``, which raises
        on over-release (the signature of a double-complete bug)."""
        rem = self.loads[replica] - cost
        if rem < -self._EPS and self.strict:
            raise ValueError(
                f"over-release on replica {replica}: outstanding "
                f"{self.loads[replica]:.6g} < released {cost:.6g} "
                "(double complete()?)"
            )
        self.loads[replica] = max(0.0, rem)

    def imbalance(self) -> float:
        """I(t) = max - avg of the current outstanding work (live replicas).

        With capacities set, both terms are capacity-normalized:
        ``max_i load_i/c_i - sum(loads)/sum(c)`` over live, positive-capacity
        replicas — the heterogeneous-cluster objective of arXiv 1705.09073
        (reduces exactly to max - mean at uniform capacity 1).
        """
        if self.capacities is None:
            live = self.loads[self.alive] if self._n_dead else self.loads
            return float(live.max() - live.mean())
        mask = self.alive if self._cap_mask is None else (self.alive & self._cap_mask)
        l, c = self.loads[mask], self.capacities[mask]
        return float((l / c).max() - l.sum() / c.sum())

    def imbalance_fraction(self) -> float:
        """I(t) normalized by the average (normalized) outstanding work per
        unit capacity — scale-invariant, 0 when idle."""
        if self.capacities is None:
            return self.imbalance() / max(float(self.loads.sum()), 1.0)
        mask = self.alive if self._cap_mask is None else (self.alive & self._cap_mask)
        total = float(self.loads[mask].sum() / self.capacities[mask].sum())
        return self.imbalance() / max(total, 1.0)


class RoutingPolicy:
    """Base policy: stateful estimator + pure decision over a loads vector.

    Subclasses implement ``decide`` (and usually override ``route_batch`` to
    hoist candidate hashing out of the loop).  ``reset()`` clears estimator
    state; ``route_batch`` calls it first, so a batch call always routes the
    stream from scratch.
    """

    name = "base"
    per_request = True  # False for device-backed batch-only policies

    def __init__(self, n_replicas: int, d: int = 2, seed: int = 0):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        self.n = n_replicas
        self.d = min(d, n_replicas)
        self.seed = seed

    def reset(self) -> None:
        """Clear estimator state (tracker, cursors); loads live elsewhere."""

    def decide(self, key: int, loads: np.ndarray,
               alive: Optional[np.ndarray] = None,
               capacities: Optional[np.ndarray] = None) -> int:
        """One routing decision over a loads vector.

        ``alive`` is the live-replica mask (None == everyone up, the fast
        path — bit-identical to the pre-failover substrate).  With a mask,
        every policy must return a live replica: a dead replica's keys are
        redistributed by the policy's own mechanism (KG rehashes down a
        deterministic candidate chain, RR skips dead slots, PoTC/W-Choices
        restrict their least-loaded choice to live candidates and spill to
        the global live argmin when all d candidates are dead).

        ``capacities`` (arXiv 1705.09073) weights every load comparison by
        ``load_i / c_i``; zero-capacity workers are folded into ``alive``
        and take the same failover paths as dead replicas.  None keeps the
        uniform-cluster path bit-identical.
        """
        raise NotImplementedError

    @staticmethod
    def _live_argmin(loads: np.ndarray, alive: np.ndarray) -> int:
        """Least-loaded live replica (lowest index ties)."""
        return int(np.argmin(np.where(alive, loads, np.inf)))

    def _batch_costs(self, m: int, costs) -> np.ndarray:
        if costs is None:
            return np.ones(m, dtype=np.float64)
        costs = np.asarray(costs, dtype=np.float64)
        if costs.shape != (m,):
            raise ValueError(f"costs shape {costs.shape} != ({m},)")
        return costs

    def route_batch(self, keys, costs=None, capacities=None) -> np.ndarray:
        """Route a stream from a fresh state; the per-request reference.

        Default implementation is the literal decide/acquire loop; overrides
        must stay bit-identical to it (that IS the adapter contract).
        Overrides that hoist candidate hashing keep their fast path for
        ``capacities=None`` and defer here for the capacity-weighted case.
        """
        self.reset()
        keys = np.asarray(keys).reshape(-1)
        costs = self._batch_costs(len(keys), costs)
        ledger = LoadLedger(self.n, capacities=capacities)
        alive = ledger.live_mask()
        out = np.empty(len(keys), dtype=np.int32)
        for i, k in enumerate(keys):
            c = self.decide(int(k), ledger.loads, alive, ledger.capacities)
            ledger.acquire(c, costs[i])
            out[i] = c
        return out


class KGPolicy(RoutingPolicy):
    """Key grouping: sticky single-choice hashing (load-oblivious)."""

    name = "kg"

    # rehash-chain length for failover: P(all chain hops dead) with k of n
    # replicas down is (k/n)^FAILOVER_CHAIN before the lowest-index fallback
    FAILOVER_CHAIN = 8

    def __init__(self, n_replicas: int, d: int = 2, seed: int = 0):
        super().__init__(n_replicas, d=d, seed=seed)
        self._seeds = derive_seeds_np(seed, 1)
        self._chain_seeds = derive_seeds_np(seed, 1 + self.FAILOVER_CHAIN)

    def decide(self, key: int, loads: np.ndarray,
               alive: Optional[np.ndarray] = None,
               capacities: Optional[np.ndarray] = None) -> int:
        alive = _cap_alive(alive, capacities)
        r = int(_hash_key_np(key, self._seeds, self.n)[0])
        if alive is None or alive[r]:
            return r
        # failover: walk a deterministic rehash chain (same SplitMix32
        # family, extra seeds) so a dead replica's keys scatter across the
        # cluster instead of piling onto one neighbour; final fallback is
        # the lowest-index live replica.
        for r in _hash_key_np(key, self._chain_seeds[1:], self.n):
            if alive[r]:
                return int(r)
        return int(np.argmax(alive))

    def route_batch(self, keys, costs=None, capacities=None) -> np.ndarray:
        if capacities is not None and not (np.asarray(capacities) > 0).all():
            # zero-capacity workers must take the rehash chain: generic loop
            return super().route_batch(keys, costs, capacities)
        self.reset()
        keys = np.asarray(keys).reshape(-1)
        self._batch_costs(len(keys), costs)  # validate shape only
        return hash_choices_np(keys, self.n, d=1, seed=self.seed)[:, 0]


class RoundRobinPolicy(RoutingPolicy):
    """Shuffle grouping: cyclic, key- and load-oblivious.

    The seed is honored as a scrambled start offset, so replicated frontends
    with different seeds don't all hammer replica 0 in lockstep.
    """

    name = "rr"

    def __init__(self, n_replicas: int, d: int = 2, seed: int = 0):
        super().__init__(n_replicas, d=d, seed=seed)
        self._offset = int(splitmix32_np(np.uint32(seed & 0xFFFFFFFF))) % self.n
        self._step = 0

    def reset(self) -> None:
        self._step = 0

    def decide(self, key: int, loads: np.ndarray,
               alive: Optional[np.ndarray] = None,
               capacities: Optional[np.ndarray] = None) -> int:
        alive = _cap_alive(alive, capacities)
        c = (self._offset + self._step) % self.n
        if alive is not None:
            while not alive[c]:  # skip dead slots; cycle stays uniform
                self._step += 1
                c = (self._offset + self._step) % self.n
        self._step += 1
        return c

    def route_batch(self, keys, costs=None, capacities=None) -> np.ndarray:
        if capacities is not None and not (np.asarray(capacities) > 0).all():
            return super().route_batch(keys, costs, capacities)
        self.reset()
        keys = np.asarray(keys).reshape(-1)
        self._batch_costs(len(keys), costs)
        out = ((self._offset + np.arange(len(keys), dtype=np.int64)) % self.n)
        self._step = len(keys)
        return out.astype(np.int32)


class PoTCPolicy(RoutingPolicy):
    """PKG at the edge: d hash candidates, least-loaded wins (first-index
    ties), loads are whatever ledger the caller carries — local estimation
    when each frontend keeps its own."""

    name = "potc"

    def __init__(self, n_replicas: int, d: int = 2, seed: int = 0):
        super().__init__(n_replicas, d=d, seed=seed)
        self._seeds = derive_seeds_np(seed, self.d)

    def candidates(self, key: int) -> np.ndarray:
        return _hash_key_np(key, self._seeds, self.n)

    def decide(self, key: int, loads: np.ndarray,
               alive: Optional[np.ndarray] = None,
               capacities: Optional[np.ndarray] = None) -> int:
        alive = _cap_alive(alive, capacities)
        loads = _cap_loads(loads, capacities)
        c = self.candidates(key)
        if alive is None:
            return int(c[np.argmin(loads[c])])
        if not alive[c].any():
            # every candidate is dead: spill to the global live argmin (the
            # W-Choices move, borrowed as the failover redistribution step)
            return self._live_argmin(loads, alive)
        return int(c[np.argmin(np.where(alive[c], loads[c], np.inf))])

    def route_batch(self, keys, costs=None, capacities=None) -> np.ndarray:
        if capacities is not None:
            return super().route_batch(keys, costs, capacities)
        self.reset()
        keys = np.asarray(keys).reshape(-1)
        costs = self._batch_costs(len(keys), costs)
        cand = hash_choices_np(keys, self.n, d=self.d, seed=self.seed)
        loads = np.zeros(self.n, dtype=np.float64)
        out = np.empty(len(keys), dtype=np.int32)
        for i in range(len(keys)):
            c = cand[i]
            w = c[np.argmin(loads[c])]
            loads[w] += costs[i]
            out[i] = w
        return out


class WChoicesPolicy(PoTCPolicy):
    """W-Choices at the edge (arXiv 1510.05714): hot keys go anywhere.

    A SPACESAVING tracker flags keys whose estimated request fraction clears
    ``theta`` (default d/n — the balanceability limit of paper §5); hot keys
    route to the globally least-loaded replica, cold keys keep PoTC's exact
    step (and therefore its <= d replica fanout / prefix-cache affinity).
    """

    name = "w_choices"

    def __init__(self, n_replicas: int, d: int = 2, seed: int = 0,
                 capacity: int = 256, theta: Optional[float] = None,
                 min_count: int = 8):
        super().__init__(n_replicas, d=d, seed=seed)
        self.theta = head_threshold(n_replicas, self.d) if theta is None else theta
        self.capacity = capacity
        self.min_count = min_count
        self.tracker = SpaceSavingTracker(capacity)

    def reset(self) -> None:
        self.tracker = SpaceSavingTracker(self.capacity)

    def is_hot(self, key: int) -> bool:
        return self.tracker.is_head(key, self.theta, min_count=self.min_count)

    def decide(self, key: int, loads: np.ndarray,
               alive: Optional[np.ndarray] = None,
               capacities: Optional[np.ndarray] = None) -> int:
        self.tracker.offer(key)
        if self.is_hot(key):
            alive = _cap_alive(alive, capacities)
            loads = _cap_loads(loads, capacities)
            if alive is None:
                return int(np.argmin(loads))
            return self._live_argmin(loads, alive)
        return super().decide(key, loads, alive, capacities)

    def route_batch(self, keys, costs=None, capacities=None) -> np.ndarray:
        if capacities is not None:
            return RoutingPolicy.route_batch(self, keys, costs, capacities)
        self.reset()
        keys = np.asarray(keys).reshape(-1)
        costs = self._batch_costs(len(keys), costs)
        cand = hash_choices_np(keys, self.n, d=self.d, seed=self.seed)
        loads = np.zeros(self.n, dtype=np.float64)
        out = np.empty(len(keys), dtype=np.int32)
        for i in range(len(keys)):
            k = int(keys[i])
            self.tracker.offer(k)
            if self.is_hot(k):
                w = int(np.argmin(loads))
            else:
                c = cand[i]
                w = c[np.argmin(loads[c])]
            loads[w] += costs[i]
            out[i] = w
        return out


class _DevicePolicy(RoutingPolicy):
    """Batch-only policy backed by the device router (unit-cost messages).

    The device router accounts loads in integer message counts, so non-unit
    costs are rejected rather than silently dropped; per-request ``decide``
    is not available — wrap the host WChoicesPolicy for the serving adapter
    and use these for device-batch sweeps.
    """

    per_request = False

    def __init__(self, n_replicas: int, d: int = 2, seed: int = 0,
                 capacity: int = 1024, theta: Optional[float] = None,
                 min_count: int = 8, block: int = 128):
        super().__init__(n_replicas, d=d, seed=seed)
        self.capacity = capacity
        self.theta = theta
        self.min_count = min_count
        self.block = block

    def decide(self, key: int, loads: np.ndarray,
               alive: Optional[np.ndarray] = None,
               capacities: Optional[np.ndarray] = None) -> int:
        raise NotImplementedError(
            f"{type(self).__name__} is device-backed and batch-only; "
            "use route_batch, or a host policy for per-request serving"
        )

    def _unit_costs(self, m: int, costs) -> None:
        costs = self._batch_costs(m, costs)
        if not np.all(costs == 1.0):
            raise ValueError(
                "device-backed policies route unit-cost messages only"
            )

    def _kernel_capacities(self, capacities) -> Optional[np.ndarray]:
        """The router normalizes by a reciprocal-capacity row, so every
        capacity must be strictly positive (fold zero-capacity workers out before the
        device batch; host policies handle them via the alive mask)."""
        cap = _check_capacities(self.n, capacities)
        if cap is not None and (cap <= 0).any():
            raise ValueError(
                "device-backed policies need strictly positive capacities"
            )
        return cap


class DeviceWChoicesPolicy(_DevicePolicy):
    """W-Choices on the device water-fill path: one shard of
    core.partitioners.w_choices_sharded_partition (which shares its head set
    with w_choices_partition)."""

    name = "w_choices_kernel"

    def route_batch(self, keys, costs=None, capacities=None) -> np.ndarray:
        from repro.core.partitioners import w_choices_sharded_partition

        keys = np.asarray(keys).reshape(-1)
        self._unit_costs(len(keys), costs)
        return np.asarray(
            w_choices_sharded_partition(
                keys, self.n, d=self.d, seed=self.seed,
                theta=self.theta, capacity=self.capacity,
                min_count=self.min_count, n_shards=1, sync_period=1,
                block=self.block,
                capacities=self._kernel_capacities(capacities),
            )
        )


class DeviceDChoicesPolicy(_DevicePolicy):
    """D-Choices on the device masked-prefix path: one shard of
    core.partitioners.d_choices_sharded_partition (which shares its
    SPACESAVING pre-pass and d(k) schedule with d_choices_partition)."""

    name = "d_choices_kernel"

    def __init__(self, n_replicas: int, d: int = 2, seed: int = 0,
                 d_max: int = 16, slack: float = 2.0, **kw):
        super().__init__(n_replicas, d=d, seed=seed, **kw)
        self.d_max = max(int(min(d_max, n_replicas)), self.d)
        self.slack = slack

    def route_batch(self, keys, costs=None, capacities=None) -> np.ndarray:
        from repro.core.partitioners import d_choices_sharded_partition

        keys = np.asarray(keys).reshape(-1)
        self._unit_costs(len(keys), costs)
        return np.asarray(
            d_choices_sharded_partition(
                keys, self.n, d=self.d, d_max=self.d_max, seed=self.seed,
                theta=self.theta, capacity=self.capacity, slack=self.slack,
                min_count=self.min_count, n_shards=1, sync_period=1,
                block=self.block,
                capacities=self._kernel_capacities(capacities),
            )
        )


class ShardedWChoicesPolicy(_DevicePolicy):
    """W-Choices on the multi-device sharded router (DESIGN.md §6.1): the
    stream splits over an ``n_shards`` ("data",) mesh, every shard routes
    against its own local loads row, and the per-shard load deltas psum
    every ``sync_period`` blocks.  ``n_shards=1, sync_period=1`` is
    DeviceWChoicesPolicy, bit for bit; the mesh is emulated (vmap + sum,
    bit-identical) when the host has fewer than n_shards devices, so the
    registered policy runs anywhere."""

    name = "w_choices_sharded"

    def __init__(self, n_replicas: int, d: int = 2, seed: int = 0,
                 n_shards: int = 1, sync_period: int = 1, **kw):
        super().__init__(n_replicas, d=d, seed=seed, **kw)
        self.n_shards = n_shards
        self.sync_period = sync_period

    def route_batch(self, keys, costs=None, capacities=None) -> np.ndarray:
        from repro.core.partitioners import w_choices_sharded_partition

        keys = np.asarray(keys).reshape(-1)
        self._unit_costs(len(keys), costs)
        return np.asarray(
            w_choices_sharded_partition(
                keys, self.n, d=self.d, seed=self.seed, theta=self.theta,
                capacity=self.capacity, min_count=self.min_count,
                n_shards=self.n_shards, sync_period=self.sync_period,
                block=self.block,
                capacities=self._kernel_capacities(capacities),
            )
        )


ROUTING_POLICIES = {
    p.name: p
    for p in (
        KGPolicy,
        RoundRobinPolicy,
        PoTCPolicy,
        WChoicesPolicy,
        DeviceWChoicesPolicy,
        DeviceDChoicesPolicy,
        ShardedWChoicesPolicy,
    )
}


DEFAULT_SCHEDULER = "w_choices"


def host_policy_names() -> tuple:
    """Registered per-request-capable policies, in registry order — THE list
    the serving demos and bench sweep iterate, so a newly registered host
    policy shows up everywhere without editing three files."""
    return tuple(n for n, c in ROUTING_POLICIES.items() if c.per_request)


def scheduler_sweep_names() -> tuple:
    """host_policy_names with the preferred default (DEFAULT_SCHEDULER)
    listed first — the display order the launcher and demo share."""
    return (DEFAULT_SCHEDULER,) + tuple(
        n for n in host_policy_names() if n != DEFAULT_SCHEDULER
    )


def make_policy(name: str, n_replicas: int, **kw) -> RoutingPolicy:
    """Instantiate a registered policy; kw pass through to its __init__."""
    try:
        cls = ROUTING_POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown routing policy {name!r}; "
            f"registered: {sorted(ROUTING_POLICIES)}"
        ) from None
    return cls(n_replicas, **kw)
