"""Stream partitioners: the paper's five techniques plus the TPU-batched PKG.

Every partitioner maps a key stream (m,) int32 -> worker assignment (m,) int32.
All are implemented as JAX programs (lax.scan for the sequential processes) so
the same code runs in simulation, in the host data pipeline, and inside jitted
steps.  Static configuration (n_workers, d, ...) is passed through
functools.partial/jit static args.

Techniques (paper SS6.2 Q1; adaptive variants from arXiv 1510.05714):
  H / KG      hash_partition          single choice, H1(k) mod n
  SG          shuffle_partition       round-robin, ignores keys
  PoTC        potc_static_partition   two choices, first decision remembered
  On-Greedy   on_greedy_partition     new key -> globally least-loaded worker
  Off-Greedy  off_greedy_partition    offline LPT on sorted key frequencies
  PKG         pkg_partition           Greedy-d with key splitting (the paper)
  PKG (TPU)   pkg_partition_batched   vector-block greedy, stale-by-<V loads
  D-Choices   d_choices_partition     head keys get skew-adaptive d(k) choices
  W-Choices   w_choices_partition     head keys may go to ANY worker
  *-sharded   pkg/d_choices/w_choices_sharded_partition  multi-device mesh,
              per-shard local loads + psum load-sync epochs (DESIGN.md §6.1)

The adaptive variants (DESIGN.md SS3.3) come in two flavours.  The *offline*
pair (d_choices_partition / w_choices_partition) runs a SPACESAVING pre-pass
to find the head keys, then a single masked greedy scan.  The *online* pair
(online_d_choices_partition / online_w_choices_partition) carries the
SPACESAVING summary inside the lax.scan itself — head detection, d(k) and the
theta test all update per element, no pre-pass, which is what a real DSPE can
actually run and what survives key drift (DESIGN.md SS3.3 "Online
estimation").  In every flavour tail keys keep PKG's exact d=2 behaviour
(identical candidates, identical tie-breaking).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.estimation import (
    OnlineSS,
    SpaceSavingTracker,
    adaptive_d_counts,
    head_test,
    head_threshold,
    online_ss_decay,
    online_ss_estimate,
    online_ss_init,
    online_ss_update,
)
from repro.core.hashing import hash_choices

__all__ = [
    "hash_partition",
    "shuffle_partition",
    "pkg_partition",
    "pkg_partition_batched",
    "potc_static_partition",
    "on_greedy_partition",
    "off_greedy_partition",
    "d_choices_partition",
    "w_choices_partition",
    "online_d_choices_partition",
    "online_w_choices_partition",
    "pkg_sharded_partition",
    "d_choices_sharded_partition",
    "w_choices_sharded_partition",
    "pkg_chunked_partition",
    "d_choices_chunked_partition",
    "w_choices_chunked_partition",
    "PARTITIONERS",
]


@functools.partial(jax.jit, static_argnames=("n_workers", "seed"))
def hash_partition(keys: jnp.ndarray, n_workers: int, seed: int = 0) -> jnp.ndarray:
    """Key grouping: single-choice hashing (the paper's baseline H)."""
    return hash_choices(keys, n_workers, d=1, seed=seed)[..., 0]


@functools.partial(jax.jit, static_argnames=("n_workers",))
def shuffle_partition(keys: jnp.ndarray, n_workers: int, offset: int = 0) -> jnp.ndarray:
    """Shuffle grouping: cyclic round-robin; imbalance <= 1 by construction."""
    m = keys.shape[0]
    return ((jnp.arange(m, dtype=jnp.int32) + offset) % n_workers).astype(jnp.int32)


def _host_inv_cap(capacities, n_workers: int):
    """Validated (n_workers,) f32 reciprocal-capacity vector, or None.

    The host partitioners' capacity normalization (arXiv 1705.09073): every
    load comparison becomes ``load * (1/c)`` in f32 — the SAME product the
    kernels form, so host/kernel differentials stay bit-exact (loads are
    integer counts < 2^24).  Strictly positive capacities required here;
    zero-capacity workers are a routing-policy concept, folded into the
    alive mask at the LoadLedger layer, not a partitioner one.
    """
    if capacities is None:
        return None
    cap = np.asarray(capacities, dtype=np.float32).reshape(-1)
    if cap.shape != (n_workers,):
        raise ValueError(f"capacities shape {cap.shape} != ({n_workers},)")
    if not (cap > 0).all():
        raise ValueError("partitioner capacities must be strictly positive")
    return jnp.asarray(1.0 / cap)


def _trace_inv_cap(capacities, n_workers: int):
    """The in-jit twin of _host_inv_cap (no host-side validation — the
    argument may be a tracer).  Division by a non-positive capacity yields
    inf/nan comparisons; jitted callers document the > 0 requirement."""
    if capacities is None:
        return None
    return 1.0 / jnp.asarray(capacities, jnp.float32).reshape(n_workers)


def _greedy_scan(cand: jnp.ndarray, n_workers: int,
                 weights: Optional[jnp.ndarray], inv_cap=None):
    """Sequential Greedy-d over candidate sets cand (m, d).

    inv_cap (n_workers,) f32 switches the argmin to capacity-normalized
    loads (loads stay integer counts; only the comparison rescales).
    """
    m = cand.shape[0]
    w = jnp.ones((m,), jnp.int32) if weights is None else weights.astype(jnp.int32)

    def step(loads, inp):
        c, wt = inp
        lc = loads[c]  # (d,) current candidate loads
        if inv_cap is not None:
            lc = lc.astype(jnp.float32) * inv_cap[c]
        choice = c[jnp.argmin(lc)]
        return loads.at[choice].add(wt), choice

    loads0 = jnp.zeros((n_workers,), jnp.int32)
    _, assign = lax.scan(step, loads0, (cand, w))
    return assign


@functools.partial(jax.jit, static_argnames=("n_workers", "d", "seed"))
def pkg_partition(
    keys: jnp.ndarray,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    weights: Optional[jnp.ndarray] = None,
    capacities: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """PARTIAL KEY GROUPING: Greedy-d with key splitting (paper SS3).

    Every message is routed to the least-loaded of its d hash candidates,
    using the loads generated by *this* stream (local estimation when the
    stream is one source's sub-stream).  `capacities` (optional strictly
    positive (n_workers,) weights) makes the argmin capacity-normalized:
    least ``load/c`` wins; None is the unweighted path, bit-identical to
    before, and uniform capacities reproduce it exactly.
    """
    cand = hash_choices(keys, n_workers, d=d, seed=seed)
    return _greedy_scan(cand, n_workers, weights,
                        inv_cap=_trace_inv_cap(capacities, n_workers))


@functools.partial(jax.jit, static_argnames=("n_workers", "d", "seed", "block"))
def pkg_partition_batched(
    keys: jnp.ndarray,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    block: int = 128,
    capacities: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """TPU-native PKG: vector-block greedy with intra-block-stale loads.

    Processes `block` keys per step: candidate loads are read once per block,
    choices for all lanes are made in parallel, then the load vector is
    updated with the block's choice histogram (one-hot matmul -> MXU).
    Equivalent to local load estimation with ceil(m/block) micro-sources
    (DESIGN.md SS2); fidelity vs the sequential scan is quantified in
    benchmarks/bench_batched_fidelity.py.  `capacities` (> 0) switches the
    lane argmin to capacity-normalized loads.
    """
    m = keys.shape[0]
    inv_cap = _trace_inv_cap(capacities, n_workers)
    nblk = -(-m // block)
    pad = nblk * block - m
    keys_p = jnp.pad(keys, (0, pad))
    valid = jnp.pad(jnp.ones((m,), jnp.int32), (0, pad))
    cand = hash_choices(keys_p, n_workers, d=d, seed=seed)  # (nblk*block, d)
    cand = cand.reshape(nblk, block, d)
    valid = valid.reshape(nblk, block)

    def step(loads, inp):
        c, v = inp  # (block, d), (block,)
        lc = loads[c]  # (block, d)
        if inv_cap is not None:
            lc = lc.astype(jnp.float32) * inv_cap[c]
        sel = jnp.argmin(lc, axis=-1)  # (block,)
        choice = jnp.take_along_axis(c, sel[:, None], axis=-1)[:, 0]
        onehot = (jax.nn.one_hot(choice, n_workers, dtype=jnp.int32) * v[:, None])
        return loads + onehot.sum(axis=0), choice

    loads0 = jnp.zeros((n_workers,), jnp.int32)
    _, assign = lax.scan(step, loads0, (cand, valid))
    return assign.reshape(-1)[:m]


@functools.partial(jax.jit, static_argnames=("n_workers", "n_keys", "d", "seed"))
def potc_static_partition(
    keys: jnp.ndarray, n_workers: int, n_keys: int, d: int = 2, seed: int = 0,
    capacities: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Static PoTC *without* key splitting (paper SS3.1): the first placement of
    each key is remembered in a routing table and reused for every repeat.

    Needs O(n_keys) state -- the very cost PKG exists to remove; simulated here
    as a baseline.  Keys must be in [0, n_keys).  `capacities` (> 0) makes the
    first-placement argmin capacity-normalized.
    """
    cand = hash_choices(keys, n_workers, d=d, seed=seed)
    inv_cap = _trace_inv_cap(capacities, n_workers)

    def step(state, c):
        loads, table = state
        k, cd = c
        prev = table[k]
        lc = loads[cd]
        if inv_cap is not None:
            lc = lc.astype(jnp.float32) * inv_cap[cd]
        fresh = cd[jnp.argmin(lc)]
        choice = jnp.where(prev >= 0, prev, fresh)
        return (loads.at[choice].add(1), table.at[k].set(choice)), choice

    loads0 = jnp.zeros((n_workers,), jnp.int32)
    table0 = jnp.full((n_keys,), -1, jnp.int32)
    _, assign = lax.scan(step, (loads0, table0), (keys, cand))
    return assign


@functools.partial(jax.jit, static_argnames=("n_workers", "n_keys"))
def on_greedy_partition(
    keys: jnp.ndarray, n_workers: int, n_keys: int,
    capacities: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """On-Greedy (paper SS6.2): a new key goes to the globally least-loaded
    worker; the choice is remembered.  Requires global load + routing table.
    `capacities` (> 0) makes the global argmin capacity-normalized."""
    inv_cap = _trace_inv_cap(capacities, n_workers)

    def step(state, k):
        loads, table = state
        prev = table[k]
        nl = loads if inv_cap is None else loads.astype(jnp.float32) * inv_cap
        fresh = jnp.argmin(nl).astype(jnp.int32)
        choice = jnp.where(prev >= 0, prev, fresh)
        return (loads.at[choice].add(1), table.at[k].set(choice)), choice

    loads0 = jnp.zeros((n_workers,), jnp.int32)
    table0 = jnp.full((n_keys,), -1, jnp.int32)
    _, assign = lax.scan(step, (loads0, table0), keys)
    return assign


@functools.partial(jax.jit, static_argnames=("n_workers", "n_keys"))
def off_greedy_partition(
    keys: jnp.ndarray, n_workers: int, n_keys: int,
    capacities: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Off-Greedy (paper SS6.2): offline LPT -- sort keys by total frequency,
    assign each key's whole mass to the least-loaded worker.  Unfair upper
    baseline: it sees the full key distribution in advance.  `capacities`
    (> 0) runs LPT on capacity-normalized loads."""
    counts = jnp.zeros((n_keys,), jnp.int32).at[keys].add(1)
    order = jnp.argsort(-counts)  # keys by decreasing frequency
    inv_cap = _trace_inv_cap(capacities, n_workers)

    def step(state, k):
        loads, key2w = state
        nl = loads if inv_cap is None else loads.astype(jnp.float32) * inv_cap
        choice = jnp.argmin(nl).astype(jnp.int32)
        return (loads.at[choice].add(counts[k]), key2w.at[k].set(choice)), None

    loads0 = jnp.zeros((n_workers,), jnp.int32)
    key2w0 = jnp.zeros((n_keys,), jnp.int32)
    (_, key2w), _ = lax.scan(step, (loads0, key2w0), order)
    return key2w[keys]


@functools.partial(jax.jit, static_argnames=("n_workers",))
def _masked_greedy_scan(
    cand: jnp.ndarray, n_cand: jnp.ndarray, n_workers: int, inv_cap=None
) -> jnp.ndarray:
    """Greedy over a variable per-message prefix of cand (m, d_max).

    Candidate j of message i participates iff j < n_cand[i]; the rest are
    masked to INT32_MAX so argmin (first-index tie-break) matches pkg's
    behaviour exactly whenever n_cand[i] == d.  With inv_cap the comparison
    runs in f32 on normalized loads, masked with the kernels' f32 sentinel.
    """
    d_max = cand.shape[1]
    col = jnp.arange(d_max, dtype=jnp.int32)
    sentinel = jnp.iinfo(jnp.int32).max

    def step(loads, inp):
        c, nc = inp
        if inv_cap is None:
            lc = jnp.where(col < nc, loads[c], sentinel)
        else:
            lc = jnp.where(
                col < nc, loads[c].astype(jnp.float32) * inv_cap[c],
                jnp.float32(1e30),
            )
        choice = c[jnp.argmin(lc)]
        return loads.at[choice].add(1), choice

    loads0 = jnp.zeros((n_workers,), jnp.int32)
    _, assign = lax.scan(step, loads0, (cand, n_cand))
    return assign


@functools.partial(jax.jit, static_argnames=("n_workers",))
def _any_worker_greedy_scan(
    cand: jnp.ndarray, is_head: jnp.ndarray, n_workers: int, inv_cap=None
) -> jnp.ndarray:
    """Greedy-d for tail messages; global least-loaded for head messages.
    inv_cap switches both argmins to capacity-normalized loads."""

    def step(loads, inp):
        c, h = inp
        nl = loads if inv_cap is None else loads.astype(jnp.float32) * inv_cap
        tail_choice = c[jnp.argmin(nl[c])]
        head_choice = jnp.argmin(nl).astype(jnp.int32)
        choice = jnp.where(h, head_choice, tail_choice)
        return loads.at[choice].add(1), choice

    loads0 = jnp.zeros((n_workers,), jnp.int32)
    _, assign = lax.scan(step, loads0, (cand, is_head))
    return assign


def _head_lookup(
    keys: np.ndarray, head_ids: np.ndarray, head_vals: np.ndarray, default
) -> np.ndarray:
    """Map keys -> head_vals for head keys, `default` elsewhere (sorted ids)."""
    if len(head_ids) == 0:
        return np.full(len(keys), default, np.int32)
    idx = np.searchsorted(head_ids, keys)
    idx_c = np.clip(idx, 0, len(head_ids) - 1)
    hit = head_ids[idx_c] == keys
    return np.where(hit, head_vals[idx_c], default).astype(np.int32)


def _head_flags(
    keys_np: np.ndarray,
    n_workers: int,
    d: int,
    theta: Optional[float],
    capacity: int,
    min_count: int,
) -> np.ndarray:
    """THE offline W-Choices head set: SPACESAVING pre-pass + canonical
    head_counts, as per-message 0/1 flags (m,) int32.  Both W-Choices
    partitioners (sequential scan and sharded router) share this one
    computation — their block=1 bit-exactness contract depends on the head
    sets being identical, so the pre-pass must not fork."""
    theta = head_threshold(n_workers, d) if theta is None else theta
    tracker = SpaceSavingTracker(capacity)
    tracker.update(keys_np)
    head_ids, _, _ = tracker.head_counts(theta, min_count)
    return _head_lookup(
        keys_np.astype(np.int64), head_ids, np.ones(len(head_ids), np.int32), 0
    )


def _adaptive_n_cand(
    keys_np: np.ndarray,
    n_workers: int,
    d: int,
    d_max: int,
    theta: Optional[float],
    capacity: int,
    slack: float,
    min_count: int,
) -> np.ndarray:
    """THE offline D-Choices pre-pass: SPACESAVING + canonical head_counts +
    integer-exact d(k), as a per-message candidate-count vector (m,) int32
    (tail messages get d).  Both D-Choices partitioners (masked greedy scan
    and sharded router) share this one computation — their block=1
    bit-exactness contract depends on identical d(k) schedules, so the
    pre-pass must not fork (the W analogue is _head_flags)."""
    theta = head_threshold(n_workers, d) if theta is None else theta
    tracker = SpaceSavingTracker(capacity)
    tracker.update(keys_np)
    head_ids, head_cnt, total = tracker.head_counts(theta, min_count)
    d_head = adaptive_d_counts(
        head_cnt, total, n_workers, d_base=d, d_max=d_max, slack=slack
    )
    return _head_lookup(keys_np.astype(np.int64), head_ids, d_head, d)


def d_choices_partition(
    keys,
    n_workers: int,
    d: int = 2,
    d_max: int = 16,
    seed: int = 0,
    theta: Optional[float] = None,
    capacity: int = 1024,
    slack: float = 2.0,
    min_count: int = 8,
    capacities=None,
) -> jnp.ndarray:
    """D-CHOICES (arXiv 1510.05714): skew-adaptive number of choices.

    Head keys (canonical head_test: frequency fraction >= theta — default
    d/W — with at least min_count observations) receive
    d(k) = clip(ceil(slack * p_k * W), d, d_max) hash candidates; tail keys
    keep PKG's exact d choices.  Frequencies come from a SPACESAVING pass
    over the stream (O(capacity) state; DESIGN.md SS3.3).  The head test and
    the integer-exact d(k) rule are shared with the online variant, which is
    what makes the frozen-carry differential bit-exact.  `capacities` (> 0)
    normalizes the masked argmin by 1/c.
    """
    keys_np = np.asarray(keys, dtype=np.int32)
    d_max = max(int(min(d_max, n_workers)), d)
    n_cand = _adaptive_n_cand(
        keys_np, n_workers, d, d_max, theta, capacity, slack, min_count
    )
    cand = hash_choices(jnp.asarray(keys_np), n_workers, d=d_max, seed=seed)
    return _masked_greedy_scan(cand, jnp.asarray(n_cand), n_workers,
                               inv_cap=_host_inv_cap(capacities, n_workers))


def w_choices_partition(
    keys,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    theta: Optional[float] = None,
    capacity: int = 1024,
    min_count: int = 8,
    capacities=None,
) -> jnp.ndarray:
    """W-CHOICES (arXiv 1510.05714): head keys may go to ANY worker.

    Tail keys are routed exactly as PKG (same candidates, same ties); head
    keys (canonical head_test, as in d_choices_partition) go to the globally
    least-loaded worker, which restores near-perfect balance however extreme
    the skew (at the cost of up to W-way key splitting for the few head
    keys; DESIGN.md SS3.3).  `capacities` (> 0) normalizes both the tail and
    the global argmin by 1/c — the heterogeneous-cluster variant (arXiv
    1705.09073): a 4x worker soaks up 4x the head traffic.
    """
    keys_np = np.asarray(keys, dtype=np.int32)
    is_head = _head_flags(
        keys_np, n_workers, d, theta, capacity, min_count
    ).astype(bool)
    cand = hash_choices(jnp.asarray(keys_np), n_workers, d=d, seed=seed)
    return _any_worker_greedy_scan(cand, jnp.asarray(is_head), n_workers,
                                   inv_cap=_host_inv_cap(capacities, n_workers))


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_workers", "d", "theta", "slack", "min_count", "decay_period",
        "any_worker", "update_tracker",
    ),
)
def _online_adaptive_scan(
    cand: jnp.ndarray,
    keys: jnp.ndarray,
    init_state: OnlineSS,
    n_workers: int,
    d: int,
    theta: float,
    slack: float,
    min_count: int,
    decay_period: int,
    any_worker: bool,
    update_tracker: bool,
    inv_cap=None,
) -> jnp.ndarray:
    """Single fused scan: SPACESAVING carry + head test + greedy routing.

    Per element, in order: (optional) windowed decay, tracker update (the
    router accounts for the message it is about to route), head verdict from
    the updated summary, then the same greedy step as the offline variants —
    masked d(k)-prefix argmin (D mode) or global argmin for head keys (W
    mode).  Tail verdicts reproduce PKG's step bit-exactly.  inv_cap
    (n_workers,) f32 switches every argmin to capacity-normalized loads,
    with the kernels' f32 1e30 sentinel masking dead candidate lanes.
    """
    m, d_max = cand.shape
    col = jnp.arange(d_max, dtype=jnp.int32)
    sentinel = jnp.iinfo(jnp.int32).max
    t_idx = jnp.arange(m, dtype=jnp.int32)

    def step(carry, inp):
        loads, state = carry
        c, k, t = inp
        if decay_period > 0:
            state = lax.cond(
                (t > 0) & (t % decay_period == 0),
                online_ss_decay, lambda s: s, state,
            )
        if update_tracker:
            state = online_ss_update(state, k)
        cnt = online_ss_estimate(state, k)
        is_head = head_test(cnt, state.total, theta, min_count)
        if any_worker:
            nl = loads if inv_cap is None else (
                loads.astype(jnp.float32) * inv_cap
            )
            tail_choice = c[jnp.argmin(nl[c])]
            head_choice = jnp.argmin(nl).astype(jnp.int32)
            choice = jnp.where(is_head, head_choice, tail_choice)
        else:
            dk = adaptive_d_counts(
                cnt, state.total, n_workers, d_base=d, d_max=d_max, slack=slack
            )
            nc = jnp.where(is_head, dk, d)
            if inv_cap is None:
                lc = jnp.where(col < nc, loads[c], sentinel)
            else:
                lc = jnp.where(
                    col < nc, loads[c].astype(jnp.float32) * inv_cap[c],
                    jnp.float32(1e30),
                )
            choice = c[jnp.argmin(lc)]
        return (loads.at[choice].add(1), state), choice

    loads0 = jnp.zeros((n_workers,), jnp.int32)
    _, assign = lax.scan(step, (loads0, init_state), (cand, keys, t_idx))
    return assign


def online_d_choices_partition(
    keys,
    n_workers: int,
    d: int = 2,
    d_max: int = 16,
    seed: int = 0,
    theta: Optional[float] = None,
    capacity: int = 256,
    slack: float = 2.0,
    min_count: int = 8,
    decay_period: int = 0,
    init_state: Optional[OnlineSS] = None,
    update_tracker: bool = True,
    capacities=None,
) -> jnp.ndarray:
    """Fully-online D-CHOICES: no pre-pass, head state lives in the scan carry.

    Identical routing rule to d_choices_partition, but the SPACESAVING summary
    is updated per element, so head keys are discovered (and, with
    decay_period > 0, forgotten) as the stream plays.  `min_count` suppresses
    head verdicts before a key has enough observations; `init_state` warm-
    starts the tracker (e.g. online_ss_from_tracker) and `update_tracker=False`
    freezes it, which reproduces the offline pre-pass variant bit-exactly
    (the differential contract in test_partitioner_invariants.py).
    `capacities` (> 0) normalizes the masked argmin by 1/c.
    """
    keys = jnp.asarray(keys, jnp.int32)
    d_max = max(int(min(d_max, n_workers)), d)
    theta = head_threshold(n_workers, d) if theta is None else float(theta)
    cand = hash_choices(keys, n_workers, d=d_max, seed=seed)
    state0 = online_ss_init(capacity) if init_state is None else init_state
    return _online_adaptive_scan(
        cand, keys, state0, n_workers=n_workers, d=d, theta=theta, slack=slack,
        min_count=min_count, decay_period=decay_period, any_worker=False,
        update_tracker=update_tracker,
        inv_cap=_host_inv_cap(capacities, n_workers),
    )


def online_w_choices_partition(
    keys,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    theta: Optional[float] = None,
    capacity: int = 256,
    min_count: int = 8,
    decay_period: int = 0,
    init_state: Optional[OnlineSS] = None,
    update_tracker: bool = True,
    capacities=None,
) -> jnp.ndarray:
    """Fully-online W-CHOICES: head keys go anywhere, detected in-scan.

    Tail messages take PKG's exact step; a message whose key currently clears
    theta in the carried summary goes to the globally least-loaded worker.
    See online_d_choices_partition for the tracker knobs.  `capacities` (> 0)
    normalizes both argmins by 1/c.
    """
    keys = jnp.asarray(keys, jnp.int32)
    theta = head_threshold(n_workers, d) if theta is None else float(theta)
    cand = hash_choices(keys, n_workers, d=d, seed=seed)
    state0 = online_ss_init(capacity) if init_state is None else init_state
    return _online_adaptive_scan(
        cand, keys, state0, n_workers=n_workers, d=d, theta=theta, slack=2.0,
        min_count=min_count, decay_period=decay_period, any_worker=True,
        update_tracker=update_tracker,
        inv_cap=_host_inv_cap(capacities, n_workers),
    )


# ---------------------------------------------------------------------------
# Multi-device sharded variants (parallel/sharded_router.py, DESIGN.md §6.1):
# the stream splits contiguously over an n_shards ("data",) mesh, each shard
# runs the shared block-greedy core on its own local loads row, and the
# per-shard load deltas psum every sync_period blocks (load-sync epochs).
# n_shards=1, sync_period=1 is one estimator over the whole stream: at
# block=1 it reproduces the sequential d_choices/w_choices scans bit-exactly.
# ---------------------------------------------------------------------------


def _sharded_dispatch(
    keys_np: np.ndarray,
    n_cand_np: Optional[np.ndarray],
    pad_ncand: int,
    n_workers: int,
    *,
    d_max: int,
    seed: int,
    n_shards: int,
    sync_period: int,
    block: int,
    w_mode: bool,
    mesh,
    emulate: Optional[bool],
    capacities=None,
    shard_weights=None,
) -> jnp.ndarray:
    """Shared pad/route/trim plumbing for the *_sharded partitioners.

    Each shard's sub-stream pads AT ITS OWN END with tail messages (n_cand =
    pad_ncand), so real assignments within a shard are unaffected; pad
    messages do enter the synced histogram other shards see in late epochs —
    at most sync_period*block - 1 of them per shard, the same order as the
    staleness the epoch contract already grants.  ``emulate=None`` picks the
    shard_map program when the host has n_shards devices and the bit-exact
    single-device oracle (ref_sharded_route) otherwise, so the registered
    partitioners run anywhere.

    ``capacities`` (strictly positive (n_workers,)) makes every shard's
    argmin capacity-normalized; ``shard_weights`` (non-negative (n_shards,))
    scales each shard's load-sync psum delta — the per-shard capacity
    weighting of DESIGN.md §6.1's epoch sync.  Both default to the exact
    unweighted program.
    """
    from repro.parallel.sharded_router import (  # parallel layers on core
        ref_sharded_route,
        shard_grid,
        sharded_route,
    )

    m = len(keys_np)
    g = shard_grid(m, n_shards, sync_period, block)
    m_local = -(-m // n_shards)
    total = n_shards * g
    keys_p = np.zeros(total, np.int32)
    nc_p = (
        None if n_cand_np is None
        else np.full(total, pad_ncand, np.int32)
    )
    idx = np.empty(m, np.int64)
    pos = 0
    for s in range(n_shards):
        lo = s * m_local
        hi = min(m, lo + m_local)
        cnt = max(hi - lo, 0)
        keys_p[s * g:s * g + cnt] = keys_np[lo:hi]
        if nc_p is not None:
            nc_p[s * g:s * g + cnt] = n_cand_np[lo:hi]
        idx[pos:pos + cnt] = np.arange(s * g, s * g + cnt)
        pos += cnt
    _host_inv_cap(capacities, n_workers)  # validate shape/positivity
    cap = (
        None if capacities is None
        else jnp.asarray(np.asarray(capacities, np.float32))
    )
    sw = None
    if shard_weights is not None:
        sw_np = np.asarray(shard_weights, np.float32).reshape(-1)
        if sw_np.shape != (n_shards,):
            raise ValueError(
                f"shard_weights shape {sw_np.shape} != ({n_shards},)"
            )
        if not (np.isfinite(sw_np).all() and (sw_np >= 0).all()):
            raise ValueError("shard_weights must be finite and non-negative")
        sw = jnp.asarray(sw_np)
    if emulate is None:
        emulate = n_shards > jax.local_device_count()
    route = ref_sharded_route if emulate else sharded_route
    kw = {} if emulate else {"mesh": mesh}
    assign, _ = route(
        jnp.asarray(keys_p),
        None if nc_p is None else jnp.asarray(nc_p),
        n_workers, d_max=d_max, seed=seed, n_shards=n_shards,
        sync_period=sync_period, block=block, w_mode=w_mode,
        capacities=cap, shard_weights=sw, **kw,
    )
    return jnp.asarray(np.asarray(assign)[idx])


def pkg_sharded_partition(
    keys,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    n_shards: int = 1,
    sync_period: int = 1,
    block: int = 128,
    mesh=None,
    emulate: Optional[bool] = None,
    capacities=None,
    shard_weights=None,
) -> jnp.ndarray:
    """PKG on the multi-device sharded router (fixed d candidates)."""
    keys_np = np.asarray(keys, dtype=np.int32)
    return _sharded_dispatch(
        keys_np, None, d, n_workers, d_max=d, seed=seed, n_shards=n_shards,
        sync_period=sync_period, block=block, w_mode=False, mesh=mesh,
        emulate=emulate, capacities=capacities, shard_weights=shard_weights,
    )


def d_choices_sharded_partition(
    keys,
    n_workers: int,
    d: int = 2,
    d_max: int = 16,
    seed: int = 0,
    theta: Optional[float] = None,
    capacity: int = 1024,
    slack: float = 2.0,
    min_count: int = 8,
    n_shards: int = 1,
    sync_period: int = 1,
    block: int = 128,
    mesh=None,
    emulate: Optional[bool] = None,
    capacities=None,
    shard_weights=None,
) -> jnp.ndarray:
    """D-Choices on the sharded router: same offline SPACESAVING pre-pass and
    d(k) schedule as d_choices_partition (shared _adaptive_n_cand)."""
    keys_np = np.asarray(keys, dtype=np.int32)
    d_max = max(int(min(d_max, n_workers)), d)
    n_cand = _adaptive_n_cand(
        keys_np, n_workers, d, d_max, theta, capacity, slack, min_count
    )
    return _sharded_dispatch(
        keys_np, n_cand, d, n_workers, d_max=d_max, seed=seed,
        n_shards=n_shards, sync_period=sync_period, block=block,
        w_mode=False, mesh=mesh, emulate=emulate, capacities=capacities,
        shard_weights=shard_weights,
    )


def w_choices_sharded_partition(
    keys,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    theta: Optional[float] = None,
    capacity: int = 1024,
    min_count: int = 8,
    n_shards: int = 1,
    sync_period: int = 1,
    block: int = 128,
    mesh=None,
    emulate: Optional[bool] = None,
    capacities=None,
    shard_weights=None,
) -> jnp.ndarray:
    """W-Choices on the sharded router: same offline head set as
    w_choices_partition (shared _head_flags); head keys take the
    water-fill global argmin over each shard's local loads view."""
    from repro.core.estimation import W_SENTINEL

    keys_np = np.asarray(keys, dtype=np.int32)
    is_head = _head_flags(keys_np, n_workers, d, theta, capacity, min_count)
    n_cand = np.where(
        is_head != 0, np.int32(W_SENTINEL), np.int32(d)
    ).astype(np.int32)
    return _sharded_dispatch(
        keys_np, n_cand, d, n_workers, d_max=d, seed=seed, n_shards=n_shards,
        sync_period=sync_period, block=block, w_mode=True, mesh=mesh,
        emulate=emulate, capacities=capacities, shard_weights=shard_weights,
    )


# ---------------------------------------------------------------------------
# Chunked streaming variants (parallel/chunked_driver.py): the same route
# core driven chunk-at-a-time with a persistent (loads, Space-Saving) carry —
# flat memory in stream length, bit-exact to the one-shot oracles of
# kernels/ref.py for every chunk size.  The adaptive variants share the
# online estimation machinery (online_ss_head_table emit, per-block stale
# tables as in estimation.online_head_tables) rather than any offline
# pre-pass: a chunked run must not require seeing the stream twice.
# ---------------------------------------------------------------------------


def pkg_chunked_partition(
    keys,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    chunk: int = 8192,
    block: int = 128,
    capacities=None,
) -> jnp.ndarray:
    """PKG routed chunk-at-a-time: bit-exact to ref.ref_pkg_route(chunk=N)
    at the same block size, with O(chunk) peak memory however long the stream.
    `keys` may be an array or an iterator of array chunks."""
    from repro.parallel.chunked_driver import ChunkedRouter  # parallel on core

    router = ChunkedRouter(
        n_workers, "pkg", d=d, chunk=chunk, block=block, seed=seed,
        capacities=capacities,
    )
    return jnp.asarray(router.route_stream(keys))


def d_choices_chunked_partition(
    keys,
    n_workers: int,
    d: int = 2,
    d_max: int = 8,
    seed: int = 0,
    theta: Optional[float] = None,
    capacity: int = 256,
    slack: float = 2.0,
    min_count: int = 8,
    decay_period: int = 0,
    chunk: int = 8192,
    block: int = 128,
    capacities=None,
) -> jnp.ndarray:
    """Online D-Choices routed chunk-at-a-time: the Space-Saving summary
    rides in the chunk-step carry and head tables are emitted per vector
    block (stale by <= block messages) — bit-exact to online_head_tables +
    ref.ref_adaptive_route_online over the whole stream, for every chunk
    size."""
    from repro.parallel.chunked_driver import ChunkedRouter  # parallel on core

    router = ChunkedRouter(
        n_workers, "d_choices", d=d, d_max=d_max, chunk=chunk, block=block,
        seed=seed, capacities=capacities, ss_capacity=capacity, theta=theta,
        slack=slack, min_count=min_count, decay_period=decay_period,
    )
    return jnp.asarray(router.route_stream(keys))


def w_choices_chunked_partition(
    keys,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    theta: Optional[float] = None,
    capacity: int = 256,
    min_count: int = 8,
    decay_period: int = 0,
    chunk: int = 8192,
    block: int = 128,
    capacities=None,
) -> jnp.ndarray:
    """Online W-Choices routed chunk-at-a-time: per-block any-worker head
    tables (W_SENTINEL) from the carried summary, head keys to the
    water-fill global argmin — bit-exact to the one-shot w-mode scan."""
    from repro.parallel.chunked_driver import ChunkedRouter  # parallel on core

    router = ChunkedRouter(
        n_workers, "w_choices", d=d, chunk=chunk, block=block, seed=seed,
        capacities=capacities, ss_capacity=capacity, theta=theta,
        min_count=min_count, decay_period=decay_period,
    )
    return jnp.asarray(router.route_stream(keys))


PARTITIONERS = {
    "kg": hash_partition,
    "sg": shuffle_partition,
    "pkg": pkg_partition,
    "pkg_batched": pkg_partition_batched,
    "potc": potc_static_partition,
    "on_greedy": on_greedy_partition,
    "off_greedy": off_greedy_partition,
    "d_choices": d_choices_partition,
    "w_choices": w_choices_partition,
    "d_choices_online": online_d_choices_partition,
    "w_choices_online": online_w_choices_partition,
    "pkg_sharded": pkg_sharded_partition,
    "d_choices_sharded": d_choices_sharded_partition,
    "w_choices_sharded": w_choices_sharded_partition,
    "pkg_chunked": pkg_chunked_partition,
    "d_choices_chunked": d_choices_chunked_partition,
    "w_choices_chunked": w_choices_chunked_partition,
}
