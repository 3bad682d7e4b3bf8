"""Load and frequency estimation (paper SS3.2, SS6.2 Q2; DESIGN.md SS3.3).

Two kinds of estimators live here:

1. Multi-source *load* simulation.  A single lax.scan walks the stream in
   global arrival order, carrying
     local_est : (S, n)  per-source local load estimates
     global_ld : (n,)    true worker loads
   Each message is routed by its source's *local* estimate (technique L), by
   the true loads (G, the global oracle), or by local estimates that are
   periodically reset to the true loads (LP, probing every probe_period
   messages).  Source assignment of messages is either round-robin shuffle
   (the default in the paper) or key grouping on a secondary key (Fig 8's
   skewed-sources setup).

2. Streaming *frequency* estimation for the adaptive multi-choice
   partitioners (arXiv 1510.05714).  SpaceSavingTracker identifies the head
   keys of the stream in O(capacity) space; head_threshold / adaptive_d
   encode the head/tail rule and the skew-adaptive choice count d(k)
   (DESIGN.md SS3.3).

3. The *online* estimator (DESIGN.md SS3.3 "Online estimation"): the same
   SPACESAVING summary as flat JAX arrays (OnlineSS) with pure per-element
   update/decay transitions, so the tracker rides inside a partitioner's
   lax.scan carry and head detection happens per message with no pre-pass.
   adaptive_d_counts is the integer-exact d(k) rule shared by the offline
   pre-pass and the scan so both paths make bit-identical decisions; its
   int32 path is exact for every count <= total < 2**31.  The remaining
   int32 limit is the summary's own: OnlineSS counts and total wrap at 2**31
   events.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.applications import SpaceSaving
from repro.core.hashing import hash_choices

__all__ = [
    "simulate_sources",
    "source_assignment",
    "local_imbalance_bound",
    "W_SENTINEL",
    "SpaceSavingTracker",
    "head_test",
    "head_threshold",
    "adaptive_d",
    "adaptive_d_counts",
    "OnlineSS",
    "online_ss_init",
    "online_ss_update",
    "online_ss_decay",
    "online_ss_estimate",
    "online_ss_from_tracker",
    "online_ss_head_table",
    "online_head_tables",
]


# Candidate-count value flagging "this key may go to ANY worker" (W-Choices,
# arXiv 1510.05714) to the device routers and their oracles.  int32 max can
# never collide with a real d(k) — clipped to d_max <= n_workers — and a
# consumer that treats it as a plain count masks nothing (every lane
# < W_SENTINEL participates), degrading to d_max choices instead of crashing.
W_SENTINEL = np.int32(np.iinfo(np.int32).max)


def source_assignment(
    n_msgs: int,
    n_sources: int,
    source_keys: Optional[np.ndarray] = None,
    seed: int = 17,
) -> np.ndarray:
    """Message -> source map: shuffle (round-robin) or KG on source_keys."""
    if source_keys is None:
        return (np.arange(n_msgs, dtype=np.int64) % n_sources).astype(np.int32)
    h = np.asarray(
        hash_choices(jnp.asarray(source_keys, jnp.int32), n_sources, d=1, seed=seed)
    )[..., 0]
    return h.astype(np.int32)


@functools.partial(
    jax.jit,
    static_argnames=("n_workers", "n_sources", "d", "seed", "mode", "probe_period"),
)
def _simulate(
    keys: jnp.ndarray,
    sources: jnp.ndarray,
    n_workers: int,
    n_sources: int,
    d: int,
    seed: int,
    mode: str,
    probe_period: int,
) -> jnp.ndarray:
    cand = hash_choices(keys, n_workers, d=d, seed=seed)  # (m, d)
    m = keys.shape[0]
    t_idx = jnp.arange(m, dtype=jnp.int32)

    def step(state, inp):
        local_est, global_ld = state
        c, s, t = inp
        if mode == "probe":
            do_probe = (t % probe_period) == 0
            local_est = jnp.where(
                do_probe, jnp.broadcast_to(global_ld, local_est.shape), local_est
            )
        if mode == "global":
            lc = global_ld[c]
        else:
            lc = local_est[s, c]
        choice = c[jnp.argmin(lc)]
        local_est = local_est.at[s, choice].add(1)
        global_ld = global_ld.at[choice].add(1)
        return (local_est, global_ld), choice

    state0 = (
        jnp.zeros((n_sources, n_workers), jnp.int32),
        jnp.zeros((n_workers,), jnp.int32),
    )
    _, assign = lax.scan(step, state0, (cand, sources, t_idx))
    return assign


def simulate_sources(
    keys: np.ndarray,
    n_workers: int,
    n_sources: int = 5,
    d: int = 2,
    seed: int = 0,
    mode: str = "local",  # local (L) | global (G) | probe (LP)
    probe_period: int = 0,
    source_keys: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Run the S-source PKG simulation; returns the assignment (m,)."""
    assert mode in ("local", "global", "probe")
    src = source_assignment(len(keys), n_sources, source_keys)
    assign = _simulate(
        jnp.asarray(keys, jnp.int32),
        jnp.asarray(src, jnp.int32),
        n_workers=n_workers,
        n_sources=n_sources,
        d=d,
        seed=seed,
        mode=mode,
        probe_period=max(probe_period, 1),
    )
    return np.asarray(assign)


def local_imbalance_bound(
    keys: np.ndarray,
    assign: np.ndarray,
    sources: np.ndarray,
    n_workers: int,
    n_sources: int,
) -> tuple[float, float]:
    """Return (global imbalance, sum of per-source local imbalances).

    Paper SS3.2 theorem: I(t) <= sum_j I_hat_j(t).  Exposed for tests.
    """
    per = np.zeros((n_sources, n_workers), dtype=np.int64)
    np.add.at(per, (sources, assign), 1)
    global_ld = per.sum(axis=0)
    gi = global_ld.max() - global_ld.mean()
    li = (per.max(axis=1) - per.mean(axis=1)).sum()
    return float(gi), float(li)


def head_test(count, total, theta: float, min_count: int = 1):
    """THE canonical head predicate: count/total >= theta, evaluated as
    float32(count) >= float32(theta) * float32(max(total, 1)), plus a
    min_count observation floor.  Every consumer — the offline pre-pass
    (numpy), the online scan carry and the per-block head tables (jnp) —
    must use this exact arithmetic: float32 on both paths is what keeps the
    frozen-carry online variants bit-identical to the offline ones even on
    theta-boundary counts (numpy and XLA f32 multiply/compare are both IEEE).
    """
    if isinstance(count, (np.ndarray, np.integer, int)):
        tot = np.float32(max(int(total), 1))
        frac_ok = np.float32(count) >= np.float32(theta) * tot
        return np.logical_and(np.asarray(count) >= min_count, frac_ok)
    tot = jnp.maximum(total, 1).astype(jnp.float32)
    frac_ok = count.astype(jnp.float32) >= jnp.float32(theta) * tot
    return (count >= min_count) & frac_ok


def head_threshold(n_workers: int, d: int = 2) -> float:
    """Head/tail frequency cut (DESIGN.md SS3.3).

    PKG with d choices balances iff p1 <= d/W (paper SS5; arXiv 1504.00788's
    bound degrades past it).  A key whose frequency fraction exceeds d/W
    therefore cannot be absorbed by d candidates and belongs to the head.
    """
    return d / n_workers


def adaptive_d(
    p_hat: np.ndarray,
    n_workers: int,
    d_base: int = 2,
    d_max: int = 16,
    slack: float = 2.0,
) -> np.ndarray:
    """D-Choices rule (arXiv 1510.05714; DESIGN.md SS3.3).

    A key with frequency fraction p spreads p/d(k) of the stream on each of
    its candidates; keeping that at most 1/(slack*W)-ish of the fair share
    needs d(k) >= slack * p * W.  Clipped to [d_base, d_max].
    """
    need = np.ceil(slack * np.asarray(p_hat, np.float64) * n_workers)
    return np.clip(need, d_base, d_max).astype(np.int32)


def adaptive_d_counts(
    counts,
    total,
    n_workers: int,
    d_base: int = 2,
    d_max: int = 16,
    slack: float = 2.0,
):
    """Integer-exact D-Choices rule on raw (count, total) pairs.

    Same rule as adaptive_d — d(k) = clip(ceil(slack * p * W), d_base, d_max)
    with p = count/total — but evaluated in integer arithmetic with slack as
    the rational s_num/s_den (limit_denominator(256): exact for the dyadic
    slacks used in practice), so the offline
    pre-pass (numpy int64) and the scan-carry online path (jnp int32) land on
    the same d(k) even when slack*p*W sits exactly on a ceil boundary, where
    float rounding would otherwise split them.  Works on numpy and jnp inputs.
    The jnp path is exact in int32 for every 0 <= count <= total < 2**31:
    `_ceil_mul_div` never forms slack_num * n_workers * count.
    """
    from fractions import Fraction

    frac = Fraction(float(slack)).limit_denominator(256)
    s_num, s_den = frac.numerator, frac.denominator
    if isinstance(counts, (np.ndarray, np.integer, int)):
        num = np.int64(s_num * n_workers) * np.asarray(counts, np.int64)
        den = np.int64(s_den) * np.int64(total)
        need = -((-num) // max(int(den), 1))
        return np.clip(need, d_base, d_max).astype(np.int32)
    # ceil(x / (s_den * t)) == ceil(ceil(x / t) / s_den); t >= 1 keeps the
    # rule defined at total = 0
    x = _ceil_mul_div(counts, jnp.maximum(total, 1), s_num * n_workers)
    need = -((-x) // s_den)
    return jnp.clip(need, d_base, d_max).astype(jnp.int32)


def _ceil_mul_div(c, t, a: int):
    """ceil(a * c / t) in int32 for 0 <= c <= t < 2**31 and a static a >= 0,
    without forming a * c: long multiplication over a's bits, keeping
    q * t + r == (a's leading bits) * c with 0 <= r < t.  Doubling r tests
    2r >= t as r >= t - r and adding c tests r + c >= t as r >= t - c, so no
    intermediate leaves [0, t]."""
    c = jnp.asarray(c, jnp.int32)
    t = jnp.asarray(t, jnp.int32)
    t_c = t - c
    q = jnp.zeros_like(c)
    r = jnp.zeros_like(c)
    for bit in f"{a:b}":
        up = r >= t - r
        r = jnp.where(up, r - (t - r), r + r)
        q = q + q + up.astype(jnp.int32)
        if bit == "1":
            up = r >= t_c
            r = jnp.where(up, r - t_c, r + c)
            q = q + up.astype(jnp.int32)
    return q + (r > 0).astype(jnp.int32)


class SpaceSavingTracker:
    """Streaming head-key tracker: weighted SPACESAVING + running total.

    Wraps applications.SpaceSaving with (a) vectorised chunked updates for
    array streams (unique+counts per chunk, heaviest offered first -- a valid
    weighted SPACESAVING schedule) and (b) frequency-*fraction* queries, which
    is what the adaptive partitioners consume.  Estimation error is bounded by
    total/capacity, so head detection at threshold theta is exact up to
    1/capacity (choose capacity >> 1/theta).
    """

    def __init__(self, capacity: int = 1024):
        self.capacity = capacity
        self._ss = SpaceSaving(capacity)
        self.total = 0

    def offer(self, key: int, weight: int = 1) -> None:
        self._ss.offer(int(key), int(weight))
        self.total += int(weight)

    def update(self, keys: np.ndarray, chunk: int = 8192) -> None:
        """Consume an array of keys in stream order (chunked internally)."""
        keys = np.asarray(keys).reshape(-1)
        for lo in range(0, len(keys), chunk):
            uniq, cnt = np.unique(keys[lo : lo + chunk], return_counts=True)
            order = np.argsort(-cnt, kind="stable")
            for k, w in zip(uniq[order], cnt[order]):
                self._ss.offer(int(k), int(w))
        self.total += len(keys)

    def guaranteed_count(self, key: int) -> int:
        """Lower bound on the true count: estimate minus inherited error."""
        k = int(key)
        return self._ss.counts.get(k, 0) - self._ss.errors.get(k, 0)

    def is_head(self, key: int, theta: float, min_count: int = 1) -> bool:
        """Streaming head query, conservative on both ends.  `min_count`
        guards against early-stream noise (with a handful of observations any
        fraction clears theta trivially); the threshold test uses the
        error-corrected count so a cold key that re-enters a saturated
        summary — inheriting the evicted minimum — cannot be mistaken for
        head when theta <= 1/capacity.  head_keys() deliberately stays on raw
        estimates: over-inclusion only costs extra splitting there, while a
        false head here breaks bounded-fanout contracts."""
        return (
            self.total > 0
            and self._ss.estimate(int(key)) >= min_count
            and self.guaranteed_count(key) >= theta * self.total
        )

    def decay(self, factor: float = 0.5) -> None:
        """Windowed/decayed mode: scale every counter (and the running total)
        by `factor`, dropping entries that reach zero.  Calling this every
        `period` messages makes the summary an exponentially-decayed window
        with half-life period/log2(1/factor) messages, so theta-relative head
        detection follows a rotating head set instead of averaging over the
        whole history (DESIGN.md SS3.3)."""
        ss = self._ss
        for k in list(ss.counts):
            c = int(ss.counts[k] * factor)
            if c <= 0:
                del ss.counts[k]
                del ss.errors[k]
            else:
                ss.counts[k] = c
                ss.errors[k] = int(ss.errors[k] * factor)
        self.total = int(self.total * factor)

    def head_counts(
        self, theta: float, min_count: int = 1
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """head_keys on raw integer counts: (ids sorted, counts aligned, total).

        This is what the integer-exact adaptive_d_counts rule consumes; the
        predicate is the canonical head_test (float32 + min_count floor), so
        the offline pre-pass and the scan-carry online path agree bit-for-bit.
        """
        if self.total == 0:
            return np.empty(0, np.int64), np.empty(0, np.int64), 0
        items = sorted(
            (k, c)
            for k, c in self._ss.counts.items()
            if bool(head_test(c, self.total, theta, min_count))
        )
        ids = np.asarray([k for k, _ in items], np.int64)
        cnt = np.asarray([c for _, c in items], np.int64)
        return ids, cnt, self.total

    def head_keys(self, theta: float) -> tuple[np.ndarray, np.ndarray]:
        """All tracked keys with estimated frequency fraction >= theta.

        Returns (ids (h,) int64 sorted, p_hat (h,) float64 aligned).
        """
        if self.total == 0:
            return np.empty(0, np.int64), np.empty(0, np.float64)
        items = [
            (k, c / self.total)
            for k, c in self._ss.counts.items()
            if c / self.total >= theta
        ]
        items.sort()
        ids = np.asarray([k for k, _ in items], np.int64)
        p = np.asarray([p for _, p in items], np.float64)
        return ids, p


# ---------------------------------------------------------------------------
# Online (scan-carry) SPACESAVING — DESIGN.md SS3.3 "Online estimation".
# ---------------------------------------------------------------------------


class OnlineSS(NamedTuple):
    """SPACESAVING summary as flat arrays, carried through lax.scan.

    keys   (C,) int32  slot key ids; a slot is live iff counts > 0
    counts (C,) int32  estimated counts (upper bounds)
    errors (C,) int32  inherited over-estimation per slot
    total  ()   int32  messages observed (decayed total in windowed mode)
    """

    keys: jnp.ndarray
    counts: jnp.ndarray
    errors: jnp.ndarray
    total: jnp.ndarray


def online_ss_init(capacity: int) -> OnlineSS:
    return OnlineSS(
        keys=jnp.full((capacity,), -1, jnp.int32),
        counts=jnp.zeros((capacity,), jnp.int32),
        errors=jnp.zeros((capacity,), jnp.int32),
        total=jnp.int32(0),
    )


def online_ss_update(state: OnlineSS, key, weight=1) -> OnlineSS:
    """One SPACESAVING offer as a pure array transition (jit/scan safe).

    Mirrors applications.SpaceSaving.offer: tracked key -> increment; untracked
    key -> evict the minimum-count slot (an empty slot is a zero-count victim,
    so fill-then-evict needs no separate branch), inheriting its count as the
    new entry's error.  O(capacity) vector ops per element.
    """
    k = jnp.asarray(key, jnp.int32)
    w = jnp.asarray(weight, jnp.int32)
    live = state.counts > 0
    match = live & (state.keys == k)
    found = match.any()
    slot = jnp.where(found, jnp.argmax(match), jnp.argmin(state.counts))
    c_slot = state.counts[slot]
    # found: count+w, same error; miss: victim_count + w, error = victim_count
    new_count = c_slot + w
    new_error = jnp.where(found, state.errors[slot], c_slot)
    return OnlineSS(
        keys=state.keys.at[slot].set(k),
        counts=state.counts.at[slot].set(new_count),
        errors=state.errors.at[slot].set(new_error),
        total=state.total + w,
    )


def online_ss_decay(state: OnlineSS, shift: int = 1) -> OnlineSS:
    """Halve all counters `shift` times (integer floor) plus the total.

    Applied every `decay_period` messages this turns the summary into an
    exponentially-decayed window (half-life ~ decay_period messages for
    shift=1); slots whose count reaches zero free themselves because liveness
    is counts > 0.  Floor halving keeps the invariant errors <= counts.
    """
    return OnlineSS(
        keys=state.keys,
        counts=state.counts >> shift,
        errors=state.errors >> shift,
        total=state.total >> shift,
    )


def online_ss_estimate(state: OnlineSS, key) -> jnp.ndarray:
    """Estimated count of `key` (0 if untracked) — upper bound as in offline."""
    k = jnp.asarray(key, jnp.int32)
    match = (state.counts > 0) & (state.keys == k)
    return jnp.where(match, state.counts, 0).max()


def online_ss_from_tracker(tracker: SpaceSavingTracker, capacity: int) -> OnlineSS:
    """Warm-start an OnlineSS from a Python-side tracker (top-`capacity`)."""
    items = tracker._ss.counts
    top = sorted(items, key=items.get, reverse=True)[:capacity]  # type: ignore[arg-type]
    state = online_ss_init(capacity)
    n = len(top)
    if n == 0:
        return state._replace(total=jnp.int32(tracker.total))
    return OnlineSS(
        keys=state.keys.at[:n].set(jnp.asarray(top, jnp.int32)),
        counts=state.counts.at[:n].set(
            jnp.asarray([items[k] for k in top], jnp.int32)
        ),
        errors=state.errors.at[:n].set(
            jnp.asarray([tracker._ss.errors[k] for k in top], jnp.int32)
        ),
        total=jnp.int32(tracker.total),
    )


def online_ss_head_table(
    state: OnlineSS,
    n_workers: int,
    d: int = 2,
    d_max: int = 16,
    theta: Optional[float] = None,
    slack: float = 2.0,
    min_count: int = 8,
    any_worker: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Emit one (tbl_keys, tbl_ncand) head table from a summary state.

    THE shared emit: `online_head_tables` calls this once per block and the
    chunked driver (parallel.chunked_driver) calls it from inside its carried
    scan, so both paths derive candidate counts from identical arithmetic —
    canonical head_test predicate, integer-exact adaptive_d_counts, and
    W_SENTINEL head slots under `any_worker`.  Slot ncand is d(k) for head
    slots and `d` otherwise (lookup miss == tail hit == plain PKG).
    """
    theta_f = head_threshold(n_workers, d) if theta is None else float(theta)
    is_head = head_test(state.counts, state.total, theta_f, min_count)
    if any_worker:
        head_nc = jnp.full_like(state.counts, jnp.int32(W_SENTINEL))
    else:
        head_nc = adaptive_d_counts(
            state.counts, state.total, n_workers,
            d_base=d, d_max=d_max, slack=slack,
        )
    return state.keys, jnp.where(is_head, head_nc, d).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block", "capacity", "n_workers", "d", "d_max", "theta", "slack",
        "min_count", "decay_period", "any_worker",
    ),
)
def online_head_tables(
    keys: jnp.ndarray,
    block: int,
    capacity: int,
    n_workers: int,
    d: int = 2,
    d_max: int = 16,
    theta: Optional[float] = None,
    slack: float = 2.0,
    min_count: int = 8,
    decay_period: int = 0,
    any_worker: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-vector-block head tables for the ref.py adaptive oracles.

    Runs the online tracker over `keys` (N, divisible by block) and emits, for
    every block b, the summary state *before* consuming block b — so a router
    reading table b sees head decisions stale by at most `block` messages,
    mirroring pkg_partition_batched's stale-loads contract (DESIGN.md SS2).

    Returns (tbl_keys (N/block, capacity) int32, tbl_ncand same shape): slot
    ncand is the integer-exact d(k) for head slots and `d` otherwise, so a
    lookup miss and a tail hit are indistinguishable — both route as PKG.
    With `any_worker=True` (W-Choices) head slots carry W_SENTINEL instead of
    d(k), flagging "route to the global least-loaded worker" to the router's
    global-argmin path — consume such tables with the router's w_mode=True
    (DESIGN.md SS3.3).
    """
    N = keys.shape[0]
    assert N % block == 0, (N, block)
    kb = keys.astype(jnp.int32).reshape(N // block, block)
    t_idx = jnp.arange(N // block, dtype=jnp.int32)

    def emit(state: OnlineSS):
        return online_ss_head_table(
            state, n_workers, d=d, d_max=d_max, theta=theta,
            slack=slack, min_count=min_count, any_worker=any_worker,
        )

    def step(state, inp):
        blk, b = inp
        out = emit(state)
        if decay_period > 0:
            do = (b * block) % decay_period < block  # crossed a period boundary
            state = lax.cond(
                (b > 0) & do, lambda s: online_ss_decay(s), lambda s: s, state
            )
        state = lax.scan(lambda s, k: (online_ss_update(s, k), None), state, blk)[0]
        return state, out

    _, (tbl_keys, tbl_ncand) = lax.scan(
        step, online_ss_init(capacity), (kb, t_idx)
    )
    return tbl_keys, tbl_ncand
