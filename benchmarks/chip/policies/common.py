"""Pieces shared by the plain reference routers in this directory.

Written from the routing semantics, in numpy, with integer loads: it imports
nothing of the program under test.  The semantics are block-synchronous
greedy routing (arXiv 1510.07623 §3 with the loads refreshed once per block):

* a key's d candidates are SplitMix32(key ^ seed_j) mod W, with the d seeds
  derived from the router's integer seed;
* every event of a block reads the loads row as it stood at the block's
  start and goes to its least-loaded candidate, ties to the first candidate;
* after the block, the row gains one per event routed.

`fetch` is how a load is read for a decision.  `exact` reads the integer
count; `bf16` rounds it to bfloat16 first (8 bits of mantissa), which is
what an f32 matrix product on the TPU does at default precision.  The
`bf16` reading is the control that the benchmark's comparison must reject.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_GOLDEN = np.uint32(0x9E3779B9)


def _mix(x: np.ndarray, rounds: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(16))
        x = x * _M1
        x = x ^ (x >> np.uint32(15))
        if rounds == 2:
            x = x * _M2
            x = x ^ (x >> np.uint32(16))
    return x


def hash_seeds(seed: int, d: int) -> np.ndarray:
    """The d per-candidate seeds of a router seeded with `seed`."""
    base = np.uint32((int(seed) * 0x9E3779B9 + 0x9E3779B9) & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        s = (np.arange(1, d + 1, dtype=np.uint32) * _GOLDEN) ^ base
    return _mix(s, rounds=1)


def candidates(keys: np.ndarray, seeds: np.ndarray, n_workers: int) -> np.ndarray:
    """(n,) keys -> (n, d) int64 candidate workers."""
    h = _mix(keys.astype(np.uint32)[:, None] ^ seeds[None, :], rounds=2)
    return (h % np.uint32(n_workers)).astype(np.int64)


def exact(loads: np.ndarray) -> np.ndarray:
    return loads


def bf16(loads: np.ndarray) -> np.ndarray:
    return loads.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


FETCH = {"exact": exact, "bf16": bf16}


def greedy_block(loads: np.ndarray, cand: np.ndarray, fetch) -> np.ndarray:
    """Least-loaded candidate per event from the block-start loads."""
    lc = fetch(loads)[cand]
    return cand[np.arange(len(cand)), np.argmin(lc, axis=1)]


def waterfill(loads: np.ndarray, n_heads: int, fetch) -> list[int]:
    """Destinations of a block's head events in order: each takes the least
    loaded worker of the block-start row plus the heads placed before it,
    ties to the lowest worker."""
    extra = np.zeros_like(loads)
    out = []
    for _ in range(n_heads):
        j = int(np.argmin(fetch(loads + extra)))
        extra[j] += 1
        out.append(j)
    return out


class SpaceSaving:
    """Space-Saving over a fixed array of slots (Metwally et al. 2005).

    A tracked key gains one.  An untracked key takes the slot with the
    smallest count, lowest slot first (an empty slot counts 0), and inherits
    that count as its error.  Slot order is part of the state, so the summary
    can be compared slot by slot with the program's.
    """

    def __init__(self, capacity: int):
        self.keys = np.full(capacity, -1, np.int64)
        self.counts = np.zeros(capacity, np.int64)
        self.errors = np.zeros(capacity, np.int64)
        self.total = 0
        self._slot: dict[int, int] = {}

    def offer(self, key: int) -> None:
        s = self._slot.get(key)
        if s is None:
            s = int(np.argmin(self.counts))
            c = int(self.counts[s])
            if c > 0:
                del self._slot[int(self.keys[s])]
            self.keys[s] = key
            self.errors[s] = c
            self._slot[key] = s
        self.counts[s] += 1
        self.total += 1

    def head_keys(self, theta: float, min_count: int) -> np.ndarray:
        """Keys whose count is at least min_count and at least theta of the
        total, tested in float32 as the configuration states."""
        thr = np.float32(theta) * np.float32(max(self.total, 1))
        head = (self.counts >= min_count) & (self.counts.astype(np.float32) >= thr)
        return self.keys[head]
