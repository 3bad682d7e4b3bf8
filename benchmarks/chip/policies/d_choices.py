"""Plain reference of D-Choices (arXiv 1510.05714): a head key, one whose
frequency passes theta in a Space-Saving summary of the stream so far, goes
to the least loaded of its first d(k) hashed candidates; every other key goes
to the less loaded of its d hashed candidates, as in PKG.

d(k) = clip(ceil(slack * p * W), d, d_max) with p = count / total read from
the summary as it stood at the block's start, computed on integers with
slack as an exact rational.  The candidates of a key are the first lanes of
one seed family, so its d(k) candidates begin with its d tail candidates.
Every event of a block reads the block-start loads, and the summary then
takes the block's keys one by one.
"""
from __future__ import annotations

from fractions import Fraction

import numpy as np

from policies.common import FETCH, SpaceSaving, candidates, greedy_block, hash_seeds


class Reference:
    def __init__(self, router: dict, seed: int, fetch: str = "exact"):
        self.n_workers = router["n_workers"]
        self.block = router["block"]
        self.theta = router["theta"]
        self.min_count = router["min_count"]
        self.d = router["d"]
        self.d_max = max(min(router["d_max"], self.n_workers), self.d)
        self.slack = Fraction(router["slack"])
        if router["decay_period"]:
            raise ValueError("this reference keeps an undecayed summary")
        self.seeds = hash_seeds(seed, self.d_max)
        self.fetch = FETCH[fetch]
        self.loads = np.zeros(self.n_workers, np.int64)
        self.ss = SpaceSaving(router["ss_capacity"])

    def n_candidates(self, count: int) -> int:
        """d(k) of a head key counted `count` times in the summary."""
        total = max(self.ss.total, 1)
        num = self.slack.numerator * self.n_workers * count
        den = self.slack.denominator * total
        return min(max(-(-num // den), self.d), self.d_max)

    def route_chunk(self, keys: np.ndarray) -> np.ndarray:
        # Every event's d tail candidates; a head key's d(k) are hashed per
        # block, for that key alone.
        cand = candidates(keys, self.seeds[: self.d], self.n_workers)
        out = np.empty(len(keys), np.int32)
        for lo in range(0, len(keys), self.block):
            kb = keys[lo : lo + self.block]
            choice = greedy_block(self.loads, cand[lo : lo + self.block], self.fetch)
            for k in self.ss.head_keys(self.theta, self.min_count).tolist():
                at = kb == k
                if not at.any():
                    continue
                dk = self.n_candidates(int(self.ss.counts[self.ss.keys == k][0]))
                ck = candidates(np.array([k]), self.seeds[:dk], self.n_workers)
                choice[at] = greedy_block(self.loads, ck, self.fetch)[0]
            out[lo : lo + self.block] = choice
            self.loads += np.bincount(choice, minlength=self.n_workers)
            for k in kb.tolist():
                self.ss.offer(k)
        return out

    def summary(self):
        return {
            "keys": self.ss.keys, "counts": self.ss.counts,
            "errors": self.ss.errors, "total": self.ss.total,
        }
