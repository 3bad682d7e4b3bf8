"""Plain reference of W-Choices (arXiv 1510.05714): a head key, one whose
frequency passes theta in a Space-Saving summary of the stream so far, may go
to any worker and takes the least loaded one; every other key goes to the
less loaded of its d hashed candidates, as in PKG.

The head set of a block is read from the summary as it stood at the block's
start, and the summary then takes the block's keys one by one.  A block's
head events fill the least loaded workers in event order, from the
block-start loads plus the heads already placed in that block.
"""
from __future__ import annotations

import numpy as np

from policies.common import (
    FETCH,
    SpaceSaving,
    candidates,
    greedy_block,
    hash_seeds,
    waterfill,
)


class Reference:
    def __init__(self, router: dict, seed: int, fetch: str = "exact"):
        self.n_workers = router["n_workers"]
        self.block = router["block"]
        self.theta = router["theta"]
        self.min_count = router["min_count"]
        if router["decay_period"]:
            raise ValueError("this reference keeps an undecayed summary")
        self.seeds = hash_seeds(seed, router["d"])
        self.fetch = FETCH[fetch]
        self.loads = np.zeros(self.n_workers, np.int64)
        self.ss = SpaceSaving(router["ss_capacity"])

    def route_chunk(self, keys: np.ndarray) -> np.ndarray:
        cand = candidates(keys, self.seeds, self.n_workers)
        out = np.empty(len(keys), np.int32)
        for lo in range(0, len(keys), self.block):
            kb = keys[lo : lo + self.block]
            choice = greedy_block(self.loads, cand[lo : lo + self.block], self.fetch)
            heads = np.isin(kb, self.ss.head_keys(self.theta, self.min_count))
            choice[heads] = waterfill(self.loads, int(heads.sum()), self.fetch)
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
