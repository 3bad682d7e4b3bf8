"""Plain reference of Partial Key Grouping (arXiv 1510.07623): every event
goes to the less loaded of its key's d hashed candidates."""
from __future__ import annotations

import numpy as np

from policies.common import FETCH, candidates, greedy_block, hash_seeds


class Reference:
    def __init__(self, router: dict, seed: int, fetch: str = "exact"):
        self.n_workers = router["n_workers"]
        self.block = router["block"]
        self.seeds = hash_seeds(seed, router["d"])
        self.fetch = FETCH[fetch]
        self.loads = np.zeros(self.n_workers, np.int64)

    def route_chunk(self, keys: np.ndarray) -> np.ndarray:
        cand = candidates(keys, self.seeds, self.n_workers)
        out = np.empty(len(keys), np.int32)
        for lo in range(0, len(keys), self.block):
            choice = greedy_block(self.loads, cand[lo : lo + self.block], self.fetch)
            out[lo : lo + self.block] = choice
            self.loads += np.bincount(choice, minlength=self.n_workers)
        return out

    def summary(self):
        return None
