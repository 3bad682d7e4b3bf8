"""The benchmark's event generator: a configuration's whole key stream.

The stream is sampled once per run from `--seed`, during set-up, and the
measured window replays it chunk by chunk, so sampling never sits on the
timed path.  Keys are Zipf ranks (0 is the most frequent key) drawn by
inverse CDF, the same construction as the repository's matched-trace
streams; this copy belongs to the benchmark so that no change to the program
can change the traffic.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Largest allowed gap between the configuration's head probability and the
# one its stored exponent gives: the exponent is stored to full precision,
# so anything above float64 rounding means the file was edited by hand.
P1_TOLERANCE = 1e-9
# Events drawn per random generator; each block has its own child of the
# seed, so the stream does not depend on how many threads draw it.
BLOCK = 1 << 20
THREADS = 8


def zipf_pmf(n_keys: int, exponent: float) -> np.ndarray:
    """Zipf probabilities over ranks 1..n_keys."""
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -exponent
    return w / w.sum()


def checked_pmf(stream: dict) -> np.ndarray:
    """The configuration's pmf, after one check that its stored exponent
    reproduces its head probability p1 (no solve at set-up)."""
    pmf = zipf_pmf(stream["n_keys"], stream["zipf_exponent"])
    if abs(pmf[0] - stream["p1"]) > P1_TOLERANCE:
        raise ValueError(
            f"zipf_exponent {stream['zipf_exponent']} gives p1 {pmf[0]!r}, "
            f"not {stream['p1']}"
        )
    return pmf


def sample(pmf: np.ndarray, n_events: int, seed: int) -> np.ndarray:
    """n_events int32 keys drawn iid from pmf by inverse CDF."""
    cdf = np.cumsum(pmf)
    cdf[-1] = 1.0
    out = np.empty(n_events, np.int32)
    starts = range(0, n_events, BLOCK)
    children = np.random.SeedSequence(seed).spawn(len(starts))

    def draw(i: int) -> None:
        lo = starts[i]
        hi = min(lo + BLOCK, n_events)
        u = np.random.default_rng(children[i]).random(hi - lo)
        out[lo:hi] = np.searchsorted(cdf, u, side="right")

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(draw, range(len(starts))))
    return out
