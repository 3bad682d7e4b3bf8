"""ChunkedRouter's D-Choices against the chip benchmark's plain reference.

`benchmarks/chip/policies/d_choices.Reference` is numpy written from the
routing semantics and imports nothing of the program; the benchmark's
`correct` replays it against what the chip routed.  Here both route small
seeded Zipf streams on the CPU, and every assignment, the loads row and the
Space-Saving summary must match exactly.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.parallel.chunked_driver import ChunkedRouter

CHIP_BENCH = str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip")
CHUNK, BLOCK = 1024, 128
ROUTER = dict(
    policy="d_choices", n_workers=100, d=2, d_max=100, chunk=CHUNK,
    block=BLOCK, theta=0.02, ss_capacity=256, min_count=8, slack=2.0,
    decay_period=0,
)


@pytest.fixture(scope="module")
def reference():
    """(Reference, candidates) from the benchmark's policies."""
    sys.path.insert(0, CHIP_BENCH)
    try:
        from policies.common import candidates
        from policies.d_choices import Reference
    finally:
        sys.path.remove(CHIP_BENCH)
    return Reference, candidates


def _zipf(n, seed, n_keys=2_000, s=1.05):
    p = 1.0 / np.arange(1, n_keys + 1) ** s
    rng = np.random.default_rng(seed)
    return rng.choice(n_keys, size=n, p=p / p.sum()).astype(np.int32)


def _on_boundary(n, seed):
    """Key 0 at 112 of the first 3,200 events, evenly, and on at that rate:
    at the block start t = 3,200, slack * p * W = 2 * 112 / 3200 * 100 is 7
    exactly, where float arithmetic reads 7.000000000000001 and rounds up."""
    keys = _zipf(n, seed) + 1
    keys[(np.arange(n * 7 // 200) * 200) // 7] = 0
    return keys


CASES = {  # name: (stream, changes to ROUTER, seed)
    # the benchmark's configuration: no cap below W, heads at d(k) up to ~29
    "w100_dmax100": (_zipf(4 * CHUNK, 3_000_000_019), {}, 3_000_000_019),
    # a seed whose 8th candidate of key 0 is the least loaded at t = 3,200,
    # so a rule that rounds 7 up to 8 routes that block differently
    "ceil_boundary": (_on_boundary(4 * CHUNK, 5), {}, 25),
    # d_max below the head's d(k): the rule clips
    "clipped_dmax8": (_zipf(4 * CHUNK, 11), {"d_max": 8}, 17),
    # a stream that ends mid-chunk: the last step is padded
    "padded_last_chunk": (_zipf(2 * CHUNK + 300, 7), {}, 17),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_d_choices_equals_reference(reference, case):
    Reference, candidates = reference
    keys, change, seed = CASES[case]
    cfg = {**ROUTER, **change}
    router = ChunkedRouter(**cfg, seed=seed)
    got = router.route_stream(keys)

    ref = Reference(cfg, seed)
    want = np.concatenate([
        ref.route_chunk(keys[lo : lo + CHUNK]) for lo in range(0, len(keys), CHUNK)
    ])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(router.loads, ref.loads)
    ss = router.tracker
    for f in ("keys", "counts", "errors"):
        np.testing.assert_array_equal(np.asarray(getattr(ss, f)), getattr(ref.ss, f))
    assert int(ss.total) == ref.ss.total == len(keys)

    # each case reaches the part of the rule it is named for
    top = int(np.bincount(keys).argmax())
    count = int(np.count_nonzero(keys == top))
    unclipped = -(-2 * 100 * count // len(keys))  # slack 2, W = 100
    assert unclipped > 8
    assert ref.n_candidates(count) == min(unclipped, cfg["d_max"])
    if case == "ceil_boundary":
        assert np.count_nonzero(keys[:3200] == 0) == 112 and 3200 % BLOCK == 0
    if case == "padded_last_chunk":
        assert len(keys) % CHUNK
    # the hottest key was spread past its two tail candidates
    tail = candidates(np.array([top]), ref.seeds[: cfg["d"]], cfg["n_workers"])[0]
    assert not np.isin(got[keys == top], tail).all()
