"""Self-check of the plain reference, run by hand on the CPU:

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest -q benchmarks/chip

The reference must agree with the repository's own oracles
(repro.kernels.ref) on small seeded WP streams, and must disagree with its
bfloat16 control once loads pass 256, so that the benchmark's exact
comparison can tell the two apart.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import check
import stream

HERE = Path(__file__).resolve().parent
CONFIGS = {p.stem: json.loads(p.read_text()) for p in (HERE / "configs").glob("*.json")}
EVENTS = 8 * 8192  # loads pass 256 after ~26,000 events at W = 100


def _keys(cfg: dict, seed: int, n: int = EVENTS) -> np.ndarray:
    return stream.sample(stream.checked_pmf(cfg["stream"]), n, seed)


def _route(cfg: dict, keys: np.ndarray, seed: int, fetch: str = "exact"):
    ref = check.reference(cfg["router"], seed, fetch)
    chunk = cfg["router"]["chunk"]
    out = np.concatenate([
        ref.route_chunk(keys[lo : lo + chunk]) for lo in range(0, len(keys), chunk)
    ])
    return out, ref


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_stored_exponent_gives_p1(name):
    s = CONFIGS[name]["stream"]
    assert (s["n_keys"], s["p1"]) == (2_900_000, 0.0932)
    pmf = stream.checked_pmf(s)
    assert abs(pmf[0] - 0.0932) < 1e-12
    assert abs(pmf.sum() - 1.0) < 1e-12


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_pkg_matches_repo_oracle(seed):
    import jax.numpy as jnp
    from repro.kernels.ref import ref_pkg_route

    cfg = CONFIGS["wp_pkg_w100"]
    r = cfg["router"]
    keys = _keys(cfg, seed)
    got, ref = _route(cfg, keys, seed)
    want, _ = ref_pkg_route(
        jnp.asarray(keys), r["n_workers"], d=r["d"], seed=seed,
        chunk=len(keys), block=r["block"],
    )
    np.testing.assert_array_equal(got, np.asarray(want))
    np.testing.assert_array_equal(ref.loads, np.bincount(got, minlength=r["n_workers"]))


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_w_choices_matches_repo_oracle(seed):
    import jax.numpy as jnp
    from repro.core.estimation import online_head_tables
    from repro.kernels.ref import ref_adaptive_route_online

    cfg = CONFIGS["wp_wchoices_w100"]
    r = cfg["router"]
    keys = _keys(cfg, seed)
    got, ref = _route(cfg, keys, seed)
    k = jnp.asarray(keys)
    tk, tn = online_head_tables(
        k, r["block"], r["ss_capacity"], r["n_workers"], d=r["d"],
        d_max=r["d"], theta=r["theta"], min_count=r["min_count"],
        any_worker=True,
    )
    want, _ = ref_adaptive_route_online(
        k, tk, tn, r["n_workers"], d_base=r["d"], d_max=r["d"], seed=seed,
        chunk=len(keys), block=r["block"], w_mode=True,
    )
    np.testing.assert_array_equal(got, np.asarray(want))
    heads = ref.ss.head_keys(r["theta"], r["min_count"])
    assert len(heads) >= 3  # the WP head: ranks 0..3 hold more than d/W each


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_bf16_control_is_rejected(name):
    import loop
    from control import control_numbers

    cfg = CONFIGS[name]
    feed = loop.Feed(_keys(cfg, 5), cfg["router"]["chunk"], None)
    numbers = control_numbers(cfg["router"], feed.piece, feed.per_pass, 5)
    correct, _ = check.verdict(numbers)
    assert not correct
    assert numbers["mismatched_events"] > 0


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_one_pass_stays_in_exact_float32_counts(name):
    """A pass routes the configuration's whole stream from an empty router.
    The busiest worker's share of a 1 M-event prefix, scaled to the pass and
    doubled for room, stays below 2^24, where float32 counts stop being
    exact; the harness also refuses a replay that passes it."""
    cfg = CONFIGS[name]
    n = 1 << 20
    _, ref = _route(cfg, _keys(cfg, 21, n), 21)
    share = ref.loads.max() / n
    assert 2 * share * cfg["stream"]["events"] < check.EXACT_LOADS


def test_replay_past_exact_counts_is_refused():
    ref = check.reference(CONFIGS["wp_pkg_w100"]["router"], 0)
    ref.loads[3] = check.EXACT_LOADS
    with pytest.raises(ValueError, match="exact"):
        check.replay(ref, lambda k: np.arange(128, dtype=np.int32), {1})
