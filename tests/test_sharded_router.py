"""Sharded router (parallel/sharded_router.py, DESIGN.md §6.1).

The load-bearing invariants:

  * n_shards=1, sync_period=1 is BIT-EXACT to the single-core Pallas routers
    (adaptive_route / w_route) — the differential that pins the sharded scan
    to the shared block-greedy core;
  * the psum load-sync conserves mass: after the final epoch every shard's
    loads row equals the global assignment histogram (loads are integer
    counts in f32, so reduction order cannot matter);
  * on a stream whose hot keys concentrate in one shard's slice (sorted
    keys = heterogeneous substreams), final imbalance is monotone in
    sync_period — staleness costs balance;
  * the shard_map program matches the vmap+sum oracle bit-exactly on a real
    8-device mesh (subprocess, slow).
"""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import zipf_stream
from repro.core.estimation import W_SENTINEL
from repro.core.hashing import derive_seeds
from repro.core.partitioners import (
    _head_flags,
    pkg_sharded_partition,
    w_choices_sharded_partition,
)
from repro.core.routing import make_policy
from repro.kernels.adaptive_route import adaptive_route, w_route
from repro.kernels.route_core import hash_candidates, oracle_block_step
from repro.launch.mesh import make_stream_mesh
from repro.parallel.sharded_router import (
    ref_sharded_route,
    routed_step_roofline,
    sharded_route,
)
from repro.roofline.analysis import V5E, hw_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 16
N = 1024


def _keys(n=N, seed=0, z=1.4):
    return jnp.asarray(zipf_stream(n, 200, z, seed=seed))


def _w_ncand(keys, d=2):
    flags = _head_flags(np.asarray(keys), W, d, None, 1024, 8)
    return jnp.asarray(
        np.where(flags != 0, np.int32(W_SENTINEL), np.int32(d)).astype(np.int32)
    )


# ---------------------------------------------------------------------------
# differential: 1 shard + sync_period=1 == the single-core kernels
# ---------------------------------------------------------------------------


def test_one_shard_sync1_bit_exact_pkg():
    keys = _keys()
    a, loads = ref_sharded_route(keys, None, W, d_max=2, n_shards=1,
                                 sync_period=1)
    nc = jnp.full((N,), 2, jnp.int32)
    a_k, l_k = adaptive_route(keys, nc, W, d_max=2, chunk=N, block=128,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(l_k[-1]))


def test_one_shard_sync1_bit_exact_d_choices():
    keys = _keys(seed=1)
    nc = jnp.asarray(
        np.random.default_rng(0).integers(1, 5, N).astype(np.int32)
    )
    a, loads = ref_sharded_route(keys, nc, W, d_max=4, n_shards=1,
                                 sync_period=1)
    a_k, l_k = adaptive_route(keys, nc, W, d_max=4, chunk=N, block=128,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(l_k[-1]))


def test_one_shard_sync1_bit_exact_w_choices():
    keys = _keys(seed=2, z=1.8)
    nc = _w_ncand(keys)
    a, loads = ref_sharded_route(keys, nc, W, d_max=2, n_shards=1,
                                 sync_period=1, w_mode=True)
    flags = (np.asarray(nc) == int(W_SENTINEL)).astype(np.int32)
    a_k, l_k = w_route(keys, jnp.asarray(flags), W, d=2, chunk=N, block=128,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(l_k[-1]))


def test_shard_map_equals_ref_on_one_device():
    # the shard_map program itself (1-device mesh) vs the vmap+sum oracle
    keys = _keys(seed=3)
    for sync in (1, 4):
        a_s, l_s = sharded_route(keys, None, W, n_shards=1, sync_period=sync)
        a_r, l_r = ref_sharded_route(keys, None, W, n_shards=1,
                                     sync_period=sync)
        np.testing.assert_array_equal(np.asarray(a_s), np.asarray(a_r))
        np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_r))


# ---------------------------------------------------------------------------
# load-sync conservation + staleness tradeoff
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards,sync", [(2, 1), (4, 4), (8, 16)])
def test_load_sync_conservation(n_shards, sync):
    n = n_shards * sync * 128 * 2  # two epochs
    keys = _keys(n, seed=4, z=1.8)
    nc = _w_ncand(keys)
    a, loads = ref_sharded_route(keys, nc, W, n_shards=n_shards,
                                 sync_period=sync, w_mode=True)
    a_np = np.asarray(a)
    assert a_np.shape == (n,) and a_np.min() >= 0 and a_np.max() < W
    hist = np.bincount(a_np, minlength=W).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(loads), hist)


def test_imbalance_monotone_in_sync_period_on_hetero_shards():
    # sorted keys concentrate the zipf head in one shard's contiguous slice;
    # the rarer the sync, the longer the other shards under-serve the head
    # workers and the worse the final imbalance.
    n = 8 * 64 * 128
    keys_np = np.sort(zipf_stream(n, 1_000, 1.8, seed=5))
    keys = jnp.asarray(keys_np)
    flags = _head_flags(keys_np, 32, 2, None, 1024, 8)
    nc = jnp.asarray(np.where(flags != 0, np.int32(W_SENTINEL),
                              np.int32(2)).astype(np.int32))
    imb = []
    for sync in (1, 4, 16):
        a, _ = ref_sharded_route(keys, nc, 32, n_shards=8, sync_period=sync,
                                 w_mode=True)
        h = np.bincount(np.asarray(a), minlength=32)
        imb.append(float(h.max() - h.mean()) / n)
    for lo, hi in zip(imb, imb[1:]):
        assert hi >= lo - 1e-4, imb
    assert imb[-1] > imb[0], imb


# ---------------------------------------------------------------------------
# partitioner / policy surface
# ---------------------------------------------------------------------------


def test_partitioner_padding_prefix_stable():
    # single shard: the padded tail rides at the END of the shard, so the
    # real prefix of a longer stream routes identically — scatter-index
    # recovery must not scramble assignments.
    keys = _keys(1280, seed=6)
    a_short = np.asarray(pkg_sharded_partition(keys[:1000], W, n_shards=1))
    a_long = np.asarray(pkg_sharded_partition(keys, W, n_shards=1))
    assert a_short.shape == (1000,)
    np.testing.assert_array_equal(a_short, a_long[:1000])


def test_partitioner_multi_shard_emulated():
    keys = zipf_stream(5000, 300, 1.6, seed=7)
    a = np.asarray(w_choices_sharded_partition(keys, W, n_shards=4,
                                               sync_period=2, emulate=True))
    b = np.asarray(w_choices_sharded_partition(keys, W, n_shards=4,
                                               sync_period=2, emulate=True))
    assert a.shape == (5000,) and a.min() >= 0 and a.max() < W
    np.testing.assert_array_equal(a, b)


def test_weighted_sync_w_choices_matches_host_oracle():
    # dyadic shard weights leave fractional (and exactly summed) loads rows
    # after each sync; the head lanes must still take the definition's
    # argmin of those rows, as the host block step (top_k water-fill) does
    n_shards, sync, n_epochs = 4, 2, 3
    weights = np.array([0.5, 1.25, 0.75, 1.5], np.float32)
    keys = np.asarray(_keys(n_shards * sync * 128 * n_epochs, seed=9, z=1.8))
    got = np.asarray(w_choices_sharded_partition(
        keys, W, n_shards=n_shards, sync_period=sync, emulate=True,
        shard_weights=weights,
    ))
    nc = np.asarray(_w_ncand(keys)).reshape(n_shards, n_epochs, sync, 128)
    assert (nc == int(W_SENTINEL)).any(axis=-1).all()  # heads in every block
    cand = np.asarray(hash_candidates(jnp.asarray(keys), derive_seeds(0, 2), W))
    cand = cand.reshape(n_shards, n_epochs, sync, 128, 2)
    step = jax.jit(functools.partial(oracle_block_step, n_entities=W,
                                     w_mode=True))
    want = np.zeros_like(nc)
    loads_g = np.zeros(W, np.float32)
    for e in range(n_epochs):
        delta = np.zeros(W, np.float32)
        for s in range(n_shards):
            loads = jnp.asarray(loads_g)
            for b in range(sync):
                loads, want[s, e, b], _, _ = step(
                    loads, jnp.asarray(cand[s, e, b]), jnp.asarray(nc[s, e, b])
                )
            delta += weights[s] * (np.asarray(loads) - loads_g)
        loads_g = loads_g + delta
        assert (loads_g != np.floor(loads_g)).any()  # fractional rows
    np.testing.assert_array_equal(got, want.reshape(-1))


def test_sharded_policy_matches_partitioner():
    pol = make_policy("w_choices_sharded", W, n_shards=2, sync_period=4)
    keys = zipf_stream(4096, 300, 1.6, seed=8)
    a_pol = pol.route_batch(keys)
    a_part = np.asarray(w_choices_sharded_partition(
        keys, W, d=pol.d, seed=pol.seed, theta=pol.theta,
        capacity=pol.capacity, min_count=pol.min_count, n_shards=2,
        sync_period=4, block=pol.block,
    ))
    np.testing.assert_array_equal(a_pol, a_part)


def test_make_stream_mesh_rejects_oversubscription():
    too_many = jax.local_device_count() + 1
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_stream_mesh(too_many)


def test_routed_step_roofline_report():
    rep = routed_step_roofline(W, n_shards=1, sync_period=4, n_epochs=2,
                               hw=hw_for(V5E))
    assert rep["flops_per_device"] > 0 and rep["hbm_bytes_per_device"] > 0
    assert rep["roofline"]["step_lower_bound_s"] > 0
    assert rep["collective_bytes_per_epoch"] >= 0
    assert rep["collective_bytes_per_device"] == (
        rep["collective_bytes_per_epoch"] * rep["n_epochs"]
    )


def test_routed_step_roofline_needs_known_device_kind():
    # the host CPU has no published peaks: no silent v5e default
    with pytest.raises(ValueError, match="no published peaks"):
        routed_step_roofline(W, n_shards=1, sync_period=4, n_epochs=2)


# ---------------------------------------------------------------------------
# real 8-device mesh (subprocess, slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_shard_map_matches_ref_on_eight_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import zipf_stream
    from repro.core.estimation import W_SENTINEL
    from repro.core.partitioners import _head_flags
    from repro.launch.mesh import make_stream_mesh
    from repro.parallel.sharded_router import ref_sharded_route, sharded_route

    assert jax.local_device_count() == 8
    mesh = make_stream_mesh(8)
    n, W = 8 * 4 * 128 * 2, 32
    keys_np = zipf_stream(n, 500, 1.8, seed=0)
    flags = _head_flags(keys_np, W, 2, None, 1024, 8)
    nc = jnp.asarray(np.where(flags != 0, np.int32(W_SENTINEL),
                              np.int32(2)).astype(np.int32))
    keys = jnp.asarray(keys_np)
    for sync in (1, 4):
        a_s, l_s = sharded_route(keys, nc, W, n_shards=8, sync_period=sync,
                                 w_mode=True, mesh=mesh)
        a_r, l_r = ref_sharded_route(keys, nc, W, n_shards=8,
                                     sync_period=sync, w_mode=True)
        np.testing.assert_array_equal(np.asarray(a_s), np.asarray(a_r))
        np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_r))
        hist = np.bincount(np.asarray(a_s), minlength=W).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(l_s), hist)
    print("8-device sharded router OK")
    """
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
