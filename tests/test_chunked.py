"""Chunked streaming engine (parallel/chunked_driver.py + core.traces +
streaming simulate_serving).

The load-bearing contract: routing a stream through the chunked driver is
BIT-EXACT to the one-shot oracle for EVERY chunk size — including chunk
sizes that force a padded final chunk — because the carried (loads,
Space-Saving summary) tuple is exactly the scan state the one-shot oracle
threads internally.  One-shot oracles (kernels/ref.py, chunk = N):

  pkg        -> ref_pkg_route (same block size)
  d_choices  -> estimation.online_head_tables + ref_adaptive_route_online
  w_choices  -> same with any_worker tables and w_mode=True

Plus: kill/revive invariance across chunk sizes, the Space-Saving carry
under drift, the epoch-aligned sharded differential, stream_chunks ==
generate() for every scenario type, trace-reader round-trips, the
compile-cache recompile warning, and streaming simulate_serving ==
array-mode aggregates.
"""
import os
import sys
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.estimation import online_head_tables
from repro.core.partitioners import (
    PARTITIONERS,
    d_choices_chunked_partition,
    pkg_chunked_partition,
    w_choices_chunked_partition,
)
from repro.core.streams import (
    DRIFT_SCENARIOS,
    SCALE_SCENARIOS,
    StreamSpec,
    drift_stream,
    stream_chunks,
    zipf_probs,
    zipf_stream,
)
from repro.core.streams import _sample_from_probs  # noqa: F401  (tested)
from repro.core.traces import (
    hash_raw_key,
    read_kv_trace,
    read_wikipedia_pagecounts,
    trace_chunks,
)
from repro.kernels import ref as oracle
from repro.parallel.chunked_driver import (
    ChunkedRouter,
    ChunkedShardedRouter,
    clear_step_cache,
)
from repro.parallel.sharded_router import ref_sharded_route

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

W = 20
CAP = 64
DECAY = 512


def _pieces(keys, c):
    return [keys[lo : lo + c] for lo in range(0, len(keys), c)]


# ---------------------------------------------------------------------------
# bit-exactness vs the one-shot references
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c", [1, 7, 128, 1000])
def test_pkg_chunked_eq_oneshot_any_chunk(c):
    """block=1 lets the one-shot reference cover stream lengths that pad the
    chunked driver's final chunk — the full {1, 7, 128, n} sweep."""
    n = 1000
    keys = zipf_stream(n, 300, 1.4, seed=1)
    ref = np.asarray(oracle.ref_pkg_route(jnp.asarray(keys), W, d=2, seed=3,
                                          chunk=n, block=1)[0])
    r = ChunkedRouter(W, "pkg", chunk=c, block=1, seed=3)
    got = r.route_stream(keys)
    assert np.array_equal(got, ref)
    # final loads == assignment histogram (pads never count)
    assert np.array_equal(r.loads, np.bincount(ref, minlength=W).astype(np.float32))


@pytest.mark.parametrize("c", [128, 256, 384, 1024])
def test_pkg_chunked_eq_oneshot_block128(c):
    n = 1024
    keys = zipf_stream(n, 300, 1.6, seed=2)
    ref = np.asarray(oracle.ref_pkg_route(jnp.asarray(keys), W, d=2, seed=0,
                                          chunk=n, block=128)[0])
    got = ChunkedRouter(W, "pkg", chunk=c, block=128, seed=0).route_stream(keys)
    assert np.array_equal(got, ref)


def _adaptive_ref(keys, n_workers, policy, block, d_max=8, capacities=None):
    w_mode = policy == "w_choices"
    kj = jnp.asarray(keys)
    tk, tn = online_head_tables(
        kj, block, CAP, n_workers, d=2, d_max=d_max,
        decay_period=DECAY, any_worker=w_mode,
    )
    lanes = 2 if w_mode else d_max
    return np.asarray(oracle.ref_adaptive_route_online(
        kj, tk, tn, n_workers, d_base=2, d_max=lanes, seed=0,
        chunk=len(keys), block=block, w_mode=w_mode, capacities=capacities,
    )[0])


@pytest.mark.parametrize("policy", ["d_choices", "w_choices"])
@pytest.mark.parametrize("c", [128, 256, 1024])
def test_adaptive_chunked_eq_oneshot(policy, c):
    """The SS summary carried across chunks reproduces the one-shot online
    head tables: same emit-before-block, cond-decay, and update order."""
    n, n_workers = 1024, 50
    keys = zipf_stream(n, 400, 1.8, seed=4)
    ref = _adaptive_ref(keys, n_workers, policy, block=128)
    r = ChunkedRouter(n_workers, policy, chunk=c, block=128, seed=0,
                      d_max=8, ss_capacity=CAP, decay_period=DECAY)
    assert np.array_equal(r.route_stream(keys), ref)


@pytest.mark.parametrize("c", [1, 7, 128, 1000])
def test_adaptive_padding_any_chunk(c):
    """Padded final chunks cannot perturb the tracker, the histogram, or the
    water-fill: d_choices at block=1 over a pad-forcing length."""
    n, n_workers = 1000, 50
    keys = zipf_stream(n, 400, 1.8, seed=5)
    ref = _adaptive_ref(keys, n_workers, "d_choices", block=1)
    r = ChunkedRouter(n_workers, "d_choices", chunk=c, block=1, seed=0,
                      d_max=8, ss_capacity=CAP, decay_period=DECAY)
    assert np.array_equal(r.route_stream(keys), ref)


def test_ss_carry_handoff_under_drift():
    """Feeding a drifting stream in pieces (at block boundaries) hands the
    Space-Saving summary across route_stream calls without drift from the
    one-shot reference — the carry IS the tracker state."""
    n, n_workers = 2048, 50
    keys = drift_stream(n, 400, 1.8, seed=6, half_life=256)
    for policy in ("d_choices", "w_choices"):
        ref = _adaptive_ref(keys, n_workers, policy, block=128)
        r = ChunkedRouter(n_workers, policy, chunk=256, block=128, seed=0,
                          d_max=8, ss_capacity=CAP, decay_period=DECAY)
        got = np.concatenate([r.route_stream(p) for p in _pieces(keys, 512)])
        assert np.array_equal(got, ref), policy


@pytest.mark.parametrize("policy", ["pkg", "d_choices", "w_choices"])
def test_capacities_chunked_eq_oneshot(policy):
    """Capacity-normalized argmins (and, for W-Choices, the sequential
    water-fill that capacities select) carry across chunks bit-exactly."""
    n = 1024
    cap = np.array([1.0 + (i % 4) for i in range(W)], np.float32)
    keys = zipf_stream(n, 300, 1.6, seed=7)
    if policy == "pkg":
        ref = np.asarray(oracle.ref_pkg_route(
            jnp.asarray(keys), W, d=2, seed=0, chunk=n, block=128,
            capacities=jnp.asarray(cap),
        )[0])
    else:
        ref = _adaptive_ref(keys, W, policy, block=128,
                            capacities=jnp.asarray(cap))
    got = ChunkedRouter(W, policy, chunk=256, block=128, seed=0, d_max=8,
                        ss_capacity=CAP, decay_period=DECAY,
                        capacities=cap).route_stream(keys)
    assert np.array_equal(got, ref)


# ---------------------------------------------------------------------------
# failure handling: kill/revive is chunk-size invariant
# ---------------------------------------------------------------------------


def test_kill_revive_chunk_invariance():
    """Killing at a chunk boundary (a multiple of both chunk sizes) yields
    identical assignments whatever the chunk size, and the dead worker is
    never chosen while masked."""
    n = 2048
    keys = zipf_stream(n, 300, 1.6, seed=8)
    outs = []
    for c in (128, 1024):
        r = ChunkedRouter(W, "pkg", chunk=c, block=128, seed=0)
        a1 = r.route_stream(keys[:1024])
        r.kill(7)
        a2 = r.route_stream(keys[1024:1536])
        r.revive(7)
        a3 = r.route_stream(keys[1536:])
        assert not (a2 == 7).any()
        # revive restored the pre-kill count: loads == live histogram again
        hist = np.bincount(np.concatenate([a1, a2, a3]), minlength=W)
        assert np.array_equal(r.loads.astype(np.int64), hist)
        outs.append(np.concatenate([a1, a2, a3]))
    assert np.array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# sharded epochs: chunk == load-sync epoch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w_mode", [False, True])
def test_sharded_epoch_differential(w_mode):
    S, P, B, n_workers, E = 4, 2, 32, 30, 5
    epoch = S * P * B
    n = E * epoch
    keys = zipf_stream(n, 200, 1.5, seed=9)
    if w_mode:
        from repro.core.estimation import W_SENTINEL
        from repro.core.partitioners import _head_flags

        flags = _head_flags(keys, n_workers, 2, None, 1024, 8)
        nc = np.where(flags != 0, np.int32(W_SENTINEL), np.int32(2))
        nc = nc.astype(np.int32)
    else:
        nc = None
    # ref layout: shard-major over the whole stream; chunked layout: epoch-
    # major ([epoch][shard][block]) — permute the stream so both routers see
    # identical (shard, epoch, block) cells
    ek = np.asarray(keys).reshape(E, S, P * B)
    ref_keys = ek.swapaxes(0, 1).reshape(-1)
    ref_nc = (
        None if nc is None
        else nc.reshape(E, S, P * B).swapaxes(0, 1).reshape(-1)
    )
    ref_a, ref_loads = ref_sharded_route(
        jnp.asarray(ref_keys),
        None if ref_nc is None else jnp.asarray(ref_nc),
        n_workers, d_max=2, seed=0, n_shards=S, sync_period=P, block=B,
        w_mode=w_mode,
    )
    ref_a = np.asarray(ref_a).reshape(S, E, P * B).swapaxes(0, 1)
    router = ChunkedShardedRouter(
        n_workers, d_max=2, n_shards=S, sync_period=P, block=B, seed=0,
        w_mode=w_mode,
    )
    for e in range(E):
        a = router.route_chunk(
            ek[e].reshape(-1),
            n_cand=None if nc is None else nc.reshape(E, -1)[e],
        )
        assert np.array_equal(a.reshape(S, P * B), ref_a[e]), e
    assert np.array_equal(router.loads, np.asarray(ref_loads))


# ---------------------------------------------------------------------------
# registry partitioners
# ---------------------------------------------------------------------------


def test_chunked_partitioners_registered():
    for name in ("pkg_chunked", "d_choices_chunked", "w_choices_chunked"):
        assert name in PARTITIONERS


def test_chunked_partitioner_matches_kernel():
    n = 1024
    keys = zipf_stream(n, 300, 1.6, seed=10)
    ref = np.asarray(oracle.ref_pkg_route(jnp.asarray(keys), W, d=2, seed=0,
                                          chunk=n, block=128)[0])
    a = np.asarray(pkg_chunked_partition(jnp.asarray(keys), W, d=2, seed=0,
                                         chunk=256, block=128))
    assert np.array_equal(a, ref)
    # adaptive variants agree with their own chunk-size sweep
    for fn in (d_choices_chunked_partition, w_choices_chunked_partition):
        a1 = np.asarray(fn(jnp.asarray(keys), 50, seed=0, chunk=256,
                           capacity=CAP, decay_period=DECAY))
        a2 = np.asarray(fn(jnp.asarray(keys), 50, seed=0, chunk=1024,
                           capacity=CAP, decay_period=DECAY))
        assert np.array_equal(a1, a2), fn.__name__


# ---------------------------------------------------------------------------
# streams: chunked sampling identities
# ---------------------------------------------------------------------------


def test_sample_from_probs_chunked_identity():
    """The bounded-chunk sampler draws the same rng sequence as one giant
    searchsorted, so outputs are bit-identical."""
    probs = zipf_probs(5000, 1.5)
    rng = np.random.default_rng(11)
    got = _sample_from_probs(probs, 100_000, rng)
    cdf = np.cumsum(probs)
    cdf[-1] = 1.0
    ref = np.searchsorted(
        cdf, np.random.default_rng(11).random(100_000), side="right"
    ).astype(np.int32)
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("spec", [
    StreamSpec("zipf", n_msgs=30_000, n_keys=2000, z=1.5),
    StreamSpec("matched", n_msgs=30_000, n_keys=2000, p1=0.2),
    StreamSpec("ln", n_msgs=30_000, n_keys=2000, mu=1.789, sigma=2.366),
    SCALE_SCENARIOS["W50_z1.6"],
    DRIFT_SCENARIOS["churn_hl8"],
])
@pytest.mark.parametrize("chunk", [1000, 4096, 65536])
def test_stream_chunks_eq_generate(spec, chunk):
    ref = np.asarray(spec.generate(seed=12, scale=0.2))
    got = np.concatenate(list(stream_chunks(spec, chunk, seed=12, scale=0.2)))
    assert got.dtype in (np.int32, ref.dtype)
    assert np.array_equal(got.astype(ref.dtype), ref)


# ---------------------------------------------------------------------------
# trace readers
# ---------------------------------------------------------------------------


def test_wikipedia_reader_expands_counts(tmp_path):
    p = tmp_path / "pagecounts"
    p.write_text(
        "en Main_Page 3 12288\n"
        "malformed-line\n"
        "de Seite 1 4096\n"
        "fr Page -2 0\n"          # non-positive count: skipped
        "en Other notanint 0\n"   # malformed count: skipped
        "ja ページ 2 8192\n"
    )
    got = np.concatenate(list(read_wikipedia_pagecounts(p, chunk=4)))
    exp = np.asarray(
        [hash_raw_key("en Main_Page")] * 3
        + [hash_raw_key("de Seite")]
        + [hash_raw_key("ja ページ")] * 2,
        np.int32,
    )
    assert np.array_equal(got, exp)
    # count expansion off: one event per surviving line
    got1 = np.concatenate(
        list(read_wikipedia_pagecounts(p, chunk=4, expand_counts=False))
    )
    assert len(got1) == 3


def test_kv_reader_and_chunk_shapes(tmp_path):
    p = tmp_path / "trace.kv"
    lines = [f"key with spaces {i % 17}\t{i}\n" for i in range(1000)]
    p.write_text("".join(lines) + "\n\n")  # trailing blanks skipped
    chunks = list(read_kv_trace(p, chunk=256))
    assert [len(c) for c in chunks] == [256, 256, 256, 232]
    got = np.concatenate(chunks)
    exp = np.asarray(
        [hash_raw_key(f"key with spaces {i % 17}") for i in range(1000)],
        np.int32,
    )
    assert np.array_equal(got, exp)
    # dispatcher + chunk-size invariance
    alt = np.concatenate(list(trace_chunks(p, "kv", chunk=999)))
    assert np.array_equal(alt, exp)
    with pytest.raises(ValueError):
        trace_chunks(p, "nope")


def test_make_trace_fixture_roundtrip(tmp_path):
    sys.path.insert(0, ROOT)
    try:
        from tools.make_trace import synth_events, write_trace_fixture
    finally:
        sys.path.remove(ROOT)
    idx = synth_events(5000, n_keys=300, seed=13)
    for fmt, key_fmt in (("wikipedia", "en Page_{}"), ("kv", "word_{}")):
        p = write_trace_fixture(tmp_path / f"t.{fmt}", fmt, 5000,
                                n_keys=300, seed=13)
        got = np.concatenate(list(trace_chunks(p, fmt, chunk=512)))
        exp = np.asarray([hash_raw_key(key_fmt.format(i)) for i in idx],
                         np.int32)
        assert np.array_equal(got, exp), fmt


# ---------------------------------------------------------------------------
# compile-cache behaviour
# ---------------------------------------------------------------------------


def test_recompile_warning_on_new_chunk_shape():
    clear_step_cache()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # first shape: no warning
            ChunkedRouter(W, "pkg", chunk=256, block=128, seed=0)
        with pytest.warns(UserWarning, match="new chunk step"):
            ChunkedRouter(W, "pkg", chunk=512, block=128, seed=0)
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # cached shape: silent
            ChunkedRouter(W, "pkg", chunk=512, block=128, seed=0)
    finally:
        clear_step_cache()


# ---------------------------------------------------------------------------
# streaming simulator
# ---------------------------------------------------------------------------


def _sim_pair(mk_sched, keys, piece, **kw):
    from repro.serving.sim import simulate_serving

    def chunks():
        for lo in range(0, len(keys), piece):
            yield keys[lo : lo + piece]

    a = simulate_serving(mk_sched(), keys, **kw)
    s = simulate_serving(mk_sched(), chunks(), **kw)
    return a, s


@pytest.mark.parametrize("kw", [
    dict(sample_every=512),
    dict(sample_every=512, utilization=1.3, queue_bound=4),
    dict(sample_every=512, kill_schedule=[(200.0, 3)],
         revive_schedule=[(900.0, 3)]),
])
def test_sim_streaming_eq_array(kw):
    from repro.serving.scheduler import PoTCScheduler

    keys = zipf_stream(20_000, 500, 1.4, seed=14)
    a, s = _sim_pair(lambda: PoTCScheduler(16, seed=1), np.asarray(keys),
                     1777, **kw)
    assert a.completed == s.completed
    assert a.shed == s.shed and a.requeued == s.requeued
    assert a.hit_rate == s.hit_rate
    assert a.makespan == s.makespan
    assert a.peak_outstanding == s.peak_outstanding
    assert a.session_fanout_max == s.session_fanout_max
    assert np.array_equal(a.assign_hist, s.assign_hist)
    assert np.array_equal(
        a.assign_hist, np.bincount(a.assign, minlength=len(a.assign_hist))
    )
    la = np.sort(a.latency[~np.isnan(a.latency)])
    assert np.array_equal(la, s.latency)  # reservoir not hit at this scale
    assert a.latency_p50 == s.latency_p50 and a.latency_p99 == s.latency_p99
    assert np.array_equal(a.sample_imbalance, s.sample_imbalance)
    assert len(s.assign) == 0 and len(s.shed_mask) == 0


def test_sim_streaming_guards():
    from repro.serving.scheduler import PoTCScheduler
    from repro.serving.sim import simulate_serving

    keys = np.zeros(10, np.int32)
    with pytest.raises(ValueError, match="costs"):
        simulate_serving(PoTCScheduler(4), iter([keys]), costs=np.ones(10))
    with pytest.raises(ValueError, match="tenants"):
        simulate_serving(PoTCScheduler(4), iter([keys]), tenants=[0] * 10)
    r = simulate_serving(PoTCScheduler(4), iter([]))
    assert r.completed == 0 and r.hit_rate == 0.0


# ---------------------------------------------------------------------------
# the 1e7-event nightly tier
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_trace_scale_1e7_tier():
    """1e7 events / 1e5 keys streamed through the chunked driver: carried
    state stays constant-size, every event lands exactly once, and the
    stream never materializes (generator in, histogram out)."""
    events, n_keys = 10_000_000, 100_000
    spec = StreamSpec("tier", n_msgs=events, n_keys=n_keys, z=1.4)
    r = ChunkedRouter(32, "pkg", chunk=8192, block=128, seed=0)
    state0 = r.state_bytes()
    hist = np.zeros(32, np.int64)

    def on_chunk(a):
        hist[:] = hist + np.bincount(a, minlength=32)

    n = r.route_stream(spec.stream_chunks(8192, seed=0), on_chunk=on_chunk)
    assert n == events
    assert int(hist.sum()) == events
    assert r.state_bytes() == state0  # flat: carry never grows
    assert np.array_equal(r.loads.astype(np.int64), hist)
    # balance sanity: at z=1.4 the head key is ~p1=32% of the stream, so
    # single-choice hashing floors at ~p1 - 1/n while PKG's key splitting
    # halves the head — assert we land at the split-head floor, not the
    # single-choice one
    p1 = float(zipf_probs(n_keys, 1.4)[0])
    frac = float(hist.max() - hist.mean()) / events
    assert frac < 0.6 * p1
    assert frac > 0.0  # not a degenerate all-one-worker histogram
