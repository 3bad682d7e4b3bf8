"""Million-key / trace-scale streaming bench: the chunked driver's flat-memory
contract, measured (DESIGN.md chunked-streaming section).

Three scenarios on the BENCH_* JSON convention:

* ``chunked_stream`` — every chunked policy (pkg / d_choices / w_choices)
  routed over a zipf stream fed through core.streams.stream_chunks.  Gated
  ``events_per_sec`` is RELATIVE: chunked throughput over the same driver
  run as one giant chunk on the same events (so CPU CI gates the chunking
  overhead, not the machine); the absolute chunked number ships un-gated as
  ``events_per_sec_abs``.  Gated ``bytes_per_key`` is
  ``ChunkedRouter.state_bytes() / distinct keys`` — the flat-memory number:
  carried routing state is constant, so bytes/key shrinks as keys grow.
* ``rss`` — the same stream routed end to end twice in this process, once
  through the flat pipeline (generator in, per-chunk histogram out), once
  through the materialize-everything pipeline (full key array in, full
  assignment array out), each reporting its post-warmup RSS growth from
  /proc/self/statm.  Gated ``rss_ratio`` = chunked growth / one-shot growth;
  the hard ``rss_flat`` (ratio <= 0.5) check arms once the measured
  stream is >= 3e6 events (below that both growths are allocator noise) —
  the nightly --scale 50 run (1e7 events) exercises it.
* ``trace_ingest`` — tools/make_trace.py fixtures in both real formats
  (Wikipedia pagecounts, key<TAB>ts) read by core.traces and routed by the
  chunked driver; un-gated ingest throughput plus a reader-determinism and
  hash-round-trip check.  No network: the fixtures are synthesized.

Bit-exactness checks (also tests/test_chunked.py): chunked == one-shot for
every policy (pkg vs ref.ref_pkg_route; d/w vs online_head_tables +
ref.ref_adaptive_route_online), and streaming simulate_serving == array-mode
aggregates.

Scale map: events = 200k * scale, keys = events / 100 — so ``--scale 50`` is
the 1e7-event nightly tier and ``--scale 500`` the un-gated 1e6-key /
1e8-event headline.
"""
from __future__ import annotations

import os
import tempfile
import time
import warnings
from pathlib import Path

import numpy as np

from benchmarks.common import Row, bench_main
from repro.core.streams import StreamSpec
from repro.core.traces import trace_chunks
from repro.parallel.chunked_driver import ChunkedRouter

QUICK_SCALE = 0.25

BASE_EVENTS = 200_000
W = 32
CHUNK = 8192
BLOCK = 128
Z = 1.4
D_MAX = 8
SS_CAP = 256
DECAY = 4096
RSS_FLAT_MIN_EVENTS = 3_000_000  # below this, RSS growth is allocator noise

_POLICY_KW = {
    "pkg": {},
    "d_choices": dict(d_max=D_MAX, ss_capacity=SS_CAP, decay_period=DECAY),
    "w_choices": dict(ss_capacity=SS_CAP, decay_period=DECAY),
}


def _spec(events: int, n_keys: int) -> StreamSpec:
    return StreamSpec(name="trace_scale", n_msgs=events, n_keys=n_keys, z=Z)


def _route_stream(router: ChunkedRouter, chunks) -> tuple[np.ndarray, int, float]:
    """Route chunks keeping only a histogram (the flat pipeline); returns
    (hist, events, seconds)."""
    hist = np.zeros(router.n_workers, np.int64)

    def on_chunk(a: np.ndarray) -> None:
        hist[:] = hist + np.bincount(a, minlength=router.n_workers)

    t0 = time.perf_counter()
    n = router.route_stream(chunks, on_chunk=on_chunk)
    return hist, n, time.perf_counter() - t0


def _chunked_stream_scenario(events: int, n_keys: int, seed: int) -> dict:
    spec = _spec(events, n_keys)
    # one-shot comparator capped: materializing 1e8 events is what this
    # module exists to avoid — the ratio is measured where both sides fit
    # (rounded to the chunk size: the one-giant-chunk step needs chunk|block)
    cmp_events = max(min(events, 262_144) // CHUNK * CHUNK, CHUNK)
    cmp_keys = np.concatenate(
        list(_spec(cmp_events, n_keys).stream_chunks(CHUNK, seed=seed))
    )
    entry = {
        "n_events": events, "n_keys": n_keys, "n_workers": W,
        "chunk": CHUNK, "block": BLOCK, "z": Z,
        "events_per_sec": {}, "events_per_sec_abs": {},
        "bytes_per_key": {}, "final_imbalance": {},
    }
    for policy, kw in _POLICY_KW.items():
        mk = lambda c: ChunkedRouter(  # noqa: E731
            W, policy, chunk=c, block=BLOCK, seed=seed, **kw
        )
        # warm both step shapes, then time (the sweep is deliberate — hush
        # the driver's retrace warning)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            mk(CHUNK).route_stream(np.zeros(CHUNK, np.int32))
            mk(cmp_events).route_stream(np.zeros(cmp_events, np.int32))
        _, _, dt_one = _route_stream(mk(cmp_events), cmp_keys)
        _, _, dt_chk_cmp = _route_stream(
            mk(CHUNK), _spec(cmp_events, n_keys).stream_chunks(CHUNK, seed=seed)
        )
        hist, n, dt_full = _route_stream(
            mk(CHUNK), spec.stream_chunks(CHUNK, seed=seed)
        )
        assert n == events, (n, events)
        router = mk(CHUNK)
        entry["events_per_sec"][policy] = (cmp_events / dt_chk_cmp) / (
            cmp_events / dt_one
        )
        entry["events_per_sec_abs"][policy] = events / dt_full
        entry["bytes_per_key"][policy] = router.state_bytes() / n_keys
        entry["final_imbalance"][policy] = float(
            hist.max() - hist.mean()
        ) / events
    return entry


# -- RSS experiment (one process; /proc/self/statm resident pages) ----------


def _rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def _rss_run(mode: str, events: int, n_keys: int, seed: int) -> dict:
    """Route one stream end to end in this process and report its RSS growth
    while the pipeline's data is live.  "chunked" is the flat pipeline
    (generator in, per-chunk histogram out); "oneshot" materializes
    everything (key array in, assignment array out, both still held when
    RSS is read).  Runs in the process that already holds the device: a
    child would have to fight the parent for the chip."""
    spec = StreamSpec(name="rss", n_msgs=events, n_keys=n_keys, z=Z)
    router = ChunkedRouter(W, "pkg", chunk=CHUNK, seed=seed)
    router.route_stream(np.zeros(CHUNK, np.int32))  # compile before baselining
    hist = np.zeros(W, np.int64)

    def on_chunk(a):
        hist[:] = hist + np.bincount(a, minlength=W)

    base = _rss_bytes()
    if mode == "chunked":
        n = router.route_stream(spec.stream_chunks(CHUNK, seed=seed),
                                on_chunk=on_chunk)
        growth = _rss_bytes() - base
    else:
        keys = np.concatenate(list(spec.stream_chunks(CHUNK, seed=seed)))
        a = router.route_stream(keys)
        hist[:] = hist + np.bincount(a, minlength=W)
        n = len(a)
        growth = _rss_bytes() - base
    return {"growth_mb": growth / 1e6, "events": int(n),
            "hist_sum": int(hist.sum())}


def _rss_scenario(events: int, n_keys: int, seed: int) -> tuple[dict, bool]:
    # cap the child stream so the materializing child stays runnable; the
    # nightly tier (>= 3e6 after the cap) arms the hard rss_flat check
    rss_events = max(min(events, 10_000_000), 1_000_000)
    rss_keys = max(min(n_keys, rss_events // 100), 1000)
    # chunked first: memory the one-shot run frees may stay mapped by the
    # allocator and would be counted against a run measured after it
    chk = _rss_run("chunked", rss_events, rss_keys, seed)
    one = _rss_run("oneshot", rss_events, rss_keys, seed)
    assert chk["events"] == one["events"] == rss_events
    assert chk["hist_sum"] == one["hist_sum"] == rss_events
    # 1 MB floor: both numbers ride on allocator noise at that granularity
    ratio = max(chk["growth_mb"], 1.0) / max(one["growth_mb"], 1.0)
    entry = {
        "n_events": rss_events, "n_keys": rss_keys,
        "growth_mb": {"chunked": chk["growth_mb"], "oneshot": one["growth_mb"]},
        "rss_ratio": {"pkg": ratio},
    }
    flat_ok = ratio <= 0.5 if rss_events >= RSS_FLAT_MIN_EVENTS else True
    return entry, flat_ok


def _trace_ingest_scenario(events: int, seed: int, tmp: Path) -> tuple[dict, bool]:
    from tools.make_trace import write_trace_fixture

    fx_events = min(events, 200_000)
    fx_keys = max(fx_events // 100, 1000)
    entry = {"n_events": fx_events, "n_keys": fx_keys,
             "ingest_events_per_sec": {}}
    deterministic = True
    for fmt in ("wikipedia", "kv"):
        path = write_trace_fixture(
            tmp / f"trace.{fmt}", fmt, fx_events, n_keys=fx_keys, z=Z,
            seed=seed,
        )
        router = ChunkedRouter(W, "pkg", chunk=CHUNK, seed=seed)
        router.route_stream(np.zeros(CHUNK, np.int32))  # compile
        hist, n, dt = _route_stream(
            router, trace_chunks(path, fmt, chunk=CHUNK)
        )
        assert n == fx_events, (fmt, n, fx_events)
        r1 = np.concatenate(list(trace_chunks(path, fmt, chunk=CHUNK)))
        r2 = np.concatenate(list(trace_chunks(path, fmt, chunk=CHUNK - BLOCK)))
        deterministic = deterministic and bool(np.array_equal(r1, r2))
        entry["ingest_events_per_sec"][fmt] = fx_events / dt
    return entry, deterministic


# -- bit-exactness checks ---------------------------------------------------


def _chunked_eq_oneshot(seed: int) -> dict:
    """chunked(chunk=c) == one-shot for every policy, c in {512, n} — the
    full sweep (down to c=1) lives in tests/test_chunked.py."""
    import jax.numpy as jnp

    from repro.core.estimation import online_head_tables
    from repro.kernels import ref

    n = 4096
    keys = np.concatenate(list(_spec(n, 500).stream_chunks(1024, seed=seed)))
    kj = jnp.asarray(keys)
    out = {}
    ref_pkg = np.asarray(
        ref.ref_pkg_route(kj, W, d=2, seed=seed, chunk=n, block=BLOCK)[0]
    )
    refs = {"pkg": ref_pkg}
    for policy in ("d_choices", "w_choices"):
        w_mode = policy == "w_choices"
        d_max = D_MAX if policy == "d_choices" else 2
        tk, tn = online_head_tables(
            kj, BLOCK, SS_CAP, W, d=2, d_max=D_MAX,
            decay_period=DECAY, any_worker=w_mode,
        )
        refs[policy] = np.asarray(ref.ref_adaptive_route_online(
            kj, tk, tn, W, d_base=2, d_max=d_max, seed=seed, chunk=n,
            block=BLOCK, w_mode=w_mode,
        )[0])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # deliberate chunk-size sweep
        for policy, kw in _POLICY_KW.items():
            ok = True
            for c in (512, n):
                r = ChunkedRouter(
                    W, policy, chunk=c, block=BLOCK, seed=seed, **kw
                )
                ok = ok and bool(
                    np.array_equal(r.route_stream(keys), refs[policy])
                )
            out[f"chunked_eq_oneshot_{policy}"] = ok
    return out


def _sim_stream_eq_array(seed: int) -> bool:
    from repro.serving.scheduler import PoTCScheduler
    from repro.serving.sim import simulate_serving

    keys = np.concatenate(
        list(_spec(20_000, 500).stream_chunks(1024, seed=seed))
    )
    a = simulate_serving(PoTCScheduler(16, seed=seed), keys, sample_every=512)
    s = simulate_serving(
        PoTCScheduler(16, seed=seed),
        _spec(20_000, 500).stream_chunks(1777, seed=seed),
        sample_every=512,
    )
    la = np.sort(a.latency[~np.isnan(a.latency)])
    return bool(
        a.completed == s.completed and a.shed == s.shed
        and a.hit_rate == s.hit_rate and a.makespan == s.makespan
        and np.array_equal(a.assign_hist, s.assign_hist)
        and np.array_equal(la, s.latency)
    )


def collect(scale: float = 1.0, seed: int = 0) -> dict:
    events = max(int(BASE_EVENTS * scale), 10_000)
    n_keys = max(events // 100, 1000)

    scenarios = {
        "chunked_stream": _chunked_stream_scenario(events, n_keys, seed),
    }
    rss_entry, flat_ok = _rss_scenario(events, n_keys, seed)
    scenarios["rss"] = rss_entry
    with tempfile.TemporaryDirectory() as td:
        ingest_entry, det_ok = _trace_ingest_scenario(events, seed, Path(td))
    scenarios["trace_ingest"] = ingest_entry

    checks = _chunked_eq_oneshot(seed + 1)
    checks["trace_reader_deterministic"] = det_ok
    checks["sim_stream_eq_array"] = _sim_stream_eq_array(seed)
    checks["rss_flat"] = flat_ok

    return {
        "n_events": events,
        "n_keys": n_keys,
        "scenarios": scenarios,
        "checks": checks,
    }


def run(scale: float = 1.0) -> list[Row]:
    report = collect(scale=scale)
    rows = []
    cs = report["scenarios"]["chunked_stream"]
    for policy in sorted(cs["events_per_sec_abs"]):
        rows.append(Row(
            f"trace_scale/chunked/{policy}",
            1e6 / cs["events_per_sec_abs"][policy],
            f"{cs['final_imbalance'][policy]:.3e}",
        ))
    rows.append(Row(
        "trace_scale/rss_ratio", 0.0,
        f"{report['scenarios']['rss']['rss_ratio']['pkg']:.3f}",
    ))
    ok = all(report["checks"].values())
    rows.append(Row("trace_scale/checks", 0.0, "pass" if ok else "FAIL"))
    return rows


if __name__ == "__main__":
    bench_main("trace_scale", collect, quick_scale=QUICK_SCALE)
