"""The chunked router's profiler spans and the chunk step's named scopes.

`ChunkedRouter.route_stream` writes `router.stream`, and per chunk
`router.put`, `router.dispatch` and `router.pull` (the last two carrying the
chunk id, the pull also the chunks still in flight) into the profiler's trace; the chunk step's program is `jit_step`
and its HLO carries the `ss_head_table`, `ss_update` and `waterfill` scopes
where the policy runs them, and `candidate_fetch` in every policy.  Read back here from a CPU trace with
`jax.profiler.ProfileData`, as the chip benchmark reads a TPU one.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.parallel.chunked_driver import _PULL_WINDOW, ChunkedRouter

W = 8
CHUNK = 256
BLOCK = 128
SCOPES = ("ss_head_table", "ss_update", "waterfill")
PER_CHUNK = ("router.put", "router.dispatch", "router.pull")
CHUNK_ID = ("router.dispatch", "router.pull")


def _host_events(trace_dir):
    """[name, start_ns, end_ns, chunk id or None, inflight or None] of every
    host event."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [
        [e.name, e.start_ns, e.start_ns + e.duration_ns,
         dict(e.stats)["chunk"] if e.name in CHUNK_ID else None,
         dict(e.stats)["inflight"] if e.name == "router.pull" else None]
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
    ]


def _traced_stream(tmp_path, policy, pieces):
    """Route `pieces` twice (two route_stream calls) under the profiler,
    with the caller's iterator and sink in spans of their own."""
    router = ChunkedRouter(W, policy, chunk=CHUNK, block=BLOCK, seed=3)
    out = []

    def feed():
        for p in pieces:
            with TraceAnnotation("test.generator"):
                p = np.asarray(p)
            yield p

    def sink(a):
        with TraceAnnotation("test.sink"):
            out.append(a)

    router.route_stream(pieces[:1], on_chunk=sink)  # compiles outside the trace
    out.clear()
    warm = router.n_chunks
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            router.route_stream(feed(), on_chunk=sink)
    finally:
        jax.profiler.stop_trace()
    assert 0 <= router.n_blocked_pulls <= router.n_chunks
    return router.n_chunks - warm, warm, out, _host_events(tmp_path)


@pytest.mark.parametrize("policy", ["pkg", "w_choices"])
def test_route_stream_spans(tmp_path, policy):
    """Five pieces of uneven sizes: three whole chunks and a padded last one
    per call.  Every chunk has a put, a dispatch and a pull; dispatch and
    pull carry the same id, counted on across calls; a chunk is pulled after
    the dispatch of the _PULL_WINDOW chunks behind it (or of the call's
    last), and its pull's `inflight` counts them; no router span overlaps
    another or the caller's spans."""
    rng = np.random.default_rng(0)
    sizes = [300, 100, 56, 200, 150]  # 806 events: chunks of 256 x 3 + 38
    pieces = [rng.integers(0, 500, n).astype(np.int32) for n in sizes]
    n, warm, out, events = _traced_stream(tmp_path, policy, pieces)
    assert sum(map(len, out)) == 2 * sum(sizes)
    assert n == 2 * -(-sum(sizes) // CHUNK) and warm == 2

    spans = {k: [e for e in events if e[0] == k]
             for k in PER_CHUNK + ("router.stream", "test.generator", "test.sink")}
    assert len(spans["router.stream"]) == 2
    ids = list(range(warm, warm + n))
    for k in CHUNK_ID:
        assert sorted(e[3] for e in spans[k]) == ids
    # each pull follows its chunk's dispatch, and those of the window behind it
    start = {k: {e[3]: e[1] for e in spans[k]} for k in CHUNK_ID}
    assert all(start["router.pull"][i] > start["router.dispatch"][i] for i in ids)
    per_call = n // 2
    for i in ids:
        last = warm + ((i - warm) // per_call + 1) * per_call - 1
        behind = min(_PULL_WINDOW, last - i)
        assert start["router.pull"][i] > start["router.dispatch"][i + behind]
        assert {e[3]: e[4] for e in spans["router.pull"]}[i] == behind
    # one copy span per piece fragment that lands in a chunk
    assert len(spans["router.put"]) >= n

    mine = sorted(e[1:3] for k in PER_CHUNK for e in spans[k])
    assert all(a[1] <= b[0] for a, b in zip(mine, mine[1:]))
    theirs = [e[1:3] for k in ("test.generator", "test.sink") for e in spans[k]]
    assert len(theirs) == 2 * len(pieces) + n
    for s, e in mine:
        assert all(e <= ts or te <= s for ts, te in theirs)
    streams = [e[1:3] for e in spans["router.stream"]]
    assert all(any(a <= s and e <= b for a, b in streams) for s, e in mine)


@pytest.mark.parametrize("policy", ["pkg", "d_choices", "w_choices"])
def test_step_program_and_scopes(policy):
    """The step's program is `jit_step`, and its compiled HLO names the
    scopes its policy runs: both Space-Saving scopes for the adaptive
    policies, the water-fill for W-Choices alone, none for PKG."""
    router = ChunkedRouter(W, policy, chunk=CHUNK, block=BLOCK)
    keys = jnp.zeros(CHUNK, jnp.int32)
    lowered = router._step.lower(router._carry, keys, keys, router._seeds, None)
    assert lowered.as_text().startswith("module @jit_step ")
    hlo = lowered.compile().as_text()
    assert hlo.startswith("HloModule jit_step,")
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    found = {s for s in SCOPES if any(f"/{s}/" in p for p in paths)}
    want = {
        "pkg": set(),
        "d_choices": {"ss_head_table", "ss_update"},
        "w_choices": set(SCOPES),
    }[policy]
    assert found == want


@pytest.mark.parametrize("policy", ["pkg", "d_choices", "w_choices"])
def test_step_has_candidate_fetch_scope(policy):
    """`route_block`'s load fetch, lane masks and candidate argmin carry the
    `candidate_fetch` scope in every policy's step, beside `waterfill`,
    never inside it."""
    router = ChunkedRouter(W, policy, chunk=CHUNK, block=BLOCK, d_max=W)
    keys = jnp.zeros(CHUNK, jnp.int32)
    lowered = router._step.lower(router._carry, keys, keys, router._seeds, None)
    paths = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    fetch = [p for p in paths if "/candidate_fetch/" in p]
    assert fetch
    assert not any("/waterfill/" in p for p in fetch)


@pytest.mark.parametrize("policy", ["pkg", "d_choices", "w_choices"])
def test_waterfill_scope_runs_no_loop(policy):
    """W-Choices' step carries the `waterfill` scope with no `while` op
    under it: without capacities the block's picks come from the level
    count, not from one argmin per lane.  PKG and D-Choices carry no
    water-fill op at all."""
    router = ChunkedRouter(W, policy, chunk=CHUNK, block=BLOCK)
    keys = jnp.zeros(CHUNK, jnp.int32)
    lowered = router._step.lower(router._carry, keys, keys, router._seeds, None)
    fill = [
        line for line in lowered.compile().as_text().splitlines()
        if re.search(r'op_name="[^"]*/waterfill/', line)
    ]
    assert bool(fill) == (policy == "w_choices")
    assert not any(re.search(r"\swhile\(", line) for line in fill)
