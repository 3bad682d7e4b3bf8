"""The trace reduction checked on small traces in its normalised form: one
worked by hand, and seeded random ones against a brute-force count on a
1 ns grid.  Run by hand on the CPU:

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest -q benchmarks/chip
"""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

import trace_reduce

HERE = Path(__file__).resolve().parent

SMALL = {
    "device_planes": 1,
    "ops": [["fusion.1", 100, 100], ["fusion.2", 150, 150], ["while.3", 500, 100],
            ["copy.4", 950, 150]],
    "modules": [["jit_step(7)", 100, 200], ["jit_step(7)", 500, 100],
                ["jit__getitem(9)", 950, 50]],
    "spans": [["bench.window", 0, 1000], ["bench.generator", 300, 100],
              ["bench.sink", 400, 50], ["bench.generator", 700, 100]],
}


def test_small_trace_by_hand():
    """Busy [100,300] + [500,600] + [950,1000]; idle [0,100], [300,500],
    [600,950], of which the generator covers 200 and the sink 50."""
    r = trace_reduce.Reduced(SMALL)
    assert (r.window_ns, r.busy_ns, r.steps, r.step_ns) == (1000, 350, 2, 300)
    assert r.idle_ns == {"generator": 200, "sink": 50, "driver": 400}
    assert r.longest_gaps(3) == [["driver", 350], ["generator", 200], ["driver", 100]]
    assert trace_reduce.step_us(r) == 0.15
    name, seconds = trace_reduce.breakdown(r)["device_ops"][0]
    assert (name, seconds) == ("fusion.2", pytest.approx(150e-9))


def _random_trace(seed: int) -> dict:
    """Steps of overlapping ops on the device; on the host, alternating
    generator and sink spans with driver time between them."""
    rng = np.random.default_rng(seed)
    ops, modules, spans = [], [], []
    t = 1_000
    for _ in range(30):
        start = t + int(rng.integers(0, 400))
        end = start
        for _ in range(int(rng.integers(1, 6))):
            s = max(end + int(rng.integers(-20, 60)), start)
            d = int(rng.integers(5, 120))
            ops.append([f"fusion.{int(rng.integers(0, 4))}", s, d])
            end = max(end, s + d)
        modules.append(["jit_step(3)", start, end - start])
        t = end
    h = 500
    while h < t:
        g = int(rng.integers(10, 300))
        spans.append(["bench.generator", h, g])
        k = int(rng.integers(10, 200))
        spans.append(["bench.sink", h + g + 40, k])
        h += g + 40 + k + int(rng.integers(50, 400))
    spans.append(["bench.window", 0, max(t, h) + 700])
    return {"device_planes": 1, "ops": ops, "modules": modules, "spans": spans}


def _grid(intervals, lo: int, hi: int) -> np.ndarray:
    on = np.zeros(hi - lo, bool)
    for s, e in intervals:
        a, b = max(int(s) - lo, 0), min(int(e) - lo, hi - lo)
        if b > a:
            on[a:b] = True
    return on


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_matches_brute_force(seed):
    trace = _random_trace(seed)
    r = trace_reduce.Reduced(trace)
    lo, hi = int(r.lo), int(r.hi)
    busy = _grid([(s, s + d) for _, s, d in trace["ops"]], lo, hi)
    assert r.busy_ns == busy.sum()
    assert r.steps == len(trace["modules"])
    assert r.step_ns == sum(d for _, _, d in trace["modules"])
    idle = ~busy
    for label, span in (("generator", trace_reduce.GENERATOR_SPAN),
                        ("sink", trace_reduce.SINK_SPAN)):
        on = _grid([(s, s + d) for n, s, d in trace["spans"] if n == span], lo, hi)
        assert r.idle_ns[label] == (idle & on).sum()
    assert sum(r.idle_ns.values()) == idle.sum()


def test_no_step_program_leaves_the_metric_out():
    r = trace_reduce.Reduced(dict(SMALL, modules=[]))
    assert r.steps == 0 and trace_reduce.step_us(r) is None


def test_no_device_plane_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.Reduced(dict(SMALL, device_planes=0))


RECORDED = sorted(HERE.glob("traces/*/*.xplane.pb"))


def _on(cuts: np.ndarray, intervals) -> np.ndarray:
    """Whether each segment between consecutive cuts lies under at least one
    of the intervals: a sweep over +1/-1 ends, apart from the reduction's
    sort-and-merge.  Every interval end must be among the cuts."""
    iv = np.clip(np.asarray(intervals, np.float64).reshape(-1, 2), cuts[0], cuts[-1])
    iv = iv[iv[:, 1] > iv[:, 0]]
    depth = np.zeros(len(cuts), np.int64)
    np.add.at(depth, np.searchsorted(cuts, iv[:, 0]), 1)
    np.add.at(depth, np.searchsorted(cuts, iv[:, 1]), -1)
    return np.cumsum(depth)[:-1] > 0


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.parent.name)
def test_recorded_trace(path):
    """A trace recorded on a TPU v5e by `run_cell.py --trace 1 --keep-trace`:
    the planes and names the reduction looks for are there, and busy time,
    the step's device time and the idle split agree with a sweep."""
    trace = trace_reduce.load(str(path.parent))
    assert trace["device_planes"] == 1
    r = trace_reduce.Reduced(trace)
    assert r.steps > 0 and 0 < r.busy_ns <= r.window_ns
    lo, hi = r.lo, r.hi

    def iv(events, name=None):
        return [(s, s + d) for n, s, d in events if name is None or n == name]

    ops = iv(trace["ops"])
    gen = iv(trace["spans"], trace_reduce.GENERATOR_SPAN)
    sink = iv(trace["spans"], trace_reduce.SINK_SPAN)
    ends = np.asarray(ops + gen + sink, np.float64).ravel()
    cuts = np.unique(np.clip(np.concatenate([[lo, hi], ends]), lo, hi))
    seg = np.diff(cuts)
    busy = _on(cuts, ops)
    assert r.busy_ns == pytest.approx(seg[busy].sum(), abs=1)
    steps = [(max(s, lo), min(e, hi)) for n, s, d in trace["modules"]
             if trace_reduce.STEP_MODULE.match(n) for e in [s + d]]
    assert r.step_ns == pytest.approx(sum(max(e - s, 0) for s, e in steps), abs=1)
    for label, spans in (("generator", gen), ("sink", sink)):
        assert r.idle_ns[label] == pytest.approx(seg[~busy & _on(cuts, spans)].sum(), abs=1)
    assert sum(r.idle_ns.values()) == pytest.approx(seg[~busy].sum(), abs=1)
