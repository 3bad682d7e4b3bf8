"""From a profiler trace to the benchmark's per-layer numbers.

A trace is first normalised to three lists of [name, start_ns, duration_ns]:

* ``ops``: device operations (the device plane's "XLA Ops" line);
* ``modules``: device executions of whole compiled programs ("XLA Modules");
* ``spans``: the benchmark's own host annotations, named ``bench.*``.

Everything below works on that form, so the reduction can be checked on
small traces without a chip (test_trace_reduce.py).  The traced window is the
``bench.window`` span; device busy time is the union of the op
intervals inside it, and an idle gap is labelled by what the host was doing:
inside the generator span, inside the sink span, or elsewhere in the driver.
"""
from __future__ import annotations

import glob
import re
import sys

import numpy as np

WINDOW_SPAN = "bench.window"
GENERATOR_SPAN = "bench.generator"
SINK_SPAN = "bench.sink"
# The chunk step is `step` inside repro.parallel.chunked_driver._build_step;
# jit names its program after it.  It has no more stable name yet.
STEP_MODULE = re.compile(r"^jit_step\b")


def load(trace_dir: str) -> dict:
    """Normalise the one .xplane.pb under trace_dir."""
    import jax

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{len(paths)} .xplane.pb files under {trace_dir}; need one")
    (path,) = paths
    data = jax.profiler.ProfileData.from_file(path)
    out = {"ops": [], "modules": [], "spans": [], "device_planes": 0}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            out["device_planes"] += 1
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    out[key] += [
                        [e.name, e.start_ns, e.duration_ns] for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events if e.name.startswith("bench.")
                ]
    return out


def _intervals(events, name=None) -> np.ndarray:
    rows = [(s, s + d) for n, s, d in events if name is None or n == name]
    return np.asarray(rows, np.float64).reshape(-1, 2)


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of intervals as sorted, disjoint [start, end] rows."""
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > reach[:-1]]
    starts = iv[new, 0]
    ends = reach[np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two unions of intervals."""
    a, b = _merge(a), _merge(b)
    return _length(a) + _length(b) - _length(_merge(np.concatenate([a, b])))


def _gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Complement of merged busy intervals within [lo, hi]."""
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


class Reduced:
    """The numbers the per-layer readers take, all in nanoseconds."""

    def __init__(self, trace: dict):
        windows = _intervals(trace["spans"], WINDOW_SPAN)
        if trace["device_planes"] == 0 or len(windows) != 1:
            raise ValueError(
                f"trace holds {trace['device_planes']} device planes and "
                f"{len(windows)} {WINDOW_SPAN} spans; need one of each at least"
            )
        self.lo, self.hi = windows[0]
        self.window_ns = self.hi - self.lo
        self.busy = _merge(_clip(_intervals(trace["ops"]), self.lo, self.hi))
        self.busy_ns = _length(self.busy)
        if self.busy_ns == 0:
            raise ValueError("no device operation in the traced window")
        steps = [m for m in trace["modules"] if STEP_MODULE.match(m[0])]
        inside = _clip(_intervals(steps), self.lo, self.hi)
        self.steps = len(inside)
        self.step_ns = _length(inside)
        self.gaps = _gaps(self.busy, self.lo, self.hi)
        self.generator = _clip(_intervals(trace["spans"], GENERATOR_SPAN), self.lo, self.hi)
        self.sink = _clip(_intervals(trace["spans"], SINK_SPAN), self.lo, self.hi)
        self.idle_ns = {
            "generator": _overlap(self.gaps, self.generator),
            "sink": _overlap(self.gaps, self.sink),
        }
        self.idle_ns["driver"] = (
            _length(self.gaps) - self.idle_ns["generator"] - self.idle_ns["sink"]
        )
        self.top_ops = _top_ops(trace["ops"], self.lo, self.hi)

    def longest_gaps(self, n: int = 6) -> list:
        """[label, ns] of the n longest idle gaps, each labelled by the host
        activity that covers most of it."""
        order = np.argsort(self.gaps[:, 0] - self.gaps[:, 1], kind="stable")[:n]
        out = []
        for g in self.gaps[order]:
            g = g.reshape(1, 2)
            cover = {
                "generator": _overlap(g, self.generator),
                "sink": _overlap(g, self.sink),
            }
            cover["driver"] = _length(g) - sum(cover.values())
            out.append([max(cover, key=cover.get), _length(g)])
        return out


def step_us(r: Reduced | None):
    """Device microseconds per execution of the chunk step, or None (said
    on stderr) when the trace holds no execution of it."""
    if r is None:
        return None
    if r.steps == 0:
        print("trace_reduce: no execution of the chunk step's program "
              "(jit_step) in the trace", file=sys.stderr)
        return None
    return r.step_ns / r.steps * 1e-3


def _top_ops(ops, lo: float, hi: float, n: int = 10) -> list:
    total: dict[str, float] = {}
    for name, s, d in ops:
        if s >= lo and s + d <= hi:
            total[name] = total.get(name, 0.0) + d
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def breakdown(r: Reduced) -> dict:
    """The result line's breakdown, in seconds."""
    idle = sorted(r.idle_ns.items(), key=lambda kv: -kv[1])
    return {
        "device_ops": [[name, ns * 1e-9] for name, ns in r.top_ops],
        "idle_gaps": [[f"{k} (all gaps)", ns * 1e-9] for k, ns in idle]
        + [[f"{k} (one gap)", ns * 1e-9] for k, ns in r.longest_gaps()],
    }
