"""Whether a run routed correctly: the delivered stream against the plain
reference, replayed from the seed over every piece the router was handed.

Every pass of a run routes the same stream from an empty router, so one
replay of the longest pass gives what every pass has to deliver.  Set-up
also routes the stream's short last piece alone, which is replayed apart.
Each number compared is exact, so each limit is 0:

* ``mismatched_events``: events whose delivered worker differs from the
  reference's, counting every event of a piece that was never delivered;
* ``chunk_faults``: pieces delivered more or fewer times than handed over,
  plus delivered pieces of the wrong length;
* ``loads_row_gap``: largest gap, over the passes, between the router's
  carried loads row and the reference's loads (the histogram of what it
  routed);
* ``summary_gap`` (policies with a frequency summary): summary slots whose
  key, count or error differs from the reference's, plus one if the totals
  differ, summed over the passes.
"""
from __future__ import annotations

import importlib
from typing import NamedTuple

import numpy as np

LIMITS = {
    "mismatched_events": 0,
    "chunk_faults": 0,
    "loads_row_gap": 0,
    "summary_gap": 0,
}
# Loads are float32 counts in the program: exact below 2^24 per worker.
EXACT_LOADS = 1 << 24


class Pass(NamedTuple):
    first: int  # index of the pass's first piece among the delivered ones
    pieces: list  # indices of the stream's pieces handed over, in order
    loads: np.ndarray  # the router's loads row after the pass
    summary: dict | None  # its frequency summary after the pass, if any


def reference(router: dict, seed: int, fetch: str = "exact"):
    """The plain reference router for the configuration's policy."""
    return importlib.import_module(f"policies.{router['policy']}").Reference(
        router, seed, fetch
    )


def replay(ref, piece, lengths) -> tuple[list, dict]:
    """Route pieces 0 .. max(lengths)-1 through `ref`; return its output per
    piece and its (loads, summary) after each length in `lengths`."""
    out, at = [], {}
    for k in range(max(lengths, default=0)):
        out.append(ref.route_chunk(piece(k)))
        if k + 1 in lengths:
            s = ref.summary()
            at[k + 1] = (ref.loads.copy(), s and {f: np.copy(v) for f, v in s.items()})
    if len(out) and ref.loads.max() >= EXACT_LOADS:
        raise ValueError(
            f"a worker's load reached {ref.loads.max()} in one pass, past "
            "float32's exact counts: the configuration's guarantee does not hold"
        )
    return out, at


def compare(make_ref, piece, passes: list[Pass], delivered: list) -> dict:
    """Compare what the sink received (`delivered`, in order) and each
    pass's final router state with a reference from `make_ref()` replayed
    over the same pieces `piece(k)`.  Passes that start at the stream's head
    share one replay; any other is replayed on its own."""
    def from_head(p):
        return list(p.pieces) == list(range(len(p.pieces)))

    shared = replay(make_ref(), piece, {len(p.pieces) for p in passes if from_head(p)})
    mismatched = faults = 0
    loads_gap = 0.0
    summary_gap = 0
    has_summary = False
    bounds = [p.first for p in passes[1:]] + [len(delivered)]
    for p, end in zip(passes, bounds):
        n_handed = len(p.pieces)
        want, at = shared if from_head(p) else replay(
            make_ref(), lambda k, p=p: piece(p.pieces[k]), {n_handed}
        )
        got = delivered[p.first : end]
        faults += abs(len(got) - n_handed)
        for k in range(n_handed):
            w = want[k]
            g = got[k] if k < len(got) else w[:0]
            if len(g) != len(w):
                faults += 1
            n = min(len(g), len(w))
            mismatched += int(np.count_nonzero(g[:n] != w[:n])) + len(w) - n
        loads, summary = at[n_handed]
        loads_gap = max(loads_gap, float(np.abs(np.asarray(p.loads, np.float64) - loads).max()))
        if summary is not None:
            has_summary = True
            gap = np.zeros(len(summary["keys"]), bool)
            for f in ("keys", "counts", "errors"):
                gap |= np.asarray(p.summary[f]) != summary[f]
            summary_gap += int(gap.sum()) + int(int(p.summary["total"]) != summary["total"])
    out = {
        "mismatched_events": mismatched,
        "chunk_faults": faults,
        "loads_row_gap": loads_gap,
    }
    if has_summary:
        out["summary_gap"] = summary_gap
    return out


def verdict(numbers: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the result line."""
    table = {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}
    return all(v <= LIMITS[k] for k, v in numbers.items()), table
