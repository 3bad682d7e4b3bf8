"""The load generator and the downstream sink around `route_stream`.

The deployment routes the configuration's whole stream from an empty router.
The window replays that stream pass after pass, each pass through a router
built afresh, so no pass carries state from the one before.

The traffic's `arrival` says how pieces are handed over.  ``closed`` is the
one this generator knows: the router is handed the next piece as soon as it
asks, so the router's own rate sets the pace.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

ARRIVALS = ("closed",)


def spans(on: bool):
    """The benchmark's span factory: profiler annotations in a traced run,
    nothing in a timed one."""
    if on:
        import jax

        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


class Sink:
    """Downstream stand-in: per-worker histogram of the delivered
    assignments, every delivered chunk kept for the check, and the time each
    chunk arrived."""

    def __init__(self, n_workers: int, span):
        self.hist = np.zeros(n_workers, np.int64)
        self.chunks: list[np.ndarray] = []
        self.times: list[float] = []
        self._span = span

    def __call__(self, a: np.ndarray) -> None:
        t = time.perf_counter()
        with self._span("bench.sink"):
            self.hist += np.bincount(a, minlength=len(self.hist))
            self.chunks.append(a)
            self.times.append(t)


class Feed:
    """The stream cut into chunk-size pieces (the last one may be short),
    handed over pass after pass."""

    def __init__(self, stream: np.ndarray, chunk: int, span):
        self.stream = stream
        self.chunk = chunk
        self.per_pass = -(-len(stream) // chunk)
        self.handed: list[int] = []  # pieces of this pass handed over
        self.events = 0  # events handed over since the window opened
        self._span = span

    def piece(self, k: int) -> np.ndarray:
        return self.stream[k * self.chunk : (k + 1) * self.chunk]

    def one_pass(self, t_end: float):
        """The pieces of one pass, as fast as the router asks, up to the end
        of the pass or of the window."""
        self.handed = []
        for k in range(self.per_pass):
            with self._span("bench.generator"):
                if time.perf_counter() >= t_end:
                    return
                c = self.piece(k)
                self.handed.append(k)
                self.events += len(c)
            yield c
