"""Memory-flat chunked streaming execution over the shared route core.

This is the one device executor for one-source routing with online head
detection (PKG, D- and W-Choices; DESIGN.md §2); per-key candidate counts
over 1..S sources are parallel/sharded_router.py's.  It routes *unbounded*
chunk iterators through the shared block-greedy core
(kernels/route_core.route_block) with constant device memory:

* **One fixed-shape jitted chunk step** per static configuration, compiled
  once and reused for every chunk of the stream (the final partial chunk is
  padded and mask-recovered, so a single executable serves all chunks).  The
  step is a ``lax.scan`` of ``route_block`` over ``chunk // block`` vector
  blocks; per-chunk cost is O(chunk) and independent of stream position.
* **A donated carry** — ``donate_argnums`` on the (loads row, Space-Saving
  summary, block counter) tuple — so the carry buffers are updated in place
  and device memory stays flat however many chunks stream through.
* **Double-buffered ingestion**: chunk k+1 is rebuffered and shipped with an
  async ``jax.device_put`` while chunk k's step executes, so host->device
  transfer overlaps routing.

Bit-exactness contract (tests/test_chunked.py): routing a stream through any
chunk size — including chunk sizes that force a padded final chunk — yields
the SAME assignment as the one-shot oracle, because the carry (integer
counts in f32 + the OnlineSS summary arrays) is exactly the scan state the
one-shot oracle threads internally, and pad lanes are masked out of the
histogram, the tracker, and the water-fill (they can never perturb a real
decision).  The one-shot oracles (kernels/ref.py) are:

  pkg        -> ref_pkg_route(chunk=N)   (same block size)
  d_choices  -> estimation.online_head_tables + ref_adaptive_route_online
  w_choices  -> same, with any_worker head tables (ref_w_route_online)

Per-block semantics for the adaptive policies mirror ``online_head_tables``
exactly: emit the head table from the summary *before* the block (stale by
<= block messages), route, cond-decay on period boundaries, then update the
tracker per element — one shared emit (estimation.online_ss_head_table), so
the chunked and one-shot paths cannot drift.  The summary never depends on
routing, so the step runs the whole chunk's tracker pass first, as one
Pallas kernel (kernels/ss_update.ss_update_chunk, the summary held on chip),
and the block scan emits each table from that pass's snapshot of the block.

``ChunkedShardedRouter`` extends the same idea across the sharded router's
load-sync epochs: each chunk is exactly one epoch (n_shards * sync_period *
block keys), routed by the same vmap-of-``_block_scan``-plus-summed-deltas
program as ``ref_sharded_route``, with the global loads row carried across
chunks — chunk boundaries ARE the load-sync boundaries.

Profiler spans (``jax.profiler.TraceAnnotation``, on the device trace's
clock; without an active profiler session each costs only its
constructor).  ``ChunkedRouter.route_stream`` opens ``router.stream`` per
call and, per chunk, ``router.put`` (rebuffer copy + ``device_put``),
``router.dispatch`` (enqueueing the step) and ``router.pull`` (trim, wait,
device->host copy); the last two carry ``chunk=<n>`` from
``ChunkedRouter.n_chunks``.  None of them encloses the caller's iterator or
``on_chunk``.  Inside the step, ``jax.named_scope`` marks ``ss_head_table``
and ``ss_update`` (adaptive policies; the latter wraps the one ``ss_update``
kernel per chunk), ``candidate_fetch`` (every policy: the load fetch, lane
masks and candidate argmin) and ``waterfill`` (W-Choices), the last two in
``route_core.route_block``; the step's program keeps jit's name,
``jit_step``.

Import directly (``from repro.parallel.chunked_driver import ChunkedRouter``);
like parallel.sharding this module is not re-exported from repro.parallel.
"""
from __future__ import annotations

import warnings
from typing import Callable, Iterable, Iterator, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.core.estimation import (
    OnlineSS,
    online_ss_head_table,
    online_ss_init,
)
from repro.core.hashing import derive_seeds
from repro.kernels.route_core import (
    MASK,
    hash_candidates,
    head_table_ncand,
    route_block,
)
from repro.kernels.ss_update import ss_update_chunk

__all__ = [
    "POLICIES",
    "ChunkedRouter",
    "ChunkedShardedRouter",
    "clear_step_cache",
]

POLICIES = ("pkg", "d_choices", "w_choices")


class _StepConfig(NamedTuple):
    """Full static configuration of one compiled chunk step (the cache key)."""

    policy: str
    chunk: int
    block: int
    n_workers: int
    d: int
    d_max: int
    has_cap: bool
    theta: Optional[float]
    slack: float
    min_count: int
    decay_period: int
    ss_capacity: int


# Compiled chunk steps, keyed on the full static config.  _SEEN_SHAPES maps
# the *logical* config (n_workers, policy, capacities-is-None) to the chunk
# shapes already compiled for it, so sweeping chunk sizes warns instead of
# silently retracing (satellite contract; benches that sweep on purpose catch
# the warning).
_STEP_CACHE: dict = {}
_SEEN_SHAPES: dict = {}


def clear_step_cache() -> None:
    """Drop all compiled steps + recompile bookkeeping (tests use this)."""
    _STEP_CACHE.clear()
    _SEEN_SHAPES.clear()


def _warn_new_shape(logical, shape, kind: str) -> None:
    seen = _SEEN_SHAPES.setdefault(logical, set())
    if seen and shape not in seen:
        warnings.warn(
            f"chunked_driver: compiling a new {kind} step for shape {shape} "
            f"(config {logical} already has compiled shapes {sorted(seen)}) "
            "— each swept chunk size traces its own executable; reuse one "
            "chunk size to avoid recompilation",
            stacklevel=3,
        )
    seen.add(shape)


def _build_step(cfg: _StepConfig) -> Callable:
    """One fixed-shape chunk step: scan route_block over the chunk's blocks.

    step(carry, keys (chunk,) i32, valid (chunk,) i32, seeds, icap) ->
    (carry', choices (chunk,)).  carry = (loads (1, n_workers) f32, OnlineSS
    or None, global block counter () i32).  For the adaptive policies the
    chunk's tracker pass runs first (one kernel) and block b's head table
    reads its snapshot b.  Pad lanes (valid == 0) route as tail messages but
    are masked out of the histogram, the tracker update, and (by never
    carrying W_SENTINEL) the water-fill rank sequence — they cannot perturb
    any real decision, which is what makes a padded final chunk bit-exact to
    the unpadded one-shot scan.
    """
    nblk = cfg.chunk // cfg.block
    w_mode = cfg.policy == "w_choices"
    adaptive = cfg.policy != "pkg"
    eid = jnp.arange(cfg.n_workers, dtype=jnp.int32)

    def step(carry, keys_c, valid_c, seeds, icap):
        loads, state, b0 = carry
        kb_all = keys_c.astype(jnp.int32).reshape(nblk, cfg.block)
        vb_all = valid_c.astype(jnp.int32).reshape(nblk, cfg.block)
        if adaptive:
            # The summary's sequence never depends on routing: one kernel
            # runs the chunk's whole pass first and hands the block scan
            # each block's snapshot.
            with jax.named_scope("ss_update"):
                snap_k, snap_c, snap_t, state = ss_update_chunk(
                    state, kb_all, vb_all, b0,
                    decay_period=cfg.decay_period,
                )
            xs = (kb_all, vb_all, snap_k, snap_c, snap_t)
        else:
            xs = (kb_all, vb_all)

        def blk(c, inp):
            loads, b = c
            kb, vb, *snap = inp
            if adaptive:
                # Table emitted from the state BEFORE this block (stale by
                # <= block messages) — online_head_tables' exact emit, which
                # reads no errors.
                sk, sc, st = snap
                with jax.named_scope("ss_head_table"):
                    tk, tn = online_ss_head_table(
                        OnlineSS(sk, sc, None, st), cfg.n_workers,
                        d=cfg.d, d_max=cfg.d_max, theta=cfg.theta,
                        slack=cfg.slack, min_count=cfg.min_count,
                        any_worker=w_mode,
                    )
                nc = head_table_ncand(kb, tk, tn, cfg.d, cfg.d_max)
                nc = jnp.where(vb > 0, nc, jnp.int32(cfg.d))
            else:
                nc = None
            cand = hash_candidates(kb, seeds, cfg.n_workers)
            choice, _, _, _ = route_block(
                cand, nc, loads, n_entities=cfg.n_workers, w_mode=w_mode,
                inv_cap=icap,
            )
            # Masked histogram instead of route_block's own: pad lanes must
            # not count.  Integer 0/1 sums in f32 are exact, so an all-valid
            # block reproduces route_block's update bit-for-bit.
            hist = ((choice[:, None] == eid) & (vb[:, None] > 0))
            loads = loads + hist.astype(jnp.float32).sum(axis=0)[None, :]
            return (loads, b + jnp.int32(1)), choice

        (loads, b), choices = lax.scan(blk, (loads, b0), xs)
        return (loads, state, b), choices.reshape(-1)

    return step


def _get_step(cfg: _StepConfig) -> Callable:
    if cfg not in _STEP_CACHE:
        _warn_new_shape(
            (cfg.n_workers, cfg.policy, cfg.has_cap), cfg.chunk, "chunk"
        )
        _STEP_CACHE[cfg] = jax.jit(_build_step(cfg), donate_argnums=(0,))
    return _STEP_CACHE[cfg]


class ChunkedRouter:
    """Route an unbounded key stream in fixed-shape chunks, flat memory.

    Policies: "pkg" (fixed d candidates), "d_choices" (adaptive d(k) from a
    carried Space-Saving summary), "w_choices" (head keys to the global
    water-fill argmin).  The carry — loads row, summary, block counter —
    persists across route_stream calls, so a stream may be fed in any number
    of pieces; assignments are bit-exact to the one-shot scan for EVERY
    chunk size as long as padding only happens at the true end of the stream
    (route_stream rebuffers arbitrary incoming pieces into exact chunk-sized
    steps, so only its final flush pads; feed whole streams, or split at
    multiples of `block` to keep block boundaries aligned across runs).

    `capacities` ((n_workers,) strictly positive) switches every argmin to
    capacity-normalized loads, exactly as the one-shot oracles do.
    """

    def __init__(
        self,
        n_workers: int,
        policy: str = "pkg",
        *,
        d: int = 2,
        d_max: int = 8,
        chunk: int = 8192,
        block: int = 128,
        seed: int = 0,
        capacities=None,
        ss_capacity: int = 256,
        theta: Optional[float] = None,
        slack: float = 2.0,
        min_count: int = 8,
        decay_period: int = 0,
    ):
        if policy not in POLICIES:
            raise ValueError(f"policy {policy!r} not in {POLICIES}")
        if chunk % block:
            raise ValueError(f"chunk={chunk} must divide by block={block}")
        if policy == "d_choices":
            d_max = max(int(min(d_max, n_workers)), d)
        else:
            d_max = d  # pkg / w_choices hash exactly d candidate lanes
        self.n_workers = int(n_workers)
        self.policy = policy
        self.chunk = int(chunk)
        self.block = int(block)
        self.d = int(d)
        self.d_max = int(d_max)
        self._cfg = _StepConfig(
            policy=policy, chunk=self.chunk, block=self.block,
            n_workers=self.n_workers, d=self.d, d_max=self.d_max,
            has_cap=capacities is not None,
            theta=None if theta is None else float(theta),
            slack=float(slack), min_count=int(min_count),
            decay_period=int(decay_period), ss_capacity=int(ss_capacity),
        )
        self._step = _get_step(self._cfg)
        self._seeds = derive_seeds(seed, self.d_max)
        if capacities is None:
            self._icap = None
        else:
            cap = np.asarray(capacities, np.float32).reshape(-1)
            if cap.shape != (self.n_workers,) or not (cap > 0).all():
                raise ValueError(
                    f"capacities must be ({self.n_workers},) strictly positive"
                )
            self._icap = jnp.asarray(1.0 / cap).reshape(1, self.n_workers)
        state = online_ss_init(ss_capacity) if policy != "pkg" else None
        self._carry = (
            jnp.zeros((1, self.n_workers), jnp.float32),
            state,
            jnp.int32(0),
        )
        self._valid_full = jax.device_put(np.ones(self.chunk, np.int32))
        self._killed: dict[int, float] = {}
        self.n_routed = 0
        self.n_chunks = 0  # chunk steps dispatched; the spans' `chunk` id

    # -- observability ------------------------------------------------------

    @property
    def loads(self) -> np.ndarray:
        """Current worker loads (n_workers,) f32 (killed workers read MASK)."""
        return np.asarray(self._carry[0]).reshape(-1)

    @property
    def tracker(self) -> Optional[OnlineSS]:
        """The carried Space-Saving summary (None for policy='pkg')."""
        return self._carry[1]

    def state_bytes(self) -> int:
        """Bytes of carried routing state — THE flat-memory number: constant
        in both stream length and distinct-key count (bytes/key -> 0)."""
        return sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self._carry)
        )

    # -- failure handling ---------------------------------------------------

    def kill(self, worker: int) -> None:
        """Mask a worker mid-stream: its loads lane becomes the f32 MASK
        sentinel, so no candidate/water-fill argmin can pick it (unless every
        candidate is dead).  Takes effect at the next chunk step — kill
        between route_stream calls for a deterministic boundary."""
        if worker in self._killed:
            return
        loads = np.asarray(self._carry[0]).copy()
        self._killed[int(worker)] = float(loads[0, worker])
        loads[0, worker] = MASK
        self._set_loads(loads)

    def revive(self, worker: int) -> None:
        """Restore a killed worker at its pre-kill load (stored host-side —
        f32 cannot recover it from MASK + count)."""
        loads = np.asarray(self._carry[0]).copy()
        loads[0, worker] = self._killed.pop(int(worker))
        self._set_loads(loads)

    def _set_loads(self, loads: np.ndarray) -> None:
        _, state, b = self._carry
        self._carry = (jnp.asarray(loads, jnp.float32), state, b)

    # -- routing ------------------------------------------------------------

    def _device_pieces(
        self, chunks: Iterable[np.ndarray]
    ) -> Iterator[tuple[jnp.ndarray, jnp.ndarray, int]]:
        """Rebuffer arbitrary-size chunks into exact `chunk`-size pieces and
        device_put them (async — overlaps the in-flight step's compute).
        Only the final piece may be partial; it ships zero-padded with a
        valid mask.  Each copy (and the device_put it completes) is one
        `router.put` span, closed before the next piece is pulled from
        `chunks` or handed to the caller."""
        buf = np.empty(self.chunk, np.int32)
        fill = 0
        for arr in chunks:
            arr = np.asarray(arr, np.int32).reshape(-1)
            off = 0
            while off < len(arr):
                piece = None
                with TraceAnnotation("router.put"):
                    n = min(len(arr) - off, self.chunk - fill)
                    buf[fill : fill + n] = arr[off : off + n]
                    fill += n
                    off += n
                    if fill == self.chunk:
                        piece = jax.device_put(buf.copy()), self._valid_full, fill
                        fill = 0
                if piece is not None:
                    yield piece
        if fill:
            with TraceAnnotation("router.put"):
                keys = np.zeros(self.chunk, np.int32)
                keys[:fill] = buf[:fill]
                valid = np.zeros(self.chunk, np.int32)
                valid[:fill] = 1
                piece = jax.device_put(keys), jax.device_put(valid), fill
            yield piece

    def route_stream(
        self,
        chunks: Union[np.ndarray, Iterable[np.ndarray]],
        on_chunk: Optional[Callable[[np.ndarray], None]] = None,
    ) -> Union[np.ndarray, int]:
        """Route a stream given as one array or an iterator of arrays.

        Double-buffered: while chunk k's step runs on device, chunk k+1 is
        rebuffered and device_put, and chunk k-1's assignments are pulled to
        host.  Returns the concatenated assignment array — or, with
        `on_chunk` (called with each piece's (n_valid,) int32 assignments in
        order), just the number of events routed, so a 1e8-event run never
        holds more than one chunk of output (flat RSS).
        """
        if isinstance(chunks, np.ndarray) or not hasattr(chunks, "__iter__"):
            chunks = [np.asarray(chunks)]
        outs: Optional[list] = [] if on_chunk is None else None
        pending = None
        n = 0
        with TraceAnnotation("router.stream"):
            it = self._device_pieces(chunks)
            cur = next(it, None)
            while cur is not None:
                keys_d, valid_d, n_valid = cur
                with TraceAnnotation("router.dispatch", chunk=self.n_chunks):
                    self._carry, choices = self._step(
                        self._carry, keys_d, valid_d, self._seeds, self._icap
                    )
                cur = next(it, None)  # prefetch overlaps the async step above
                if pending is not None:
                    self._pull(pending, outs, on_chunk)
                pending = (choices, n_valid, self.n_chunks)
                self.n_chunks += 1
                n += n_valid
            if pending is not None:
                self._pull(pending, outs, on_chunk)
        self.n_routed += n
        if outs is not None:
            return (
                np.concatenate(outs) if outs else np.empty(0, np.int32)
            )
        return n

    def _pull(self, pending, outs, on_chunk) -> None:
        """Hand a dispatched chunk's assignments on: `_emit`'s trim, wait
        and device->host copy inside the chunk's `router.pull` span, and
        `on_chunk` after the span has closed."""
        choices, n_valid, chunk = pending
        host: list = []
        with TraceAnnotation("router.pull", chunk=chunk):
            self._emit(
                (choices, n_valid), outs,
                None if on_chunk is None else host.append,
            )
        for a in host:
            on_chunk(a)

    @staticmethod
    def _emit(pending, outs, on_chunk) -> None:
        choices, n_valid = pending
        # scatter-index recovery is a trim: pads are always the tail lanes
        a = np.asarray(choices[:n_valid], dtype=np.int32)
        if on_chunk is not None:
            on_chunk(a)
        else:
            outs.append(a)


# ---------------------------------------------------------------------------
# Chunked sharded routing: chunk == load-sync epoch.
# ---------------------------------------------------------------------------


class _ShardedStepConfig(NamedTuple):
    n_workers: int
    n_shards: int
    sync_period: int
    block: int
    d_max: int
    w_mode: bool
    has_nc: bool
    has_cap: bool


def _build_sharded_step(cfg: _ShardedStepConfig) -> Callable:
    """One load-sync epoch from a carried global loads row: vmap the shared
    per-shard _block_scan and sum the deltas — the exact epoch body of
    sharded_router.ref_sharded_route, with the scan-over-epochs replaced by
    the host loop feeding chunks."""
    from repro.parallel.sharded_router import _block_scan

    S, P, B = cfg.n_shards, cfg.sync_period, cfg.block

    def step(loads_g, keys, nc, seeds, icap):
        cand = hash_candidates(
            keys.astype(jnp.int32).reshape(-1), seeds, cfg.n_workers
        ).reshape(S, P, B, cfg.d_max)
        ncr = None if not cfg.has_nc else nc.astype(jnp.int32).reshape(S, P, B)

        def per_shard(c_s, n_s=None):
            return _block_scan(
                loads_g, c_s, n_s, n_workers=cfg.n_workers,
                w_mode=cfg.w_mode, inv_cap=icap,
            )

        if ncr is None:
            loads_end, choices = jax.vmap(per_shard)(cand)
        else:
            loads_end, choices = jax.vmap(per_shard)(cand, ncr)
        delta = (loads_end - loads_g).sum(axis=0)  # integer counts: exact
        return loads_g + delta, choices.reshape(-1)

    return step


def _get_sharded_step(cfg: _ShardedStepConfig) -> Callable:
    if cfg not in _STEP_CACHE:
        _warn_new_shape(
            (cfg.n_workers, "sharded", cfg.has_cap),
            (cfg.n_shards, cfg.sync_period, cfg.block),
            "sharded epoch",
        )
        _STEP_CACHE[cfg] = jax.jit(
            _build_sharded_step(cfg), donate_argnums=(0,)
        )
    return _STEP_CACHE[cfg]


class ChunkedShardedRouter:
    """Chunked streaming over the sharded router: every chunk is exactly one
    load-sync epoch (n_shards * sync_period * block keys, laid out
    [shard][block-in-epoch][lane]), so chunk boundaries align with the epoch
    psum by construction and the carried loads row IS the globally-synced
    histogram.  k chunks through this router are bit-exact to
    ref_sharded_route over the same stream in its shard-major layout
    (differential in tests/test_chunked.py).

    n_cand follows sharded_route's contract: None for plain PKG, per-key
    counts (W_SENTINEL heads under w_mode=True) otherwise.
    """

    def __init__(
        self,
        n_workers: int,
        *,
        d_max: int = 2,
        n_shards: int = 1,
        sync_period: int = 1,
        block: int = 128,
        seed: int = 0,
        w_mode: bool = False,
        has_n_cand: bool = False,
        capacities=None,
    ):
        self.n_workers = int(n_workers)
        self.epoch_chunk = int(n_shards) * int(sync_period) * int(block)
        self._cfg = _ShardedStepConfig(
            n_workers=self.n_workers, n_shards=int(n_shards),
            sync_period=int(sync_period), block=int(block),
            d_max=int(d_max), w_mode=bool(w_mode),
            has_nc=bool(has_n_cand or w_mode),
            has_cap=capacities is not None,
        )
        self._step = _get_sharded_step(self._cfg)
        self._seeds = derive_seeds(seed, int(d_max))
        if capacities is None:
            self._icap = None
        else:
            cap = np.asarray(capacities, np.float32).reshape(-1)
            self._icap = jnp.asarray(1.0 / cap).reshape(1, self.n_workers)
        self._loads = jnp.zeros((1, self.n_workers), jnp.float32)
        self.n_routed = 0

    @property
    def loads(self) -> np.ndarray:
        return np.asarray(self._loads).reshape(-1)

    def route_chunk(self, keys, n_cand=None) -> np.ndarray:
        """Route exactly one epoch of keys (len == epoch_chunk).  For a final
        partial epoch, pad with repeated tail keys first (the
        _sharded_dispatch contract: pads route and count, bounded by one
        epoch of staleness) and trim the returned assignments."""
        keys = np.asarray(keys, np.int32).reshape(-1)
        if keys.shape[0] != self.epoch_chunk:
            raise ValueError(
                f"chunk length {keys.shape[0]} != epoch_chunk "
                f"{self.epoch_chunk} (chunks must align with load-sync epochs)"
            )
        if self._cfg.has_nc:
            if n_cand is None:
                raise ValueError("this router was built with has_n_cand/w_mode")
            nc = jnp.asarray(np.asarray(n_cand, np.int32).reshape(-1))
        else:
            nc = None
        self._loads, choices = self._step(
            self._loads, jnp.asarray(keys), nc, self._seeds, self._icap
        )
        self.n_routed += self.epoch_chunk
        return np.asarray(choices, dtype=np.int32)
