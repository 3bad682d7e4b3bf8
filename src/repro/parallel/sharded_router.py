"""Multi-device sharded stream router with load-sync epochs (DESIGN.md §6.1).

The paper's two enabling techniques — key splitting and *local* load
estimation — are exactly the contract a device mesh needs: each source can
route with only its own view of the worker loads, so the key stream shards
over a 1-D ``("data",)`` mesh with ``shard_map`` and every shard runs the
SAME block-greedy core as the single-core Pallas routers
(kernels/route_core.route_block — one implementation, zero drift) against
its own local copy of the ``(1, n_workers)`` loads row.

Staleness contract, lifted across chips: the single-core router's loads are
stale by < ``block`` messages (DESIGN.md §2); here each shard's view of the
OTHER shards' loads is additionally stale by < one *load-sync epoch* =
``sync_period`` blocks.  Every ``sync_period`` blocks the per-shard load
deltas are ``psum``-ed over the mesh, so every shard re-synchronizes on the
global histogram — the paper's local-estimation trick with periodic
reconciliation.  ``n_shards=1, sync_period=1`` replays the single-core
kernel bit-exactly (the differential contract in
tests/test_sharded_router.py); larger ``sync_period`` trades collective
bytes for imbalance, a curve bench_sharded_router.py measures.

Two formulations, bit-identical by construction (integer counts in f32 are
exact under any reduction order):

* ``sharded_route`` — the shard_map program: per-shard scan over epochs,
  inner scan over blocks, ``lax.psum`` of the epoch's load delta.
* ``ref_sharded_route`` — the single-device oracle: the same epoch/block
  scans with the shard axis ``vmap``-ed and the psum replaced by a plain
  sum over shards.  Tests and single-device benches run this.

``routed_step_roofline`` lowers the compiled routed step and feeds
roofline/analysis.py: HLO flops / HBM bytes vs the memory-bandwidth bound,
plus per-epoch collective bytes (the psum traffic is ``n_workers`` f32 per
shard per epoch — tiny by design, which is why load-sync epochs scale).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.estimation import W_SENTINEL
from repro.core.hashing import derive_seeds
from repro.kernels.route_core import hash_candidates, route_block

__all__ = [
    "SHARD_AXIS",
    "shard_grid",
    "sharded_route",
    "ref_sharded_route",
    "sharded_pkg_route",
    "sharded_w_route",
    "routed_step_roofline",
]

SHARD_AXIS = "data"  # the 1-D stream mesh axis (launch.mesh.make_stream_mesh)


def shard_grid(m: int, n_shards: int, sync_period: int, block: int) -> int:
    """Smallest per-shard length that fits m messages over n_shards on the
    (sync_period x block) epoch grid: every shard routes the same number of
    epochs, so the stream pads to n_shards * shard_grid(...) messages."""
    m_local = -(-m // n_shards)
    epoch = sync_period * block
    return max(-(-m_local // epoch), 1) * epoch


def _cap_row(icap, weights, n_workers: int):
    """The reciprocal-capacity row the block scan runs with.  Weighted
    load-sync deltas leave fractional loads, and route_block's loop-free
    water-fill (route_core.waterfill_levels) holds for integer loads only,
    so shard weights without capacities route against a uniform ones row:
    route_block then takes the sequential water-fill, and ``x * 1.0 == x``
    keeps every compare bit-identical to the row without capacities."""
    if icap is None and weights is not None:
        return jnp.ones((1, n_workers), jnp.float32)
    return icap


def _block_scan(loads0, cand_e, nc_e, *, n_workers: int, w_mode: bool,
                inv_cap=None):
    """One epoch on one shard: scan route_block over sync_period blocks from
    the epoch-start (globally synced) loads row.  Returns (epoch-end local
    loads (1, n_workers), choices (sync_period, block)).  inv_cap
    (1, n_workers) f32 makes every block's argmin capacity-normalized."""

    def blk(loads, inp):
        cand_b, nc_b = inp if nc_e is not None else (inp, None)
        choice, _, _, loads = route_block(
            cand_b, nc_b, loads, n_entities=n_workers, w_mode=w_mode,
            inv_cap=inv_cap,
        )
        return loads, choice

    xs = cand_e if nc_e is None else (cand_e, nc_e)
    return lax.scan(blk, loads0, xs)


@functools.lru_cache(maxsize=None)
def _build_sharded(n_workers, d_max, n_shards, n_epochs, sync_period, block,
                   w_mode, has_nc, has_cap, has_w, mesh):
    """Jitted shard_map program for one static configuration."""

    def shard_fn(keys_l, nc_l, seeds, icap, w_s):
        # keys_l (m_local,) — this shard's contiguous sub-stream; icap
        # (1, n_workers) replicated reciprocal capacities or None; w_s (1,)
        # this shard's load-sync delta weight or None.
        icap = _cap_row(icap, w_s, n_workers)
        cand = hash_candidates(keys_l, seeds, n_workers)
        cand = cand.reshape(n_epochs, sync_period, block, d_max)
        nc = None if nc_l is None else nc_l.reshape(n_epochs, sync_period, block)

        def epoch(loads_g, inp):
            cand_e, nc_e = inp if nc is not None else (inp, None)
            loads_end, choices = _block_scan(
                loads_g, cand_e, nc_e, n_workers=n_workers, w_mode=w_mode,
                inv_cap=icap,
            )
            # load-sync: every shard contributes its epoch delta; the synced
            # row is the exact global histogram at the epoch boundary.  With
            # shard weights each delta is scaled BEFORE the psum (the
            # PR-8-follow-up capacity weighting); w == 1 is bit-exact to the
            # unweighted sync.
            delta = loads_end - loads_g
            if w_s is not None:
                delta = w_s.reshape(1, 1) * delta
            delta = lax.psum(delta, SHARD_AXIS)
            return loads_g + delta, choices

        loads0 = jnp.zeros((1, n_workers), jnp.float32)
        xs = cand if nc is None else (cand, nc)
        loads_f, assign = lax.scan(epoch, loads0, xs)
        return assign.reshape(-1), loads_f.reshape(n_workers)

    def fn(*a):
        it = iter(a)
        keys_l = next(it)
        nc_l = next(it) if has_nc else None
        seeds = next(it)
        icap = next(it) if has_cap else None
        w_s = next(it) if has_w else None
        return shard_fn(keys_l, nc_l, seeds, icap, w_s)

    # specs live in parallel.sharding next to the model-sharding plans
    # (lazy import: sharding pulls in the model registry)
    from repro.parallel.sharding import stream_shard_specs

    in_specs, out_specs = stream_shard_specs(
        has_ncand=has_nc, has_cap=has_cap, has_weights=has_w
    )
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=None)
def _build_ref(n_workers, d_max, n_shards, n_epochs, sync_period, block,
               w_mode, has_nc, has_cap, has_w):
    """Jitted single-device oracle: vmap over the shard axis, psum -> sum."""

    def ref_fn(keys, nc_all, seeds, icap, w):
        icap = _cap_row(icap, w, n_workers)
        cand = hash_candidates(keys, seeds, n_workers)
        cand = cand.reshape(n_shards, n_epochs, sync_period, block, d_max)
        cand = cand.swapaxes(0, 1)  # epoch-major for the outer scan
        nc = (
            None if nc_all is None
            else nc_all.reshape(n_shards, n_epochs, sync_period, block).swapaxes(0, 1)
        )

        def epoch(loads_g, inp):
            cand_e, nc_e = inp if nc is not None else (inp, None)

            def per_shard(c_s, n_s=None):
                return _block_scan(
                    loads_g, c_s, n_s, n_workers=n_workers, w_mode=w_mode,
                    inv_cap=icap,
                )

            if nc_e is None:
                loads_end, choices = jax.vmap(per_shard)(cand_e)
            else:
                loads_end, choices = jax.vmap(per_shard)(cand_e, nc_e)
            deltas = loads_end - loads_g  # (n_shards, 1, n_workers)
            if w is not None:
                deltas = w[:, None, None] * deltas
            return loads_g + deltas.sum(axis=0), choices

        loads0 = jnp.zeros((1, n_workers), jnp.float32)
        xs = cand if nc is None else (cand, nc)
        loads_f, assign = lax.scan(epoch, loads0, xs)
        # (n_epochs, n_shards, sync, block) -> shard-major stream order
        return assign.swapaxes(0, 1).reshape(-1), loads_f.reshape(n_workers)

    def fn(*a):
        it = iter(a)
        keys = next(it)
        nc_all = next(it) if has_nc else None
        seeds = next(it)
        icap = next(it) if has_cap else None
        w = next(it) if has_w else None
        return ref_fn(keys, nc_all, seeds, icap, w)

    return jax.jit(fn)


def _check_shapes(N: int, n_shards: int, sync_period: int, block: int) -> int:
    epoch = sync_period * block
    if n_shards < 1 or sync_period < 1:
        raise ValueError(f"n_shards/sync_period must be >= 1, got "
                         f"{n_shards}/{sync_period}")
    if N % (n_shards * epoch):
        raise ValueError(
            f"N={N} must divide by n_shards*sync_period*block = "
            f"{n_shards}*{sync_period}*{block} (pad with shard_grid)"
        )
    return N // (n_shards * epoch)  # n_epochs


def sharded_route(
    keys: jnp.ndarray,
    n_cand: Optional[jnp.ndarray],
    n_workers: int,
    *,
    d_max: int = 2,
    seed: int = 0,
    n_shards: int = 1,
    sync_period: int = 1,
    block: int = 128,
    w_mode: bool = False,
    mesh=None,
    capacities: Optional[jnp.ndarray] = None,
    shard_weights: Optional[jnp.ndarray] = None,
):
    """Route keys (N,) over an n_shards-device ("data",) mesh.

    Shard s routes the contiguous sub-stream keys[s*N/n_shards:(s+1)*...]
    with its own local loads row; every ``sync_period`` blocks the per-shard
    deltas are psum-ed (the load-sync epoch).  ``n_cand`` is the per-message
    candidate count (None: all d_max lanes live, plain PKG; W_SENTINEL
    entries take the global-argmin W path under ``w_mode=True`` — same
    contract as kernels.adaptive_route).  Returns (assign (N,) int32,
    final synced global loads (n_workers,) f32).

    ``capacities`` ((n_workers,) strictly positive) makes every shard's
    argmin capacity-normalized — each shard receives the same replicated
    reciprocal-capacity row the single-core kernels consume.
    ``shard_weights`` ((n_shards,) non-negative f32) scales each shard's
    load-sync delta before the psum, weighting the synced histogram by
    per-shard capacity; None or all-ones is bit-exact to the unweighted
    sync (integer counts in f32).

    ``n_shards=1, sync_period=1`` is bit-exact to the single-core Pallas
    routers (pkg_route / adaptive_route / w_route) over one chunk — they all
    call the same route_core.route_block.
    """
    N = keys.shape[0]
    n_epochs = _check_shapes(N, n_shards, sync_period, block)
    if mesh is None:
        from repro.launch.mesh import make_stream_mesh

        mesh = make_stream_mesh(n_shards)
    fn = _build_sharded(
        n_workers, d_max, n_shards, n_epochs, sync_period, block,
        bool(w_mode), n_cand is not None, capacities is not None,
        shard_weights is not None, mesh,
    )
    seeds = derive_seeds(seed, d_max)
    args = [keys.astype(jnp.int32)]
    if n_cand is not None:
        args.append(n_cand.astype(jnp.int32))
    args.append(seeds)
    if capacities is not None:
        args.append(
            1.0 / jnp.asarray(capacities, jnp.float32).reshape(1, n_workers)
        )
    if shard_weights is not None:
        args.append(jnp.asarray(shard_weights, jnp.float32).reshape(n_shards))
    return fn(*args)


def ref_sharded_route(
    keys: jnp.ndarray,
    n_cand: Optional[jnp.ndarray],
    n_workers: int,
    *,
    d_max: int = 2,
    seed: int = 0,
    n_shards: int = 1,
    sync_period: int = 1,
    block: int = 128,
    w_mode: bool = False,
    capacities: Optional[jnp.ndarray] = None,
    shard_weights: Optional[jnp.ndarray] = None,
):
    """Single-device oracle of sharded_route: identical epoch/block scans,
    shard axis vmap-ed, psum replaced by a sum over shards.  Bit-exact to
    the shard_map program (loads are integer counts in f32, so the reduction
    order cannot matter; weighted deltas sum in the same shard-major order
    the psum's ring reduction uses on a 1-D mesh), and the path
    single-device benches/tests run."""
    N = keys.shape[0]
    n_epochs = _check_shapes(N, n_shards, sync_period, block)
    fn = _build_ref(
        n_workers, d_max, n_shards, n_epochs, sync_period, block,
        bool(w_mode), n_cand is not None, capacities is not None,
        shard_weights is not None,
    )
    seeds = derive_seeds(seed, d_max)
    args = [keys.astype(jnp.int32)]
    if n_cand is not None:
        args.append(n_cand.astype(jnp.int32))
    args.append(seeds)
    if capacities is not None:
        args.append(
            1.0 / jnp.asarray(capacities, jnp.float32).reshape(1, n_workers)
        )
    if shard_weights is not None:
        args.append(jnp.asarray(shard_weights, jnp.float32).reshape(n_shards))
    return fn(*args)


def sharded_pkg_route(keys, n_workers: int, d: int = 2, **kw):
    """Plain PKG (fixed d candidates) on the sharded router."""
    return sharded_route(keys, None, n_workers, d_max=d, **kw)


def sharded_w_route(keys, is_head, n_workers: int, d: int = 2, **kw):
    """W-Choices on the sharded router: head keys (is_head != 0) go to the
    shard-locally least-loaded worker via the water-fill global argmin; tail
    keys take PKG's exact d-candidate step.  Same flag convention as
    kernels.adaptive_route.w_route."""
    flags = jnp.asarray(is_head).astype(jnp.int32)
    n_cand = jnp.where(flags != 0, jnp.int32(W_SENTINEL), jnp.int32(d))
    return sharded_route(keys, n_cand, n_workers, d_max=d, w_mode=True, **kw)


def routed_step_roofline(
    n_workers: int,
    *,
    n_shards: int = 1,
    sync_period: int = 1,
    n_epochs: int = 4,
    block: int = 128,
    d_max: int = 2,
    w_mode: bool = False,
    seed: int = 0,
    mesh=None,
    hw=None,
):
    """Compile the routed step and report its roofline terms + collective
    bytes (roofline/analysis.py): how far the compiled program sits from the
    memory-bandwidth bound, and what one load-sync epoch costs on the wire.

    Returns a dict with flops / hbm bytes / collective bytes per device,
    per-epoch collective bytes (the psum traffic), and the three-term
    roofline report.  Collective bytes are parsed from the post-SPMD HLO,
    so on a 1-shard mesh they are exactly zero — the sync is free when
    there is nobody to sync with.  ``hw`` (a roofline.analysis.HW) defaults
    to the peaks of the mesh's device kind; a kind without published peaks
    raises, so a CPU compile must name the chip it models.
    """
    from repro.roofline.analysis import collective_bytes, hw_for, roofline_report

    if mesh is None:
        from repro.launch.mesh import make_stream_mesh

        mesh = make_stream_mesh(n_shards)
    hw = hw or hw_for(mesh.devices.flat[0].device_kind)
    N = n_shards * n_epochs * sync_period * block
    fn = _build_sharded(
        n_workers, d_max, n_shards, n_epochs, sync_period, block,
        bool(w_mode), True, False, False, mesh,
    )
    args = (
        jax.ShapeDtypeStruct((N,), jnp.int32),
        jax.ShapeDtypeStruct((N,), jnp.int32),
        jax.ShapeDtypeStruct((d_max,), jnp.uint32),
    )
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax returns [dict]
        ca = ca[0] if ca else {}
    ca = ca or {}
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    # loads row is genuinely f32 (no bf16 wire correction applies).  The
    # load-sync all-reduce lives in the epoch loop's body computation, so the
    # static HLO parse counts it ONCE — that is the per-epoch wire cost; the
    # program executes it n_epochs times.
    coll = collective_bytes(hlo, bf16_wire=False)
    per_epoch = float(coll["total"])
    report = roofline_report(flops, hbm, per_epoch * n_epochs, hw=hw)
    return {
        "n_msgs": N,
        "n_shards": n_shards,
        "sync_period": sync_period,
        "n_epochs": n_epochs,
        "flops_per_device": flops,
        "hbm_bytes_per_device": hbm,
        "collective_bytes_per_epoch": per_epoch,
        "collective_bytes_per_device": per_epoch * n_epochs,
        "collective_counts": coll["counts"],
        "roofline": report,
    }
