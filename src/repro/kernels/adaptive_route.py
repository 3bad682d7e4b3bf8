"""Pallas TPU kernel: adaptive multi-choice stream router (D-/W-Choices).

Same batch-greedy skeleton as pkg_route.py (one program per chunk, VMEM load
vector, vector blocks of V lanes) but the number of candidates is
*data-dependent per key*: the router consumes a second int32 array
n_cand (N,) with values in [1, d_max] (produced by the SPACESAVING head
tracker, DESIGN.md SS3.3).  All d_max hashes are always computed and padded
into the one-hot matmul — the TPU-native formulation of DESIGN.md SS2/SS7 is
preserved — and candidates j >= n_cand[i] are masked to +MASK before the
lane-wise argmin, so tail keys (n_cand == 2) reproduce plain PKG bit-exactly.

W-CHOICES ("head goes anywhere", arXiv 1510.05714) is in-kernel too: with
the static opt-in w_mode=True (set by the W-named wrappers below), a key
whose n_cand equals estimation.W_SENTINEL skips the hashed-candidate argmin
and routes by a *global* masked argmin over the full (1, n_workers) loads row
(pad lanes hold the MASK sentinel, ties break to the lowest worker index), so
n_workers need not be a power of two nor fit one VPU lane group.  The r-th
head lane of a block takes the r-th argmin of the sequential water-fill of
that row (route_core.waterfill_assign: one masked argmin per head lane) — so
head messages reproduce w_choices_partition's global step exactly from
block-start loads instead of piling a whole block onto a single stale
minimum.

The per-block machinery (hash, one-hot load fetch, mask, argmin, water-fill,
histogram update) all lives in kernels/route_core.py — ONE routing core
shared with pkg_route.py, moe_pkg_dispatch.py, and every ref.py oracle —
this module only wires chunk/block iteration and the head-table plumbing
around route_core.route_block:

  hash   : SplitMix32 over (key ^ seed_j), j < d_max      (VPU int ops)
  lookup : one-hot(cand) @ loads                          (MXU matmul)
  mask   : lane j participates iff j < n_cand             (VPU select)
  choose : lane-wise argmin over d_max masked candidates,
           or water-fill global argmin over all n_workers
           lanes when n_cand == W_SENTINEL                (lane reduction)
  update : loads += ones @ one-hot(choice)                (MXU matmul)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.estimation import W_SENTINEL
from repro.core.hashing import derive_seeds
from repro.kernels.platform import resolve_interpret
from repro.kernels.route_core import (
    chunk_loads_spec,
    chunk_rows_spec,
    hash_candidates,
    head_table_ncand,
    route_block,
)


def _kernel(keys_ref, ncand_ref, seeds_ref, *rest, n_workers, d_max, block,
            w_mode, has_cap):
    if has_cap:
        icap_ref, assign_ref, loads_ref = rest
        icap = icap_ref[...]  # (1, n_workers) f32 reciprocal capacities
    else:
        assign_ref, loads_ref = rest
        icap = None
    nblk = keys_ref.shape[0]
    seeds = seeds_ref[...]  # (d_max,) uint32

    def body(i, loads):  # loads (1, n_workers) f32
        kb = keys_ref[pl.ds(i, 1), :].reshape(block)  # (V,)
        nc = ncand_ref[pl.ds(i, 1), :].reshape(block)  # (V,)
        cand = hash_candidates(kb, seeds, n_workers)  # (V, d_max)
        choice, _, _, loads = route_block(
            cand, nc, loads, n_entities=n_workers, w_mode=w_mode,
            inv_cap=icap,
        )
        assign_ref[pl.ds(i, 1), :] = choice.reshape(1, block)
        return loads

    loads = lax.fori_loop(0, nblk, body, jnp.zeros((1, n_workers), jnp.float32))
    loads_ref[...] = loads


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_workers", "d_max", "seed", "chunk", "block", "interpret", "w_mode"
    ),
)
def adaptive_route(
    keys: jnp.ndarray,
    n_cand: jnp.ndarray,
    n_workers: int,
    d_max: int = 4,
    seed: int = 0,
    chunk: int = 1024,
    block: int = 128,
    interpret: Optional[bool] = None,
    w_mode: bool = False,
    capacities: Optional[jnp.ndarray] = None,
):
    """Route keys (N,) int32 with per-key candidate counts n_cand (N,).

    n_cand values are in [1, d_max]; with w_mode=True a value of W_SENTINEL
    routes that key to the globally least-loaded worker (W-Choices; see
    w_route for the flag-based wrapper, which sets w_mode itself).  Returns
    (assign (N,), per-chunk loads (N/chunk, n_workers)).  N must divide by
    chunk; chunk by block.  interpret=None resolves via kernels.platform
    (compile on TPU, interpret elsewhere).  The default w_mode=False keeps
    the sentinel check and the water-fill reduction out of the inner loop —
    D-Choices callers never emit the sentinel and pay nothing; sentinel-free
    streams route bit-identically under both settings.

    `capacities` (optional (n_workers,) strictly positive weights) routes on
    capacity-normalized loads (route_core inv_cap row, arXiv 1705.09073):
    both the masked candidate argmin AND the W water-fill compare
    loads * (1/c).  None leaves the program unchanged; uniform capacities
    are bit-exact to it.
    """
    N = keys.shape[0]
    assert N % chunk == 0 and chunk % block == 0, (N, chunk, block)
    grid = (N // chunk,)
    has_cap = capacities is not None
    kern = functools.partial(
        _kernel, n_workers=n_workers, d_max=d_max, block=block, w_mode=w_mode,
        has_cap=has_cap,
    )
    in_specs = [
        chunk_rows_spec(chunk, block),
        chunk_rows_spec(chunk, block),
        pl.BlockSpec((d_max,), lambda i: (0,)),
    ]
    operands = [
        keys.astype(jnp.int32).reshape(N // block, block),
        n_cand.astype(jnp.int32).reshape(N // block, block),
        derive_seeds(seed, d_max),
    ]
    if has_cap:
        icap = 1.0 / jnp.asarray(capacities, jnp.float32).reshape(1, n_workers)
        in_specs.append(pl.BlockSpec((1, n_workers), lambda i: (0, 0)))
        operands.append(icap)
    assign, loads = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            chunk_rows_spec(chunk, block),
            chunk_loads_spec(n_workers),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N // block, block), jnp.int32),
            jax.ShapeDtypeStruct((N // chunk, 1, n_workers), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(*operands)
    return assign.reshape(N), loads.reshape(N // chunk, n_workers)


# ---------------------------------------------------------------------------
# Online variant: head table refreshed between vector blocks (DESIGN.md SS3.3
# "Online estimation").  The tracker itself runs upstream
# (core.estimation.online_head_tables, one lax.scan over blocks); the kernel
# consumes its per-block snapshots as a device-resident operand — table b is
# the summary state *before* block b, so head verdicts are stale by at most
# `block` messages, the same contract as the stale loads of
# pkg_partition_batched.  In-kernel the lookup is a (V, H) equality compare +
# masked max (VPU only, no gather): a miss or a tail hit both yield d_base
# candidates, i.e. exact PKG routing.
# ---------------------------------------------------------------------------


def _kernel_online(keys_ref, tblk_ref, tbln_ref, seeds_ref, *rest, n_workers,
                   d_base, d_max, block, w_mode, has_cap):
    if has_cap:
        icap_ref, assign_ref, loads_ref = rest
        icap = icap_ref[...]  # (1, n_workers) f32 reciprocal capacities
    else:
        assign_ref, loads_ref = rest
        icap = None
    nblk = keys_ref.shape[0]
    seeds = seeds_ref[...]  # (d_max,) uint32
    H = tblk_ref.shape[1]

    def body(i, loads):  # loads (1, n_workers) f32
        kb = keys_ref[pl.ds(i, 1), :].reshape(block)  # (V,) int32
        tk = tblk_ref[pl.ds(i, 1), :].reshape(H)  # (H,) int32 head-table keys
        tn = tbln_ref[pl.ds(i, 1), :].reshape(H)  # (H,) int32 head-table d(k)
        nc = head_table_ncand(kb, tk, tn, d_base, d_max)
        cand = hash_candidates(kb, seeds, n_workers)  # (V, d_max)
        choice, _, _, loads = route_block(
            cand, nc, loads, n_entities=n_workers, w_mode=w_mode,
            inv_cap=icap,
        )
        assign_ref[pl.ds(i, 1), :] = choice.reshape(1, block)
        return loads

    loads = lax.fori_loop(0, nblk, body, jnp.zeros((1, n_workers), jnp.float32))
    loads_ref[...] = loads


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_workers", "d_base", "d_max", "seed", "chunk", "block", "interpret",
        "w_mode",
    ),
)
def adaptive_route_online(
    keys: jnp.ndarray,
    tbl_keys: jnp.ndarray,
    tbl_ncand: jnp.ndarray,
    n_workers: int,
    d_base: int = 2,
    d_max: int = 8,
    seed: int = 0,
    chunk: int = 1024,
    block: int = 128,
    interpret: Optional[bool] = None,
    w_mode: bool = False,
    capacities: Optional[jnp.ndarray] = None,
):
    """Route keys (N,) against per-block head tables (N/block, H).

    tbl_keys/tbl_ncand come from core.estimation.online_head_tables(block=...)
    with the same `block`; H is the tracker capacity.  Keys absent from their
    block's table (or present with ncand == d_base) route exactly as PKG.
    Tables emitted with any_worker=True carry W_SENTINEL for head slots, which
    routes those keys through the in-kernel global argmin (online W-Choices) —
    pass w_mode=True (static) with such tables; the default w_mode=False keeps
    the water-fill reduction out of the loop for sentinel-free D-Choices
    tables (a sentinel met without w_mode degrades to d_max candidates).
    Returns (assign (N,), per-chunk loads (N/chunk, n_workers)).
    """
    N = keys.shape[0]
    H = tbl_keys.shape[1]
    assert N % chunk == 0 and chunk % block == 0, (N, chunk, block)
    assert tbl_keys.shape == (N // block, H) == tbl_ncand.shape
    grid = (N // chunk,)
    has_cap = capacities is not None
    kern = functools.partial(
        _kernel_online, n_workers=n_workers, d_base=d_base, d_max=d_max,
        block=block, w_mode=w_mode, has_cap=has_cap,
    )
    blocks_per_chunk = chunk // block
    in_specs = [
        chunk_rows_spec(chunk, block),
        pl.BlockSpec((blocks_per_chunk, H), lambda i: (i, 0)),
        pl.BlockSpec((blocks_per_chunk, H), lambda i: (i, 0)),
        pl.BlockSpec((d_max,), lambda i: (0,)),
    ]
    operands = [
        keys.astype(jnp.int32).reshape(N // block, block),
        tbl_keys.astype(jnp.int32),
        tbl_ncand.astype(jnp.int32),
        derive_seeds(seed, d_max),
    ]
    if has_cap:
        icap = 1.0 / jnp.asarray(capacities, jnp.float32).reshape(1, n_workers)
        in_specs.append(pl.BlockSpec((1, n_workers), lambda i: (0, 0)))
        operands.append(icap)
    assign, loads = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            chunk_rows_spec(chunk, block),
            chunk_loads_spec(n_workers),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N // block, block), jnp.int32),
            jax.ShapeDtypeStruct((N // chunk, 1, n_workers), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(*operands)
    return assign.reshape(N), loads.reshape(N // chunk, n_workers)


# ---------------------------------------------------------------------------
# W-Choices entry point: per-key head flags instead of candidate counts.
# ---------------------------------------------------------------------------


def w_route(
    keys: jnp.ndarray,
    is_head: jnp.ndarray,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    chunk: int = 1024,
    block: int = 128,
    interpret: Optional[bool] = None,
    capacities: Optional[jnp.ndarray] = None,
):
    """W-Choices Pallas router: head keys (is_head != 0) go to the globally
    least-loaded worker via the in-kernel global argmin; tail keys take PKG's
    exact d-candidate step.  is_head (N,) is any int/bool array (e.g. from
    SpaceSavingTracker.head_counts); with block=1 and chunk=N this reproduces
    core.partitioners.w_choices_partition bit-exactly given the same head set
    (the differential contract in tests/test_kernels.py).  `capacities`
    weights both the tail argmin and the head water-fill by 1/c (see
    adaptive_route); the block=1 contract extends to the capacity-weighted
    host scan.

    Returns (assign (N,), per-chunk loads (N/chunk, n_workers)).
    """
    flags = jnp.asarray(is_head).astype(jnp.int32)
    n_cand = jnp.where(flags != 0, jnp.int32(W_SENTINEL), jnp.int32(d))
    return adaptive_route(
        keys, n_cand, n_workers, d_max=d, seed=seed, chunk=chunk, block=block,
        interpret=interpret, w_mode=True, capacities=capacities,
    )
