"""Pallas TPU kernels: fused PKG expert choice for MoE dispatch — plain
2-choice PoTC (moe_pkg_dispatch) and the adaptive D-/W-Choices variant
(moe_adaptive_dispatch) that consumes per-block expert-popularity head tables.

Grid: one program per block of T_blk tokens; TPU grid steps run sequentially
on a core, so the (1, E) fp32 expert-load vector persists in VMEM scratch
across blocks — a single running local estimator, exactly the semantics of
models.moe._pkg_choose (intra-block-stale loads, paper §3.2).

Per block the k slots of every token flatten into blk*k routing lanes and go
through the SAME route_block core as the stream routers
(kernels/route_core.py): candidate loads are fetched with a one-hot matmul,
the lane-wise argmin picks the less-loaded candidate, and the block histogram
updates the load vector — no gathers or scatters.  The winning candidate
column (`sel`) selects the matching gate weight.

The adaptive variant is the MoE incarnation of the chunk step's online
D-/W-Choices (parallel/chunked_driver.py): each block reads a head-table
snapshot of the *expert-popularity* SPACESAVING summary (keys = expert ids,
emitted by models.moe.expert_head_tables /
core.estimation.online_head_tables over the stream of router-preferred
experts).  A token whose preferred expert is hot gets more candidate lanes
(D-Choices: d(e) of its d_max router-ranked experts) or, with w_mode=True and
W_SENTINEL table entries, spills to ANY expert via the capacity-aware
water-fill over the running loads row (W-Choices: consecutive head tokens
take consecutive global argmins, so a hot-expert token flood spreads over the
emptiest experts instead of piling onto one).  Spilled lanes keep their
slot's top-ranked gate weight (lane 0) — the router's confidence in the slot,
not in the arbitrary expert the flood landed on.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret
from repro.kernels.route_core import head_table_ncand, pick_lane, route_block


def _dispatch_block(cand, gate, nc, loads, *, n_experts, w_mode):
    """One token block through the shared core: flatten (blk, k, C) slot
    candidates into blk*k lanes, route, select the winning gate per lane.
    Returns (idx (blk,k), gsel (blk,k), new loads)."""
    blk, k, C = cand.shape
    cand_f = cand.reshape(blk * k, C)
    gate_f = gate.reshape(blk * k, C)
    choice, sel, is_w, loads = route_block(
        cand_f, nc, loads, n_entities=n_experts, w_mode=w_mode
    )
    if w_mode:
        # spilled lanes: sel is meaningless; keep the slot's top gate
        sel = jnp.where(is_w, 0, sel)
    gsel = pick_lane(gate_f, sel)
    return choice.reshape(blk, k), gsel.reshape(blk, k), loads


def _kernel(cand_ref, gate_ref, idx_ref, gsel_ref, loads_ref, *, n_experts):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        loads_ref[...] = jnp.zeros_like(loads_ref)

    idx, gsel, loads = _dispatch_block(
        cand_ref[...], gate_ref[...], None, loads_ref[...],
        n_experts=n_experts, w_mode=False,
    )
    idx_ref[...] = idx
    gsel_ref[...] = gsel
    loads_ref[...] = loads


@functools.partial(jax.jit, static_argnames=("n_experts", "block", "interpret"))
def moe_pkg_dispatch(
    cand: jnp.ndarray,
    cgate: jnp.ndarray,
    n_experts: int,
    block: int = 256,
    interpret: Optional[bool] = None,
):
    """cand (T,k,2) int32, cgate (T,k,2) f32 -> (idx (T,k), gates (T,k), loads (E,)).

    T must divide by block.  interpret=None resolves via kernels.platform
    (compile on TPU, interpret elsewhere).
    """
    T, k, _ = cand.shape
    assert T % block == 0, (T, block)
    grid = (T // block,)
    kern = functools.partial(_kernel, n_experts=n_experts)
    idx, gsel, loads = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, k, 2), lambda i: (i, 0, 0)),
            pl.BlockSpec((block, k, 2), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, k), lambda i: (i, 0)),
            pl.BlockSpec((block, k), lambda i: (i, 0)),
            pl.BlockSpec((1, n_experts), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.int32),
            jax.ShapeDtypeStruct((T, k), cgate.dtype),
            jax.ShapeDtypeStruct((1, n_experts), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(cand.astype(jnp.int32), cgate)
    return idx, gsel, loads[0]


def _kernel_adaptive(cand_ref, gate_ref, pref_ref, tblk_ref, tbln_ref, idx_ref,
                     gsel_ref, loads_ref, *, n_experts, d_base, d_max, w_mode):
    H = tblk_ref.shape[1]
    V = pref_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        loads_ref[...] = jnp.zeros_like(loads_ref)

    tk = tblk_ref[...].reshape(H)  # (H,) expert ids in this block's snapshot
    tn = tbln_ref[...].reshape(H)  # (H,) d(e) / W_SENTINEL per head expert
    # head verdict is per TOKEN, keyed by its preferred (top-ranked) expert;
    # pref_ref holds it once per slot lane, so no (blk, k) -> (blk*k,)
    # relayout happens in the kernel
    pref = pref_ref[...].reshape(V)  # (blk*k,)
    nc = head_table_ncand(pref, tk, tn, d_base, d_max)
    idx, gsel, loads = _dispatch_block(
        cand_ref[...], gate_ref[...], nc, loads_ref[...],
        n_experts=n_experts, w_mode=w_mode,
    )
    idx_ref[...] = idx
    gsel_ref[...] = gsel
    loads_ref[...] = loads


@functools.partial(
    jax.jit,
    static_argnames=("n_experts", "d_base", "d_max", "block", "interpret",
                     "w_mode"),
)
def moe_adaptive_dispatch(
    cand: jnp.ndarray,
    cgate: jnp.ndarray,
    tbl_keys: jnp.ndarray,
    tbl_ncand: jnp.ndarray,
    n_experts: int,
    d_base: int = 2,
    d_max: int = 4,
    block: int = 256,
    interpret: Optional[bool] = None,
    w_mode: bool = False,
):
    """Adaptive MoE dispatch: cand/cgate (T, k, d_max) router-ranked expert
    candidates per slot, tbl_keys/tbl_ncand (T/block, H) per-block
    expert-popularity head tables (models.moe.expert_head_tables with the
    same `block`).  Tokens whose preferred expert misses the table (or hits
    as tail) use d_base candidate lanes — exact PKG-PoTC; head hits open
    d(e) <= d_max lanes, and W_SENTINEL entries (any_worker tables) route the
    token's slots through the global water-fill — pass w_mode=True with such
    tables.  Returns (idx (T,k), gates (T,k), loads (E,)).

    T must divide by block.  interpret=None resolves via kernels.platform.
    """
    T, k, _ = cand.shape
    H = tbl_keys.shape[1]
    assert T % block == 0, (T, block)
    assert tbl_keys.shape == (T // block, H) == tbl_ncand.shape
    grid = (T // block,)
    kern = functools.partial(
        _kernel_adaptive, n_experts=n_experts, d_base=d_base, d_max=d_max,
        w_mode=w_mode,
    )
    idx, gsel, loads = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, k, cand.shape[2]), lambda i: (i, 0, 0)),
            pl.BlockSpec((block, k, cand.shape[2]), lambda i: (i, 0, 0)),
            pl.BlockSpec((pl.Squeezed(), 1, block * k), lambda i: (i, 0, 0)),
            # (T/block, 1, H) tables, one squeezed (1, H) row per step: a
            # (1, H) block of a (T/block, H) array breaks the TPU 8-row rule
            pl.BlockSpec((pl.Squeezed(), 1, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((pl.Squeezed(), 1, H), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, k), lambda i: (i, 0)),
            pl.BlockSpec((block, k), lambda i: (i, 0)),
            pl.BlockSpec((1, n_experts), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.int32),
            jax.ShapeDtypeStruct((T, k), cgate.dtype),
            jax.ShapeDtypeStruct((1, n_experts), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(
        cand.astype(jnp.int32),
        cgate,
        jnp.repeat(cand[:, 0, 0].astype(jnp.int32), k).reshape(
            T // block, 1, block * k
        ),
        tbl_keys.astype(jnp.int32).reshape(T // block, 1, H),
        tbl_ncand.astype(jnp.int32).reshape(T // block, 1, H),
    )
    return idx, gsel, loads[0]
