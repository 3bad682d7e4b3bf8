"""Pallas TPU kernel: one chunk's per-event Space-Saving pass, the summary
held on chip for the whole chunk.

The chunked router's summary sequence over a chunk depends only on the
chunk's keys, its valid mask, the global block counter and the decay rule —
never on routing — so the whole chunk's pass runs first, as one kernel, and
the block scan reads its per-block snapshots.  Per block b, in the order
`estimation.online_head_tables` and the chunked step use:

  1. snapshot (keys, counts, total): the state before the block and its decay
  2. `online_ss_decay` when global block b0 + b opens a decay period
  3. offer the block's events in lane order; a lane with valid == 0 leaves
     the state untouched

The per-event transition is exactly `estimation.online_ss_update`, written
with lane reductions and selects only (no gather, scatter or top_k), so it
compiles under Mosaic:

  hit    : the lowest slot with live & (keys == k)
  miss   : the lowest slot of the minimum count, an empty slot counting 0
  either : counts[slot] += 1, keys[slot] = k; a miss also sets
           errors[slot] to the victim's count (a hit keeps its error)

Each argmin is a `min` over ``where(cond, slot_id, Cp)``.  The slot axis is
padded to a multiple of 128 lanes and held as (Cp/128, 128): pad slots keep
count 0, so they never match, and the argmin masks them out, so they never
evict.  The keys and the valid mask sit in SMEM, where each event reads its
key and its flag as scalars; the summary stays in vector registers / VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.estimation import OnlineSS
from repro.kernels.platform import resolve_interpret
from repro.kernels.route_core import LANES

__all__ = ["ss_update_chunk"]


def _min11(x):
    """(R, 128) -> (1, 1) minimum."""
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _kernel(keys_ref, valid_ref, decay_ref, tot_ref, sk_ref, sc_ref, se_ref,
            snap_k_ref, snap_c_ref, snap_t_ref, fk_ref, fc_ref, fe_ref,
            ft_ref, *, capacity, decay):
    nblk, block = keys_ref.shape
    rows = sk_ref.shape[0]
    cp = rows * LANES
    slot_id = (
        lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
        + lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    )
    real = slot_id < capacity
    big = jnp.int32(jnp.iinfo(jnp.int32).max)

    def offer(b, j, st):
        skeys, scnt, serr, tot = st
        k = keys_ref[b, j]
        on = valid_ref[b, j] > 0
        hit = _min11(jnp.where((scnt > 0) & (skeys == k), slot_id, cp))
        m = _min11(jnp.where(real, scnt, big))
        victim = _min11(jnp.where(real & (scnt == m), slot_id, cp))
        found = hit < cp
        sel = (slot_id == jnp.where(found, hit, victim)) & on
        return (
            jnp.where(sel, k, skeys),
            jnp.where(sel, scnt + 1, scnt),
            jnp.where(sel & ~found, scnt, serr),
            tot + on.astype(jnp.int32),
        )

    def block_body(b, st):
        skeys, scnt, serr, tot = st
        snap_k_ref[b] = skeys
        snap_c_ref[b] = scnt
        snap_t_ref[b] = tot
        if decay:
            shift = decay_ref[b]  # 1 where the block opens a period, else 0
            scnt = scnt >> shift
            serr = serr >> shift
            tot = tot >> shift
        return lax.fori_loop(
            0, block, functools.partial(offer, b), (skeys, scnt, serr, tot)
        )

    skeys, scnt, serr, tot = lax.fori_loop(
        0, nblk, block_body, (sk_ref[...], sc_ref[...], se_ref[...], tot_ref[0])
    )
    fk_ref[...] = skeys
    fc_ref[...] = scnt
    fe_ref[...] = serr
    ft_ref[0] = tot


@functools.partial(jax.jit, static_argnames=("decay_period", "interpret"))
def ss_update_chunk(
    state: OnlineSS,
    keys: jnp.ndarray,
    valid: jnp.ndarray,
    b0,
    *,
    decay_period: int = 0,
    interpret: Optional[bool] = None,
):
    """Offer a chunk's keys to the summary, block by block, in one kernel.

    state: the carried OnlineSS, capacity C.  keys, valid: (nblk, block)
    int32, the chunk's keys by vector block and the lanes to offer.  b0: the
    global index of the chunk's first block, which places the decay periods
    (``decay_period`` 0 never decays).

    Returns (snap_keys (nblk, C), snap_counts (nblk, C), snap_total (nblk,),
    final OnlineSS): snapshot b is the state before block b and before its
    decay, which is what `estimation.online_ss_head_table` reads for block b.
    """
    nblk, block = keys.shape
    capacity = state.keys.shape[0]
    cp = -(-capacity // LANES) * LANES
    rows = cp // LANES

    def slots(x, fill):
        x = jnp.pad(x.astype(jnp.int32), (0, cp - capacity), constant_values=fill)
        return x.reshape(rows, LANES)

    if decay_period > 0:
        b = jnp.asarray(b0, jnp.int32) + jnp.arange(nblk, dtype=jnp.int32)
        do = (b > 0) & ((b * block) % decay_period < block)
        shift = do.astype(jnp.int32)
    else:
        shift = jnp.zeros((nblk,), jnp.int32)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    row = jax.ShapeDtypeStruct((rows, LANES), jnp.int32)
    snap = jax.ShapeDtypeStruct((nblk, rows, LANES), jnp.int32)
    sk, sc, st, fk, fc, fe, ft = pl.pallas_call(
        functools.partial(_kernel, capacity=capacity, decay=decay_period > 0),
        in_specs=[smem, smem, smem, smem, vmem, vmem, vmem],
        out_specs=[vmem, vmem, smem, vmem, vmem, vmem, smem],
        out_shape=[
            snap, snap, jax.ShapeDtypeStruct((nblk,), jnp.int32),
            row, row, row, jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
        name="ss_update",
    )(
        keys.astype(jnp.int32), valid.astype(jnp.int32), shift,
        jnp.asarray(state.total, jnp.int32).reshape(1),
        slots(state.keys, -1), slots(state.counts, 0), slots(state.errors, 0),
    )

    def unpad(x):
        return x.reshape(*x.shape[:-2], cp)[..., :capacity]

    final = OnlineSS(keys=unpad(fk), counts=unpad(fc), errors=unpad(fe),
                     total=ft[0])
    return unpad(sk), unpad(sc), st, final
