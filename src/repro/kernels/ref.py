"""Pure-jnp oracles for every device router and Pallas kernel (the allclose
ground truth).

Each `ref_*` mirrors its executor's contract exactly, including tie-breaking
(argmin -> first candidate) and block-staleness semantics of the PKG routers.
The routing oracles are all built on kernels/route_core.oracle_block_step —
the gather-based host twin of the executors' route_block — so the mask /
W-sentinel / water-fill / tie-break semantics are SHARED with the kernels
(one implementation, it cannot drift) while the load fetch and the W pick
deliberately use plain indexing instead of the kernels' one-hot matmuls:
the differential tests check the MXU formulation against straightforward
gathers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.estimation import W_SENTINEL
from repro.core.hashing import hash_choices
from repro.kernels.route_core import head_table_ncand, oracle_block_step


def _ref_inv_cap(capacities, n_workers):
    """(n_workers,) f32 reciprocal-capacity row, or None — the SAME
    1/f32(cap) the device routers form, so oracle and router normalize by
    bit-identical factors."""
    if capacities is None:
        return None
    return 1.0 / jnp.asarray(capacities, jnp.float32).reshape(n_workers)


def ref_pkg_route(keys, n_workers: int, d: int = 2, seed: int = 0,
                  chunk: int = 1024, block: int = 128, capacities=None):
    """Chunked batch-greedy PKG: ChunkedRouter("pkg") at chunk=N, and
    sharded_route with one epoch per chunk-sized shard.

    Chunks are independent local estimators; within a chunk, loads update
    every `block` keys.  Returns (assign (N,), loads (N//chunk, n_workers)).
    """
    N = keys.shape[0]
    assert N % chunk == 0 and chunk % block == 0
    icap = _ref_inv_cap(capacities, n_workers)
    cand = hash_choices(keys, n_workers, d=d, seed=seed)  # (N, d)
    cand = cand.reshape(N // chunk, chunk // block, block, d)

    def chunk_fn(cand_c):
        def step(loads, cb):  # cb (block, d)
            loads, choice, _, _ = oracle_block_step(
                loads, cb, None, n_entities=n_workers, w_mode=False,
                inv_cap=icap,
            )
            return loads, choice

        loads0 = jnp.zeros((n_workers,), jnp.float32)
        loads, choices = lax.scan(step, loads0, cand_c)
        return choices.reshape(-1), loads

    assign, loads = jax.vmap(chunk_fn)(cand)
    return assign.reshape(-1).astype(jnp.int32), loads


def ref_adaptive_route(keys, n_cand, n_workers: int, d_max: int = 4,
                       seed: int = 0, chunk: int = 1024, block: int = 128,
                       w_mode: bool = False, capacities=None):
    """Chunked batch-greedy with per-key candidate counts (the contract of
    parallel.sharded_router, including the route_core MASK sentinel and,
    with w_mode=True, the W_SENTINEL water-fill path).

    Returns (assign (N,), loads (N//chunk, n_workers))."""
    N = keys.shape[0]
    assert N % chunk == 0 and chunk % block == 0
    icap = _ref_inv_cap(capacities, n_workers)
    cand = hash_choices(keys, n_workers, d=d_max, seed=seed)  # (N, d_max)
    cand = cand.reshape(N // chunk, chunk // block, block, d_max)
    nc = n_cand.astype(jnp.int32).reshape(N // chunk, chunk // block, block)

    def chunk_fn(cand_c, nc_c):
        def step(loads, inp):  # cb (block, d_max), ncb (block,)
            cb, ncb = inp
            loads, choice, _, _ = oracle_block_step(
                loads, cb, ncb, n_entities=n_workers, w_mode=w_mode,
                inv_cap=icap,
            )
            return loads, choice

        loads0 = jnp.zeros((n_workers,), jnp.float32)
        loads, choices = lax.scan(step, loads0, (cand_c, nc_c))
        return choices.reshape(-1), loads

    assign, loads = jax.vmap(chunk_fn)(cand, nc)
    return assign.reshape(-1).astype(jnp.int32), loads


def ref_adaptive_route_online(keys, tbl_keys, tbl_ncand, n_workers: int,
                              d_base: int = 2, d_max: int = 8, seed: int = 0,
                              chunk: int = 1024, block: int = 128,
                              w_mode: bool = False, capacities=None):
    """Chunked batch-greedy against per-block head tables (at chunk=N, the
    contract of ChunkedRouter("d_choices") with tables from
    estimation.online_head_tables; the table lookup is literally the chunk
    step's head_table_ncand and the greedy core is the shared
    oracle_block_step).

    Returns (assign (N,), loads (N//chunk, n_workers))."""
    N = keys.shape[0]
    H = tbl_keys.shape[1]
    assert N % chunk == 0 and chunk % block == 0
    icap = _ref_inv_cap(capacities, n_workers)
    cand = hash_choices(keys, n_workers, d=d_max, seed=seed)  # (N, d_max)
    cand = cand.reshape(N // chunk, chunk // block, block, d_max)
    kb = keys.astype(jnp.int32).reshape(N // chunk, chunk // block, block)
    tk = tbl_keys.astype(jnp.int32).reshape(N // chunk, chunk // block, H)
    tn = tbl_ncand.astype(jnp.int32).reshape(N // chunk, chunk // block, H)

    def chunk_fn(cand_c, kb_c, tk_c, tn_c):
        def step(loads, inp):
            cb, kbb, tkb, tnb = inp  # (block,d_max) (block,) (H,) (H,)
            nc = head_table_ncand(kbb, tkb, tnb, d_base, d_max)
            loads, choice, _, _ = oracle_block_step(
                loads, cb, nc, n_entities=n_workers, w_mode=w_mode,
                inv_cap=icap,
            )
            return loads, choice

        loads0 = jnp.zeros((n_workers,), jnp.float32)
        loads, choices = lax.scan(step, loads0, (cand_c, kb_c, tk_c, tn_c))
        return choices.reshape(-1), loads

    assign, loads = jax.vmap(chunk_fn)(cand, kb, tk, tn)
    return assign.reshape(-1).astype(jnp.int32), loads


def ref_w_route(keys, is_head, n_workers: int, d: int = 2, seed: int = 0,
                chunk: int = 1024, block: int = 128, capacities=None):
    """W-Choices with per-key head flags (sharded_route under w_mode):
    head-flagged keys take the global argmin, tail keys PKG's d-candidate
    step.

    Returns (assign (N,), loads (N//chunk, n_workers))."""
    flags = jnp.asarray(is_head).astype(jnp.int32)
    n_cand = jnp.where(flags != 0, jnp.int32(W_SENTINEL), jnp.int32(d))
    return ref_adaptive_route(
        keys, n_cand, n_workers, d_max=d, seed=seed, chunk=chunk, block=block,
        w_mode=True, capacities=capacities,
    )


def ref_w_route_online(keys, tbl_keys, tbl_ncand, n_workers: int,
                       d_base: int = 2, d_max: int = 8, seed: int = 0,
                       chunk: int = 1024, block: int = 128, capacities=None):
    """Oracle for the online W-Choices path (ChunkedRouter("w_choices")):
    per-block head tables emitted by
    estimation.online_head_tables(any_worker=True), whose W_SENTINEL entries
    route through the global argmin.  Identical code to
    ref_adaptive_route_online with w_mode=True — the sentinel handling lives
    in the shared oracle_block_step/head_table_ncand pair — named
    separately so callers state which contract they exercise."""
    return ref_adaptive_route_online(
        keys, tbl_keys, tbl_ncand, n_workers, d_base=d_base, d_max=d_max,
        seed=seed, chunk=chunk, block=block, w_mode=True, capacities=capacities,
    )


def _ref_dispatch_block(loads, cand, gate, nc, *, n_experts, w_mode):
    """One MoE token block through the shared oracle core: flatten the k
    slots into blk*k lanes, route, gather the winning gate (spilled W lanes
    keep their slot's top-ranked gate — the kernel's contract)."""
    blk, k, C = cand.shape
    cand_f = cand.reshape(blk * k, C)
    gate_f = gate.reshape(blk * k, C)
    loads, choice, sel, is_w = oracle_block_step(
        loads, cand_f, nc, n_entities=n_experts, w_mode=w_mode
    )
    gsel = jnp.take_along_axis(gate_f, sel[:, None], axis=-1)[:, 0]
    if w_mode:
        gsel = jnp.where(is_w, gate_f[:, 0], gsel)
    return loads, choice.reshape(blk, k), gsel.reshape(blk, k)


def ref_moe_pkg_dispatch(cand, cgate, n_experts: int, block: int = 256):
    """Sequential block-greedy PoTC over expert candidate pairs.

    cand (T,k,2) int32, cgate (T,k,2) f32 -> (idx (T,k), gates (T,k),
    loads (n_experts,)).  Loads persist across blocks (single estimator).
    """
    T, k, _ = cand.shape
    assert T % block == 0
    cand_b = cand.reshape(T // block, block, k, 2)
    gate_b = cgate.reshape(T // block, block, k, 2)

    def step(loads, inp):
        c, g = inp
        loads, idx, gsel = _ref_dispatch_block(
            loads, c, g, None, n_experts=n_experts, w_mode=False
        )
        return loads, (idx, gsel)

    loads0 = jnp.zeros((n_experts,), jnp.float32)
    loads, (idx, gates) = lax.scan(step, loads0, (cand_b, gate_b))
    return idx.reshape(T, k), gates.reshape(T, k), loads


def ref_moe_adaptive_dispatch(cand, cgate, tbl_keys, tbl_ncand,
                              n_experts: int, d_base: int = 2, d_max: int = 4,
                              block: int = 256, w_mode: bool = False):
    """Oracle for kernels/moe_pkg_dispatch.py::moe_adaptive_dispatch — and
    THE host routing path of models.moe's d_choices/w_choices router modes
    (models.moe._adaptive_choose wraps this, so layer, kernel, and oracle
    share one choose implementation; it is differentiable w.r.t. cgate).

    cand/cgate (T, k, d_max), tbl_keys/tbl_ncand (T/block, H) expert-
    popularity head tables.  Returns (idx (T,k), gates (T,k), loads (E,)).
    """
    T, k, C = cand.shape
    H = tbl_keys.shape[1]
    assert T % block == 0, (T, block)
    assert tbl_keys.shape == (T // block, H) == tbl_ncand.shape
    cand_b = cand.astype(jnp.int32).reshape(T // block, block, k, C)
    gate_b = cgate.reshape(T // block, block, k, C)
    tk = tbl_keys.astype(jnp.int32)
    tn = tbl_ncand.astype(jnp.int32)

    def step(loads, inp):
        c, g, tkb, tnb = inp  # (block,k,C) (block,k,C) (H,) (H,)
        pref = c[:, 0, 0]  # token's preferred (top-ranked) expert
        nc_tok = head_table_ncand(pref, tkb, tnb, d_base, d_max)
        nc = jnp.broadcast_to(nc_tok[:, None], (block, k)).reshape(block * k)
        loads, idx, gsel = _ref_dispatch_block(
            loads, c, g, nc, n_experts=n_experts, w_mode=w_mode
        )
        return loads, (idx, gsel)

    loads0 = jnp.zeros((n_experts,), jnp.float32)
    loads, (idx, gates) = lax.scan(step, loads0, (cand_b, gate_b, tk, tn))
    return idx.reshape(T, k), gates.reshape(T, k), loads


def ref_flash_attention(q, k, v, causal: bool = True, window: int = 0):
    """Exact softmax attention with GQA + causal + sliding-window masks.

    q (B,S,H,hd), k/v (B,T,Kv,hd) -> (B,S,H,hd).  fp32 softmax.
    """
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qg = q.reshape(B, S, Kv, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    logits = logits * (hd ** -0.5)
    q_pos = jnp.arange(S)[:, None] + (T - S)  # assume k covers [0, T)
    k_pos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)
    logits = jnp.where(mask[None, None, None], logits, -1e9)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", p, v)
    return out.reshape(B, S, H, hd)


def ref_rmsnorm(x, w, eps: float = 1e-6):
    """(..., D) RMS norm with (1 + w) scale, fp32 accumulation."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
