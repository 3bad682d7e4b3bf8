"""Pallas TPU kernel: PKG stream router (batch-greedy Greedy-d).

Grid: one program per chunk of C keys.  Each program is an independent
local load estimator (paper §3.2): its (1, n_workers) fp32 load vector lives
in VMEM scratch and starts at zero.  Inside, keys are processed in vector
blocks of V lanes by the shared routing core (kernels/route_core.py — the
same route_block that powers adaptive_route.py and moe_pkg_dispatch.py,
called here with nc=None: every candidate lane live, no mask materialised):

  hash   : SplitMix32 over (key ^ seed_j) per choice j        (VPU int ops)
  lookup : one-hot(cand) @ loads                              (MXU matmul)
  choose : lane-wise argmin over d candidates
  update : loads += ones @ one-hot(choice)                    (MXU matmul)

Gathers/scatters are avoided entirely — candidate load lookup and the
histogram update are both expressed as one-hot matmuls, which is the
TPU-native formulation (DESIGN.md §2, §7).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.hashing import derive_seeds
from repro.kernels.platform import resolve_interpret
from repro.kernels.route_core import (
    chunk_loads_spec,
    chunk_rows_spec,
    hash_candidates,
    route_block,
)


def _kernel(keys_ref, seeds_ref, *rest, n_workers, d, block, has_cap):
    if has_cap:
        icap_ref, assign_ref, loads_ref = rest
        icap = icap_ref[...]  # (1, n_workers) f32 reciprocal capacities
    else:
        assign_ref, loads_ref = rest
        icap = None
    nblk = keys_ref.shape[0]
    seeds = seeds_ref[...]  # (d,) uint32

    def body(i, loads):  # loads (1, n_workers) f32
        kb = keys_ref[pl.ds(i, 1), :].reshape(block)  # (V,)
        cand = hash_candidates(kb, seeds, n_workers)  # (V, d)
        choice, _, _, loads = route_block(
            cand, None, loads, n_entities=n_workers, w_mode=False,
            inv_cap=icap,
        )
        assign_ref[pl.ds(i, 1), :] = choice.reshape(1, block)
        return loads

    loads = lax.fori_loop(0, nblk, body, jnp.zeros((1, n_workers), jnp.float32))
    loads_ref[...] = loads


@functools.partial(
    jax.jit, static_argnames=("n_workers", "d", "seed", "chunk", "block", "interpret")
)
def pkg_route(
    keys: jnp.ndarray,
    n_workers: int,
    d: int = 2,
    seed: int = 0,
    chunk: int = 1024,
    block: int = 128,
    interpret: Optional[bool] = None,
    capacities: Optional[jnp.ndarray] = None,
):
    """Route keys (N,) int32 -> (assign (N,), per-chunk loads (N/chunk, n)).

    N must divide by chunk; chunk by block.  interpret=None resolves via
    kernels.platform (compile on TPU, interpret elsewhere).  `capacities`
    (optional (n_workers,) strictly positive weights, arXiv 1705.09073) makes
    the candidate argmin capacity-normalized: the kernel receives a
    reciprocal-capacity row and compares loads * (1/c).  None routes the
    pre-capacity program unchanged; uniform capacities are bit-exact to it.
    """
    N = keys.shape[0]
    assert N % chunk == 0 and chunk % block == 0, (N, chunk, block)
    grid = (N // chunk,)
    has_cap = capacities is not None
    kern = functools.partial(
        _kernel, n_workers=n_workers, d=d, block=block, has_cap=has_cap
    )
    in_specs = [
        chunk_rows_spec(chunk, block),
        pl.BlockSpec((d,), lambda i: (0,)),
    ]
    operands = [keys.astype(jnp.int32).reshape(N // block, block),
                derive_seeds(seed, d)]
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
