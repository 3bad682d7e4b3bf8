"""GPipe-style pipeline parallelism over a mesh "stage" axis (shard_map).

Each device (or device group) holds one stage's parameters; microbatches
stream through the stages via lax.ppermute inside a lax.scan over the
M + S - 1 schedule steps.  Differentiable end to end (autodiff through
ppermute/scan), so the same primitive serves training.

This composes with the other axes: a (stage, data, model) mesh runs PP x DP
x TP; the dry-run meshes use (pod, data, model) since the assigned shapes
fit without PP, but the primitive + parity tests keep the capability honest
(DESIGN.md §6).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_forward", "make_pipelined_fn"]


def pipeline_forward(
    stage_fn: Callable,
    stage_params,
    x_microbatches: jnp.ndarray,
    axis_name: str = "stage",
) -> jnp.ndarray:
    """Run microbatches through S pipeline stages (call inside shard_map).

    stage_fn(params, x) -> y, same shape; stage_params are THIS device's.
    x_microbatches: (M, mb, ...), replicated across the stage axis.
    Returns (M, mb, ...) outputs (replicated; produced on the last stage and
    broadcast with a psum).
    """
    # jax.lax.axis_size only exists in newer jax; psum(1) is the portable form
    S = lax.psum(1, axis_name)
    sidx = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def step(carry, t):
        buf, outs = carry
        inject = x_microbatches[jnp.minimum(t, M - 1)]
        cur = jnp.where(sidx == 0, inject, buf)
        y = stage_fn(stage_params, cur)
        nxt = lax.ppermute(y, axis_name, perm)
        m_out = t - (S - 1)
        idx = jnp.maximum(m_out, 0)
        emit = jnp.logical_and(sidx == S - 1, m_out >= 0)
        outs = outs.at[idx].set(jnp.where(emit, y, outs[idx]))
        return (nxt, outs), None

    buf0 = jnp.zeros_like(x_microbatches[0])
    outs0 = jnp.zeros_like(x_microbatches)
    (_, outs), _ = lax.scan(step, (buf0, outs0), jnp.arange(T))
    # broadcast the last stage's outputs to every stage
    outs = lax.psum(jnp.where(sidx == S - 1, outs, jnp.zeros_like(outs)), axis_name)
    return outs


def make_pipelined_fn(stage_fn: Callable, mesh, n_stages: int, axis_name: str = "stage"):
    """Wrap stage_fn into a jit'd (stacked_params, x_microbatches) -> outs.

    stacked_params: leading dim n_stages on every leaf (stage s's slice lives
    on stage s); x_microbatches (M, mb, ...) replicated.
    """
    from jax.sharding import PartitionSpec as P

    def inner(stacked_params, x_mb):
        my = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
        return pipeline_forward(stage_fn, my, x_mb, axis_name)

    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)
