"""Training step factories: jit/pjit step with microbatch gradient
accumulation, global-norm clip, AdamW; plus an explicit-DP variant with
int8+error-feedback compressed cross-pod gradient reduction (shard_map).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.transformer import loss_fn
from repro.optim.adamw import adamw_init, adamw_update, clip_by_global_norm
from repro.optim.schedules import make_schedule
from repro.parallel.compression import compressed_psum_mean, ef_init

__all__ = ["init_train_state", "make_train_step", "make_dp_train_step"]


def _id_sh(name, x):
    return x


def init_train_state(cfg, key, param_dtype=jnp.float32):
    from repro.models.transformer import init_params

    params = init_params(cfg, key, param_dtype)
    return params, adamw_init(params)


def make_train_step(
    cfg,
    tcfg,
    sh: Callable = _id_sh,
    microbatches: Optional[int] = None,
    grad_shardings=None,
):
    """Returns train_step(params, opt_state, batch, step) -> (p, o, metrics).

    Mixed precision: fp32 master params are cast to bf16 *once per step,
    before use*, so FSDP all-gathers and gradient reductions move bf16 on the
    wire (2x fewer collective bytes than gathering fp32 masters).
    `grad_shardings` (optional, == param shardings) constrains the gradient
    tree so XLA emits reduce-scatters into the FSDP shards rather than full
    all-reduces.
    """
    lr_fn = make_schedule(
        tcfg.schedule, tcfg.learning_rate, tcfg.warmup_steps, tcfg.total_steps
    )
    n_micro = microbatches or tcfg.microbatches

    def loss_of(p, mb):
        def cast(a, s=None):
            b = a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
            # keep the bf16 copy on the master's FSDP shards so the convert
            # is local and the all-gather at use moves bf16, not fp32
            return b if s is None else jax.lax.with_sharding_constraint(b, s)

        if grad_shardings is None:
            pc = jax.tree_util.tree_map(cast, p)
        else:
            pc = jax.tree_util.tree_map(cast, p, grad_shardings)
        return loss_fn(pc, mb, cfg, sh=sh, remat=tcfg.remat, z_loss=tcfg.z_loss)

    def _constrain(grads):
        if grad_shardings is None:
            return grads
        return jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, grad_shardings
        )

    def train_step(params, opt_state, batch, step):
        if n_micro > 1:
            mb = jax.tree_util.tree_map(
                lambda x: x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:]),
                batch,
            )

            def acc(carry, one):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_of, has_aux=True)(params, one)
                g = _constrain(g)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g
                )
                return (gsum, lsum + l), None

            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, lsum), _ = lax.scan(acc, (zero, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, gsum)
            loss = lsum / n_micro
        else:
            (loss, _), grads = jax.value_and_grad(loss_of, has_aux=True)(params, batch)
            grads = _constrain(grads)

        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        lr = lr_fn(step)
        params, opt_state = adamw_update(
            params, grads, opt_state, lr,
            b1=tcfg.b1, b2=tcfg.b2, eps=tcfg.eps, weight_decay=tcfg.weight_decay,
        )
        metrics = {"loss": loss, "gnorm": gnorm, "lr": lr}
        return params, opt_state, metrics

    return train_step


def make_dp_train_step(cfg, tcfg, mesh, dp_axis: str = "pod"):
    """Explicit data-parallel step over `dp_axis` with compressed gradients.

    Params/opt replicated across dp_axis; the batch splits along it; the
    cross-axis gradient reduction uses int8 codes + error feedback
    (parallel.compression).  opt_state gains an "ef" residual tree.
    Returns (train_step, init_fn) where init_fn wraps adamw_init.
    """

    lr_fn = make_schedule(
        tcfg.schedule, tcfg.learning_rate, tcfg.warmup_steps, tcfg.total_steps
    )

    def loss_of(p, mb):
        return loss_fn(p, mb, cfg, remat=tcfg.remat, z_loss=tcfg.z_loss)

    def init_fn(params):
        st = adamw_init(params)
        st["ef"] = ef_init(params)
        return st

    def _step(params, opt_state, batch, step):
        (loss, _), grads = jax.value_and_grad(loss_of, has_aux=True)(params, batch)
        grads, new_ef = compressed_psum_mean(grads, opt_state["ef"], dp_axis)
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        lr = lr_fn(step)
        st = {k: opt_state[k] for k in ("m", "v", "count")}
        params, st = adamw_update(
            params, grads, st, lr,
            b1=tcfg.b1, b2=tcfg.b2, eps=tcfg.eps, weight_decay=tcfg.weight_decay,
        )
        st["ef"] = new_ef
        loss = lax.pmean(loss, dp_axis)
        return params, st, {"loss": loss, "gnorm": gnorm, "lr": lr}

    step_fn = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(P(), P(), P(dp_axis), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(step_fn), init_fn
