"""Training launcher: data pipeline -> sharded train step -> checkpoints.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --tiny \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --tiny \
      --router pkg_potc --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --tiny \
      --router w_choices --steps 2   # adaptive W-Choices expert routing

On a real TPU slice this same entry point runs the production mesh
(--mesh data,model); on CPU it defaults to a single device.  Fault tolerance:
--fail-at N injects a failure; rerunning the same command resumes from the
latest checkpoint and replays the stream deterministically.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true", help="reduced same-family config")
    ap.add_argument(
        "--router",
        default=None,
        choices=[None, "topk_aux", "pkg_potc", "d_choices", "w_choices"],
    )
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--partitioner", default="pkg", choices=["pkg", "kg", "sg"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    from repro.checkpoint import CheckpointManager
    from repro.configs import TrainConfig, get_config, make_tiny
    from repro.data import PKGDataPipeline, SyntheticCorpus
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params
    from repro.optim import adamw_init
    from repro.train import TrainingHarness, make_train_step

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.tiny:
        cfg = make_tiny(cfg)
    if args.router:
        cfg = dataclasses.replace(cfg, router=args.router)
    assert cfg.frontend == "tokens", "token-frontend archs only in this driver"

    tcfg = TrainConfig(
        learning_rate=args.lr,
        total_steps=args.steps,
        warmup_steps=max(args.steps // 20, 2),
        microbatches=args.microbatches,
    )
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n:,} devices={jax.device_count()}")

    pipe = PKGDataPipeline(
        batch_size=args.batch,
        seq_len=args.seq,
        vocab_size=cfg.vocab_size,
        partitioner=args.partitioner,
        corpus=SyntheticCorpus(cfg.vocab_size, seed=args.seed),
        seed=args.seed,
    )
    manager = CheckpointManager(args.ckpt_dir, keep=3)
    step = jax.jit(make_train_step(cfg, tcfg))
    harness = TrainingHarness(
        step, pipe, manager, checkpoint_every=args.ckpt_every, fail_at_step=args.fail_at
    )
    params, opt, history = harness.run(
        params, adamw_init(params), args.steps, log_every=args.log_every
    )
    print(f"done: first-5 loss {history[:5]} last-5 loss {history[-5:]}")


if __name__ == "__main__":
    main()
