"""Serving launcher: batched generation + closed-loop replica routing demo.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --tiny \
      --batch 4 --prompt-len 16 --new-tokens 32 --replicas 50 \
      --scheduler w_choices

The routing demo drives the discrete-event simulator (serving.sim), so
schedulers receive completion events and their ledgers track OUTSTANDING
work — the number printed as "outstanding imbalance" is a true queue-depth
imbalance, not a cumulative total.  Cumulative routed-work balance and the
prefix-cache hit-rate are reported alongside, plus per-tenant SLO violations
over a skewed multi-tenant session stream.

Operational knobs mirror the simulator's failure/overload/elastic surfaces
(docs/operator-guide.md): --queue-bound bounds each replica's FIFO and
sheds overflow, --kill-at fails a replica mid-stream (its queue drains and
requeues over the live mask), --capacities gives replicas heterogeneous
speeds (a pattern like "1,2,4" tiles across the pool; routing normalizes
loads by capacity and the simulator serves at the true rates), and
--autoscale MIN:MAX runs serving.sim.Autoscaler so the live pool tracks the
offered load.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.routing import DEFAULT_SCHEDULER, scheduler_sweep_names

SCHEDULERS = scheduler_sweep_names()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=50)
    ap.add_argument("--requests", type=int, default=4000)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--scheduler", default=DEFAULT_SCHEDULER, choices=SCHEDULERS,
                    help="routing policy for the detailed run (others are "
                         "printed side by side for comparison)")
    ap.add_argument("--utilization", type=float, default=0.7,
                    help="offered load as a fraction of aggregate capacity; "
                         ">= 1 needs --queue-bound (shedding) to stay bounded")
    ap.add_argument("--cache-capacity", type=int, default=64)
    ap.add_argument("--slo", type=float, default=0.1)
    ap.add_argument("--queue-bound", type=int, default=None,
                    help="bounded per-replica FIFO: overflow arrivals are "
                         "shed (queue-based load leveling)")
    ap.add_argument("--kill-at", type=float, default=None, metavar="FRAC",
                    help="kill one replica after this fraction of the stream "
                         "(0-1): its queue drains and redistributes via the "
                         "live-replica mask")
    ap.add_argument("--capacities", default=None, metavar="C1,C2,...",
                    help="per-replica speed pattern, tiled across the pool "
                         "(e.g. '1,2,4'): load comparisons become capacity-"
                         "normalized and replicas serve at their true rates")
    ap.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                    help="elastic replica pool (serving.sim.Autoscaler): "
                         "start at MIN live replicas, grow to at most MAX "
                         "under load, shrink back in the lulls")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config, make_tiny
    from repro.core.routing import make_policy
    from repro.launch.compile_cache import enable_compile_cache
    from repro.core.streams import multi_tenant_stream
    from repro.models import init_params
    from repro.serving import (
        Autoscaler,
        PolicyScheduler,
        ServeEngine,
        simulate_serving,
    )

    enable_compile_cache()
    capacities = None
    if args.capacities is not None:
        pat = np.asarray([float(c) for c in args.capacities.split(",")])
        capacities = np.resize(pat, args.replicas)
    autoscaler = None
    if args.autoscale is not None:
        lo, hi = (int(v) for v in args.autoscale.split(":"))
        autoscaler = Autoscaler(
            min_replicas=lo, max_replicas=hi, initial=lo,
            check_every=max(args.requests // 100, 1),
            cooldown=max(args.requests // 40, 1),
        )

    cfg = make_tiny(get_config(args.arch)) if args.tiny else get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, max_len=args.prompt_len + args.new_tokens)

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(1, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )
    out = engine.generate(prompts, n_new=args.new_tokens)
    print(f"generated batch {out.shape}; sample row: {np.asarray(out[0])[:24]}...")

    # closed-loop replica routing: skewed multi-tenant session keys, with
    # completions driven by the simulator (loads = outstanding work).
    keys, tenants = multi_tenant_stream(
        args.requests, n_tenants=args.tenants,
        n_keys=max(args.requests // 40, 50), z=1.6,
        weights=np.arange(args.tenants, 0, -1), seed=args.seed,
    )
    kill_schedule = None
    if args.kill_at is not None:
        # kill replica 0 after --kill-at of the stream's arrival window
        dt = 1.0 / (args.utilization * args.replicas)
        kill_schedule = [(args.kill_at * args.requests * dt, 0)]
    print(
        f"\nrouting {args.requests} requests, {args.replicas} replicas, "
        f"{args.tenants} tenants, util={args.utilization:.0%}, "
        f"prefix-cache {args.cache_capacity}/replica, SLO {args.slo}"
        + (f", queue-bound {args.queue_bound}" if args.queue_bound else "")
        + (f", kill replica 0 @ {args.kill_at:.0%}" if kill_schedule else "")
        + (f", capacities {args.capacities} tiled" if capacities is not None
           else "")
        + (f", autoscale {args.autoscale}" if autoscaler else "")
        + ":"
    )
    order = [args.scheduler] + [s for s in SCHEDULERS if s != args.scheduler]
    for name in order:
        sched = PolicyScheduler(
            make_policy(name, args.replicas, d=2, seed=args.seed),
            capacities=capacities,
        )
        res = simulate_serving(
            sched, keys, tenants=tenants, utilization=args.utilization,
            cache_capacity=args.cache_capacity, slo=args.slo,
            queue_bound=args.queue_bound, kill_schedule=kill_schedule,
            autoscaler=autoscaler,
        )
        star = "*" if name == args.scheduler else " "
        print(
            f" {star}{name:10s} cache-hit={res.hit_rate:.3f}  "
            f"outstanding-imbalance={res.outstanding_imbalance:.4f}  "
            f"routed-work-imbalance={res.assign_imbalance:.4f}  "
            f"p50/p99 latency={res.latency_p50:.2f}/{res.latency_p99:.2f}  "
            f"shed={res.shed}  requeued={res.requeued}  "
            f"SLO-violating-tenants={res.tenant_report['tenants_violating']}"
            f"/{args.tenants}  session-fanout<= {res.session_fanout_max}"
            + (f"  scale-events={len(res.scale_events)}" if autoscaler else "")
        )
        assert res.completed + res.shed == args.requests, "lost completions"
        assert sched.loads.sum() == 0.0, "drain left outstanding work"
    print(
        "\n(*) = --scheduler selection.  W-Choices keeps cold sessions on "
        "<= 2 replicas (warm\nprefix caches) while hot sessions spread for "
        "balance — the paper's key splitting\nat the serving edge."
    )


if __name__ == "__main__":
    main()
