#!/usr/bin/env python3
"""The control of the benchmark's comparison: the plain reference with every
load it reads for a decision rounded to bfloat16, put in the program's
place, checked by `check.compare` against the exact reference.

    python3 benchmarks/chip/control.py --workload wp_pkg_w100.saturate \
        --pieces 2686 --seeds 11 12 13

--pieces is how many pieces of one pass a run of the cell hands the router
(every pass is the same, so at most one pass); each seed samples that run's
stream and routes that many pieces.  One JSON line per
seed gives the numbers `check.compare` reads, which the cell's limits must
reject.  It runs on the host alone (numpy); the benchmark's own runs never
run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import loop  # noqa: E402
import run_cell  # noqa: E402
import stream  # noqa: E402


def control_numbers(router: dict, feed_piece, n_pieces: int, seed: int) -> dict:
    """check.compare's numbers for the bf16 reference, routing the first
    n_pieces pieces of one pass, against the exact one."""
    low = check.reference(router, seed, fetch="bf16")
    delivered = [low.route_chunk(feed_piece(k)) for k in range(n_pieces)]
    done = check.Pass(0, range(n_pieces), low.loads, low.summary())
    return check.compare(
        lambda: check.reference(router, seed), feed_piece, [done], delivered
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pieces", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cfg = run_cell.load_cell(args.workload)["config"]
    pmf = stream.checked_pmf(cfg["stream"])
    chunk = cfg["router"]["chunk"]
    for seed in args.seeds:
        t = time.perf_counter()
        feed = loop.Feed(stream.sample(pmf, cfg["stream"]["events"], seed), chunk, None)
        n = min(args.pieces, feed.per_pass)
        numbers = control_numbers(cfg["router"], feed.piece, n, seed)
        correct, _ = check.verdict(numbers)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "pieces": n,
            "correct": correct, **numbers,
            "seconds": time.perf_counter() - t,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
