#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run_cell.py --workload wp_pkg_w100.saturate \
        --seed 7 --seconds 30 --trace 0

A cell (an entry of BENCHMARK.json's `workloads`) names a configuration and
a traffic mix.  Everything else is found by name: the configuration's file,
`traffic/<traffic>.json`, `end_to_end/<metric>.py` and
`layer_metrics/<metric>.py`.

Set-up samples the configuration's whole key stream from --seed, builds
`ChunkedRouter` from the configuration and routes the stream's first pieces
through it, which compiles the chunk step (or reads it from the compile
cache).  The window then replays the stream for --seconds, pass after pass,
each pass through a router built afresh (the deployment routes the stream
from an empty router), with the traffic's arrival, into the benchmark's
sink.  Afterwards the reference (policies/) replays the stream and every
pass is compared with it: what the sink received and the router's state.

--trace 0 prints the cell's end-to-end metrics; --trace 1 runs the window
under the profiler and prints its per-layer metrics.  Without a TPU, or with
fewer chips than the cell asks for, the run exits 3 and prints no result.
--rehearse runs the same path on any backend with a short stream, for trying
the harness without a chip; it prints the check but no metrics.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import loop  # noqa: E402
import stream  # noqa: E402

# Pieces routed in set-up: the first compiles the step, the rest let the
# driver's double buffering reach its steady state.
WARM_PIECES = 4
# A rehearsal's stream: short, so that its window makes many passes.
REHEARSAL_PIECES = 16
# A traced run profiles this much of its window at most: the trace of a
# chunk step holds every operation of its scans, and the whole traced run,
# reading the trace included, has to end within the run's time limit.
TRACE_SECONDS = 3.0
COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/core/compile/jaxpr_trace_duration",
)


class NoChip(Exception):
    pass


def _read_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    bench = _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run_cell: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]

    def for_cell(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "cell": cell,
        "config": _read_json(ROOT / conf["file"]),
        "traffic": _read_json(HERE / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": for_cell(bench["end_to_end"]),
        "per_layer": for_cell(bench["per_layer"]),
    }


def reader(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"{kind}.{name}", HERE / kind / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _setup_jax():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def _device(jax, chips: int, rehearse: bool):
    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(
            f"needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s)"
        )
    return devs[0]


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR")
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    cfg, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    if traffic["arrival"] not in loop.ARRIVALS:
        raise SystemExit(f"run_cell: no generator for arrival {traffic['arrival']!r}")
    router_cfg = cfg["router"]
    chunk = router_cfg["chunk"]

    sys.path.insert(0, str(ROOT / "src"))
    jax = _setup_jax()
    from repro.parallel.chunked_driver import ChunkedRouter

    dev = _device(jax, cell["chips"], args.rehearse)
    compiles = {"window": 0}
    in_window = [False]

    def on_compile(event, duration, **kw):
        if in_window[0] and event in COMPILE_EVENTS:
            compiles["window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    # -- set-up --------------------------------------------------------------
    pmf = stream.checked_pmf(cfg["stream"])
    n_events = cfg["stream"]["events"]
    if args.rehearse:  # as many pieces as said, and the stream's short last one
        n_events = REHEARSAL_PIECES * chunk + n_events % chunk
    events = stream.sample(pmf, n_events, args.seed)
    del pmf
    span = loop.spans(bool(args.trace))
    sink = loop.Sink(router_cfg["n_workers"], span)
    feed = loop.Feed(events, chunk, span)
    passes: list[check.Pass] = []
    routers = []

    def route_pass(pieces):
        """Route one pass of the stream through a router built afresh."""
        first = len(sink.chunks)
        router = ChunkedRouter(**router_cfg, seed=args.seed)
        router.route_stream(pieces, on_chunk=sink)
        if feed.handed:
            routers.append((router, first, feed.handed))

    def warm(ks):
        feed.handed = []
        for k in ks:
            feed.handed.append(k)
            yield feed.piece(k)

    # Every shape the window meets: whole pieces, and the stream's short
    # last one, which the driver pads (its own program for the trim).
    route_pass(warm(range(WARM_PIECES)))
    route_pass(warm([feed.per_pass - 1]))
    first_window_piece = len(sink.chunks)

    trace_dir = None
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, TRACE_SECONDS)
        trace_dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no Python calls
        jax.profiler.start_trace(trace_dir.name, profiler_options=options)

    # -- window --------------------------------------------------------------
    in_window[0] = True
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with span("bench.window"):
        while True:
            route_pass(feed.one_pass(t_end))
            if len(feed.handed) < feed.per_pass:
                break
    in_window[0] = False

    reduced = None
    if trace_dir is not None:
        jax.profiler.stop_trace()
        import trace_reduce

        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            for p in Path(trace_dir.name).rglob("*.xplane.pb"):
                shutil.copy(p, args.keep_trace)
        try:
            reduced = trace_reduce.Reduced(trace_reduce.load(trace_dir.name))
        except ValueError as e:
            print(f"run_cell: trace not reduced: {e}", file=sys.stderr)
        trace_dir.cleanup()

    stats = dev.memory_stats() or {}
    for router, first, handed in routers:
        s = router.tracker
        passes.append(check.Pass(first, handed, router.loads, s and {
            f: np.asarray(getattr(s, f)) for f in ("keys", "counts", "errors", "total")
        }))

    # -- check ---------------------------------------------------------------
    numbers = check.compare(
        lambda: check.reference(router_cfg, args.seed), feed.piece, passes,
        sink.chunks,
    )
    correct, table = check.verdict(numbers)

    # What the metric readers read: the window's clock readings and (traced
    # runs) the reduced trace.
    window = sink.chunks[first_window_piece:]
    r = SimpleNamespace(
        t0=t0, seconds=seconds, setup_s=t0 - PROCESS_START, trace=reduced,
        sink_times=np.asarray(sink.times[first_window_piece:]),
        sink_sizes=np.asarray([len(a) for a in window], np.int64),
    )
    print(json.dumps({
        "window_compiles": compiles["window"],
        "window_passes": len(passes) - 2,
        "window_events_handed": feed.events,
        "window_pieces_delivered": len(window),
    }), flush=True)

    result = {
        "correct": correct,
        "attempted": sum(len(feed.piece(k)) for p in passes for k in p.pieces),
        "failed": numbers["mismatched_events"],
    }
    if args.rehearse:
        result["rehearsal"] = True
    else:
        kind, metrics = (
            ("layer_metrics", spec["per_layer"]) if args.trace
            else ("end_to_end", spec["end_to_end"])
        )
        result["metrics"] = {}
        for m in metrics:
            value = reader(kind, m["name"])(r)
            if value is None:
                print(f"run_cell: {m['name']}: nothing to read", file=sys.stderr)
                continue
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"] = {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": cell["chips"],
            "memory_peak_bytes": stats.get("peak_bytes_in_use"),
        }
        if reduced is not None:
            import trace_reduce

            result["device"]["busy_s"] = reduced.busy_ns * 1e-9
            result["device"]["window_s"] = reduced.window_ns * 1e-9
            result["breakdown"] = trace_reduce.breakdown(reduced)
    result["checks"] = table
    for name, row in table.items():
        print(f"check {name} = {row['value']} (limit {row['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


def main() -> int:
    try:
        run()
    except NoChip as e:
        print(f"run_cell: {e}; no result", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
