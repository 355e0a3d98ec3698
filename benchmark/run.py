#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` (its configuration file, its
traffic mix, its metrics' readers, all found by name), starts the served
scheduler in this process, warms up, measures for ``--seconds``, judges
the window's commits against the plain reference and prints one JSON
object as the last line of standard output.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, with the device's busy time and a breakdown from the
profiler's trace.

No TPU, or fewer chips than the cell asks for, is an error: non-zero
exit, no result.  ``--nodes N`` is a rehearsal at another cluster size;
it may run without a TPU, prints what it measured to standard error and
NO result line, and exits 3.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from lib import registry  # noqa: E402

#: where the readers of each list of ``BENCHMARK.json`` live: one small
#: file each, ``read(run)``
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def metrics_of(bench: dict, kind: str, cell: dict, run,
               rehearsal: bool) -> dict:
    """Every metric of ``kind`` that this cell reports and whose reader
    found something to read."""
    out = {}
    for m in bench[kind]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        try:
            value = registry.module(READERS[kind], m["name"]).read(run)
        except KeyError as exc:
            # a device with no peaks is an error on the chip; a rehearsal
            # on the CPU has none and says so
            if not rehearsal:
                raise
            print(f"rehearsal: {m['name']} not read: {exc}", file=sys.stderr)
            continue
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def bind_wait_quantiles(run) -> dict:
    """The whole distribution behind the tail metric, for the reader of
    the ledger (nearest rank)."""
    waits = sorted(w for c in run.cycles for w in c["bind_wait_s"])
    if not waits:
        return {}
    return {"n": len(waits), **{
        f"p{q}": 1e3 * waits[max(0, -(-q * len(waits) // 100) - 1)]
        for q in (50, 90, 95, 99, 100)}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--nodes", type=int, default=None,
                    help="rehearsal at another cluster size: no result")
    ap.add_argument("--cycles", type=int, default=None,
                    help="rehearsal only: close the window after so many")
    args = ap.parse_args()
    if args.cycles is not None and args.nodes is None:
        ap.error("--cycles is for a --nodes rehearsal")

    try:
        bench, cell, config, mix = registry.load_cell(args.workload)
    except KeyError as exc:
        sys.exit(exc.args[0])
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds

    from kai_scheduler_tpu.runtime import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    # one process, nobody else writing: cache every program, however
    # quick, so that a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if args.nodes is None and not (on_tpu and len(devices) >= cell["chips"]):
        sys.exit(f"benchmark: {cell['name']} needs {cell['chips']} TPU "
                 f"chip(s); JAX found {len(devices)} x "
                 f"{devices[0].platform}")

    from lib import loop, meters
    meter = meters.CompileMeter()
    run = loop.Run(config, mix, args.seed, ROOT, nodes=args.nodes)
    imports_s = time.perf_counter() - _T0
    run.device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    try:
        run.start(meter)
        run.warm_up()
        run.setup_s = time.perf_counter() - _T0
        run.setup["imports_and_device_s"] = imports_s
        run.measure(seconds, bool(args.trace), max_cycles=args.cycles)
    finally:
        run.stop()
    run.device["memory_peak_bytes"] = run.window["memory_peak_bytes"]
    t_judge = time.perf_counter()
    checks = run.judge()
    judge_s = time.perf_counter() - t_judge
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    kind = "per_layer" if args.trace else "end_to_end"
    result = {"correct": correct, "attempted": len(run.cycles),
              "failed": run.failed,
              "metrics": metrics_of(bench, kind, cell, run,
                                    args.nodes is not None),
              "device": run.device}
    if args.trace:
        if run.trace is None and args.nodes is None:
            sys.exit("benchmark: the trace holds no device operation "
                     "inside the traced window")
        if run.trace is not None:
            result["device"]["busy_s"] = run.trace["busy_s"]
            result["device"]["window_s"] = run.trace["window_s"]
            result["breakdown"] = {"device_ops": run.trace["device_ops"],
                                   "idle_gaps": run.trace["idle_gaps"]}
    # how far the window stayed in the cell's regime, for the reader of
    # the ledger; the driver ignores these
    result["window"] = {
        "seconds": run.window["seconds"],
        "cycles_with_binds": sum(1 for c in run.cycles if c["binds"]),
        "cycles_with_evictions": sum(1 for c in run.cycles
                                     if c["evictions"]),
        "warmup_cycles": len(run.setup["warmup_cycles"]),
        "pending_gangs": sorted({t["pending_gangs"] for t in run.tallies}),
        "placed_pods": sorted({t["placed_pods"] for t in run.tallies}),
        "compiles": run.window["compile_requests"] + run.window["jit_misses"],
        # [index in the window, seconds, the program's phases if read]
        "slowest_cycles": [
            [i, c["iter_s"], c.get("health", {}).get("phase_seconds")]
            for i, c in sorted(enumerate(run.cycles),
                               key=lambda ic: -ic[1]["iter_s"])[:4]],
        "bind_wait_ms": bind_wait_quantiles(run),
        "reference_replay_s": judge_s,
        # memory_peak_bytes in its two parts: [in use, reserved]
        "memory_in_use_and_reserved": run.window["memory_in_use_and_reserved"],
        "cache_dir": os.path.relpath(cache_dir, ROOT)}
    if run.trace is not None:
        result["window"]["idle_by_host_span"] = run.trace["idle_by_host_span"]
    result["checks"] = checks

    print(json.dumps({"setup": run.setup}), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    if args.nodes is not None:
        print(json.dumps({"rehearsal": result, "setup": run.setup}),
              file=sys.stderr)
        print("benchmark: rehearsal finished; no result", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
