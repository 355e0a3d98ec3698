#!/usr/bin/env python3
"""The control and the program's own readings, at a cell's own size, in
one process (set-up is long; the compiled program is shared).

    python3 benchmark/tests/control.py --workload <cell> --seeds 1,2,3 \
        [--faults partial_gang,half_left_out] [--seconds 8] [--nodes N]

For each seed: a sound run and, for each fault named (``faults.FAULTS``:
the control, the faults a served scheduler can have, and those against
a guarantee one configuration states), a run with that fault planted
under the timed path, each with a short window at the cell's own load.
Prints one JSON line per run with every number compared beside its
limit.  The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [HERE, BENCH, ROOT]


def one_run(config, mix, seed, seconds, fault, nodes, meter) -> dict:
    import faults
    from lib import loop
    run = loop.Run(config, mix, seed, ROOT, nodes=nodes)
    with faults.planted(fault, run):
        try:
            run.start(meter)
            run.warm_up()
            run.measure(seconds, trace=False)
        finally:
            run.stop()
    checks = run.judge()
    return {"seed": seed, "fault": fault, "cycles": len(run.cycles),
            "correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "cycle_ms": 1e3 * run.window["seconds"] / len(run.cycles),
            "compiles": (run.window["compile_requests"]
                         + run.window["jit_misses"]),
            "checks": {k: c["value"] for k, c in checks.items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="",
                    help="comma-separated names from tests/faults.py")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--nodes", type=int, default=None)
    args = ap.parse_args()
    from lib import registry
    _bench, _cell, config, mix = registry.load_cell(args.workload)
    from kai_scheduler_tpu.runtime import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.nodes is None and jax.devices()[0].platform != "tpu":
        sys.exit("control: no TPU; pass --nodes for a rehearsal")
    from lib import meters
    meter = meters.CompileMeter()
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in [None, *filter(None, args.faults.split(","))]:
            print(json.dumps(one_run(config, mix, seed, args.seconds, fault,
                                     args.nodes, meter)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
