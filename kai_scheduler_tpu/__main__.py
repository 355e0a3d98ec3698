"""Scheduler CLI — the ``cmd/scheduler`` entry point.

Mirrors the reference's flag surface (``cmd/scheduler/app/options/
options.go:90-131``: schedule period, node-pool partition, config file)
over the config-layering stack (``conf.py`` ≡ ``conf_util``).  Because
the TPU framework's API hub is an in-process document store rather than
a kube-apiserver, the CLI operates on snapshot documents (the same JSON
the snapshot plugin emits) and can:

  print-config  resolve flags + config file into the effective config
  cycle         run one scheduling cycle over a snapshot file (replay)
  serve         run the sidecar HTTP server for a snapshot file

Usage::

  python -m kai_scheduler_tpu print-config --config sched.yaml
  python -m kai_scheduler_tpu cycle --snapshot cluster.json.gz
  python -m kai_scheduler_tpu serve --snapshot cluster.json.gz --port 8080
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time


def _build_config(args) -> "SchedulerConfig":
    from . import conf
    from .apis import types as apis

    cfg = None
    if args.config:
        with open(args.config) as fh:
            cfg = conf.load_config(fh.read())
    else:
        cfg = conf.load_config(None)
    if args.schedule_period is not None:
        cfg = dataclasses.replace(cfg,
                                  schedule_period_s=args.schedule_period)
    if args.partition_label_value is not None or args.queue_depth:
        shard = apis.SchedulingShard(
            name="cli",
            partition_label_value=args.partition_label_value,
            queue_depth_per_action={
                k: int(v) for k, v in
                (kv.split("=", 1) for kv in args.queue_depth)})
        cfg = dataclasses.replace(cfg, shard=shard)
    if args.node_pool_label_key:
        cfg = dataclasses.replace(
            cfg, node_pool_label_key=args.node_pool_label_key)
    if args.pyroscope_address is not None:
        cfg = dataclasses.replace(
            cfg, pyroscope_address=args.pyroscope_address)
    if args.profiler_sample_hz is not None:
        cfg = dataclasses.replace(
            cfg, profiler_sample_hz=args.profiler_sample_hz)
    return cfg


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="kai_scheduler_tpu")
    parser.add_argument("command",
                        choices=("print-config", "cycle", "serve"))
    parser.add_argument("--config", help="scheduler config YAML/JSON file")
    parser.add_argument("--schedule-period", type=float, default=None,
                        help="seconds between cycles (ref options.go:33)")
    parser.add_argument("--node-pool-label-key", default=None)
    parser.add_argument("--partition-label-value", default=None,
                        help="serve only this node-pool partition")
    parser.add_argument("--queue-depth", action="append", default=[],
                        metavar="ACTION=N",
                        help="per-action queue depth override")
    parser.add_argument("--snapshot", help="cluster snapshot JSON(.gz)")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--pyroscope-address", default=None,
                        help="continuous-profile push URL (ref "
                             "pyroscope-address, options.go:110)")
    parser.add_argument("--profiler-sample-hz", type=float, default=None,
                        help="continuous profiler wall-stack sample "
                             "rate; 0 disables")
    args = parser.parse_args(argv)

    from . import conf
    cfg = _build_config(args)
    if args.command == "print-config":
        print(conf.dumps_effective(cfg))
        return 0

    from .framework.scheduler import Scheduler
    from .runtime import compile_cache, snapshot
    compile_cache.enable()
    if not args.snapshot:
        parser.error(f"{args.command} requires --snapshot")
    cluster = snapshot.load(args.snapshot)
    scheduler = Scheduler(cfg)

    if args.command == "cycle":
        result = scheduler.run_once(cluster)
        print(json.dumps({
            "bind_requests": len(result.bind_requests),
            "evictions": len(result.evictions),
            "open_seconds": round(result.open_seconds, 4),
            "commit_seconds": round(result.commit_seconds, 4),
            "total_seconds": round(result.session_seconds, 4),
            "phase_seconds": {k: round(v, 4)
                              for k, v in result.phase_seconds.items()},
        }))
        return 0

    from .framework.server import SchedulerServer
    server = SchedulerServer(cluster, scheduler, port=args.port).start()
    print(f"serving on 127.0.0.1:{server.port}", file=sys.stderr)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
