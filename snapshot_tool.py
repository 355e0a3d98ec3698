#!/usr/bin/env python
"""Offline snapshot replay — ref ``cmd/snapshot-tool/main.go:30-90``.

Usage:
    python snapshot_tool.py dump OUT.json[.gz]        # synthetic demo dump
    python snapshot_tool.py replay SNAP.json[.gz]     # one cycle, print commits
    python snapshot_tool.py replay STREAM.json[.gz]   # twin stream: oracle replay
    python snapshot_tool.py record OUT --url BASE     # pull /debug/twin stream
    python snapshot_tool.py record OUT --family F [--seed N] [--scale X]

``replay`` on a cluster snapshot loads it, runs exactly one scheduling
cycle with the default config, and prints the commit set (bind requests
+ evictions) as JSON lines — deterministic for a given file.  On a
kai-twin stream file (``format: kai-twin-stream``) it instead replays
the whole stream through the differential oracle and prints the
verdict; exit code 1 on any digest divergence.

``record`` captures a stream: ``--url`` pulls the live recorder's
stream from a running server's ``GET /debug/twin?stream=1``;
``--family`` generates one synthetically from a fuzzer family.
"""
from __future__ import annotations

import json
import sys


def _dump(path: str) -> None:
    from kai_scheduler_tpu.runtime.cluster import Cluster
    from kai_scheduler_tpu.runtime.snapshot import save
    from kai_scheduler_tpu.state import make_cluster

    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=8, node_accel=8.0, num_gangs=8, tasks_per_gang=2)
    cluster = Cluster.from_objects(nodes, queues, groups, pods, topo)
    save(cluster, path)
    print(f"wrote synthetic snapshot to {path}")


def _replay_stream(path: str) -> int:
    from kai_scheduler_tpu.twin import replay as twin_replay
    from kai_scheduler_tpu.twin import stream as twin_stream

    stream = twin_stream.read_stream(path)
    verdict = twin_replay.oracle(stream)
    print(json.dumps({
        "kind": "TwinOracle", "ok": verdict["ok"],
        "checks": verdict["checks"],
        "divergences": len(verdict["divergences"]),
        "events_applied": verdict["replay"]["events_applied"],
        "cycles": verdict["replay"]["cycles"],
    }, sort_keys=True))
    for d in verdict["divergences"]:
        print(json.dumps({"kind": "Divergence", "detail": d},
                         sort_keys=True))
    # throughput goes to stderr so stdout stays byte-identical
    print(json.dumps({"events_per_s": verdict["replay"]["events_per_s"]}),
          file=sys.stderr)
    return 0 if verdict["ok"] else 1


def _replay(path: str) -> int:
    from kai_scheduler_tpu.twin import stream as twin_stream

    # sniff the format field: a twin stream replays through the oracle,
    # anything else stays the classic one-cycle snapshot replay
    doc = twin_stream.read_doc(path)
    if isinstance(doc, dict) and doc.get("format") == twin_stream.FORMAT:
        return _replay_stream(path)

    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.runtime.snapshot import load

    cluster = load(path)
    result = Scheduler().run_once(cluster)
    for br in result.bind_requests:
        print(json.dumps({
            "kind": "BindRequest", "pod": br.pod_name,
            "node": br.selected_node,
            "type": br.received_resource_type.value,
            "accel_count": br.received_accel_count,
            "accel_portion": br.received_accel_portion,
        }, sort_keys=True))
    for ev in result.evictions:
        print(json.dumps({
            "kind": "Eviction", "pod": ev.pod_name, "group": ev.group,
            "move_to": ev.move_to,
        }, sort_keys=True))
    # timings go to stderr so stdout stays byte-identical across replays
    print(json.dumps({
        "kind": "Summary",
        "bind_requests": len(result.bind_requests),
        "evictions": len(result.evictions),
    }, sort_keys=True))
    print(json.dumps({k: round(v, 4)
                      for k, v in result.action_seconds.items()}),
          file=sys.stderr)
    return 0


def _record(out: str, opts: dict) -> int:
    from kai_scheduler_tpu.twin import stream as twin_stream

    if opts.get("url"):
        import urllib.request
        with urllib.request.urlopen(
                opts["url"].rstrip("/") + "/debug/twin?stream=1") as r:
            doc = json.loads(r.read())
        stream_doc = doc.get("stream")
        if not stream_doc:
            print("server has no recorded stream "
                  "(twinRecord: false?)", file=sys.stderr)
            return 1
        stream = twin_stream.Stream.from_doc(stream_doc)
    elif opts.get("family"):
        from kai_scheduler_tpu.twin import fuzz
        stream = fuzz.generate(opts["family"],
                               seed=int(opts.get("seed", 0)),
                               scale=float(opts.get("scale", 1.0)))
    else:
        print("record needs --url BASE or --family NAME",
              file=sys.stderr)
        return 2
    twin_stream.write_stream(stream, out)
    print(f"wrote twin stream ({len(stream.events)} events) to {out}")
    return 0


def main(argv: list[str]) -> int:
    from kai_scheduler_tpu.runtime import compile_cache
    compile_cache.enable()
    args = argv[1:]
    if not args or args[0] not in ("dump", "replay", "record"):
        print(__doc__, file=sys.stderr)
        return 2
    cmd, args = args[0], args[1:]
    if cmd in ("dump", "replay"):
        if len(args) != 1:
            print(__doc__, file=sys.stderr)
            return 2
        if cmd == "dump":
            _dump(args[0])
            return 0
        return _replay(args[0])
    # record OUT [--url BASE | --family NAME [--seed N] [--scale X]]
    if not args:
        print(__doc__, file=sys.stderr)
        return 2
    out, opts = args[0], {}
    it = iter(args[1:])
    for flag in it:
        if not flag.startswith("--"):
            print(__doc__, file=sys.stderr)
            return 2
        opts[flag[2:]] = next(it, "")
    return _record(out, opts)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
