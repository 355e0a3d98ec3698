#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the scheduler starts on the chip.

One process, run from the root of a checkout::

    python chip_smoke.py              # one TPU chip, the full size
    python chip_smoke.py --chips 4    # only the node-sharded cycle, 4 chips

With no arguments it drives the main path once at the ``BASELINE.json``
size (10 000 nodes x 50 000 pods, saturated, all five default actions):

``classic``   a ``SchedulerServer`` on port 0 over HTTP — ``POST
              /cycle/stored``, a seeded ``POST /cluster/delta``, a seeded
              ``POST /intake``, two more cycles, then ``GET /healthz``,
              ``/metrics``, ``/debug/wire``.
``precision`` small clusters whose requests bf16 cannot hold (1.37 CPU,
              13.7 GiB, portion 0.35): the five actions on the chip and
              on the CPU backend of the same process, same snapshot.

Every commit set is checked on the host with NumPy, independent of the
kernels.  Each phase prints one JSON line; the LAST line of stdout is the
result, ``{"ok": true, "device": {...}}``, printed only after every check
passed on a TPU.  Any failure is an exception and a non-zero exit: there
is no CPU branch and no try/except around a phase.  ``--nodes`` shrinks
the cluster for a rehearsal; a run that found no TPU then still runs the
phases but prints no result and exits 3.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import urllib.request

import numpy as np

FULL_NODES = 10_000
TASKS_PER_GANG = 8


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


# ---------------------------------------------------------------------------
# compile accounting: JAX's own monitoring events
# ---------------------------------------------------------------------------

class CompileMeter:
    """Counts persistent-cache requests/hits and sums backend compile
    seconds, from the events JAX records itself."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def read(self) -> dict:
        return {"compile_requests": self.requests, "cache_hits": self.hits,
                "cache_misses": self.requests - self.hits,
                "backend_compile_s": round(self.compile_s, 3)}

    def since(self, before: dict) -> dict:
        now = self.read()
        return {k: round(now[k] - before[k], 3) for k in now}


# ---------------------------------------------------------------------------
# the seeded cluster and its mutations
# ---------------------------------------------------------------------------

def saturated_objects(nodes: int, seed: int):
    """bench_e2e's saturated shape: running pods fill every accelerator,
    pending gangs sit in under-served queues, so allocate fails capacity
    and reclaim/preempt/consolidation/stale all have work."""
    from kai_scheduler_tpu.state import make_cluster
    return make_cluster(
        num_nodes=nodes, node_accel=4.0, num_gangs=nodes * 5 // 8,
        tasks_per_gang=TASKS_PER_GANG, running_fraction=0.8,
        queue_accel_quota=nodes / 10.0,
        partition_queues_by_running=True, seed=seed)


def seeded_mutations(objs, seed: int, evictions: list[dict]
                     ) -> tuple[dict, dict]:
    """The two documents posted between cycle 1 and cycle 2.  The
    ``/cluster/delta`` advances the clock, reports the pods cycle 1
    evicted as gone (pipelined placements bind only once the capacity
    has really been released) and deletes the pods of whole running
    gangs; the ``/intake`` creates new pending gangs and deletes a few
    more.  Several hundred seeded pod creates/deletes in all; gangs
    cycle 1 evicted from are left alone."""
    from kai_scheduler_tpu.framework.session import _pow4_ceil
    _nodes, _queues, groups, pods, _topo = objs
    evicted = {ev["pod"] for ev in evictions}
    rng = np.random.default_rng(seed + 1)
    by_gang: dict[str, list] = {}
    for p in pods:
        by_gang.setdefault(p.group, []).append(p)
    untouched = [g.name for g in groups
                 if g.last_start_timestamp is not None
                 and not any(p.name in evicted for p in by_gang[g.name])]
    n_gone = max(2, len(groups) // 180)
    gone = [untouched[i] for i in
            rng.choice(len(untouched), size=n_gone, replace=False)]
    first, second = gone[:n_gone * 2 // 3], gone[n_gone * 2 // 3:]
    pending = [g for g in groups if g.last_start_timestamp is None]
    pending_queues = sorted({g.queue for g in pending})
    # the preempt wavefront's lane width is a static argument bucketed
    # on the pending-gang count in powers of four up to a cap (session.
    # _preempt_lane_width).  Cycle 3 sees only the new gangs pending
    # that cycle 2 could not bind at once (the deleted gangs' capacity
    # takes as many), so there are enough to keep it in cycle 1's
    # bucket — another bucket is another compiled program by design,
    # not a fault
    n_new = min(_pow4_ceil(len(pending)) // 4, 64) + n_gone + 1
    new_groups, new_pods = [], []
    for i in range(n_new):
        name = f"smoke-gang-{i}"
        new_groups.append({
            "name": name, "queue": pending_queues[i % len(pending_queues)],
            "min_member": TASKS_PER_GANG, "creation_timestamp": 1e6 + i})
        new_pods += [{
            "name": f"{name}-pod-{t}", "group": name,
            "resources": {"accel": 1.0, "cpu": 1.0, "memory": 4.0},
            "creation_timestamp": 1e6 + i} for t in range(TASKS_PER_GANG)]
    delta = {"now": 1.0,
             "pods_delete": [ev["pod"] for ev in evictions
                             if ev["move_to"] is None]
             + [p.name for g in first for p in by_gang[g]]}
    intake = {"pod_groups_upsert": new_groups, "pods_upsert": new_pods,
              "pods_delete": [p.name for g in second for p in by_gang[g]]}
    return delta, intake


class HostModel:
    """The cluster as plain NumPy, kept beside the server and never
    shown to it: what the commit sets are checked against."""

    def __init__(self, objs):
        nodes, _queues, groups, pods, _topo = objs
        self.node_ix = {n.name: i for i, n in enumerate(nodes)}
        self.alloc = np.array([[n.allocatable.accel, n.allocatable.cpu,
                                n.allocatable.memory] for n in nodes])
        self.min_member = {g.name: g.min_member for g in groups}
        #: pod -> [gang, request vector, node index or -1 (holds nothing)]
        self.pods = {p.name: [p.group, np.array(
            [p.resources.accel, p.resources.cpu, p.resources.memory]),
            self.node_ix[p.node] if p.node is not None else -1]
            for p in pods}

    def apply_doc(self, doc: dict) -> None:
        for g in doc.get("pod_groups_upsert", []):
            self.min_member[g["name"]] = g["min_member"]
        for p in doc.get("pods_upsert", []):
            r = p["resources"]
            self.pods[p["name"]] = [p["group"], np.array(
                [r["accel"], r["cpu"], r["memory"]]), -1]
        for name in doc.get("pods_delete", []):
            del self.pods[name]

    def check_commit(self, commit: dict) -> None:
        """bound + running - evicted fits every node in every resource;
        every bind and eviction names a real pod and node; no gang is
        bound below ``min_member``."""
        for ev in commit["evictions"]:
            pod = self.pods[ev["pod"]]
            assert pod[2] >= 0, f"evicted {ev['pod']} holds no node"
            pod[2] = (self.node_ix[ev["move_to"]]
                      if ev["move_to"] is not None else -1)
        bound_gangs = set()
        for br in commit["bind_requests"]:
            pod = self.pods[br["pod"]]
            assert pod[2] < 0, f"bound {br['pod']} already holds a node"
            pod[2] = self.node_ix[br["node"]]
            bound_gangs.add(pod[0])
        held = [p for p in self.pods.values() if p[2] >= 0]
        used = np.zeros_like(self.alloc)
        np.add.at(used, [p[2] for p in held], np.stack([p[1] for p in held]))
        over = used > self.alloc + 1e-3
        assert not over.any(), (
            f"{int(over.any(axis=1).sum())} nodes over allocatable")
        members: dict[str, int] = {}
        for gang, _req, node in self.pods.values():
            if node >= 0 and gang in bound_gangs:
                members[gang] = members.get(gang, 0) + 1
        short = [g for g in bound_gangs
                 if members.get(g, 0) < self.min_member[g]]
        assert not short, f"gangs bound below min_member: {short[:5]}"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _http(port: int, path: str, doc: dict | None = None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if doc is None else json.dumps(doc).encode(),
        method="GET" if doc is None else "POST")
    with urllib.request.urlopen(req, timeout=1100) as resp:
        body = resp.read()
        assert resp.status == 200, (path, resp.status)
    return body


def drive_server(phase: str, nodes: int, seed: int, config, meter,
                 device) -> list[dict]:
    """Three HTTP-driven cycles against a server started the way
    ``python -m kai_scheduler_tpu serve`` starts it, over a freshly
    built copy of the seeded cluster (a cycle mutates it).  Returns the
    three commit documents."""
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.framework.server import SchedulerServer
    from kai_scheduler_tpu.runtime.cluster import Cluster

    # two builds of the seeded cluster: the server mutates its own, the
    # host model and the mutations are drawn from one it never sees
    pristine = saturated_objects(nodes, seed)
    model = HostModel(pristine)
    server = SchedulerServer(
        Cluster.from_objects(*saturated_objects(nodes, seed)),
        Scheduler(config), port=0).start()
    commits, misses = [], []
    for cycle in (1, 2, 3):
        before = meter.read()
        t0 = time.perf_counter()
        commit = json.loads(_http(server.port, "/cycle/stored", {}))
        wall = time.perf_counter() - t0
        model.check_commit(commit)
        health = json.loads(_http(server.port, "/healthz"))
        assert health["ok"] and health["last_cycle"]["cycles"] == cycle
        wire = json.loads(_http(server.port, "/debug/wire"))
        misses.append(sum(e["misses"] for e in
                          wire["compile"]["entries"].values()))
        say(phase=phase, cycle=cycle, wall_s=round(wall, 4),
            phase_seconds=health["last_cycle"]["phase_seconds"],
            binds=len(commit["bind_requests"]),
            evictions=len(commit["evictions"]),
            jit_cache_misses=misses[-1], **meter.since(before),
            peak_bytes_in_use=(device.memory_stats() or {}).get(
                "peak_bytes_in_use"))
        commits.append(commit)
        if cycle == 1:
            assert commit["evictions"], "cycle 1 evicted nothing"
            delta, intake = seeded_mutations(pristine, seed,
                                             commit["evictions"])
            _http(server.port, "/cluster/delta", delta)
            out = json.loads(_http(server.port, "/intake", intake))
            assert out["shed"] == 0 and out["accepted"] == out["total"]
            model.apply_doc(delta)
            model.apply_doc(intake)
    # a saturated cluster binds nothing in the cycle that evicts: the
    # placements pipeline onto the victims' capacity and bind in the
    # cycle after the delta reported those pods gone
    assert commits[1]["bind_requests"], "cycle 2 bound nothing"
    metrics = _http(server.port, "/metrics").decode()
    assert "kai_e2e_scheduling_latency_seconds" in metrics
    server.stop()
    # warm-up is cycle 1: nothing compiles after it
    assert misses[-1] == misses[0], (
        f"{phase}: a jit entry compiled anew after cycle 1: {misses}")
    return commits


def phase_precision(seed: int) -> None:
    """Requests that bf16 cannot hold: the five actions on the default
    device and on the CPU backend of this process, same snapshot, same
    fair share.  Decisions equal exactly, pools to f32 rounding."""
    import jax
    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.framework.scheduler import (SchedulerConfig,
                                                       run_actions)
    from kai_scheduler_tpu.framework.session import Session
    from kai_scheduler_tpu.state import make_cluster

    cpu = jax.devices("cpu")[0]

    def odd_cluster(fraction: bool):
        nodes, queues, groups, pods, topo = make_cluster(
            num_nodes=64, node_accel=4.0, node_cpu=63.3, node_mem=250.9,
            num_gangs=40, tasks_per_gang=TASKS_PER_GANG,
            task_cpu=1.37, task_mem=13.7, running_fraction=0.8,
            queue_accel_quota=6.4, partition_queues_by_running=True,
            seed=seed)
        if fraction:
            # pending pods ask for 0.35 of one accelerator each
            for p in pods:
                if p.status == apis.PodStatus.PENDING:
                    p.resources = apis.ResourceVec(0.0, 1.37, 13.7)
                    p.accel_portion = 0.35
        return nodes, queues, groups, pods, topo

    failed = []
    for case, fraction in (("whole", False), ("portion", True)):
        ses = Session.open(*odd_cluster(fraction))
        cfg = ses.config
        fn = jax.jit(functools.partial(
            run_actions, actions=SchedulerConfig().actions,
            num_levels=cfg.num_levels, acfg=cfg.allocate,
            vcfg=cfg.victims, grace_s=cfg.stale_grace_s))
        fair = ses.state.queues.fair_share
        here = fn(ses.state, fair)
        there = fn(jax.device_put(ses.state, cpu),
                   jax.device_put(fair, cpu))
        exact = {k: bool(np.array_equal(np.asarray(getattr(here, k)),
                                        np.asarray(getattr(there, k))))
                 for k in ("placements", "pipelined", "allocated",
                           "victim", "victim_move")}
        drift = {k: float(np.max(np.abs(
            np.asarray(getattr(here, k)) - np.asarray(getattr(there, k)))))
            for k in ("free", "queue_allocated")}
        say(phase="precision", case=case,
            allocated=int(np.asarray(here.allocated).sum()),
            victims=int(np.asarray(here.victim).sum()),
            equal=exact, max_abs_diff=drift)
        # 1e-3 of a CPU core or a GiB: f32 summation order, nothing
        # more; one bf16 pass over 13.7 is already off by 0.0125
        if not (all(exact.values()) and max(drift.values()) < 1e-3
                and np.asarray(here.allocated).any()):
            failed.append(case)
    assert not failed, f"chip and CPU disagree on: {failed}"


def run_one_chip(args, device, meter) -> None:
    from kai_scheduler_tpu.framework.scheduler import SchedulerConfig

    drive_server("classic", args.nodes, args.seed, SchedulerConfig(),
                 meter, device)
    phase_precision(args.seed)


def run_four_chips(args, meter) -> None:
    """Only the node-sharded full cycle and what it is compared with:
    the same program on one chip."""
    import jax
    import __graft_entry__ as graft

    devices = jax.devices()[:4]
    assert len(devices) == 4, f"need 4 devices, found {len(jax.devices())}"
    out = {}
    for name, devs in (("four_chips", devices), ("one_chip", devices[:1])):
        before = meter.read()
        t0 = time.perf_counter()
        out[name] = graft.sharded_cycle(
            devs, saturated_objects(args.nodes, args.seed))
        say(phase=name, wall_s=round(time.perf_counter() - t0, 3),
            allocated=int(out[name][1].sum()),
            victims=int(out[name][2].sum()), **meter.since(before),
            memory=[{"id": d.id, **{k: (d.memory_stats() or {}).get(k)
                                    for k in ("bytes_in_use",
                                              "peak_bytes_in_use")}}
                    for d in devices])
    for i, field in enumerate(("placements", "allocated", "victim")):
        assert np.array_equal(out["four_chips"][i], out["one_chip"][i]), (
            f"{field} differs between four chips and one")
    assert out["one_chip"][1].any() and out["one_chip"][2].any()
    say(phase="four_chips==one_chip", equal=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--nodes", type=int, default=FULL_NODES,
                    help="cluster size; smaller than the default is a "
                         "rehearsal and may run without a TPU")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from kai_scheduler_tpu.runtime import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    # one process, nobody else writing: cache every program, however
    # quick, so that "a warm run compiles nothing" can be checked
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and args.nodes >= FULL_NODES:
        sys.exit(f"chip_smoke: no TPU (JAX found {device.platform})")
    meter = CompileMeter()
    t0 = time.perf_counter()
    say(phase="start", platform=device.platform, kind=device.device_kind,
        count=len(jax.devices()), nodes=args.nodes,
        pods=args.nodes * 5, jax=jax.__version__, cache_dir=cache_dir)
    if args.chips == 4:
        run_four_chips(args, meter)
    else:
        run_one_chip(args, device, meter)
    say(phase="done", total_s=round(time.perf_counter() - t0, 1),
        **meter.read())
    if not on_tpu:
        print("chip_smoke: rehearsal finished; no TPU, so no result",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
