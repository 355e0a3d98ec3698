"""Served cycles against the plain subgroup oracle
(``tests/oracles/subgroup_oracle.py``).

Clusters of training jobs as Kubeflow's operators create them and the
podgrouper groups them: PyTorchJobs (a master and workers, every pod
with an accelerator) and MPIJobs (a launcher that asks for no
accelerator, and workers), each one pod group with a subgroup per
replica type.  One such job and the session's ``uniform_tasks`` is
false: allocate and every victim placement run the per-task kernel.
The snapshot's patch carries a declared subgroup (each pod's slot, each
gang's ``[S]`` quorum rows), so after the cold build every refresh is a
patch, and ``verify_incremental`` holds each one to a fresh build.  The
default wavefront and the ``B=1`` sequential scan are both held to the
oracle, and to each other.
"""
import functools
import json
import urllib.request

import numpy as np
import pytest

from oracles import subgroup_oracle as oracle

from kai_scheduler_tpu.framework.scheduler import Scheduler, SchedulerConfig
from kai_scheduler_tpu.framework.server import SchedulerServer
from kai_scheduler_tpu.framework.session import SessionConfig
from kai_scheduler_tpu.ops.allocate import AllocateConfig
from kai_scheduler_tpu.ops.victims import VictimConfig
from kai_scheduler_tpu.runtime.snapshot import load_cluster

WORKER = {"accel": 1.0, "cpu": 1.0, "memory": 4.0}
#: kind -> [(role, replicas, request)], the leader first
JOBS = {"pytorch": [("master", 1, WORKER), ("worker", 3, WORKER)],
        "mpi": [("launcher", 1, {"accel": 0.0, "cpu": 1.0, "memory": 2.0}),
                ("worker", 4, WORKER)]}
NODE = {"accel": 4.0, "cpu": 8.0, "memory": 64.0}


def _job(name, kind, queue, created, node=None):
    """One job's pod group and pods.  The leader's pod is named so that
    it sorts LAST by name: only the job-role label can put it first."""
    replica_types = JOBS[kind]
    total = sum(n for _role, n, _req in replica_types)
    group = {"name": name, "queue": queue, "min_member": total,
             "sub_groups": [{"name": role, "min_member": n}
                            for role, n, _req in replica_types],
             "priority": 0, "preemptibility": "Preemptible",
             "phase": "Pending", "creation_timestamp": created,
             "last_start_timestamp": 0.0 if node else None}
    pods = []
    for role, n, req in replica_types:
        for _ in range(n):
            t = total - 1 - len(pods)
            pod = {"name": f"{name}-pod-{t}", "group": name,
                   "subgroup": role, "status": 0, "resources": dict(req),
                   "labels": {oracle.ROLE_LABEL: role},
                   "creation_timestamp": created}
            if node:
                pod["status"], pod["node"] = 2, node
            pods.append(pod)
    return group, pods


def _hog(i, node, cpu):
    """A running pod that holds ``cpu`` of a node and no accelerator."""
    name = f"hog-{i}"
    group = {"name": name, "queue": "team-0", "min_member": 1,
             "priority": 0, "preemptibility": "NonPreemptible",
             "phase": "Running", "creation_timestamp": 0.0,
             "last_start_timestamp": 0.0}
    pod = {"name": f"{name}-pod-0", "group": name, "status": 2,
           "node": node, "creation_timestamp": 0.0,
           "resources": {"accel": 0.0, "cpu": cpu, "memory": 1.0}}
    return group, pod


def _cluster(seed, nodes=64, shape="half"):
    """Nodes of 4 accelerators and 8 CPUs.  ``half``: running
    PyTorchJobs hold every second node of a seeded order whole, the rest
    are free.  ``full``: they hold every node.  ``no_cpu``: as ``half``,
    and a pod without accelerator holds all the CPU that is left on
    every node but one, which keeps 4 accelerators and 4 CPUs."""
    rng = np.random.default_rng(seed)
    order = [int(i) for i in rng.permutation(nodes)]
    node_docs = [{"name": f"node-{i}", "allocatable": dict(NODE),
                  "labels": {"kubernetes.io/hostname": f"node-{i}"}}
                 for i in range(nodes)]
    res = {"quota": -1.0, "over_quota_weight": 1.0, "limit": -1.0}
    queues = [{"name": "dept", "parent": None, "accel": dict(res),
               "cpu": dict(res), "memory": dict(res),
               "creation_timestamp": 0.0}]
    queues += [{"name": f"team-{j}", "parent": "dept", "accel": dict(res),
                "cpu": dict(res), "memory": dict(res),
                "creation_timestamp": float(j)} for j in range(4)]
    held = order if shape == "full" else order[::2]
    groups, pods = [], []
    for g, i in enumerate(held):
        grp, gp = _job(f"run-{g}", "pytorch", f"team-{g % 4}", float(g),
                       f"node-{i}")
        groups.append(grp)
        pods += gp
    if shape == "no_cpu":
        spare = order[1]
        for i in range(nodes):
            cpu = 4.0 if i in held or i == spare else 8.0
            grp, pod = _hog(i, f"node-{i}", cpu)
            groups.append(grp)
            pods.append(pod)
    return {"version": 1, "now": 0.0, "nodes": node_docs, "queues": queues,
            "pod_groups": groups, "pods": pods, "topology": None}


def _arrivals(cycle, kinds, created0):
    groups, pods = [], []
    for i, kind in enumerate(kinds):
        grp, gp = _job(f"job-{cycle}-{i}-{kind}", kind, f"team-{i % 4}",
                       float(created0 + i))
        groups.append(grp)
        pods += gp
    return {"pod_groups_upsert": groups, "pods_upsert": pods}


#: the ``B=1`` sequential scan, the judge of every wavefront
SCAN = SessionConfig(
    allocate=AllocateConfig(batch_size=1),
    victims=VictimConfig(batch_size=1, batch_size_preempt=1,
                         placement=AllocateConfig(batch_size=1)))


def _post(base, path, doc):
    req = urllib.request.Request(base + path, data=json.dumps(doc).encode(),
                                 headers={"Content-Type": "application/json"})
    return json.load(urllib.request.urlopen(req))


@functools.lru_cache(maxsize=None)
def _served(seed, nodes=64, shape="half", scan=False,
            kinds=("pytorch", "pytorch", "mpi", "pytorch")):
    """Three served cycles over the seeded cluster.  Returns per cycle
    the oracle's verdicts and counts, the commit, and ``/healthz``'s
    ``last_cycle``."""
    doc = _cluster(seed, nodes, shape)
    model = oracle.Oracle(doc)
    config = SchedulerConfig(verify_incremental=True,
                             **({"session": SCAN} if scan else {}))
    server = SchedulerServer(load_cluster(doc), Scheduler(config),
                             port=0).start()
    base = f"http://127.0.0.1:{server.port}"
    rng = np.random.default_rng([seed, 7])
    out = []
    try:
        for cycle in range(1, 4):
            delta = {"now": float(cycle)}
            if shape == "half":   # one running job finishes
                done = f"run-{cycle - 1}"
                delta.update(pod_groups_delete=[done], pods_delete=[
                    f"{done}-pod-{t}" for t in range(4)])
            order = [kinds[i] for i in rng.permutation(len(kinds))]
            intake = _arrivals(cycle, order, 1000 * cycle)
            _post(base, "/cluster/delta", delta)
            accepted = _post(base, "/intake", intake)
            assert accepted["shed"] == 0
            assert accepted["accepted"] == accepted["total"]
            model.apply(delta)
            model.apply(intake)
            commit = _post(base, "/cycle/stored", {})
            verdict = model.verdicts()
            counts = model.judge(commit)
            health = json.load(urllib.request.urlopen(
                f"{base}/healthz"))["last_cycle"]
            out.append({"counts": counts, "commit": commit,
                        "verdict": verdict, "health": health})
    finally:
        server.stop()
    return out


def _bound_gangs(commit):
    return {b["pod"].rsplit("-pod-", 1)[0] for b in commit["bind_requests"]}


CASES = [(0, 64), (1, 64), (2, 64), (3, 128), (4, 256)]


@pytest.mark.parametrize("scan", [False, True], ids=["wavefront", "scan"])
@pytest.mark.parametrize("seed,nodes", CASES)
def test_every_job_that_fits_is_bound_whole(seed, nodes, scan):
    """Four jobs arrive a cycle, three PyTorchJobs and an MPIJob, into a
    half-empty cluster: each is bound whole in its cycle, its master or
    launcher first, every subgroup at its quorum, no node over."""
    for n, c in enumerate(_served(seed, nodes, scan=scan)):
        assert c["counts"] == oracle.ZERO, (n, c["verdict"])
        assert c["commit"]["evictions"] == []
        assert sorted(c["verdict"].values()) == ["fits"] * 4, c["verdict"]
        assert len(c["commit"]["bind_requests"]) == 3 * 4 + 5
        first = {}
        for b in c["commit"]["bind_requests"]:
            first.setdefault(b["pod"].rsplit("-pod-", 1)[0], b["pod"])
        # the leader's pod sorts last by name and is bound first
        assert sorted(first.values()) == sorted(
            f"{g}-pod-{4 if g.endswith('-mpi') else 3}" for g in first)


@pytest.mark.parametrize("seed,nodes", CASES)
def test_served_cycles_run_the_per_task_kernel_and_patch_after_the_cold_cycle(
        seed, nodes):
    """What the session chose is served: the per-task kernel at the
    auto-tuned lane width, over padded task and subgroup axes that hold
    the MPIJob's five pods and two subgroups; after the cold build
    every refresh is a patch that equals a fresh build
    (``verify_incremental``), and the oracle finds nothing."""
    cycles = _served(seed, nodes)
    for n, c in enumerate(cycles):
        assert c["counts"] == oracle.ZERO, (n, c["verdict"])
        k = c["health"]["kernels"]
        assert k["uniform_tasks"] is False and k["track_devices"] is False
        assert k["dense_feasibility"] is True
        assert k["pending_gangs"] == 4 and k["allocate_lanes"] > 1
        assert k["tasks"] >= 5 and k["subgroups"] >= 3
        snap = c["health"]["snapshot"]
        assert snap["nonplain_pods"] == snap["nonplain_gangs"] == 0
        if n == 0:
            assert snap["mode"] == "full"
            assert snap["fallback_reason"] == "cold"
            continue
        assert snap["mode"] == "patched" and snap["fallback_reason"] == ""
        # every job but the one that finished, the binds of the cycles
        # before and this cycle's arrivals: every pod names a subgroup
        assert snap["subgrouped_gangs"] == nodes // 2 + 3 * (n + 1)
        assert snap["subgrouped_pods"] == 4 * (nodes // 2) + 13 * (n + 1)


@pytest.mark.parametrize("seed,nodes", CASES[:3])
def test_the_scan_binds_the_same_pods(seed, nodes):
    """Lanes break score ties apart by design, so the node a pod takes
    may differ; which pods are bound, and in what task order, may
    not."""
    for w, s in zip(_served(seed, nodes), _served(seed, nodes, scan=True)):
        assert ([b["pod"] for b in w["commit"]["bind_requests"]]
                == [b["pod"] for b in s["commit"]["bind_requests"]])


@pytest.mark.parametrize("scan", [False, True], ids=["wavefront", "scan"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_no_cpu_for_the_launcher_leaves_the_job_pending_whole(seed, scan):
    """Accelerators are free on half the nodes and CPU on one alone, 4
    of it: an MPIJob's four workers would fit there, its launcher
    nowhere, so the job stays pending whole, workers too, in every
    cycle; the PyTorchJob beside it takes the node in the first."""
    cycles = _served(seed, shape="no_cpu", scan=scan,
                     kinds=("mpi", "pytorch"))
    for n, c in enumerate(cycles):
        assert c["counts"] == oracle.ZERO, (n, c["verdict"])
        waiting = {g for g, v in c["verdict"].items() if v == "cannot"}
        assert {g for g in c["verdict"] if g.endswith("-mpi")} <= waiting
        bound = _bound_gangs(c["commit"])
        assert not bound & waiting
        assert not any("-mpi-pod-" in b["pod"]
                       for b in c["commit"]["bind_requests"])
        assert len(bound) == (1 if n == 0 else 0)
    assert all(g.endswith("-pytorch")
               for g in _bound_gangs(cycles[0]["commit"]))


@pytest.mark.parametrize("scan", [False, True], ids=["wavefront", "scan"])
@pytest.mark.parametrize("seed", [0, 1])
def test_a_full_cluster_leaves_every_job_pending(seed, scan):
    for c in _served(seed, shape="full", scan=scan):
        assert c["counts"] == oracle.ZERO
        assert set(c["verdict"].values()) == {"cannot"}
        assert c["commit"]["bind_requests"] == []
        assert c["commit"]["evictions"] == []


# -- the oracle's own semantics ------------------------------------------

def _tiny(free_cpu):
    """One node with 8 accelerators and ``free_cpu`` CPUs, one pending
    MPIJob."""
    node = {"name": "n", "allocatable": dict(NODE, accel=8.0, cpu=free_cpu)}
    group, pods = _job("j", "mpi", "q", 0.0)
    return oracle.Oracle({"nodes": [node], "pod_groups": [group],
                          "pods": pods})


@pytest.mark.parametrize("free_cpu,expected",
                         [(8.0, "fits"), (5.0, "fits"), (4.0, "cannot")])
def test_oracle_counts_the_launchers_cpu(free_cpu, expected):
    assert _tiny(free_cpu).verdicts() == {"j": expected}


@pytest.mark.parametrize("drop,expected", [
    (None, oracle.ZERO),
    ("j-pod-4", dict(oracle.ZERO, split=1, below_quorum=1, leader_late=1)),
    ("j-pod-0", dict(oracle.ZERO, split=1, below_quorum=1)),
])
def test_oracle_sees_a_subgroup_below_quorum(drop, expected):
    model = _tiny(8.0)
    binds = [{"pod": f"j-pod-{t}", "node": "n"} for t in (4, 3, 2, 1, 0)
             if f"j-pod-{t}" != drop]
    assert model.judge({"bind_requests": binds}) == expected


def test_oracle_sees_a_leader_bound_late_and_a_node_over():
    model = _tiny(4.0)
    binds = [{"pod": f"j-pod-{t}", "node": "n"} for t in range(5)]
    assert model.judge({"bind_requests": binds}) == dict(
        oracle.ZERO, leader_late=1, over_capacity=1, wrongly_bound=1)
