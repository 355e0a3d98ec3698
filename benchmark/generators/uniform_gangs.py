"""A cluster of identical nodes and identical gangs, as the wire's own
snapshot document.

A configuration names its generator (``cluster.generator``), and the
harness finds this file by that name.  A generator is NumPy and the
standard library only, and gives:

``scaled(spec, nodes)``        the configuration at a rehearsal's size
``cluster_doc(spec, seed)``    the document ``POST /cluster`` and ``python
                               -m kai_scheduler_tpu serve --snapshot``
                               take (``version`` 1)
``gang_docs(...)``             one pod group and its pods, for the churn
``arriving_leaves(spec)``      the leaf queues new gangs join
``shapes(spec)``               the cluster's logical sizes, for
                               ``lib/solve_bytes.py``

The seed chooses *which* — the node permutation the running gangs occupy
and the gangs' creation order — and never a count or a shape: every seed
gives the same sizes.
"""
from __future__ import annotations

import numpy as np

RESOURCES = ("accel", "cpu", "memory")


def _queue_resource(quota: float = -1.0) -> dict:
    return {"quota": quota, "over_quota_weight": 1.0, "limit": -1.0}


def leaf_names(spec: dict) -> list[str]:
    return [f"queue-{d}-{j}" for d in range(spec["departments"])
            for j in range(spec["queues_per_department"])]


def leaves_of(spec: dict, which: str) -> list[str]:
    """``all``, ``first_half`` or ``second_half`` of the leaf queues."""
    leaves = leaf_names(spec)
    half = len(leaves) // 2
    return {"all": leaves, "first_half": leaves[:half],
            "second_half": leaves[half:]}[which]


def arriving_leaves(spec: dict) -> list[str]:
    return leaves_of(spec, spec["arriving_leaves"])


def gang_docs(name: str, queue: str, spec: dict, created: float,
              node_names: list[str] | None = None) -> tuple[dict, list]:
    """One pod group and its pods; running on ``node_names`` when given,
    pending otherwise.  Pods are named ``<name>-pod-<t>``."""
    group = {"name": name, "queue": queue,
             "min_member": spec["tasks_per_gang"], "priority": 0,
             "preemptibility": "Preemptible", "phase": "Pending",
             "creation_timestamp": created,
             "last_start_timestamp": 0.0 if node_names else None}
    pods = []
    for t in range(spec["tasks_per_gang"]):
        pod = {"name": f"{name}-pod-{t}", "group": name,
               "resources": dict(spec["task"]), "status": 0,
               "creation_timestamp": created}
        if node_names:
            pod["status"] = 2
            pod["node"] = node_names[t]
        pods.append(pod)
    return group, pods


def scaled(spec: dict, nodes: int | None) -> dict:
    """The configuration at another node count (``--nodes``, a rehearsal):
    gangs, tenants and quota shrink in proportion, shapes stay."""
    if nodes is None or nodes == spec["nodes"]:
        return spec
    k = nodes / spec["nodes"]
    out = dict(spec, nodes=nodes,
               running_gangs=max(1, int(spec["running_gangs"] * k)),
               queues_per_department=max(
                   2, round(spec["queues_per_department"] * k)))
    if spec["leaf_quota_accel"] is not None:
        out["leaf_quota_accel"] = spec["leaf_quota_accel"] * k
    return out


def shapes(spec: dict) -> dict:
    return {"nodes": spec["nodes"], "gangs": spec["running_gangs"],
            "tasks_per_gang": spec["tasks_per_gang"],
            "placed_pods": spec["running_gangs"] * spec["tasks_per_gang"],
            "queues": spec["departments"] * (1 + spec["queues_per_department"]),
            "resources": len(RESOURCES)}


def cluster_doc(spec: dict, seed: int) -> dict:
    """The cluster before the first cycle: ``running_gangs`` gangs placed
    round-robin over a seeded permutation of the nodes."""
    rng = np.random.default_rng([seed, 1])
    n = spec["nodes"]
    nodes = [{"name": f"node-{i}", "allocatable": dict(spec["node"]),
              "labels": {"kubernetes.io/hostname": f"node-{i}"}}
             for i in range(n)]
    leaves = leaf_names(spec)
    quota = spec["leaf_quota_accel"]
    if quota is None:
        quota = n * spec["node"]["accel"] / len(leaves)
    per_dept = spec["queues_per_department"]
    queues = [{"name": f"dept-{d}", "parent": None,
               "accel": _queue_resource(quota * per_dept),
               "cpu": _queue_resource(), "memory": _queue_resource(),
               "creation_timestamp": float(d)}
              for d in range(spec["departments"])]
    queues += [{"name": leaf, "parent": f"dept-{i // per_dept}",
                "accel": _queue_resource(quota),
                "cpu": _queue_resource(), "memory": _queue_resource(),
                "creation_timestamp": float(i)}
               for i, leaf in enumerate(leaves)]

    running_in = leaves_of(spec, spec["running_leaves"])
    g_run = spec["running_gangs"]
    tasks = spec["tasks_per_gang"]
    node_order = rng.permutation(n)
    created = rng.permutation(g_run)
    groups, pods = [], []
    for g in range(g_run):
        slots = [f"node-{node_order[(g * tasks + t) % n]}"
                 for t in range(tasks)]
        grp, gp = gang_docs(f"gang-{g}", running_in[g % len(running_in)],
                            spec, float(created[g]), slots)
        groups.append(grp)
        pods += gp
    return {"version": 1, "now": 0.0, "nodes": nodes, "queues": queues,
            "pod_groups": groups, "pods": pods, "topology": None}
