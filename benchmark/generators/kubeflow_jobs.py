"""Training jobs as Kubeflow's Training Operator creates them and
upstream's podgrouper groups them: a pod group with one subgroup per
replica type, each with that type's replica count as its quorum, and
pods labelled ``training.kubeflow.org/job-role``.

``uniform_gangs`` with exactly that added: its nodes and queues and the
five functions it documents are taken from it as they are.  The
configuration gives, beside ``uniform_gangs``' sizes:

``kinds``      the kind of each of ``len(kinds)`` consecutive jobs,
               indexed by the job's creation counter modulo its length —
               never by the seed
``jobs``       ``{kind: [replica type, ...]}``, leader first; a replica
               type is ``{"role", "replicas", "resources"}``

``tasks_per_gang`` is the largest job's pod count and ``running_gangs``
a whole number of rounds of ``kinds`` (``scaled`` keeps it so).  The
seed chooses which nodes a running job holds and the jobs' creation
order; never a count or a shape.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "generators.uniform_gangs",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "uniform_gangs.py"))
_uniform = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_uniform)

arriving_leaves = _uniform.arriving_leaves

ROLE_LABEL = "training.kubeflow.org/job-role"


def kind_of(spec: dict, created: float) -> str:
    """The kind of the job with this creation counter."""
    return spec["kinds"][int(created) % len(spec["kinds"])]


def _pods_of(spec: dict, kind: str) -> int:
    return sum(rt["replicas"] for rt in spec["jobs"][kind])


def gang_docs(name: str, queue: str, spec: dict, created: float,
              node_names: list[str] | None = None) -> tuple[dict, list]:
    """One job's pod group and pods, the leader first; running on
    ``node_names`` (one per pod that asks for an accelerator, the others
    beside the first of them) when given, pending otherwise.  Pods are
    named ``<name>-pod-<t>``."""
    replica_types = spec["jobs"][kind_of(spec, created)]
    group = {"name": name, "queue": queue,
             "min_member": sum(rt["replicas"] for rt in replica_types),
             "sub_groups": [{"name": rt["role"],
                             "min_member": rt["replicas"]}
                            for rt in replica_types],
             "priority": 0, "preemptibility": "Preemptible",
             "phase": "Pending", "creation_timestamp": created,
             "last_start_timestamp": 0.0 if node_names else None}
    pods, slot = [], 0
    for rt in replica_types:
        for _ in range(rt["replicas"]):
            pod = {"name": f"{name}-pod-{len(pods)}", "group": name,
                   "subgroup": rt["role"],
                   "labels": {ROLE_LABEL: rt["role"]},
                   "resources": dict(rt["resources"]), "status": 0,
                   "creation_timestamp": created}
            if node_names:
                pod["status"] = 2
                if rt["resources"]["accel"] > 0:
                    pod["node"] = node_names[slot]
                    slot += 1
                else:
                    pod["node"] = node_names[0]
            pods.append(pod)
    return group, pods


def scaled(spec: dict, nodes: int | None) -> dict:
    """The configuration at a rehearsal's size: ``uniform_gangs``'
    scaling, the running jobs a whole number of rounds of ``kinds``."""
    if nodes is None or nodes == spec["nodes"]:
        return spec
    out = _uniform.scaled(spec, nodes)
    rounds = len(spec["kinds"])
    out["running_gangs"] = max(rounds,
                               out["running_gangs"] // rounds * rounds)
    return out


def shapes(spec: dict) -> dict:
    """``uniform_gangs``' sizes, the pods counted job by job."""
    out = _uniform.shapes(spec)
    rounds, rest = divmod(spec["running_gangs"], len(spec["kinds"]))
    assert rest == 0, "running jobs are whole rounds of kinds"
    out["placed_pods"] = rounds * sum(_pods_of(spec, k)
                                      for k in spec["kinds"])
    return out


def cluster_doc(spec: dict, seed: int) -> dict:
    """The cluster before the first cycle: ``running_gangs`` jobs whose
    accelerator pods are placed round-robin over a seeded permutation of
    the nodes, as ``uniform_gangs`` places its gangs."""
    doc = _uniform.cluster_doc(dict(spec, running_gangs=0), seed)
    rng = np.random.default_rng([seed, 4])
    n = spec["nodes"]
    leaves = _uniform.leaves_of(spec, spec["running_leaves"])
    g_run = spec["running_gangs"]
    node_order = rng.permutation(n)
    created = rng.permutation(g_run)
    taken = 0   # accelerator pods placed so far
    for g in range(g_run):
        accel_pods = sum(
            rt["replicas"]
            for rt in spec["jobs"][kind_of(spec, float(created[g]))]
            if rt["resources"]["accel"] > 0)
        slots = [f"node-{node_order[(taken + t) % n]}"
                 for t in range(accel_pods)]
        taken += accel_pods
        grp, gp = gang_docs(f"gang-{g}", leaves[g % len(leaves)], spec,
                            float(created[g]), slots)
        doc["pod_groups"].append(grp)
        doc["pods"] += gp
    return doc
