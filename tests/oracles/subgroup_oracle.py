"""The plain subgroup reference for the tests: what a cycle owes a job
whose pod group declares subgroups and whose pods may differ.

A Kubeflow job reaches the scheduler as one pod group with a subgroup
per replica type (``min_member`` = that type's replicas) and pods that
name their subgroup and carry the operator's job-role label.  NumPy over
the wire's own documents (``POST /cluster``, ``/intake``, the commit of
``/cycle/stored``); it imports nothing of the package.

* a gang is bound whole or not at all, with every declared subgroup at
  its quorum, its leader (job-role ``master`` or ``launcher``) first in
  task order — a commit lists a gang's binds in task order — and no
  node over its allocatable in any resource;
* a pending gang **fits** when the free capacity holds all of it even
  after every other pending pod has taken a place of the gang's largest
  request, so whatever order and nodes the scheduler chooses it must be
  bound; it **cannot fit** when the cluster's free capacity in some
  resource is short of its total request, or one of its pods fits on no
  node, so it must stay pending, whole; between the two the oracle says
  nothing.
"""
from __future__ import annotations

import numpy as np

RESOURCES = ("accel", "cpu", "memory")
ROLE_LABEL = "training.kubeflow.org/job-role"
LEADERS = ("master", "launcher")
EPS = 1e-6

ZERO = {"split": 0, "below_quorum": 0, "leader_late": 0,
        "over_capacity": 0, "unbound": 0, "wrongly_bound": 0}


def _vec(d: dict) -> np.ndarray:
    return np.array([d[r] for r in RESOURCES], dtype=np.float64)


class Oracle:
    """The cluster as documents.  ``apply`` follows what was posted,
    ``judge`` holds one commit against it and then applies it."""

    def __init__(self, cluster: dict):
        self.node_ix = {n["name"]: i for i, n in enumerate(cluster["nodes"])}
        self.alloc = np.stack([_vec(n["allocatable"])
                               for n in cluster["nodes"]])
        self.pods: dict = {}          # name -> pod document
        self.node_of: dict = {}       # name -> node it holds
        self.groups: dict = {}        # name -> pod group document
        self.apply({"pod_groups_upsert": cluster["pod_groups"],
                    "pods_upsert": cluster["pods"]})

    def apply(self, doc: dict) -> None:
        for g in doc.get("pod_groups_upsert", []):
            self.groups[g["name"]] = g
        for p in doc.get("pods_upsert", []):
            self.pods[p["name"]] = p
            if p.get("node"):
                self.node_of[p["name"]] = p["node"]
        for name in doc.get("pods_delete", []):
            del self.pods[name]
            self.node_of.pop(name, None)
        for name in doc.get("pod_groups_delete", []):
            del self.groups[name]

    # -- what a cycle owes --------------------------------------------------

    def free(self) -> np.ndarray:
        used = np.zeros_like(self.alloc)
        for name, node in self.node_of.items():
            used[self.node_ix[node]] += _vec(self.pods[name]["resources"])
        return self.alloc - used

    def pending_gangs(self) -> dict:
        """gang -> its pods' documents, for gangs none of whose pods
        holds a node."""
        by_gang: dict = {}
        for pod in self.pods.values():
            by_gang.setdefault(pod["group"], []).append(pod)
        return {g: pods for g, pods in by_gang.items()
                if not any(p["name"] in self.node_of for p in pods)}

    def verdicts(self) -> dict:
        """gang -> ``"fits"`` | ``"cannot"`` | ``"either"`` for every
        pending gang."""
        pending = self.pending_gangs()
        free = np.maximum(self.free(), 0.0)
        out = {}
        for gang, pods in pending.items():
            reqs = np.stack([_vec(p["resources"]) for p in pods])
            rivals = sum(len(o) for g, o in pending.items() if g != gang)
            largest = reqs.max(axis=0)
            slots = int(np.floor(
                free[:, largest > 0] / largest[largest > 0] + EPS
            ).min(axis=1).sum())
            homeless = any(not (free + EPS >= r).all(axis=1).any()
                           for r in reqs)
            if homeless or (free.sum(axis=0) + EPS < reqs.sum(axis=0)).any():
                out[gang] = "cannot"
            elif slots - rivals >= len(pods):
                out[gang] = "fits"
            else:
                out[gang] = "either"
        return out

    # -- one commit -----------------------------------------------------------

    def judge(self, commit: dict) -> dict:
        """Counts of what the commit got wrong (all 0 on a sound one),
        then the commit applied."""
        verdict = self.verdicts()
        counts = dict(ZERO)
        by_gang: dict = {}            # gang -> its binds, in commit order
        for b in commit["bind_requests"]:
            by_gang.setdefault(self.pods[b["pod"]]["group"], []).append(b)
        for gang, binds in by_gang.items():
            group = self.groups[gang]
            members = [p for p in self.pods.values() if p["group"] == gang]
            if len(binds) != len(members) or len(binds) < group["min_member"]:
                counts["split"] += 1
            placed: dict = {}
            for b in binds:
                sub = self.pods[b["pod"]].get("subgroup")
                placed[sub] = placed.get(sub, 0) + 1
            counts["below_quorum"] += any(
                placed.get(s["name"], 0) < s["min_member"]
                for s in group.get("sub_groups", []))
            has_leader = any(p.get("labels", {}).get(ROLE_LABEL) in LEADERS
                             for p in members)
            first = self.pods[binds[0]["pod"]].get("labels", {})
            counts["leader_late"] += (
                has_leader and first.get(ROLE_LABEL) not in LEADERS)
            counts["wrongly_bound"] += verdict.get(gang) == "cannot"
        counts["unbound"] = sum(1 for g, v in verdict.items()
                                if v == "fits" and g not in by_gang)
        for b in commit["bind_requests"]:
            self.node_of[b["pod"]] = b["node"]
        counts["over_capacity"] = int(
            (self.free() < -EPS).any(axis=1).sum())
        return counts
