"""The plain subgroup reference: what a declared subgroup's quorum and a
gang of unequal pods ask of a commit.

A pod group may declare subgroups, each with a ``min_member``, and each
pod names the subgroup it belongs to (upstream's podgrouper makes one
per replica type of a Kubeflow job).  The guarantee: a gang is bound
only with every declared subgroup at its quorum, and a pending gang of
unequal pods that fits the free capacity is bound in that cycle.

``lib/host_model.py`` decides ``correct``, knows no subgroup and claims
nothing for a gang whose pods differ in request; until it does,
``layer_metrics/subgroup_violations.py`` counts through this file,
beside it.  Plain Python and NumPy over the wire's own documents; it
imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

RES = ("accel", "cpu", "memory")
EPS = 1e-3


def _vec(d: dict) -> np.ndarray:
    return np.array([d[r] for r in RES], dtype=np.float64)


def first_fit(free: np.ndarray, requests: list) -> np.ndarray | None:
    """What is left of ``free`` ([N, R]) once every request has taken
    the first node that holds it, the largest request first; ``None``
    where some request finds no node.  A sufficient test of fitting: it
    never says a gang fits that cannot be placed."""
    left = free.copy()
    for req in sorted(requests, key=lambda r: tuple(-r)):
        rows = np.flatnonzero((left + EPS >= req).all(axis=1))
        if not len(rows):
            return None
        left[rows[0]] -= req
    return left


class SubgroupModel:
    """Follows nodes, gangs and pods through the documents posted and
    counts, commit by commit, the gangs bound below a subgroup's quorum
    and the gangs of unequal pods left pending though they fit."""

    def __init__(self, cluster: dict):
        self.node_ix = {n["name"]: i for i, n in enumerate(cluster["nodes"])}
        self.alloc = np.stack([_vec(n["allocatable"])
                               for n in cluster["nodes"]])
        self.used = np.zeros_like(self.alloc)
        #: gang -> [{subgroup: min_member}, creation, pod names]
        self.gangs: dict = {}
        #: pod -> [gang, subgroup, request, node index or -1]
        self.pods: dict = {}
        self.apply_doc({"pod_groups_upsert": cluster["pod_groups"],
                        "pods_upsert": cluster["pods"]})

    def apply_doc(self, doc: dict) -> None:
        for g in doc.get("pod_groups_upsert", []):
            quorums = {s["name"]: s["min_member"]
                       for s in g.get("sub_groups", [])}
            self.gangs[g["name"]] = [quorums, g["creation_timestamp"], set()]
        for p in doc.get("pods_upsert", []):
            node = self.node_ix[p["node"]] if p.get("node") else -1
            req = _vec(p["resources"])
            self.pods[p["name"]] = [p["group"], p.get("subgroup"), req, node]
            self.gangs[p["group"]][2].add(p["name"])
            if node >= 0:
                self.used[node] += req
        for name in doc.get("pods_delete", []):
            gang, _sub, req, node = self.pods.pop(name)
            self.gangs[gang][2].discard(name)
            if node >= 0:
                self.used[node] -= req
        for name in doc.get("pod_groups_delete", []):
            del self.gangs[name]

    def check_commit(self, commit: dict) -> dict:
        """Apply one commit, then count.  An evicted pod holds its node
        until it is reported deleted; a moved one changes node.  Names
        the documents do not know are the other reference's to count
        (``dangling_names``)."""
        for ev in commit["evictions"]:
            pod = self.pods.get(ev["pod"])
            if pod and pod[3] >= 0 and ev.get("move_to") in self.node_ix:
                self.used[pod[3]] -= pod[2]
                pod[3] = self.node_ix[ev["move_to"]]
                self.used[pod[3]] += pod[2]
        bound = set()
        for br in commit["bind_requests"]:
            pod = self.pods.get(br["pod"])
            node = self.node_ix.get(br["node"], -1)
            if pod is None or pod[3] >= 0 or node < 0:
                continue
            pod[3] = node
            self.used[node] += pod[2]
            bound.add(pod[0])
        below = 0
        for gang in bound:
            quorums, _created, names = self.gangs[gang]
            placed: dict = {}
            for p in names:
                if self.pods[p][3] >= 0:
                    sub = self.pods[p][1]
                    placed[sub] = placed.get(sub, 0) + 1
            below += any(placed.get(sub, 0) < need
                         for sub, need in quorums.items())
        # gangs of unequal pods still pending, oldest first, each fitted
        # into what the ones before it left
        free = np.maximum(self.alloc - self.used, 0.0)
        mixed_pending = mixed_left = 0
        for _quorums, _created, names in sorted(
                (g for g in self.gangs.values()
                 if g[2] and all(self.pods[p][3] < 0 for p in g[2])),
                key=lambda g: g[1]):
            reqs = [self.pods[p][2] for p in sorted(names)]
            if all((r == reqs[0]).all() for r in reqs):
                continue
            mixed_pending += 1
            left = first_fit(free, reqs)
            if left is not None:
                free = left
                mixed_left += 1
        return {"bound_gangs": len(bound), "below_quorum": below,
                "mixed_pending": mixed_pending,
                "mixed_left_pending": mixed_left}
