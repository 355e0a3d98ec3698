"""The plain topology reference: what a required and a preferred level
ask of a commit.

The cluster document names a tree (``topology``: its levels are node
label keys, outermost first), every node carries the labels, and a pod
group may ask with its ``topology_constraint`` for a required level
(all its pods inside one domain of that level, or not bound) and a
preferred one (best effort).  A domain is a label path from the
outermost level down, so two racks of the same name in different blocks
are different domains.  The guarantee: a gang with a required level is
bound only inside one domain of it, and a pending gang of equal pods for
which some domain of its required level has room for all of it is bound
in that cycle.

``lib/host_model.py`` decides ``correct`` and knows no tree, and the
harness builds it by that name (``lib/loop.py``: ``Run.judge``), so
``TreeHostModel`` is that reference with a ``TopologyModel`` fed beside
it: the two counts of the guarantee join the numbers compared, each
with limit 0, and each cycle's tally carries the tree's under
``"topology"`` for the per-layer readers.  ``generators/topology_tree.py``
puts it in ``HostModel``'s place.  Plain Python and NumPy over the
wire's own documents; it imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from . import host_model

RES = ("accel", "cpu", "memory")
EPS = 1e-3


def _vec(d: dict) -> np.ndarray:
    return np.array([d[r] for r in RES], dtype=np.float64)


def replicas(free: np.ndarray, req: np.ndarray) -> np.ndarray:
    """How many pods of ``req`` each node's ``free`` ([N, R]) holds."""
    per = np.where(req > 0, (free + EPS) / np.where(req > 0, req, 1.0),
                   np.inf)
    return np.floor(per.min(axis=1)).clip(min=0).astype(np.int64)


class TopologyModel:
    """Follows nodes, gangs and pods through the documents posted and
    counts, commit by commit, the gangs bound across domains of their
    required level, the gangs of equal pods left pending though a domain
    of that level had room, and the gangs bound inside one domain of
    their preferred level."""

    def __init__(self, cluster: dict):
        nodes = cluster["nodes"]
        self.node_ix = {n["name"]: i for i, n in enumerate(nodes)}
        self.alloc = np.stack([_vec(n["allocatable"]) for n in nodes])
        self.used = np.zeros_like(self.alloc)
        topo = cluster.get("topology") or {"name": None, "levels": []}
        self.tree, self.levels = topo["name"], list(topo["levels"])
        #: node -> domain id at each level (-1 where a label is missing,
        #: there and below); ids are dense over all levels
        self.dom = np.full((len(nodes), max(1, len(self.levels))), -1,
                           np.int64)
        ids: dict = {}
        for i, n in enumerate(nodes):
            path: tuple = ()
            for lvl, key in enumerate(self.levels):
                if key not in n.get("labels", {}):
                    break
                path += (n["labels"][key],)
                self.dom[i, lvl] = ids.setdefault(path, len(ids))
        self.domains = len(ids)
        #: gang -> [required level, preferred level, creation, pod names]
        self.gangs: dict = {}
        #: pod -> [gang, request, node index or -1]
        self.pods: dict = {}
        self.apply_doc({"pod_groups_upsert": cluster["pod_groups"],
                        "pods_upsert": cluster["pods"]})

    def _level(self, tc: dict | None, attr: str) -> int:
        if not tc or tc.get("topology") not in (None, self.tree):
            return -1
        name = tc.get(attr)
        return self.levels.index(name) if name in self.levels else -1

    def apply_doc(self, doc: dict) -> None:
        for g in doc.get("pod_groups_upsert", []):
            tc = g.get("topology_constraint")
            self.gangs[g["name"]] = [
                self._level(tc, "required_level"),
                self._level(tc, "preferred_level"),
                g["creation_timestamp"], set()]
        for p in doc.get("pods_upsert", []):
            node = self.node_ix[p["node"]] if p.get("node") else -1
            req = _vec(p["resources"])
            self.pods[p["name"]] = [p["group"], req, node]
            self.gangs[p["group"]][3].add(p["name"])
            if node >= 0:
                self.used[node] += req
        for name in doc.get("pods_delete", []):
            gang, req, node = self.pods.pop(name)
            self.gangs[gang][3].discard(name)
            if node >= 0:
                self.used[node] -= req
        for name in doc.get("pod_groups_delete", []):
            del self.gangs[name]

    def _one_domain(self, names, level: int) -> bool:
        """All placed pods of ``names`` on nodes of one domain of
        ``level``; a node without the level's label is in none."""
        held = {int(self.dom[self.pods[p][2], level]) for p in names
                if self.pods[p][2] >= 0}
        return len(held) == 1 and -1 not in held

    def check_commit(self, commit: dict) -> dict:
        """Apply one commit, then count.  An evicted pod holds its node
        until it is reported deleted; a moved one changes node.  Names
        the documents do not know are the other reference's to count
        (``dangling_names``)."""
        for ev in commit["evictions"]:
            pod = self.pods.get(ev["pod"])
            if pod and pod[2] >= 0 and ev.get("move_to") in self.node_ix:
                self.used[pod[2]] -= pod[1]
                pod[2] = self.node_ix[ev["move_to"]]
                self.used[pod[2]] += pod[1]
        bound = set()
        for br in commit["bind_requests"]:
            pod = self.pods.get(br["pod"])
            node = self.node_ix.get(br["node"], -1)
            if pod is None or pod[2] >= 0 or node < 0:
                continue
            pod[2] = node
            self.used[node] += pod[1]
            bound.add(pod[0])
        tally = {"bound_gangs": len(bound), "required_bound": 0,
                 "required_split": 0, "preferred_bound": 0,
                 "preferred_together": 0, "required_pending": 0,
                 "domain_left_pending": 0}
        for gang in bound:
            req_lvl, pref_lvl, _created, names = self.gangs[gang]
            if req_lvl >= 0:
                tally["required_bound"] += 1
                tally["required_split"] += not self._one_domain(names,
                                                                req_lvl)
            if pref_lvl >= 0:
                tally["preferred_bound"] += 1
                tally["preferred_together"] += self._one_domain(names,
                                                                pref_lvl)
        # gangs of equal pods with a required level still pending, oldest
        # first: each into the fullest domain of its level that holds
        # all of it, out of what the ones before it left
        free = np.maximum(self.alloc - self.used, 0.0)
        for req_lvl, _pref, _created, names in sorted(
                (g for g in self.gangs.values()
                 if g[0] >= 0 and g[3]
                 and all(self.pods[p][2] < 0 for p in g[3])),
                key=lambda g: g[2]):
            reqs = [self.pods[p][1] for p in sorted(names)]
            if any((r != reqs[0]).any() for r in reqs):
                continue   # unequal pods: the subgroup reference's
            tally["required_pending"] += 1
            fits = replicas(free, reqs[0])
            ids = self.dom[:, req_lvl]
            room = np.bincount(ids[ids >= 0], weights=fits[ids >= 0],
                               minlength=self.domains)
            holds = np.flatnonzero(room >= len(reqs))
            if not len(holds):
                continue
            tally["domain_left_pending"] += 1
            left = len(reqs)
            for node in np.flatnonzero(ids == holds[np.argmin(room[holds])]):
                take = min(left, int(fits[node]))
                free[node] -= take * reqs[0]
                left -= take
        return tally


class TreeHostModel(host_model.HostModel):
    """The plain reference, holding a cluster with a tree to
    ``topology_required`` as well: gangs bound across domains of their
    required level and gangs left pending though a domain of it had
    room are compared with 0 like its own numbers.  A cluster without a
    tree is judged as ``HostModel`` judges it (both read 0)."""

    LIMITS = {**host_model.HostModel.LIMITS,
              "gangs_split_across_domains": 0, "domain_left_pending": 0}
    #: set once ``HostModel.__init__`` has applied the cluster's own pod
    #: groups and pods, which ``TopologyModel.__init__`` applies itself
    tree = None

    def __init__(self, cluster: dict):
        super().__init__(cluster)
        if cluster.get("topology"):
            self.tree = TopologyModel(cluster)

    def apply_doc(self, doc: dict) -> None:
        super().apply_doc(doc)
        if self.tree is not None:
            self.tree.apply_doc(doc)

    def check_commit(self, commit: dict) -> dict:
        tally = super().check_commit(commit)
        if self.tree is not None:
            seen = tally["topology"] = self.tree.check_commit(commit)
            self.counts["gangs_split_across_domains"] += \
                seen["required_split"]
            self.counts["domain_left_pending"] += seen["domain_left_pending"]
        return tally


def window_tallies(run) -> list[dict]:
    """The tree's tally of each window cycle, as ``Run.judge`` left
    them (the benchmark judges before it reads a metric); none where
    another reference judged."""
    return [t["topology"] for t in getattr(run, "tallies", [])
            if "topology" in t]
