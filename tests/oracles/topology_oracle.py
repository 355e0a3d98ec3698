"""The plain topology reference for the tests: what a cycle owes a gang
that asks for a required or a preferred level of the cluster's tree.

The cluster document names a tree (``topology``: node label keys,
outermost first) and every node carries the labels; a pod group asks
with ``topology_constraint`` for a ``required_level`` (all its pods
inside one domain of that level, or none bound) and a
``preferred_level`` (best effort).  A domain is a label path from the
outermost level down.  NumPy over the wire's own documents (``POST
/cluster``, ``/intake``, the commit of ``/cycle/stored``); it imports
nothing of the package.

Two parts:

* a **checker** (``Oracle.judge``): gangs bound across more than one
  domain of their required level, gangs bound in part, nodes over their
  allocatable, gangs of equal pods left pending though a domain of their
  level had room for all of them after the commit, and of the gangs
  bound with a preferred level how many lie inside one domain of it;
* a **sequential placer** (``Oracle.place``): the pending gangs one at a
  time in the scheduler's order, each into the fullest domain of its
  required level that holds all of it — least free accelerators first,
  the first in node order among equals — and bin-packed inside it: the
  fullest node first, the lowest index among equals, a node filled
  before the next; with a preferred level, the nodes that share that
  domain with the fullest node first.  A gang's pods take its nodes in
  ascending node order (equal pods are interchangeable).
"""
from __future__ import annotations

import numpy as np

RESOURCES = ("accel", "cpu", "memory")
EPS = 1e-6

ZERO = {"split": 0, "partial": 0, "over_capacity": 0, "left_pending": 0}


def _vec(d: dict) -> np.ndarray:
    return np.array([d[r] for r in RESOURCES], dtype=np.float64)


def replicas(free: np.ndarray, req: np.ndarray) -> np.ndarray:
    """Pods of ``req`` each node's ``free`` ([N, R]) holds."""
    per = np.where(req > 0, (free + EPS) / np.where(req > 0, req, 1.0),
                   np.inf)
    return np.floor(per.min(axis=1)).clip(min=0).astype(np.int64)


class Oracle:
    """The cluster as documents.  ``apply`` follows what was posted,
    ``place`` says where a sequential scheduler would put the pending
    gangs, ``judge`` holds one commit to the guarantee and applies it."""

    def __init__(self, cluster: dict):
        nodes = cluster["nodes"]
        self.node_names = [n["name"] for n in nodes]
        self.node_ix = {name: i for i, name in enumerate(self.node_names)}
        self.alloc = np.stack([_vec(n["allocatable"]) for n in nodes])
        topo = cluster["topology"]
        self.tree, self.levels = topo["name"], list(topo["levels"])
        #: node -> its domain at each level, as the label path so far
        self.path = [[None] * len(self.levels) for _ in nodes]
        for i, n in enumerate(nodes):
            so_far: tuple = ()
            for lvl, key in enumerate(self.levels):
                if key not in n.get("labels", {}):
                    break
                so_far += (n["labels"][key],)
                self.path[i][lvl] = so_far
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

    # -- the cluster as it stands -------------------------------------------

    def free(self) -> np.ndarray:
        used = np.zeros_like(self.alloc)
        for name, node in self.node_of.items():
            used[self.node_ix[node]] += _vec(self.pods[name]["resources"])
        return self.alloc - used

    def level_of(self, gang: str, attr: str) -> int:
        """The level index a gang's constraint names, -1 for none."""
        tc = self.groups[gang].get("topology_constraint")
        if not tc or tc.get("topology") not in (None, self.tree):
            return -1
        return (self.levels.index(tc[attr])
                if tc.get(attr) in self.levels else -1)

    def pending_gangs(self) -> dict:
        """gang -> its pods' names in task order (a gang's pods are
        created together: by name, as strings), for gangs none of whose
        pods holds a node; in the scheduler's order (the tests' gangs
        share one queue and one priority: by creation, then name)."""
        by_gang: dict = {}
        for pod in self.pods.values():
            by_gang.setdefault(pod["group"], []).append(pod["name"])
        pending = [g for g, names in by_gang.items()
                   if not any(p in self.node_of for p in names)]
        pending.sort(key=lambda g: (self.groups[g]["creation_timestamp"], g))
        return {g: sorted(by_gang[g]) for g in pending}

    def domains(self, level: int) -> dict:
        """domain of ``level`` -> its nodes' indices, in node order."""
        out: dict = {}
        for i, path in enumerate(self.path):
            if path[level] is not None:
                out.setdefault(path[level], []).append(i)
        return out

    def one_domain(self, nodes: list, level: int) -> bool:
        held = {self.path[self.node_ix[n]][level] for n in nodes}
        return len(held) == 1 and None not in held

    # -- the sequential placer ----------------------------------------------

    def place(self) -> dict:
        """gang -> ``{"domain": label path, "nodes": {pod: node}}`` for
        every pending gang with a required level the placer binds, in
        the scheduler's order against what the gangs before it left;
        a gang no domain holds is absent."""
        free = np.maximum(self.free(), 0.0)
        out = {}
        for gang, names in self.pending_gangs().items():
            level = self.level_of(gang, "required_level")
            if level < 0:
                continue
            req = _vec(self.pods[names[0]]["resources"])
            fits = replicas(free, req)
            best = None   # (free accelerators, first node) of the fullest
            for dom, rows in self.domains(level).items():
                if fits[rows].sum() >= len(names):
                    key = (free[rows, 0].sum(), rows[0])
                    if best is None or key < best[0]:
                        best = (key, dom, rows)
            if best is None:
                continue
            _key, dom, rows = best
            rows = [i for i in rows if fits[i] > 0]
            rows.sort(key=lambda i: (free[i, 0], i))
            pref = self.level_of(gang, "preferred_level")
            if pref >= 0:
                near = self.path[rows[0]][pref]
                rows.sort(key=lambda i: self.path[i][pref] != near)
            left, chosen = len(names), []
            for i in rows:
                take = min(left, int(fits[i]))
                chosen += [i] * take
                free[i] -= take * req
                left -= take
                if not left:
                    break
            out[gang] = {"domain": dom, "nodes": {
                pod: self.node_names[i]
                for pod, i in zip(names, sorted(chosen))}}
        return out

    # -- one commit ---------------------------------------------------------

    def judge(self, commit: dict) -> dict:
        """``{"counts", "preferred_bound", "preferred_together",
        "domain"}``: what the commit got wrong (all 0 on a sound one),
        the preferred level's tally, and gang -> the domain of its
        required level it was bound in; then the commit applied."""
        pending = self.pending_gangs()
        counts = dict(ZERO)
        by_gang: dict = {}
        for b in commit["bind_requests"]:
            by_gang.setdefault(self.pods[b["pod"]]["group"], {})[
                b["pod"]] = b["node"]
        out = {"counts": counts, "preferred_bound": 0,
               "preferred_together": 0, "domain": {}}
        for gang, binds in by_gang.items():
            counts["partial"] += sorted(binds) != sorted(pending[gang])
            nodes = list(binds.values())
            level = self.level_of(gang, "required_level")
            if level >= 0:
                whole = self.one_domain(nodes, level)
                counts["split"] += not whole
                if whole:
                    out["domain"][gang] = self.path[
                        self.node_ix[nodes[0]]][level]
            pref = self.level_of(gang, "preferred_level")
            if pref >= 0:
                out["preferred_bound"] += 1
                out["preferred_together"] += self.one_domain(nodes, pref)
        for binds in by_gang.values():
            self.node_of.update(binds)
        counts["over_capacity"] = int((self.free() < -EPS).any(axis=1).sum())
        # what the commit left pending, oldest first, each against what
        # the ones before it would take: the placer once more
        left = self.place()
        counts["left_pending"] = len(left)
        return out
