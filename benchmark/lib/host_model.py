"""The plain reference: the cluster as NumPy and dicts, never shown to
the server.  It decides ``correct``.

It replays, once the window has closed, every document the harness
posted and every commit the server returned, and holds the server to
the guarantees the configuration file states:

* capacity     no node over allocatable in any resource; an evicted pod
               holds its node until it is reported deleted
* gangs        a gang is bound at ``min_member`` or not at all
* liveness     binds name live pending pods and live nodes, evictions
               name pods that hold a node
* intake       what the reference says is due is done: a pending gang
               that fits the free capacity is bound in that cycle; one
               that does not, in a queue under its fair share while
               others are over theirs, has that much capacity evicted
* fairness     victims come only from leaf queues over their fair share
* read-back    once the window has closed the server's own snapshot
               places every pod where its commits said

Fair share is the reference's own water-filling over the queue tree
(quota first, the rest by over-quota weight, capped by request), on
accelerators.  It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

RES = ("accel", "cpu", "memory")
EPS = 1e-3


def _vec(d: dict) -> np.ndarray:
    return np.array([d[r] for r in RES], dtype=np.float64)


def fair_shares(capacity: float, parent: dict, quota: dict, weight: dict,
                request: dict) -> dict:
    """Accelerators each queue deserves.  Siblings first take
    ``min(quota, request)``, then share what is left of their parent's
    share by weight, never past their request."""
    kids: dict = {}
    for q, p in parent.items():
        kids.setdefault(p, []).append(q)

    def total_request(q):
        return (sum(total_request(k) for k in kids[q]) if q in kids
                else request.get(q, 0.0))

    req = {q: total_request(q) for q in parent}
    share: dict = {}

    def split(total: float, group: list) -> None:
        for q in group:
            share[q] = min(quota[q], req[q]) if quota[q] >= 0 else 0.0
        left = total - sum(share[q] for q in group)
        active = [q for q in group if req[q] - share[q] > 1e-9]
        while left > 1e-9 and active:
            wsum = sum(weight[q] for q in active)
            given = 0.0
            for q in list(active):
                add = min(left * weight[q] / wsum, req[q] - share[q])
                share[q] += add
                given += add
                if req[q] - share[q] <= 1e-9:
                    active.remove(q)
            if given <= 1e-9:
                break
            left -= given
        for q in group:
            if q in kids:
                split(share[q], kids[q])

    split(capacity, kids.get(None, []))
    return share


class HostModel:
    #: every number compared, with its limit (all exact: 0)
    LIMITS = {"nodes_over_allocatable": 0, "gangs_below_min_member": 0,
              "dangling_names": 0, "gangs_bound_short": 0,
              "evicted_accel_short": 0, "evicted_accel_excess": 0,
              "evictions_within_fair_share": 0}

    def __init__(self, cluster: dict):
        self.node_ix = {n["name"]: i for i, n in enumerate(cluster["nodes"])}
        self.alloc = np.stack([_vec(n["allocatable"])
                               for n in cluster["nodes"]])
        self.used = np.zeros_like(self.alloc)
        self.parent = {q["name"]: q.get("parent")
                       for q in cluster["queues"]}
        self.quota = {q["name"]: q["accel"]["quota"]
                      for q in cluster["queues"]}
        self.weight = {q["name"]: q["accel"]["over_quota_weight"]
                       for q in cluster["queues"]}
        inner = set(self.parent.values())
        self.leaves = [q for q in self.parent if q not in inner]
        self.held = {q: 0.0 for q in self.leaves}
        #: gang -> [queue, min_member, created, pod names]
        self.gangs: dict = {}
        #: pod -> [gang, request, node index or -1, evicted]
        self.pods: dict = {}
        self.counts = {k: 0 for k in self.LIMITS}
        self.cycles = 0
        self.apply_doc({"pod_groups_upsert": cluster["pod_groups"],
                        "pods_upsert": cluster["pods"]})

    # -- documents ---------------------------------------------------------

    def apply_doc(self, doc: dict) -> None:
        for g in doc.get("pod_groups_upsert", []):
            old = self.gangs.get(g["name"])
            assert not (old and old[3]), f"{g['name']} replaced with pods"
            self.gangs[g["name"]] = [g["queue"], g["min_member"],
                                     g["creation_timestamp"], set()]
        for p in doc.get("pods_upsert", []):
            node = self.node_ix[p["node"]] if p.get("node") else -1
            req = _vec(p["resources"])
            self.pods[p["name"]] = [p["group"], req, node, False]
            self.gangs[p["group"]][3].add(p["name"])
            if node >= 0:
                self.used[node] += req
                self.held[self.gangs[p["group"]][0]] += req[0]
        for name in doc.get("pods_delete", []):
            gang, req, node, evicted = self.pods.pop(name)
            self.gangs[gang][3].discard(name)
            if node >= 0:
                self.used[node] -= req
                if not evicted:
                    self.held[self.gangs[gang][0]] -= req[0]
        for name in doc.get("pod_groups_delete", []):
            assert not self.gangs[name][3], f"{name} deleted with pods"
            del self.gangs[name]

    # -- what is due -------------------------------------------------------

    def _due(self) -> tuple[int, float, dict]:
        """(gangs that must bind, accelerators that must be evicted,
        fair share by queue) for the cycle about to be judged."""
        pending = sorted(
            (g for g in self.gangs.values()
             if g[3] and all(self.pods[p][2] < 0 for p in g[3])),
            key=lambda g: g[2])
        request = dict(self.held)
        for queue, _mm, _c, names in pending:
            request[queue] += sum(self.pods[p][1][0] for p in names)
        fair = fair_shares(float(self.alloc[:, 0].sum()), self.parent,
                           self.quota, self.weight, request)
        over = any(self.held[q] > fair[q] + EPS for q in self.leaves)
        free = np.maximum(self.alloc - self.used, 0.0)
        slots: dict = {}
        taken: dict = {}
        claimed = {q: 0.0 for q in self.leaves}
        must_bind, must_evict = 0, 0.0
        for queue, _mm, _c, names in pending:
            reqs = [self.pods[p][1] for p in names]
            key = tuple(reqs[0])
            if any(tuple(r) != key for r in reqs):
                continue  # mixed gangs: the reference claims nothing
            if key not in slots:
                r = np.where(reqs[0] > 0, reqs[0], np.inf)
                slots[key] = int(np.floor(free / r + 1e-6).min(axis=1).sum())
                taken[key] = 0
            need = len(names)
            accel = need * reqs[0][0]
            if taken[key] + need <= slots[key]:
                taken[key] += need
                must_bind += 1
            elif over and (self.held[queue] + claimed[queue] + accel
                           <= fair[queue] + EPS):
                claimed[queue] += accel
                must_evict += accel
        self.pending_gangs = len(pending)
        return must_bind, must_evict, fair

    # -- commits -----------------------------------------------------------

    def check_commit(self, commit: dict) -> dict:
        """Judge one commit against what was due, then apply it.
        Returns this cycle's tallies."""
        self.cycles += 1
        must_bind, must_evict, fair = self._due()
        c = self.counts
        held_before = dict(self.held)
        evicted_accel = 0.0
        for ev in commit["evictions"]:
            pod = self.pods.get(ev["pod"])
            if pod is None or pod[2] < 0 or pod[3]:
                c["dangling_names"] += 1
                continue
            queue = self.gangs[pod[0]][0]
            if held_before[queue] <= fair[queue] + EPS:
                c["evictions_within_fair_share"] += 1
            if ev["move_to"] is None:
                pod[3] = True
                self.held[queue] -= pod[1][0]
                evicted_accel += pod[1][0]
            elif ev["move_to"] in self.node_ix:
                self.used[pod[2]] -= pod[1]
                pod[2] = self.node_ix[ev["move_to"]]
                self.used[pod[2]] += pod[1]
            else:
                c["dangling_names"] += 1
        bound_gangs, touched = set(), set()
        for br in commit["bind_requests"]:
            pod = self.pods.get(br["pod"])
            node = self.node_ix.get(br["node"], -1)
            if pod is None or pod[2] >= 0 or node < 0:
                c["dangling_names"] += 1
                continue
            pod[2] = node
            self.used[node] += pod[1]
            self.held[self.gangs[pod[0]][0]] += pod[1][0]
            bound_gangs.add(pod[0])
            touched.add(node)
        if touched:
            rows = sorted(touched)
            over = (self.used[rows] > self.alloc[rows] + EPS).any(axis=1)
            c["nodes_over_allocatable"] += int(over.sum())
        for gang in bound_gangs:
            _q, min_member, _c, names = self.gangs[gang]
            placed = sum(1 for p in names
                         if self.pods[p][2] >= 0 and not self.pods[p][3])
            if placed < min_member:
                c["gangs_below_min_member"] += 1
        c["gangs_bound_short"] += max(0, must_bind - len(bound_gangs))
        c["evicted_accel_short"] += max(0.0, must_evict - evicted_accel)
        c["evicted_accel_excess"] += max(0.0, evicted_accel - must_evict)
        return {"must_bind": must_bind, "bound_gangs": len(bound_gangs),
                "must_evict": must_evict, "evicted_accel": evicted_accel,
                "pending_gangs": self.pending_gangs,
                "placed_pods": sum(1 for p in self.pods.values()
                                   if p[2] >= 0)}

    def recount_over(self) -> int:
        """Capacity once more from scratch, over every node: the check
        on the incremental ``used``."""
        used = np.zeros_like(self.alloc)
        held = [p for p in self.pods.values() if p[2] >= 0]
        if held:
            np.add.at(used, [p[2] for p in held],
                      np.stack([p[1] for p in held]))
        assert np.allclose(used, self.used, atol=1e-6), "host model drifted"
        return int((used > self.alloc + EPS).any(axis=1).sum())

    def readback_mismatch(self, stored_nodes: dict) -> int:
        """Pods that the server's own snapshot places elsewhere than the
        commits it returned did: what a commit said is what is stored."""
        names = list(self.node_ix)
        mine = {pod: names[p[2]] for pod, p in self.pods.items()
                if p[2] >= 0}
        return sum(1 for pod in mine.keys() | stored_nodes.keys()
                   if mine.get(pod) != stored_nodes.get(pod))

    def checks(self) -> dict:
        """Each number compared, beside its limit."""
        return {k: {"value": self.counts[k], "limit": lim}
                for k, lim in self.LIMITS.items()}
