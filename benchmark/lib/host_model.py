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
* fairness     victims come only from leaf queues over their fair share;
               a move (an eviction with a ``move_to``) takes nothing
               from its queue and is not a victim
* read-back    once the window has closed the server's own snapshot
               places every pod where its commits said

Fair share is the reference's own water-filling over the queue tree
(quota first, the rest by over-quota weight, capped by request), on
accelerators.  It imports nothing of the program.

**References a configuration names.**  A guarantee beyond these is a
module of its own under ``lib/``, named in the configuration's
``references`` list; ``HostModel(cluster, names)`` builds each one's
``Reference`` and feeds it the same documents and commits.  A reference
has

``KEY``, ``LIMITS``  the key of its tally in each cycle's tally, and
                     its numbers compared with their limits
``__init__(cluster)``, ``apply_doc(doc)``
                     follow the cluster document and what was posted
``check_commit(commit)``
                     applies one commit, adds to ``counts`` (keyed as
                     ``LIMITS``) and returns its tally for the cycle

and may tell ``_due`` what it knows of where a gang may go:

``nodes_for(pod)``   the nodes a pending pod may take ([N] bool)
``domain_of(gang)``  a domain id per node ([N], -1: none) inside one of
                     which all of the gang must be placed, or ``None``
``FITS_UNEQUAL``     true: a gang of unequal pods is fitted pod by pod

``_due`` places each pending gang, oldest first, node by node into what
the gangs before it left, on the nodes and inside the domains the
references allow: a sufficient test, never claiming what cannot be
placed.  With no reference named every node is allowed and a gang of
unequal pods is not judged.
"""
from __future__ import annotations

import importlib

import numpy as np

RES = ("accel", "cpu", "memory")
EPS = 1e-3


def vec(d: dict) -> np.ndarray:
    """A resource document as a vector in the order of ``RES``."""
    return np.array([d[r] for r in RES], dtype=np.float64)


def replicas(free: np.ndarray, req: np.ndarray) -> np.ndarray:
    """How many pods of ``req`` each node's ``free`` ([N, R]) holds."""
    per = np.where(req > 0, (free + EPS) / np.where(req > 0, req, 1.0),
                   np.inf)
    return np.floor(per.min(axis=1)).clip(min=0).astype(np.int64)


def first_fit(free: np.ndarray, requests: list,
              allowed: list | None = None) -> np.ndarray | None:
    """What is left of ``free`` ([N, R]) once every request has taken
    the first node that holds it (and that ``allowed[i]`` allows), the
    largest request first; ``None`` where some request finds no node.
    A sufficient test of fitting: it never says a gang fits that cannot
    be placed."""
    left = free.copy()
    holds: dict = {}    # request -> the nodes whose ``left`` holds one
    for i in sorted(range(len(requests)), key=lambda i: tuple(-requests[i])):
        key = tuple(requests[i])
        if key not in holds:
            holds[key] = (left + EPS >= requests[i]).all(axis=1)
        ok = holds[key] if allowed is None else holds[key] & allowed[i]
        rows = np.flatnonzero(ok)
        if not len(rows):
            return None
        row = rows[0]
        left[row] -= requests[i]
        for k, h in holds.items():
            h[row] = (left[row] + EPS >= k).all()
    return left


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

    def __init__(self, cluster: dict, references=()):
        self.node_ix = {n["name"]: i for i, n in enumerate(cluster["nodes"])}
        self.alloc = np.stack([vec(n["allocatable"])
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
        self.references = []
        self.apply_doc({"pod_groups_upsert": cluster["pod_groups"],
                        "pods_upsert": cluster["pods"]})
        # each follows the cluster document by itself
        self.references = [
            importlib.import_module(f".{name}", __package__).Reference(
                cluster) for name in references]
        self.fits_unequal = any(getattr(r, "FITS_UNEQUAL", False)
                                for r in self.references)

    # -- documents ---------------------------------------------------------

    def apply_doc(self, doc: dict) -> None:
        for g in doc.get("pod_groups_upsert", []):
            old = self.gangs.get(g["name"])
            assert not (old and old[3]), f"{g['name']} replaced with pods"
            self.gangs[g["name"]] = [g["queue"], g["min_member"],
                                     g["creation_timestamp"], set()]
        for p in doc.get("pods_upsert", []):
            node = self.node_ix[p["node"]] if p.get("node") else -1
            req = vec(p["resources"])
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
        for ref in self.references:
            ref.apply_doc(doc)

    # -- what is due -------------------------------------------------------

    def _due(self) -> tuple[int, float, dict]:
        """(gangs that must bind, accelerators that must be evicted,
        fair share by queue) for the cycle about to be judged."""
        pending = sorted(
            ((name, g) for name, g in self.gangs.items()
             if g[3] and all(self.pods[p][2] < 0 for p in g[3])),
            key=lambda ng: ng[1][2])
        request = dict(self.held)
        for _name, (queue, _mm, _c, names) in pending:
            request[queue] += sum(self.pods[p][1][0] for p in names)
        fair = fair_shares(float(self.alloc[:, 0].sum()), self.parent,
                           self.quota, self.weight, request)
        over = any(self.held[q] > fair[q] + EPS for q in self.leaves)
        free = np.maximum(self.alloc - self.used, 0.0)
        fits = self._fitter(free)
        claimed = {q: 0.0 for q in self.leaves}
        must_bind, must_evict = 0, 0.0
        for name, (queue, _mm, _c, names) in pending:
            fit = fits(name, names)
            if fit is None:
                continue  # a gang the reference cannot judge: no claim
            accel = sum(self.pods[p][1][0] for p in names)
            if fit:
                must_bind += 1
            elif over and (self.held[queue] + claimed[queue] + accel
                           <= fair[queue] + EPS):
                claimed[queue] += accel
                must_evict += accel
        self.pending_gangs = len(pending)
        return must_bind, must_evict, fair

    def _fitter(self, free: np.ndarray):
        """``fits(gang, pod names)``: whether the gang can be placed into
        what the gangs before it left of ``free`` (which it takes over),
        on the nodes and inside one domain of what the references allow
        — equal pods packed node by node, into the fullest domain that
        holds them all; unequal ones first fit, largest request first —
        and if so takes its room.  ``None`` for a gang of unequal pods
        unless a reference fits them, and for one that must also stay
        inside a domain."""
        everywhere = np.ones(len(free), bool)
        #: request -> how many of it each node's ``free`` holds, kept up
        #: to date on the rows a gang takes
        rooms: dict = {}

        def room_of(req: np.ndarray) -> np.ndarray:
            key = tuple(req)
            if key not in rooms:
                rooms[key] = replicas(free, req)
            return rooms[key]

        def take(rows: np.ndarray, amount: np.ndarray) -> None:
            free[rows] -= amount
            for key, room in rooms.items():
                room[rows] = replicas(free[rows], np.array(key))

        def allowed(pod: str) -> np.ndarray:
            ok = everywhere
            for ref in self.references:
                if hasattr(ref, "nodes_for"):
                    mask = ref.nodes_for(pod)
                    ok = mask if ok is everywhere else ok & mask
            return ok

        def fits(gang, names) -> bool | None:
            pods = sorted(names)
            reqs = [self.pods[p][1] for p in pods]
            dom = next((d for d in (ref.domain_of(gang)
                                    for ref in self.references
                                    if hasattr(ref, "domain_of"))
                        if d is not None), None)
            masks = [allowed(p) for p in pods]
            if any((r != reqs[0]).any() for r in reqs):
                if dom is not None or not self.fits_unequal:
                    return None
                left = first_fit(free, reqs, masks)
                if left is None:
                    return False
                rows = np.flatnonzero((left != free).any(axis=1))
                take(rows, free[rows] - left[rows])
                return True
            distinct = {id(m): m for m in masks}.values()
            room = np.where(np.logical_and.reduce(list(distinct)),
                            room_of(reqs[0]), 0)
            if dom is not None:
                inside = np.bincount(dom[dom >= 0], weights=room[dom >= 0])
                holds = np.flatnonzero(inside >= len(pods))
                if not len(holds):
                    return False
                room = np.where(dom == holds[np.argmin(inside[holds])],
                                room, 0)
            elif room.sum() < len(pods):
                return False
            counts = np.clip(len(pods) - (np.cumsum(room) - room), 0, room)
            rows = np.flatnonzero(counts)
            take(rows, counts[rows, None] * reqs[0])
            return True

        return fits

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
            if ev["move_to"] is None:
                if held_before[queue] <= fair[queue] + EPS:
                    c["evictions_within_fair_share"] += 1
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
        tally = {"must_bind": must_bind, "bound_gangs": len(bound_gangs),
                 "must_evict": must_evict, "evicted_accel": evicted_accel,
                 "pending_gangs": self.pending_gangs,
                 "placed_pods": sum(1 for p in self.pods.values()
                                    if p[2] >= 0)}
        for ref in self.references:
            tally[ref.KEY] = ref.check_commit(commit)
        return tally

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
        """Each number compared, beside its limit: this reference's,
        then each named one's."""
        return {k: {"value": counts[k], "limit": lim}
                for counts, limits in [(self.counts, self.LIMITS)] + [
                    (r.counts, r.LIMITS) for r in self.references]
                for k, lim in limits.items()}
