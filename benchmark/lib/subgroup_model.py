"""The plain subgroup reference: what a declared subgroup's quorum and a
gang of unequal pods ask of a commit.

A pod group may declare subgroups, each with a ``min_member``, and each
pod names the subgroup it belongs to (upstream's podgrouper makes one
per replica type of a Kubeflow job).  The guarantee: a gang is bound
only with every declared subgroup at its quorum, and a pending gang of
unequal pods that fits the free capacity is bound in that cycle.

``kubeflow-10k``'s configuration names this module among its
``references``: ``lib/host_model.py`` builds its ``Reference`` beside
itself and compares ``subgroups_below_quorum`` with 0 like its own
numbers; ``FITS_UNEQUAL`` has its ``_due`` fit a gang of unequal pods
pod by pod (first fit, largest request first: a sufficient test), so
that ``gangs_bound_short`` counts one left pending though it fits.
Plain Python over the wire's own documents; it imports nothing of the
program.
"""
from __future__ import annotations


class Reference:
    """Follows gangs and pods through the documents posted and counts,
    commit by commit, the gangs bound with a declared subgroup below its
    quorum."""

    KEY = "subgroups"
    LIMITS = {"subgroups_below_quorum": 0}
    FITS_UNEQUAL = True

    def __init__(self, cluster: dict):
        self.counts = {k: 0 for k in self.LIMITS}
        self.nodes = {n["name"] for n in cluster["nodes"]}
        #: gang -> [{subgroup: min_member}, pod names]
        self.gangs: dict = {}
        #: pod -> [gang, subgroup, placed]
        self.pods: dict = {}
        self.apply_doc({"pod_groups_upsert": cluster["pod_groups"],
                        "pods_upsert": cluster["pods"]})

    def apply_doc(self, doc: dict) -> None:
        for g in doc.get("pod_groups_upsert", []):
            quorums = {s["name"]: s["min_member"]
                       for s in g.get("sub_groups", [])}
            self.gangs[g["name"]] = [quorums, set()]
        for p in doc.get("pods_upsert", []):
            self.pods[p["name"]] = [p["group"], p.get("subgroup"),
                                    bool(p.get("node"))]
            self.gangs[p["group"]][1].add(p["name"])
        for name in doc.get("pods_delete", []):
            self.gangs[self.pods.pop(name)[0]][1].discard(name)
        for name in doc.get("pod_groups_delete", []):
            del self.gangs[name]

    def check_commit(self, commit: dict) -> dict:
        """Apply one commit's binds, then count.  An evicted pod holds
        its node until it is reported deleted.  Names the documents do
        not know are the other reference's to count
        (``dangling_names``)."""
        bound = set()
        for br in commit["bind_requests"]:
            pod = self.pods.get(br["pod"])
            if pod is None or pod[2] or br["node"] not in self.nodes:
                continue
            pod[2] = True
            bound.add(pod[0])
        below = 0
        for gang in bound:
            quorums, names = self.gangs[gang]
            placed: dict = {}
            for p in names:
                if self.pods[p][2]:
                    sub = self.pods[p][1]
                    placed[sub] = placed.get(sub, 0) + 1
            below += any(placed.get(sub, 0) < need
                         for sub, need in quorums.items())
        self.counts["subgroups_below_quorum"] += below
        return {"bound_gangs": len(bound), "below_quorum": below}
