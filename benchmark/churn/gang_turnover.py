"""Gangs finish, gangs are evicted, gangs arrive: what a Kubernetes-side
shim sends between two cycles.  The one general churn generator; a mix
names it (``churn``) and gives its numbers.

Per cycle (``per_cycle`` of the mix):

``complete_gangs``  so many gangs that hold nodes finish: their pods, the
                    bind requests that placed them and their pod group
                    are deleted, as a cluster deletes a group with its
                    owner
``submit_gangs``    so many new gangs, each with a pod group of its own,
                    join the configuration's arriving leaf queues

Whatever the last commit evicted is reported deleted before the next
cycle, pods and — once a gang has no pod left — its pod group.  The seed
picks which gangs finish and the order in which new gangs go round the
queues; counts and shapes are the mix's and the configuration's, so
every seed drives the same compiled program.
"""
from __future__ import annotations

import numpy as np


def scaled(mix: dict, k: float) -> dict:
    """The mix at a rehearsal's scale: counts shrink, none below one
    where the mix has any."""
    if k == 1.0:
        return mix
    return dict(mix, per_cycle={key: max(1, int(v * k)) if v else 0
                                for key, v in mix["per_cycle"].items()})


class Churn:
    """Per-cycle documents for one run.  Keeps only what the next
    document needs: the gangs that hold nodes, and the pods the last
    commit evicted."""

    def __init__(self, generator, spec: dict, mix: dict, cluster: dict,
                 seed: int):
        self.gen, self.spec, self.per = generator, spec, mix["per_cycle"]
        self.rng = np.random.default_rng([seed, 2])
        leaves = generator.arriving_leaves(spec)
        self.leaves = [leaves[i] for i in self.rng.permutation(len(leaves))]
        self.arrived = 0
        #: gang -> names of its pods that hold a node
        self.placed: dict = {}
        for p in cluster["pods"]:
            if p.get("node"):
                self.placed.setdefault(p["group"], set()).add(p["name"])
        self.gang_of = {p["name"]: p["group"] for p in cluster["pods"]}
        #: gang -> names of all its pods, placed or not
        self.members: dict = {}
        for pod, gang in self.gang_of.items():
            self.members.setdefault(gang, set()).add(pod)
        self.bound_by_commit: set[str] = set()
        self.evicted: list[str] = []
        self.cycle = 0
        self.created = float(len(cluster["pod_groups"]))

    @property
    def arriving_gangs(self) -> int:
        """Gangs a cycle meets beyond those that hold nodes."""
        return self.per["submit_gangs"]

    def documents(self) -> tuple[dict, dict]:
        """(delta, intake) to post before the next cycle."""
        self.cycle += 1
        gone_pods, touched = [], {}   # touched: gangs that lost a pod

        def report_deleted(pod: str) -> None:
            gone_pods.append(pod)
            gang = self.gang_of.pop(pod)
            self.members[gang].discard(pod)
            touched[gang] = None

        for pod in self.evicted:
            report_deleted(pod)
        self.evicted = []
        if self.per["complete_gangs"]:
            # insertion order: the cluster's, then the commits' own
            names = list(self.placed)
            for i in self.rng.choice(len(names), replace=False,
                                     size=self.per["complete_gangs"]):
                del self.placed[names[i]]
                for pod in sorted(self.members[names[i]]):
                    report_deleted(pod)
        gone_binds = [p for p in gone_pods if p in self.bound_by_commit]
        self.bound_by_commit.difference_update(gone_binds)
        # a group goes once it holds no pod
        gone_groups = [g for g in touched if not self.members[g]]
        for gang in gone_groups:
            del self.members[gang]
        delta: dict = {"now": float(self.cycle)}
        for key, names in (("pods_delete", gone_pods),
                           ("pod_groups_delete", gone_groups),
                           ("bind_requests_delete", gone_binds)):
            if names:
                delta[key] = names

        groups, pods = [], []
        n_new = self.per["submit_gangs"]
        order = self.rng.permutation(n_new)
        for i in range(n_new):
            # round-robin over the arriving leaves, carried on from cycle
            # to cycle so that the split is even whatever the two counts
            # are; the seed orders the leaves and the document
            queue = self.leaves[(self.arrived + int(order[i]))
                                % len(self.leaves)]
            self.created += 1.0
            name = f"job-{self.cycle}-{i}"
            grp, gp = self.gen.gang_docs(name, queue, self.spec, self.created)
            groups.append(grp)
            pods += gp
            self.gang_of.update((p["name"], name) for p in gp)
            self.members[name] = {p["name"] for p in gp}
        self.arrived += n_new
        return delta, {"pod_groups_upsert": groups, "pods_upsert": pods}

    def observe(self, commit: dict) -> None:
        """Take from a commit what the next documents need."""
        for ev in commit["evictions"]:
            if ev["move_to"] is None:
                self.evicted.append(ev["pod"])
                left = self.placed.get(ev["group"])
                if left is not None:
                    left.discard(ev["pod"])
                    if not left:
                        del self.placed[ev["group"]]
        for br in commit["bind_requests"]:
            gang = self.gang_of[br["pod"]]
            self.placed.setdefault(gang, set()).add(br["pod"])
            self.bound_by_commit.add(br["pod"])
