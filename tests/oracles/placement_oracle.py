"""The plain placement reference for the tests: where Kubernetes lets a
pod land, and which pending gangs a cycle then has to bind.

Upstream ``TaintToleration`` and ``nodeSelector`` semantics in plain
Python over the wire's own documents (``POST /cluster``, ``/intake``,
the commit of ``/cycle/stored``).  It imports nothing of ``state/`` or
``ops/`` — nor of the package at all.

* a pod may be bound to a node only if the node carries every label of
  its ``node_selector`` with that value, and one of its tolerations
  tolerates every ``NoSchedule`` / ``NoExecute`` taint of the node
  (``PreferNoSchedule`` forbids nothing);
* a pending gang of equal pods **fits** when the nodes that allow it
  have room for all of it even after every other pending pod that those
  nodes allow has been placed there, so whatever order and nodes the
  scheduler chooses it must be bound; it **cannot fit** when those nodes
  lack the room even with nothing else placed, so it must stay pending;
  between the two the oracle says nothing.
"""
from __future__ import annotations

import math

HARD_EFFECTS = ("NoSchedule", "NoExecute")
RESOURCES = ("accel", "cpu", "memory")


def tolerates(toleration: dict, taint: dict) -> bool:
    """corev1 ``Toleration.ToleratesTaint``."""
    effect = toleration.get("effect")
    if effect and effect != taint.get("effect", "NoSchedule"):
        return False
    operator = toleration.get("operator", "Equal")
    if not toleration.get("key"):
        return operator == "Exists"
    if toleration["key"] != taint["key"]:
        return False
    return (operator == "Exists"
            or toleration.get("value", "") == taint.get("value", ""))


def node_allows(node: dict, pod: dict) -> bool:
    labels = node.get("labels", {})
    if any(labels.get(k) != v
           for k, v in pod.get("node_selector", {}).items()):
        return False
    return all(
        any(tolerates(t, taint) for t in pod.get("tolerations", []))
        for taint in node.get("taints", [])
        if taint.get("effect", "NoSchedule") in HARD_EFFECTS)


class Oracle:
    """The cluster as documents.  ``apply`` follows what was posted,
    ``judge`` holds one commit against it and then applies it."""

    def __init__(self, cluster: dict):
        self.nodes = {n["name"]: n for n in cluster["nodes"]}
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

    def _slots(self, pod: dict) -> dict:
        """node -> how many pods like ``pod`` it still has room for,
        over the nodes that allow it."""
        used = {n: dict.fromkeys(RESOURCES, 0.0) for n in self.nodes}
        for name, node in self.node_of.items():
            for r in RESOURCES:
                used[node][r] += self.pods[name]["resources"][r]
        out = {}
        for name, node in self.nodes.items():
            if node_allows(node, pod):
                out[name] = min(
                    math.floor((node["allocatable"][r] - used[name][r])
                               / pod["resources"][r] + 1e-6)
                    for r in RESOURCES if pod["resources"][r] > 0)
        return out

    def pending_gangs(self) -> dict:
        """gang -> its pods' documents, for gangs none of whose pods
        holds a node."""
        by_gang: dict = {}
        for name, pod in self.pods.items():
            by_gang.setdefault(pod["group"], []).append(pod)
        return {g: pods for g, pods in by_gang.items()
                if not any(p["name"] in self.node_of for p in pods)}

    def verdicts(self) -> dict:
        """gang -> ``"fits"`` | ``"cannot"`` | ``"either"`` for every
        pending gang (of equal pods, of one resource shape)."""
        pending = self.pending_gangs()
        slots = {g: self._slots(pods[0]) for g, pods in pending.items()}
        out = {}
        for gang, pods in pending.items():
            room = sum(slots[gang].values())
            rivals = sum(
                len(other) for g, other in pending.items()
                if g != gang and slots[g].keys() & slots[gang].keys())
            if room < len(pods):
                out[gang] = "cannot"
            elif room - rivals >= len(pods):
                out[gang] = "fits"
            else:
                out[gang] = "either"
        return out

    # -- a commit -----------------------------------------------------------

    def judge(self, commit: dict) -> dict:
        """Counts of what ``commit`` got wrong, each 0 on a sound one:
        ``misplaced`` binds onto a node that does not allow the pod,
        ``split`` gangs bound in part, ``unbound`` gangs that fit and
        were left pending, ``wrongly_bound`` gangs that cannot fit and
        were bound.  Then the binds take their nodes."""
        verdict = self.verdicts()
        pending = self.pending_gangs()
        bound: dict = {}
        misplaced = 0
        for b in commit["bind_requests"]:
            pod = self.pods[b["pod"]]
            bound.setdefault(pod["group"], []).append(b["pod"])
            if not node_allows(self.nodes[b["node"]], pod):
                misplaced += 1
        out = {
            "misplaced": misplaced,
            "split": sum(1 for g, names in bound.items()
                         if len(names) < self.groups[g]["min_member"]),
            "unbound": sum(1 for g, v in verdict.items()
                           if v == "fits" and g not in bound),
            "wrongly_bound": sum(1 for g in bound
                                 if verdict.get(g) == "cannot"
                                 or g not in pending),
        }
        for b in commit["bind_requests"]:
            self.node_of[b["pod"]] = b["node"]
        return out
