"""The plain placement reference: where Kubernetes lets a pod land.

Upstream ``TaintToleration`` and ``nodeSelector`` semantics over the
wire's own documents, in plain Python; it imports nothing of the program.
A pod may be bound to a node only if the node carries every label of the
pod's ``node_selector`` with that value, and every taint of the node
whose effect is ``NoSchedule`` or ``NoExecute`` is tolerated by one of
the pod's tolerations.  (``PreferNoSchedule`` is a preference and forbids
nothing.)

``pools-10k``'s configuration names this module among its
``references``: ``lib/host_model.py`` builds its ``Reference`` beside
itself, asks it which nodes a pending pod may take (``nodes_for``), so
that ``gangs_bound_short`` counts free slots only there, and compares
``binds_outside_pool`` with 0 like its own numbers.
"""
from __future__ import annotations

import json

import numpy as np

HARD_EFFECTS = ("NoSchedule", "NoExecute")


def tolerates(toleration: dict, taint: dict) -> bool:
    """corev1 ``Toleration.ToleratesTaint``: an empty effect matches
    every effect, an empty key with ``Exists`` every taint; ``Equal``
    (the default) compares values too."""
    effect = toleration.get("effect")
    if effect and effect != taint.get("effect", "NoSchedule"):
        return False
    key = toleration.get("key")
    operator = toleration.get("operator", "Equal")
    if not key:
        return operator == "Exists"
    if key != taint["key"]:
        return False
    return (operator == "Exists"
            or toleration.get("value", "") == taint.get("value", ""))


def node_allows(node: dict, pod: dict) -> bool:
    """Whether ``pod`` (a pod document) may be bound to ``node`` (a node
    document)."""
    labels = node.get("labels", {})
    if any(labels.get(k) != v
           for k, v in pod.get("node_selector", {}).items()):
        return False
    return all(
        any(tolerates(t, taint) for t in pod.get("tolerations", []))
        for taint in node.get("taints", [])
        if taint.get("effect", "NoSchedule") in HARD_EFFECTS)


class Reference:
    """Follows the pods through the documents posted and counts the
    binds and moves of each commit that land where they may not."""

    KEY = "placement"
    LIMITS = {"binds_outside_pool": 0}

    def __init__(self, cluster: dict):
        self.counts = {k: 0 for k in self.LIMITS}
        self.nodes = {n["name"]: n for n in cluster["nodes"]}
        self.pods: dict = {}
        #: a pod's selector and tolerations -> the nodes they allow
        self.allowed: dict = {}
        self.apply_doc({"pods_upsert": cluster["pods"]})

    def apply_doc(self, doc: dict) -> None:
        for pod in doc.get("pods_upsert", []):
            self.pods[pod["name"]] = pod
        for name in doc.get("pods_delete", []):
            self.pods.pop(name, None)

    def nodes_for(self, pod: str) -> np.ndarray:
        doc = self.pods[pod]
        key = json.dumps([doc.get("node_selector", {}),
                          doc.get("tolerations", [])], sort_keys=True)
        if key not in self.allowed:
            self.allowed[key] = np.array(
                [node_allows(n, doc) for n in self.nodes.values()], bool)
        return self.allowed[key]

    def check_commit(self, commit: dict) -> dict:
        """Binds, and evictions that move a pod, onto a node that lacks
        the pod's selector labels or carries a taint it does not
        tolerate.  Names the documents do not know are the other
        reference's to count (``dangling_names``)."""
        placed = [(b["pod"], b["node"]) for b in commit["bind_requests"]]
        placed += [(e["pod"], e["move_to"]) for e in commit["evictions"]
                   if e.get("move_to")]
        outside = sum(1 for pod, node in placed
                      if pod in self.pods and node in self.nodes
                      and not node_allows(self.nodes[node], self.pods[pod]))
        self.counts["binds_outside_pool"] += outside
        return {"outside_pool": outside}
