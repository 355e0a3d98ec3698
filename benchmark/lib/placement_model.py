"""The plain placement reference: where Kubernetes lets a pod land.

Upstream ``TaintToleration`` and ``nodeSelector`` semantics over the
wire's own documents, in plain Python; it imports nothing of the program.
A pod may be bound to a node only if the node carries every label of the
pod's ``node_selector`` with that value, and every taint of the node
whose effect is ``NoSchedule`` or ``NoExecute`` is tolerated by one of
the pod's tolerations.  (``PreferNoSchedule`` is a preference and forbids
nothing.)

``lib/host_model.py`` decides ``correct`` and knows no label or taint;
until it does, ``layer_metrics/placement_violations.py`` counts through
this file, beside it.
"""
from __future__ import annotations

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


class PlacementModel:
    """Follows the pods through the documents posted and counts the
    binds and moves of each commit that land where they may not."""

    def __init__(self, cluster: dict):
        self.nodes = {n["name"]: n for n in cluster["nodes"]}
        self.pods: dict = {}
        self.apply_doc({"pods_upsert": cluster["pods"]})

    def apply_doc(self, doc: dict) -> None:
        for pod in doc.get("pods_upsert", []):
            self.pods[pod["name"]] = pod
        for name in doc.get("pods_delete", []):
            self.pods.pop(name, None)

    def violations(self, commit: dict) -> int:
        """Binds, and evictions that move a pod, onto a node that lacks
        the pod's selector labels or carries a taint it does not
        tolerate.  Names the documents do not know are the other
        reference's to count (``dangling_names``)."""
        placed = [(b["pod"], b["node"]) for b in commit["bind_requests"]]
        placed += [(e["pod"], e["move_to"]) for e in commit["evictions"]
                   if e.get("move_to")]
        return sum(1 for pod, node in placed
                   if pod in self.pods and node in self.nodes
                   and not node_allows(self.nodes[node], self.pods[pod]))
