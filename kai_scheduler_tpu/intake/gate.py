"""The hub-journal mutation gate — kai-intake's write choke point.

Every write into a cluster's :class:`~..state.incremental.MutationJournal`
outside the journal's own module flows through THIS module (lint rule
KAI091 enforces it, mirroring KAI071's wire discipline): the hub's own
mutators (``runtime/cluster.py``), the binder's commit write-backs, the
wire codec's delta appliers, and the intake router's coalesce step all
mark through these helpers.  One choke point buys two things:

- **ordering discipline** — the kai-intake differential bar (a storm
  coalesced through the lanes must be bit-identical to the sequential
  classic path) only holds while every journal write follows the same
  upsert/delete → mark mapping; scattering that mapping across call
  sites is how the two paths drift apart silently;
- **a place to stand** — future per-origin write accounting (the
  TransferLedger precedent) lands here once instead of N times.

The helpers are deliberately thin pass-throughs: the journal's locking
and cursor fan-out live with the journal (``state/incremental.py``);
the gate owns only the *semantic mapping* from object mutations to mark
kinds: pods, pod groups and bind requests map to per-key marks the
snapshotter patches (added, touched, removed); a new or deleted node or
queue, and any write to the DRA/volume stores, is ``structural`` and
rebuilds.  Dependency-free by design so ``runtime/cluster.py`` (which
everything imports) can route through it without cycles.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # pragma: no cover — typing (and kai-race) only
    from ..state.incremental import MutationJournal

#: collections whose upsert/delete journal mapping the gate knows; the
#: order is the canonical apply order of a delta document (see
#: ``intake/apply.py`` — both the classic path and the router's
#: coalesce decompose deltas in this order)
COLLECTIONS = ("nodes", "queues", "pod_groups", "pods", "bind_requests",
               "resource_claims", "device_classes", "volume_claims",
               "storage_classes")


# -- hub-mutator marks (runtime/cluster.py, binder) -----------------------

def pod_touched(journal: "MutationJournal", name: str) -> None:
    journal.mark_pod(name)


def pod_added(journal: "MutationJournal", name: str) -> None:
    journal.mark_pod_added(name)


def pod_removed(journal: "MutationJournal", name: str) -> None:
    journal.mark_pod_removed(name)


def gang_touched(journal: "MutationJournal", name: str) -> None:
    journal.mark_gang(name)


def gang_added(journal: "MutationJournal", name: str) -> None:
    journal.mark_gang_added(name)


def gang_removed(journal: "MutationJournal", name: str) -> None:
    journal.mark_gang_removed(name)


def node_touched(journal: "MutationJournal", name: str) -> None:
    journal.mark_node(name)


def structural(journal: "MutationJournal", reason: str) -> None:
    journal.mark_structural(reason)


def time_advanced(journal: "MutationJournal") -> None:
    journal.mark_time()


def merge_marks(journal: "MutationJournal", marks) -> None:
    """Bulk-replay an ordered ``(kind, name)`` mark batch — the
    coalesce step's single-lock-acquisition merge (see
    ``MutationJournal.merge``)."""
    journal.merge(marks)


# -- delta-document marks (wire codec + classic/lane delta apply) ---------

def upsert_marks(coll: str, key: str, obj, existed: bool,
                 out: list) -> None:
    """Append the ``(kind, name)`` mark ops an upsert of ``key`` into
    ``coll`` records, to ``out`` — the single source of the wire-delta
    journal mapping (formerly ``wire/codec._journal_upsert``)."""
    if coll == "pods":
        out.append(("pod", key) if existed else ("pod_added", key))
    elif coll == "pod_groups":
        out.append(("gang", key) if existed else ("gang_added", key))
    elif coll == "bind_requests":
        # a Pending BindRequest changes its pod's snapshot presentation
        out.append(("pod", obj.pod_name))
    elif coll == "nodes":
        # node rows anchor vocabularies/masks/device tables — dirty
        # nodes force a full snapshot rebuild either way
        out.append(("node", key) if existed
                   else ("structural", "node-added"))
    elif coll == "queues":
        if not existed:
            out.append(("structural", "queue-added"))
        # field updates on an existing queue re-encode every refresh
    else:
        out.append(("structural", f"{coll}-upsert"))


def delete_marks(coll: str, name: str, existed: bool, out: list) -> None:
    """Append the mark ops a delete records (formerly
    ``wire/codec._journal_delete``)."""
    if not existed:
        return
    if coll == "pods":
        out.append(("pod_removed", name))
    elif coll == "pod_groups":
        # a cluster deletes a group with its owner, so this is every
        # completion and every eviction: the snapshotter closes up the
        # gang's ledger row instead of rebuilding.  The delta applies
        # this collection before ``pods`` (COLLECTIONS), so the group's
        # pods may outlive the mark; the consumer orders the batch
        out.append(("gang_removed", name))
    elif coll == "bind_requests":
        out.append(("pod", name))
    else:
        # node/queue rows anchor vocabularies and dense id spaces
        out.append(("structural", f"{coll}-delete"))
