"""The hub-journal mutation gate — kai-intake's write choke point.

Every write into a cluster's :class:`~..state.incremental.MutationJournal`
outside the journal's own module flows through THIS module (lint rule
KAI091 enforces it, mirroring KAI071's wire discipline): the store's
collections as each write lands (``runtime/store.py``), the hub's own
mutators (``runtime/cluster.py``), the wire codec's delta appliers, and
the intake router's coalesce step all mark through these helpers.  One
choke point buys two things:

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
snapshotter patches (added, touched, removed), which the store makes
itself (``STORE_MARKS``); a new or deleted node or queue, and any write
to the DRA/volume stores, is ``structural`` and rebuilds.
Dependency-free by design so ``runtime/cluster.py`` (which everything
imports) can route through it without cycles.
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


# -- the store's own marks (runtime/store.py) ----------------------------

#: what a write to a store collection marks, by collection: (an object
#: stored under a new key, an object stored over another, an object
#: removed, a watched field of a stored object written) -> (kind, name;
#: None for the key), and the name its marks are counted under
#: (``JournalBatch.store_marks``).  A bind request is its pod's
#: presentation.  A node row anchors vocabularies, masks and device
#: tables: a node stored or removed, or written in place, rebuilds, under
#: the name the write has (``drift``); a node stored over another is the
#: delta's ``node`` mark.
STORE_MARKS = {
    "pods": ((("pod_added", None), ("pod", None), ("pod_removed", None),
              ("pod", None)), "pods"),
    "pod_groups": ((("gang_added", None), ("gang", None),
                    ("gang_removed", None), ("gang", None)), "gangs"),
    "bind_requests": ((("pod", None),) * 4, "bind_requests"),
    "nodes": ((("drift", "node-membership-drift"), ("node", None),
               ("drift", "node-membership-drift"), ("drift", "node-drift")),
              "nodes"),
}


#: the collections whose every write the store marks as it lands: a
#: delta document adds no mark of its own for them
_SELF_MARKED = ("pods", "pod_groups", "bind_requests")


def store_mark(journal: "MutationJournal", coll: str, event: int,
               key: str) -> None:
    """What ``event`` (an index into the collection's ``STORE_MARKS``)
    at ``key`` of ``coll`` marks, as the write lands."""
    marks, counted = STORE_MARKS[coll]
    kind, name = marks[event]
    journal.mark_stored(counted, kind, key if name is None else name)


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
    journal mapping (formerly ``wire/codec._journal_upsert``).  Pods,
    pod groups and bind requests are marked by the store itself as the
    write lands (``STORE_MARKS``)."""
    if coll in _SELF_MARKED:
        return
    if coll == "nodes":
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
    ``wire/codec._journal_delete``); as ``upsert_marks``, none for the
    collections the store marks itself."""
    if existed and coll not in _SELF_MARKED:
        # node/queue rows anchor vocabularies and dense id spaces
        out.append(("structural", f"{coll}-delete"))
