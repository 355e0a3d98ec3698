"""Incremental snapshot engine: journaled dirty-set refresh.

The reference keeps cluster state *incrementally* current via API-server
watches (SURVEY §2.6): each ``runOnce`` starts from an already-warm
cache and only the objects that changed since the last cycle cost any
work.  The seed port re-ran the full vectorized ``build_snapshot`` host
pass (~0.2 s warm at 10k nodes × 50k pods) plus one monolithic
``device_put`` every cycle — historically several times the entire
on-device solve, until this module (PR 1) made the host pass patch
dirty rows and ship only the leaves that changed.  At production
scale, cycle-to-cycle churn is a tiny fraction of the cluster; state
refresh cost should be proportional to *change*, not cluster size (the
Tesserae approach, arXiv:2508.04953).

Three pieces:

- :class:`MutationJournal` — the cluster hub's change feed.  Every
  mutation (``submit``/``bind_pod``/``evict_pod``/``tick``, binder
  commits, wire-delta upserts/deletes) records dirty, added and removed
  node/gang/pod keys under a generation counter.  Multiple consumers
  each get their own :class:`JournalCursor`.

- :class:`IncrementalSnapshotter` — retains the previous cycle's host
  arrays + ``SnapshotIndex`` and re-derives only dirty rows through the
  per-section builders factored out of ``build_snapshot``
  (``build_queue_tables``/``derive_rollups`` are shared verbatim; the
  pending-task and running-pod sections are re-assembled from cached
  per-entity encodes with vectorized numpy).  The tables keyed by a
  gang, a node or a leaf queue (running counts, device cells, the
  rollups) are kept from patch to patch, and only the entries of the
  keys a dirty row feeds are summed again, from their members in row
  order (``_rederive``).  Only changed leaves ship to the device;
  unchanged leaves reuse the previous cycle's device buffers.

- Automatic **fallback to the full rebuild** whenever a patch cannot be
  proven bit-identical to a fresh ``build_snapshot``:

  * structural change — node/queue set or order changed, topology
    swapped, padded-dim overflow (entity counts outgrew the pinned
    :class:`~.cluster_state.SnapshotCapacity`: ``overflow-gangs``,
    ``-tasks``, ``-types``, ``-running``, and ``overflow-subgroups``
    for a gang that declares more subgroups than the pinned ``S``
    holds).  The pod-group set is
    NOT structural: a new group appends a ledger row, a deleted one
    has its row closed up (``_remove_gangs`` — a cluster deletes a
    group with its owner, so every completion and eviction does
    this); only a name deleted and created again inside one window
    escalates, since it moved to the end of the store;
  * vocabulary growth (``vocab-growth``) — a node selector and
    tolerations ride the patch: the snapshotter pins the selector
    keys, label ids and node-only filter specs of the last rebuild
    (:class:`~.cluster_state.SnapshotVocabulary`, beside the capacity)
    and a pod's selector row and filter class are lookups in it.  A
    selector key, a pending pod's label value or a filter spec it does
    not hold would be numbered by a fresh build, so that one cycle
    rebuilds, pins the larger vocabulary, and the next patches.  Like
    the capacity it only grows: label columns and ``filter_masks`` rows
    are compiled shapes and ``dense_feasibility`` a static argument of
    the solve, so they stay when the last pod that used them leaves;
  * vocabulary residue (``vocab-residue``) — what the patch still
    cannot carry after its pods or nodes are gone: extended (MIG) keys,
    or a spec numbered by the last build but not pinned;
  * feature pods (``nonplain-pods``) — fractional/memory-share
    requests, DRA claims, volumes, host ports, pod affinity, node
    affinity, nominated nodes, extended resources (the irregular
    intake paths stay on the proven full builder; a pod-affinity or
    host-port spec is evaluated against the running pods, so it is
    never pinned).  A declared subgroup is not among them: the gang
    ledger holds each gang's name -> slot map and its ``[S]`` quorum
    rows, the pod ledger each pod's slot, and ``S`` is pinned with the
    capacity, so a Kubeflow job patches like a plain gang (a plain
    gang is one whose only slot is 0);
  * dirty fraction above ``dirty_threshold`` — patching stops paying
    once most of the cluster changed;
  * ledger drift — an object mutated without a journal mark (the
    object model is uninstrumented; a cheap identity/field sweep
    detects direct writes and falls back rather than serving a stale
    snapshot).

``verify=True`` (the scheduler's ``verify_incremental`` flag) rebuilds
from scratch — the same builder, under the pinned capacity and
vocabulary — after every patch and asserts the patched ``ClusterState``
is element-wise identical — including ``SnapshotIndex`` name maps.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import weakref

import jax
import numpy as np

from ..apis import types as apis
from ..runtime import wire_ledger as _wire
from ..runtime.tracing import SpanSections, span_of
from . import cluster_state as _cs
from . import node_filters
from .cluster_state import (
    SnapshotCapacity,
    SnapshotVocabulary,
    _LEADER_ROLES,
    _round_up,
    build_queue_tables,
    dense_row_ids,
    derive_rollups,
)

R = apis.NUM_RESOURCES

_PENDING = int(apis.PodStatus.PENDING)
_BOUND = int(apis.PodStatus.BOUND)
_RUNNING = int(apis.PodStatus.RUNNING)
_RELEASING = int(apis.PodStatus.RELEASING)


class IncrementalVerifyError(AssertionError):
    """A patched snapshot diverged from a fresh full rebuild."""


class _Fallback(Exception):
    """Internal: abandon the patch attempt, run the full rebuild."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


# ---------------------------------------------------------------------------
# Mutation journal
# ---------------------------------------------------------------------------


_CURSOR_FIELDS = ("pods_dirty", "pods_added", "pods_removed",
                  "gangs_dirty", "gangs_added", "gangs_removed",
                  "nodes_dirty", "structural", "time_dirty")


class JournalBatch:
    """One drained window of changes — private to the consumer that
    drained it (no lock needed to read it)."""

    __slots__ = _CURSOR_FIELDS

    def __init__(self):
        self.pods_dirty: set[str] = set()
        self.pods_added: list[str] = []
        self.pods_removed: set[str] = set()
        self.gangs_dirty: set[str] = set()
        self.gangs_added: list[str] = []
        self.gangs_removed: set[str] = set()
        self.nodes_dirty: set[str] = set()
        self.structural: list[str] = []
        self.time_dirty = False


class JournalCursor:
    """One consumer's pending change sets (drained by ``consume``).

    The cursor shares its journal's lock: marks (any thread — binder,
    status-updater workers, HTTP handler deltas) and ``consume`` (the
    snapshotter's refresh) are mutually exclusive, so a drain can never
    observe a half-recorded mutation or drop a mark that raced the
    field swap.
    """

    __slots__ = _CURSOR_FIELDS + ("_lock", "__weakref__")

    def __init__(self, lock: threading.Lock | None = None):
        self._lock = lock if lock is not None else threading.Lock()
        self._reset()

    def _reset(self) -> None:
        self.pods_dirty: set[str] = set()
        self.pods_added: list[str] = []
        self.pods_removed: set[str] = set()
        self.gangs_dirty: set[str] = set()
        self.gangs_added: list[str] = []
        self.gangs_removed: set[str] = set()
        self.nodes_dirty: set[str] = set()
        self.structural: list[str] = []
        self.time_dirty = False

    def consume(self) -> "JournalBatch":
        """Move the accumulated sets into a private batch and reset —
        atomically with respect to concurrent marks."""
        out = JournalBatch()
        with self._lock:
            for slot in _CURSOR_FIELDS:
                setattr(out, slot, getattr(self, slot))
            self._reset()
        return out


class MutationJournal:
    """The cluster hub's change feed (fan-out to registered cursors).

    Marks are cheap set/list inserts; with no cursor registered only the
    generation counter moves.  Consumers (one ``IncrementalSnapshotter``
    each) register a :class:`JournalCursor` and drain it per refresh.

    Thread-safe: marks arrive from the binder, the async status-updater
    workers, and ThreadingHTTPServer delta handlers while the scheduler
    thread drains cursors — every mark and every ``consume`` runs under
    one journal lock (a torn or lost mark would let the snapshotter
    serve a silently stale patch; see ``tests/test_incremental.py``
    journal-hammer regression).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.generation = 0  # kai-race: guarded-by=_lock
        self._cursors: list = []  # weakrefs to JournalCursor

    def __deepcopy__(self, memo):
        # a deep-copied cluster document (profile_cycle's private copy)
        # starts its own change feed: locks are not copyable, and the
        # copy's mutations must not dirty the original's consumers
        return MutationJournal()

    def register(self) -> JournalCursor:
        cur = JournalCursor(self._lock)
        with self._lock:
            self._cursors.append(weakref.ref(cur))
        return cur

    def _each(self):
        if not self._cursors:
            return
        dead = False
        for ref in self._cursors:
            cur = ref()
            if cur is None:
                dead = True
            else:
                yield cur
        if dead:
            self._cursors = [r for r in self._cursors if r() is not None]

    # -- marks ------------------------------------------------------------

    def _apply_mark(self, kind: str, name: str) -> None:
        """One mark's cursor fan-out — caller holds ``self._lock``.
        The single implementation behind both the per-mark methods and
        the kai-intake bulk :meth:`merge`, so a coalesced lane batch can
        never drift from the sequential mark semantics."""
        self.generation += 1
        for c in self._each():
            if kind == "pod":
                c.pods_dirty.add(name)
            elif kind == "pod_added":
                if name not in c.pods_removed and name not in c.pods_dirty:
                    c.pods_added.append(name)
                else:
                    # removed-then-readded (or dirtied) inside one window:
                    # position in the dict may have moved — too subtle to
                    # patch, let the sweep/full rebuild sort it out
                    c.structural.append("pod-readded")
            elif kind == "pod_removed":
                c.pods_removed.add(name)
            elif kind == "gang":
                c.gangs_dirty.add(name)
            elif kind == "gang_added":
                if name not in c.gangs_removed:
                    c.gangs_added.append(name)
                else:
                    # removed-then-readded inside one window: the name
                    # moved to the end of the store, every row between
                    # shifted — same escalation as pod-readded
                    c.structural.append("gang-readded")
            elif kind == "gang_removed":
                c.gangs_removed.add(name)
            elif kind == "node":
                c.nodes_dirty.add(name)
            elif kind == "structural":
                c.structural.append(name)
            elif kind == "time":
                c.time_dirty = True
            else:
                raise ValueError(f"unknown journal mark kind {kind!r}")

    def mark_pod(self, name: str) -> None:
        with self._lock:
            self._apply_mark("pod", name)

    def mark_pod_added(self, name: str) -> None:
        with self._lock:
            self._apply_mark("pod_added", name)

    def mark_pod_removed(self, name: str) -> None:
        with self._lock:
            self._apply_mark("pod_removed", name)

    def mark_gang(self, name: str) -> None:
        with self._lock:
            self._apply_mark("gang", name)

    def mark_gang_added(self, name: str) -> None:
        with self._lock:
            self._apply_mark("gang_added", name)

    def mark_gang_removed(self, name: str) -> None:
        with self._lock:
            self._apply_mark("gang_removed", name)

    def mark_node(self, name: str) -> None:
        with self._lock:
            self._apply_mark("node", name)

    def mark_structural(self, reason: str) -> None:
        with self._lock:
            self._apply_mark("structural", reason)

    def mark_time(self) -> None:
        with self._lock:
            self._apply_mark("time", "")

    def merge(self, marks) -> None:
        """Replay an ordered batch of ``(kind, name)`` mark operations
        under ONE lock acquisition — the kai-intake ``coalesce()``
        step's bulk merge of per-lane staged marks into the hub journal
        (``intake/router.py``).

        Event-for-event identical to calling the individual ``mark_*``
        methods in the same order: same per-cursor set/list mutations
        (including the pod-readded structural escalation, which is
        order-sensitive) and the same generation count.  Only the lock
        traffic is batched, so a 1M-event storm pays one acquisition
        per coalesce instead of one per mark."""
        if not marks:
            return
        with self._lock:
            for kind, name in marks:
                self._apply_mark(kind, name)


# ---------------------------------------------------------------------------
# The incremental snapshotter
# ---------------------------------------------------------------------------


def _slack(n: int) -> int:
    """Capacity headroom so modest growth between full rebuilds never
    changes a compiled shape (shapes recompile kernels)."""
    return n + max(2, n // 8)


def _is_plain_pod(pod: apis.Pod) -> bool:
    """Pods the patch path can encode row-wise: a node selector and
    tolerations are looked up in the pinned vocabulary
    (``_encode_pod``).  Everything else rides the irregular intake
    paths of the full builder (affinity terms, device-share
    bookkeeping, claims) and forces a fallback."""
    return not (
        pod.node_affinity
        or pod.pod_affinity or pod.extended or pod.resource_claims
        or pod.volume_claims or pod.host_ports
        or pod.nominated_node is not None
        or pod.accel_portion > 0 or pod.accel_memory_gib > 0
        or pod.dra_accel_count > 0)


#: the gang ledger's per-row arrays as (attribute, dtype, fill, one
#: entry per subgroup slot): built by ``_rebuild_ledgers``, grown by
#: ``_grow_gangs``, closed up by ``_remove_gangs`` (the lists of
#: ``_GANG_LISTS`` beside them hold exactly one entry per row)
_GANG_COLUMNS = (
    ("g_queue", np.int32, 0, False), ("g_minm", np.int32, 0, False),
    ("g_prio", np.int32, 0, False), ("g_preempt", bool, False, False),
    ("g_unsched", bool, False, False), ("g_start", np.float64, -1.0, False),
    ("g_stale", np.float64, np.nan, False),
    ("g_reqlvl", np.int32, -1, False), ("g_preflvl", np.int32, -1, False),
    ("g_sub_valid", bool, False, True), ("g_sub_minm", np.int32, 0, True),
    ("g_sub_rlvl", np.int32, -1, True),
)

#: the lists: the object, its name, the identities the sweep compares
#: (``topology_constraint``, ``sub_groups``) and the subgroup
#: name -> slot map
_GANG_LISTS = ("g_objs", "g_names", "g_tc", "g_subs", "g_slot")

#: likewise the pod ledger's, as (attribute, dtype, fill, row shape);
#: ``p_objs`` and ``p_sweep`` are the lists beside them
_POD_COLUMNS = (
    ("p_names", object, None, ()), ("p_live", bool, False, ()),
    ("p_req", np.float32, 0.0, (R,)), ("p_prio", np.int64, 0, ()),
    ("p_crea", np.float64, 0.0, ()), ("p_group", np.int32, -1, ()),
    ("p_leader", bool, False, ()), ("p_plain", bool, False, ()),
    ("p_devmask", np.int32, 0, ()), ("p_held", np.float32, 0.0, ()),
    ("p_hasdev", bool, False, ()), ("p_eff_status", np.int8, -1, ()),
    ("p_eff_node", np.int32, -1, ()), ("p_iid", np.int32, -1, ()),
    ("p_ti", np.int32, -1, ()), ("p_sub", np.int32, 0, ()),
    # what ``_occupancy`` gave a running pod (``devices_mask`` and
    # ``accel_held``): kept by pod because the running section is
    # positional, and derived again when the pod's node is touched
    ("p_occ_mask", np.int32, 0, ()), ("p_occ_held", np.float32, 0.0, ()),
)


def _gather_rows(table: np.ndarray, src: np.ndarray, fill) -> np.ndarray:
    """``table`` with rows ``src`` moved up to the front, in order, and
    every row behind them padding — a fresh array (the old one may be
    the previous cycle's leaf or index view)."""
    out = np.full_like(table, fill)
    out[:len(src)] = table[src]
    return out


#: ``SnapshotterStats.last`` of a rebuilt cycle, but for its reason
_FULL_STATS = {
    "mode": "full", "fallback_reason": "",
    "dirty_pods": 0, "dirty_gangs": 0,
    "pods_removed": 0, "gangs_removed": 0,
    "leaves_shipped": 0, "bytes_shipped": 0,
    "ship_seconds": 0.0, "ship_dispatches": 0, "filtered_pods": 0,
    "subgrouped_pods": 0, "subgrouped_gangs": 0,
    # nothing of a gang refuses the patch: the key stays for its readers
    "nonplain_gangs": 0,
}

#: in the intern tables, a label value or a filter spec the pinned
#: vocabulary does not hold (``_assemble`` refuses with ``vocab-growth``
#: where a fresh build would number it)
_UNKNOWN = -2


@dataclasses.dataclass
class SnapshotterStats:
    full_builds: int = 0
    patched: int = 0
    fallbacks: dict = dataclasses.field(default_factory=dict)
    leaves_shipped: int = 0
    bytes_shipped: int = 0
    #: the LAST refresh's journal-delta stats — mode (patched/full),
    #: fallback reason, dirty rows, changed leaves/bytes uploaded and
    #: upload seconds; feeds the kai-trace snapshot span's attributes
    #: and the bench phase attribution (runtime/tracing.py)
    last: dict = dataclasses.field(default_factory=dict)

    def fallback(self, reason: str) -> None:
        key = reason.split(":")[0]
        self.fallbacks[key] = self.fallbacks.get(key, 0) + 1


class IncrementalSnapshotter:
    """Journal-driven snapshot refresher for one ``Cluster``.

    ``refresh(cluster, now=..., queue_usage=...)`` returns the same
    ``(ClusterState, SnapshotIndex)`` pair ``build_snapshot`` would,
    either by patching the cached previous snapshot (dirty rows only,
    changed leaves only to device) or by falling back to the full
    builder.  Single consumer per journal cursor; one snapshotter per
    cluster document.
    """

    def __init__(self, *, verify: bool = False,
                 dirty_threshold: float = 0.35, tracer=None):
        self.verify = verify
        self.dirty_threshold = dirty_threshold
        self.stats = SnapshotterStats()
        #: optional runtime.tracing.CycleTracer — when the scheduler
        #: drives the refresh inside an open cycle trace, the patch /
        #: full-build sections and the device upload record themselves
        #: as child spans of the cycle's "snapshot" phase.  Tracer calls
        #: no-op without an open cycle (bench/CLI refreshes stay free).
        self._tracer = tracer
        self._cluster_ref = None
        self._cursor: JournalCursor | None = None
        self._host = None        # numpy ClusterState (previous cycle)
        self._dev = None         # device ClusterState (previous cycle)
        self._index = None
        self._capacity = SnapshotCapacity()
        #: selector keys, label ids and node-only filter specs of the
        #: last rebuild, pinned like the capacity: they only grow (a
        #: spec that reads the running pods is never among them)
        self._vocabulary = SnapshotVocabulary()
        #: the last rebuild's irregular vocabularies (``stats.last``)
        self._built_vocab: dict = {}
        #: the patch's derived tables as the last patch left them, by
        #: gang (``running_count``, ``sub_running``), by node
        #: (``dev_free``, ``dev_rel`` and ``derive_rollups``' own) and
        #: by queue before the parents are added: ``_rederive`` derives
        #: the touched keys' entries again and takes the rest from here
        self._kept: dict | None = None
        self._forget_touched()

    def _add_span(self, name: str, start: float, **attrs) -> None:
        if self._tracer is not None:
            self._tracer.add_span(name, start, time.perf_counter(),
                                  **attrs)

    def _span(self, name: str, **attrs):
        """A child span of whatever is open (``with``): nothing
        recorded without a tracer or outside a cycle."""
        return span_of(self._tracer, name, **attrs)

    # -- public -----------------------------------------------------------

    def refresh(self, cluster, *, now: float | None = None,
                queue_usage=None):
        if (self._cluster_ref is None
                or self._cluster_ref() is not cluster):
            self._cluster_ref = weakref.ref(cluster)
            journal = getattr(cluster, "journal", None)
            self._cursor = (journal.register()
                            if journal is not None else None)
            self._host = None
        j = (self._cursor.consume() if self._cursor is not None
             else None)
        reason = self._patch_blockers(cluster, j)
        if reason is None:
            with self._span("snapshot.patch") as patch_sp:
                try:
                    host_new, index = self._patch(cluster, j, now,
                                                  queue_usage)
                except _Fallback as exc:
                    reason = exc.reason
                    patch_sp.name = "snapshot.patch_abandoned"
                    patch_sp.attrs["fallback_reason"] = reason
            if reason is None:
                state = self._ship(host_new)
                self._index = index
                self.stats.patched += 1
                ship = self._last_ship
                self.stats.last = {
                    "mode": "patched", "fallback_reason": "",
                    "dirty_pods": self._last_dirty[0],
                    "dirty_gangs": self._last_dirty[1],
                    "pods_removed": self._last_removed[0],
                    "gangs_removed": self._last_removed[1],
                    "leaves_shipped": ship[0], "bytes_shipped": ship[1],
                    "ship_seconds": ship[2], "ship_dispatches": ship[3],
                    # what the patch cannot carry ...
                    "nonplain_pods": self._nonplain,
                    "nonplain_gangs": 0,
                    # ... and what it carried, of the pinned vocabulary
                    # and of declared subgroups
                    "filter_classes": len(self._vocabulary.filter_specs),
                    "selector_keys": len(self._vocabulary.selector_keys),
                    "filtered_pods": self._last_filtered,
                    "subgrouped_pods": self._last_subgrouped,
                    "subgrouped_gangs": self._subgrouped_gangs,
                    # the keys whose entries were derived again, and
                    # the running rows that fed one
                    **self._last_rederived,
                }
                patch_sp.attrs.update(self.stats.last)
                if self.verify:
                    self._verify(cluster, now, queue_usage)
                return state, index
        self.stats.fallback(reason)
        # the transfer happens inside the rebuild (its own span,
        # snapshot.transfer) and the wire ledger books it under
        # "fallback", so this cycle's upload phase reads 0
        with self._span("snapshot.full_build", fallback_reason=reason):
            out = self._full(cluster, now, queue_usage)
        self.stats.last = dict(_FULL_STATS, fallback_reason=reason,
                               **self._built_vocab)
        return out

    # -- fallback decisions ----------------------------------------------

    def _patch_blockers(self, cluster, j) -> str | None:
        # environment conditions first: they also tell _full whether a
        # ledger rebuild is worth paying for
        if self._cursor is None:
            return "no-journal"
        if (cluster.resource_claims or cluster.device_classes
                or cluster.volume_claims or cluster.storage_classes):
            return "feature-stores"
        if self._host is None:
            return "cold"
        if j.structural:
            return f"structural:{j.structural[0]}"
        if j.nodes_dirty:
            return "node-dirty"
        if cluster.topology is not self._topology:
            return "topology-changed"
        # the pods and gangs that are here before the vocabulary they
        # leave behind: a reason names its cause
        if self._nonplain > 0:
            return "nonplain-pods"
        if not self._clean:
            return "vocab-residue"
        if self._present_twice > 0:
            return "inflight-move"
        return None

    # ------------------------------------------------------------------
    # Full rebuild: run build_snapshot, then rebuild every ledger/cache
    # ------------------------------------------------------------------

    def _full(self, cluster, now, queue_usage):
        self.stats.full_builds += 1
        # go cold first: if the build raises (bad config propagates to
        # the caller), the next refresh must not patch over a cache that
        # no longer matches the already-consumed journal
        self._host = None
        with self._span("snapshot.lists"):
            lists = cluster.snapshot_lists()
            nodes, queues, groups, pods, topology = lists
            live_nodes = [n for n in nodes if not n.unschedulable]
            pend_per_group: dict[str, int] = {g.name: 0 for g in groups}
            n_running = 0
            for p in pods:
                if p.status == apis.PodStatus.PENDING:
                    if p.group in pend_per_group:
                        pend_per_group[p.group] += 1
                elif p.status in (apis.PodStatus.BOUND,
                                  apis.PodStatus.RUNNING,
                                  apis.PodStatus.RELEASING):
                    n_running += 1
            max_pending = max(pend_per_group.values(), default=0)
        old = self._capacity

        def keep(floor: int, count: int) -> int:
            # capacity only grows: a rebuild keeps the axis it already
            # compiled for while the count still fits it — a churn-
            # driven fallback must not also cost a recompile of the
            # fused pipeline (minutes at 10k nodes)
            return floor if count <= floor else _slack(count)

        cap = SnapshotCapacity(
            nodes=keep(old.nodes, len(live_nodes)),
            queues=keep(old.queues, len(queues)),
            gangs=keep(old.gangs, len(groups)),
            tasks=keep(old.tasks, max_pending),
            running=keep(old.running, n_running), types=old.types,
            subgroups=old.subgroups)
        # through the module attribute so test harnesses that wrap
        # build_snapshot (padding unification) stay in effect.  The
        # wire ledger re-labels the build's transfer "fallback": the
        # incremental engine rebuilt in full (cold start included) —
        # distinguishable on /debug/wire from a deliberate full build
        with _wire.LEDGER.override_reason(_wire.REASON_FALLBACK):
            state, index, host = _cs.build_snapshot(
                *lists, now=now, queue_usage=queue_usage,
                resource_claims=cluster.resource_claims,
                device_classes=cluster.device_classes,
                volume_claims=cluster.volume_claims,
                storage_classes=cluster.storage_classes,
                capacity=cap, vocabulary=self._vocabulary,
                _return_host=True, tracer=self._tracer)
        self._built_vocab = {
            "filter_classes": int(host.nodes.filter_masks.shape[0]),
            "selector_keys": len(index.selector_keys)}
        # the per-entity ledger only pays off if a later cycle can
        # actually patch — skip it (stay cold) while a persistent
        # environment condition forces full rebuilds regardless, e.g. a
        # DRA/volume deployment whose feature stores never empty
        if (self._cursor is None or cluster.resource_claims
                or cluster.device_classes or cluster.volume_claims
                or cluster.storage_classes):
            return state, index
        # pin realized padded dims as the next capacity (floors already
        # include the slack via `cap`; Y absorbs its own round-up slack)
        self._capacity = SnapshotCapacity(
            nodes=host.nodes.valid.shape[0],
            queues=host.queues.valid.shape[0],
            gangs=host.gangs.valid.shape[0],
            tasks=host.gangs.task_valid.shape[1],
            running=host.running.valid.shape[0],
            types=host.gangs.type_req.shape[0],
            subgroups=host.gangs.subgroup_valid.shape[1])
        self._vocabulary = index.vocabulary.node_only()
        self._host, self._dev, self._index = host, state, index
        with self._span("snapshot.ledgers"):
            self._rebuild_ledgers(cluster, lists, host, index)
        self._built_vocab.update(nonplain_pods=self._nonplain)
        return state, index

    def _rebuild_ledgers(self, cluster, lists, host, index) -> None:
        nodes, queues, groups, pods, topology = lists
        self._topology = cluster.topology
        self._kept = None
        # --- node-section caches (valid until any node is dirty) ---------
        self._node_names = index.node_names
        self._node_names_arr = np.array(index.node_names, dtype=object)
        self._node_index = {n: i for i, n in enumerate(index.node_names)}
        live_nodes = [n for n in nodes if not n.unschedulable]
        self._node_objs = live_nodes
        self._node_cache = [
            (n, n.allocatable, n.labels, n.taints, n.extended,
             n.accel_memory_gib) for n in live_nodes]
        # the patch path looks selectors and filter classes up in the
        # pinned vocabulary; what it cannot carry keeps forcing full
        # rebuilds until a build comes out without it: extended (MIG)
        # keys, and a spec numbered in this build but not pinned (its
        # mask reads the running pods)
        vocab = self._vocabulary
        self._clean = (
            not index.extended_keys
            and vocab.filter_specs == index.vocabulary.filter_specs)
        self._accel_counts = np.fromiter(
            (int(round(n.allocatable.accel)) for n in live_nodes),
            np.int64, len(live_nodes))
        N = host.nodes.valid.shape[0]
        D = host.nodes.device_free.shape[1]
        tmpl = np.zeros((N, D), np.float32)
        for i, c in enumerate(self._accel_counts):
            tmpl[i, :c] = 1.0
        self._dev_template = tmpl
        self._queue_names = list(index.queue_names)
        # topology level resolution caches (gang encodes)
        if topology is None:
            topos: list[apis.Topology] = []
        elif isinstance(topology, apis.Topology):
            topos = [topology]
        else:
            topos = list(topology)
        self._topo_levels = [lvl for t in topos for lvl in t.levels]
        self._topo_slices = {}
        off = 0
        for t in topos:
            self._topo_slices[t.name] = (off, list(t.levels))
            off += len(t.levels)
        # --- gang ledger --------------------------------------------------
        NG = len(groups)
        # rows start as None so _encode_gang's delta-tracking sees a
        # fresh row (not the gang it is about to encode)
        for name in _GANG_LISTS:
            setattr(self, name, [None] * NG)
        self._gang_index = {g.name: i for i, g in enumerate(groups)}
        S = self._capacity.subgroups
        for name, dtype, fill, wide in _GANG_COLUMNS:
            setattr(self, name,
                    np.full((NG, S) if wide else (NG,), fill, dtype))
        self._q_index = {n: i for i, n in enumerate(self._queue_names)}
        #: live gangs that declare subgroups (``uniform_gangs``)
        self._subgrouped_gangs = 0
        for i, g in enumerate(groups):
            self._encode_gang(i, g)
        # --- pod ledger ---------------------------------------------------
        U = len(pods)
        self.p_objs: list = [None] * U
        #: per-row (obj, raw status, raw node) — ONE list index per pod
        #: in the sweep's hot loop
        self.p_sweep: list = [None] * U
        for name, dtype, fill, tail in _POD_COLUMNS:
            setattr(self, name, np.full((U,) + tail, fill, dtype))
        #: distinct (request, selector items, filter class) of the
        #: ledger's pods, as the builder's ``_tkey`` tells task types
        #: apart, with each one's encoded rows
        self._intern: dict[tuple, int] = {}
        self._intern_req = np.zeros((0, R), np.float32)
        self._intern_sel = np.zeros(
            (0, max(1, len(vocab.selector_keys))), np.int32)
        self._intern_cls = np.zeros((0,), np.int32)
        self._intern_newkey = np.zeros((0,), bool)
        self._class_of: dict[tuple, int] = {}
        self._nonplain = 0
        self._present_twice = 0
        # NOTE: ledger rows follow the RAW pod-dict order — the lists
        # argument interleaves presentation copies, so encode from the
        # cluster store itself (presentation is re-derived per row)
        self._pod_row = {}
        for row, (name, pod) in enumerate(cluster.pods.items()):
            self._pod_row[name] = row
            self._encode_pod(row, pod, cluster)
        self._order = np.arange(U, dtype=np.int64)
        self._order_list = list(range(U))
        #: BindRequest presentation cache — a Pending BR re-presents its
        #: pod as bound (snapshot_lists), so BR creation/phase/target
        #: drift must dirty the pod even when the pod object is untouched
        self._br_cache = {
            name: (br, br.phase, br.selected_node)
            for name, br in cluster.bind_requests.items()}
        # cached per-pod task slots come from the freshly built tables
        self._task_names_obj = np.array(index.task_names, dtype=object) \
            if index.task_names else np.full(
                (host.gangs.valid.shape[0],
                 host.gangs.task_valid.shape[1]), None, object)
        self._seed_task_slots(host)
        # constant gang-side tables reused by identity between refreshes
        g = host.gangs
        self._const = dict(
            task_portion=np.asarray(g.task_portion),
            task_accel_mem=np.asarray(g.task_accel_mem),
            task_nominated=np.asarray(g.task_nominated),
            anti_self_level=np.asarray(g.anti_self_level),
            anti_marks=np.asarray(g.anti_marks),
            anti_avoids=np.asarray(g.anti_avoids),
            attract_needs=np.asarray(g.attract_needs),
            anti_term_level=np.asarray(g.anti_term_level),
            attract_static=np.asarray(g.attract_static),
            task_extended=np.asarray(g.task_extended),
            task_dra=np.asarray(g.task_dra),
            ext_accel=np.asarray(g.ext_accel),
        )
        self._forget_touched()
        # where a patch can follow (``_patch_blockers``), seed what it
        # keeps; elsewhere its derivation has nothing to say (the
        # irregular intake paths are the builder's alone)
        if self._clean and not (self._nonplain or self._present_twice):
            self._seed_kept(host)

    def _seed_task_slots(self, host) -> None:
        """Recover per-pod (gang, slot) assignments from the built task
        tables so undirty gangs never need re-sorting."""
        self.p_ti[:] = -1
        names = self._task_names_obj
        G, T = names.shape
        name_row = self._pod_row
        gi, ti = np.nonzero(np.asarray(host.gangs.task_valid))
        for g0, t0 in zip(gi.tolist(), ti.tolist()):
            nm = names[g0, t0]
            if nm is not None:
                row = name_row.get(nm)
                if row is not None:
                    self.p_ti[row] = t0

    # -- per-entity encodes ------------------------------------------------

    def _encode_gang(self, i, g: apis.PodGroup) -> None:
        prev = self.g_objs[i]
        subs = g.sub_groups
        S = self._capacity.subgroups
        if len(subs) > S - 1:
            raise _Fallback("overflow-subgroups")
        self._subgrouped_gangs += int(bool(subs)) - int(
            prev is not None and bool(self.g_subs[i]))
        # declared subgroups take slots 1.., slot 0 is the default
        # subgroup (the builder's ``sub_slot``)
        slot = {sg.name: si for si, sg in enumerate(subs, start=1)}
        reslot = prev is not None and slot != self.g_slot[i]
        self.g_subs[i] = subs
        self.g_slot[i] = slot
        if reslot:
            # the pods this row already has keep their names and move
            mine = np.flatnonzero(self.p_live & (self.p_group == i))
            for row in mine.tolist():
                self.p_sub[row] = self._slot_of(i, self.p_objs[row])
        if prev is not None:
            self._touched_queues.add(int(self.g_queue[i]))
        self.g_objs[i] = g
        self.g_names[i] = g.name
        self.g_queue[i] = self._q_index.get(g.queue, 0)
        self._touched_queues.add(int(self.g_queue[i]))
        self._touched_gangs.add(i)
        self.g_minm[i] = g.min_member
        self.g_prio[i] = g.priority
        self.g_preempt[i] = (
            g.preemptibility == apis.Preemptibility.PREEMPTIBLE)
        self.g_unsched[i] = bool(g.unschedulable)
        self.g_start[i] = (-1.0 if g.last_start_timestamp is None
                           else g.last_start_timestamp)
        self.g_stale[i] = (np.nan if g.stale_since is None
                           else g.stale_since)
        tc = g.topology_constraint
        self.g_tc[i] = tc
        req = self._resolve_level(tc, "required_level")
        self.g_reqlvl[i] = req
        self.g_preflvl[i] = self._resolve_level(tc, "preferred_level")
        self.g_sub_valid[i] = np.arange(S) <= len(subs)
        self.g_sub_minm[i] = 0
        self.g_sub_minm[i, 0] = 0 if subs else g.min_member
        # a slot without a level of its own, padding included, takes
        # the gang's required level
        self.g_sub_rlvl[i] = req
        for si, sg in enumerate(subs, start=1):
            self.g_sub_minm[i, si] = sg.min_member
            own = self._resolve_level(sg.topology_constraint,
                                      "required_level")
            if own >= 0:
                self.g_sub_rlvl[i, si] = own

    def _resolve_level(self, tc, attr) -> int:
        if tc is None or not self._topo_levels:
            return -1
        start, lvls = self._topo_slices.get(
            tc.topology, (0, self._topo_levels))
        name = getattr(tc, attr)
        return start + lvls.index(name) if name in lvls else -1

    def _encode_pod(self, row, pod: apis.Pod, cluster) -> None:
        was_plain = bool(self.p_plain[row]) if self.p_live[row] else True
        was_twice = bool(self.p_live[row]
                         and self.p_eff_status[row] == -2)
        if self.p_live[row]:
            self._touch_row(row)
        self.p_occ_mask[row] = 0
        self.p_occ_held[row] = 0.0
        self.p_objs[row] = pod
        self.p_names[row] = pod.name
        self.p_live[row] = True
        self.p_sweep[row] = (pod, pod.status, pod.node)
        self.p_req[row] = pod.resources.as_tuple()
        self.p_prio[row] = pod.priority
        self.p_crea[row] = pod.creation_timestamp
        gi = self._gang_index.get(pod.group, -1)
        self.p_group[row] = gi
        self.p_sub[row] = self._slot_of(gi, pod)
        labels = pod.labels
        self.p_leader[row] = (
            (labels.get("training.kubeflow.org/job-role")
             or labels.get("ray.io/node-type")) not in _LEADER_ROLES
            if labels else True)
        plain = _is_plain_pod(pod) and all(
            0 <= d < 32 for d in pod.accel_devices)
        self.p_plain[row] = plain
        self._nonplain += (not plain) - (not was_plain)
        k = int(round(pod.resources.accel))
        devs = list(pod.accel_devices)[:k] if plain else []
        mask = 0
        for d in devs:
            mask |= 1 << int(d)
        self.p_devmask[row] = mask
        self.p_held[row] = float(len(devs))
        self.p_hasdev[row] = bool(pod.accel_devices)
        # presented (effective) status — the snapshot_lists semantics
        st, nd = int(pod.status), pod.node
        twice = False
        br = cluster.bind_requests.get(pod.name)
        if br is not None and br.phase == "Pending":
            if st == _PENDING:
                st, nd = _BOUND, br.selected_node
            elif st == _RELEASING:
                twice = True  # presented twice: old node + rebind target
        self._present_twice += int(twice) - int(was_twice)
        self.p_eff_status[row] = -2 if twice else st
        self.p_eff_node[row] = (self._node_index.get(nd, -1)
                                if nd is not None else -1)
        # the class the builder's filter_class_of gives a pod the patch
        # carries: its spec holds tolerations and nothing else
        cls = 0
        if plain and pod.tolerations:
            tol = tuple(pod.tolerations)
            cls = self._class_of.get(tol)
            if cls is None:
                spec = node_filters.pod_filter_spec(pod)
                specs = self._vocabulary.filter_specs
                cls = self._class_of[tol] = (
                    specs.index(spec) if spec in specs else _UNKNOWN)
        key = (tuple(float(x) for x in pod.resources.as_tuple()),
               tuple(sorted(pod.node_selector.items()))
               if pod.node_selector else (), cls)
        iid = self._intern.get(key)
        if iid is None:
            iid = self._intern_add(key)
        self.p_iid[row] = iid
        self._touch_row(row)

    def _touch_row(self, row) -> None:
        """The keys that live row ``row`` feeds as it stands — its
        node, its gang, the queue its request is booked to — are
        derived again this cycle.  Called on what a row held before it
        is encoded anew or released, and on what it holds after: a
        key's entry depends on its members alone, so these are all the
        entries a change of the row can move."""
        node, gi = int(self.p_eff_node[row]), int(self.p_group[row])
        if node >= 0:
            self._touched_nodes.add(node)
        if gi >= 0:
            self._touched_gangs.add(gi)
        # a running pod without a gang is booked to queue 0
        self._touched_queues.add(int(self.g_queue[gi]) if gi >= 0 else 0)

    def _forget_touched(self) -> None:
        #: node rows, gang rows and queue rows to derive again, or
        #: every key: what ``_touched_masks`` hands over
        self._touched_nodes: set[int] = set()
        self._touched_gangs: set[int] = set()
        self._touched_queues: set[int] = set()
        self._touch_all = False

    def _slot_of(self, gi: int, pod: apis.Pod) -> int:
        """The pod's subgroup slot in gang row ``gi``, as the builder
        looks it up: a name the gang does not declare, no name and no
        gang are the default slot."""
        if gi < 0:
            return 0
        return self.g_slot[gi].get(pod.subgroup or "", 0)

    def _intern_add(self, key: tuple) -> int:
        """A new row of the intern tables: the request, the selector
        row and the class, by lookup in the pinned vocabulary."""
        req, sel_items, cls = key
        iid = len(self._intern)
        self._intern[key] = iid
        sel = np.full((1, self._intern_sel.shape[1]), -1, np.int32)
        vocab = self._vocabulary
        newkey = False
        for k, v in sel_items:
            if k in vocab.selector_keys:
                sel[0, vocab.selector_keys.index(k)] = (
                    vocab.label_vocab.get((k, v), _UNKNOWN))
            else:
                newkey = True
        self._intern_req = np.concatenate(
            [self._intern_req, np.asarray([req], np.float32)])
        self._intern_sel = np.concatenate([self._intern_sel, sel])
        self._intern_cls = np.append(self._intern_cls, np.int32(cls))
        self._intern_newkey = np.append(self._intern_newkey, newkey)
        return iid

    def _release_pod(self, row) -> None:
        if not self.p_live[row]:
            return
        self._touch_row(row)
        self.p_live[row] = False
        self._nonplain -= int(not self.p_plain[row])
        self._present_twice -= int(self.p_eff_status[row] == -2)
        self.p_objs[row] = None
        self.p_sweep[row] = None

    # ------------------------------------------------------------------
    # Patch path
    # ------------------------------------------------------------------

    def _grow_pods(self, extra: int) -> None:
        """Grow the ARRAY capacity (lists append exactly; arrays carry
        slack so appends stay amortized O(1))."""
        U = len(self.p_live)
        n = max(extra, U // 2, 64)
        for name, dtype, fill, tail in _POD_COLUMNS:
            setattr(self, name, np.concatenate(
                [getattr(self, name), np.full((n,) + tail, fill, dtype)]))

    def _compact_pods(self) -> None:
        """Close up the pod ledger over its released rows (appends only
        ever take new rows, so churn leaves dead ones behind).  Live
        rows keep their order, which is the store's; nothing they hold
        changes, so nothing becomes dirty."""
        keep = self.p_live[:len(self.p_objs)]
        src = np.flatnonzero(keep)
        n = len(src)
        self._touch_all = True
        remap = np.cumsum(keep) - 1
        kept = keep.tolist()
        self.p_objs = list(itertools.compress(self.p_objs, kept))
        self.p_sweep = list(itertools.compress(self.p_sweep, kept))
        for name, _dtype, fill, _tail in _POD_COLUMNS:
            col = getattr(self, name)
            col[:n] = col[src]
            col[n:] = fill
        self._pod_row = {name: row for row, name
                         in enumerate(self.p_names[:n].tolist())}
        self._order = remap[self._order]
        self._order_list = self._order.tolist()

    def _grow_gangs(self, extra: int) -> None:
        """Array-capacity growth; the g_* lists append exactly."""
        n = max(extra, 8)
        for name, dtype, fill, _wide in _GANG_COLUMNS:
            col = getattr(self, name)
            setattr(self, name, np.concatenate(
                [col, np.full((n,) + col.shape[1:], fill, dtype)]))

    def _apply_journal(self, cluster, j, sections
                       ) -> tuple[set, set, np.ndarray | None]:
        """Membership + dirty-field updates → (dirty pod rows, dirty
        gang rows, old row of each gang row).  The last is None unless
        gang rows went (``_remove_gangs``).  Raises _Fallback on
        anything unpatchable.  ``sections(name)`` marks its steps:
        ``journal.removed`` (pods that went, and the closing-up of
        removed gang rows), ``journal.gangs`` (added and dirty gangs),
        ``journal.pods`` (added and dirty pods, ``_encode_pod``)."""
        sections("journal.removed")
        dirty_gangs: set[int] = set()
        dirty_rows: set[int] = set()
        membership = False
        pods_gone = 0
        # removals first, pods ahead of gangs: a delta deletes a group
        # before its pods (gate.COLLECTIONS) but the batch is applied
        # after the fact, and a pod that went in the same window is not
        # an orphan of its group
        for name in j.pods_removed:
            row = self._pod_row.get(name)
            if row is None or not self.p_live[row]:
                continue
            gi = int(self.p_group[row])
            if gi >= 0:
                dirty_gangs.add(gi)
            self._release_pod(row)
            del self._pod_row[name]
            membership = True
            pods_gone += 1
        gang_src = None
        gangs_gone = 0
        if j.gangs_removed:
            gang_src, gangs_gone = self._remove_gangs(
                cluster, j.gangs_removed, dirty_rows, dirty_gangs)
        self._last_removed = (pods_gone, gangs_gone)
        # gang appends before pod appends so new pods resolve their row
        sections("journal.gangs")
        if j.gangs_added:
            for name in j.gangs_added:
                g = cluster.pod_groups.get(name)
                if g is None and name in j.gangs_removed:
                    continue  # added then removed within the window
                if g is None or name in self._gang_index:
                    raise _Fallback("gang-add-drift")
                i = len(self._gang_index)
                if i >= len(self.g_queue):
                    self._grow_gangs(max(8, i // 4))
                for lst in _GANG_LISTS:
                    getattr(self, lst).append(None)
                self._gang_index[name] = i
                self._encode_gang(i, g)
                dirty_gangs.add(i)
            # a pod encoded before its group existed now resolves
            unresolved = np.nonzero(self.p_live
                                    & (self.p_group < 0))[0]
            for row in unresolved.tolist():
                gi = self._gang_index.get(self.p_objs[row].group, -1)
                if gi >= 0:
                    self._touch_row(row)
                    self.p_group[row] = gi
                    self.p_sub[row] = self._slot_of(gi, self.p_objs[row])
                    self._touch_row(row)
                    dirty_rows.add(row)
                    dirty_gangs.add(gi)
        for name in j.gangs_dirty:
            i = self._gang_index.get(name)
            if i is None:
                continue  # touched, then removed within the window
            g = cluster.pod_groups.get(name)
            if g is None:
                raise _Fallback("gang-removed-unjournaled")
            self._encode_gang(i, g)
            dirty_gangs.add(i)
        sections("journal.pods")
        added_rows: list[int] = []
        for name in j.pods_added:
            pod = cluster.pods.get(name)
            if pod is None:
                continue  # added then removed within the window
            if name in self._pod_row:
                raise _Fallback("pod-add-drift")
            row = len(self.p_objs)
            if row >= len(self.p_live):
                self._grow_pods(64)
            self.p_objs.append(None)
            self.p_sweep.append(None)
            self._pod_row[name] = row
            self._encode_pod(row, pod, cluster)
            dirty_rows.add(row)
            added_rows.append(row)
            gi = int(self.p_group[row])
            if gi >= 0:
                dirty_gangs.add(gi)
        for name in j.pods_dirty:
            row = self._pod_row.get(name)
            if row is None:
                continue
            pod = cluster.pods.get(name)
            if pod is None:
                raise _Fallback("pod-removed-unjournaled")
            gi_old = int(self.p_group[row])
            self._encode_pod(row, pod, cluster)
            dirty_rows.add(row)
            for gi in (gi_old, int(self.p_group[row])):
                if gi >= 0:
                    dirty_gangs.add(gi)
        if membership or added_rows:
            keep = self.p_live[self._order]
            order = self._order[keep]
            if added_rows:
                order = np.concatenate(
                    [order, np.asarray(added_rows, np.int64)])
            self._order = order
            self._order_list = order.tolist()
        return dirty_rows, dirty_gangs, gang_src

    def _remove_gangs(self, cluster, names, dirty_rows: set,
                      dirty_gangs: set) -> tuple[np.ndarray | None, int]:
        """Close up the ledger over the removed gangs' rows, in one
        pass whatever their number → (src, rows removed).

        A gang's row is its position in ``cluster.pod_groups``, so a
        removal moves every later row up.  ``src[i]`` is the old row
        of new row ``i``: ``_assemble`` sends the retained ``[G, T]``
        task rows through it; everything else it derives from the
        ledger.  A row that only moved is NOT dirty — its task slots
        stand, it is not re-sorted and it does not count toward
        ``dirty_threshold``; ``dirty_gangs`` comes out in new rows.  A
        live pod of a removed gang is an orphan, encoded as a rebuild
        would encode it (``p_group`` -1)."""
        # a name not in the ledger was added in this same window and
        # never got a row
        gone = [i for i in map(self._gang_index.get, names)
                if i is not None]
        if not gone:
            return None, 0
        keep = np.ones((len(self.g_objs),), bool)
        keep[gone] = False
        src = np.flatnonzero(keep)
        remap = np.where(keep, np.cumsum(keep) - 1, -1).astype(np.int32)
        self._subgrouped_gangs -= sum(bool(self.g_subs[i]) for i in gone)
        # a gang that goes takes its pending request out of its queue
        self._touched_queues.update(self.g_queue[gone].tolist())
        kept = keep.tolist()
        for name in _GANG_LISTS:
            setattr(self, name,
                    list(itertools.compress(getattr(self, name), kept)))
        self._gang_index = {n: i for i, n in enumerate(self.g_names)}
        for name, _dtype, _fill, _wide in _GANG_COLUMNS:
            col = getattr(self, name)
            col[:len(src)] = col[src]
        for rows in (dirty_gangs, self._touched_gangs):
            moved = [int(remap[i]) for i in rows if keep[i]]
            rows.clear()
            rows.update(moved)
        had = self.p_group >= 0
        self.p_group[had] = remap[self.p_group[had]]
        orphans = np.flatnonzero(self.p_live & had & (self.p_group < 0))
        for row in orphans.tolist():
            self._encode_pod(row, self.p_objs[row], cluster)
            dirty_rows.add(row)
        return src, len(gone)

    def _sweep(self, cluster, dirty_rows: set, dirty_gangs: set,
               sections) -> None:
        """Detect un-journaled drift: object replacement, status/node
        writes, gang status writes, node mutations.  Cheap identity and
        field compares; anything the ledger cannot attribute raises
        _Fallback (full rebuild) rather than serving stale state.
        ``sections(name)`` marks its four loops: ``sweep.bind_requests``,
        ``sweep.pods``, ``sweep.gangs``, ``sweep.nodes``."""
        sections("sweep.bind_requests")
        if len(cluster.pods) != len(self._order_list):
            raise _Fallback("pod-membership-drift")
        # BindRequest drift (created/replaced/phase-flipped/cleared —
        # bench and test harnesses touch the store directly): re-encode
        # the affected pods' presentation
        brs = cluster.bind_requests
        br_cache = self._br_cache
        br_dirty: list[str] = []
        if brs or br_cache:
            for name, br in brs.items():
                c = br_cache.get(name)
                if (c is None or c[0] is not br or c[1] != br.phase
                        or c[2] != br.selected_node):
                    br_dirty.append(name)
            if len(br_cache) != len(brs) or br_dirty:
                # sorted: the set difference iterates in hash order,
                # which would make the dirty-row encode order (and any
                # tie-broken downstream buffer) run-dependent (KAI041)
                for name in sorted(br_cache.keys() - brs.keys()):
                    br_dirty.append(name)
                self._br_cache = {
                    name: (br, br.phase, br.selected_node)
                    for name, br in brs.items()}
        for name in br_dirty:
            row = self._pod_row.get(name)
            if row is None or not self.p_live[row]:
                continue
            if row not in dirty_rows:
                self._encode_pod(row, self.p_objs[row], cluster)
                dirty_rows.add(row)
                gi = int(self.p_group[row])
                if gi >= 0:
                    dirty_gangs.add(gi)
        sections("sweep.pods")
        cache = self.p_sweep
        changed: list[int] = []
        for row, pod in zip(self._order_list, cluster.pods.values()):
            c = cache[row]
            if c[1] is not pod.status or c[0] is not pod \
                    or c[2] != pod.node:
                changed.append(row)
        for row in changed:
            pod = self.p_objs[row]
            if pod is not cache[row][0] or pod is not cluster.pods.get(
                    pod.name if pod is not None else ""):
                raise _Fallback("pod-object-drift")
            if row not in dirty_rows:
                self._encode_pod(row, pod, cluster)
                dirty_rows.add(row)
                gi = int(self.p_group[row])
                if gi >= 0:
                    dirty_gangs.add(gi)
        sections("sweep.gangs")
        if len(cluster.pod_groups) != len(self.g_objs):
            raise _Fallback("gang-membership-drift")
        for i, g in enumerate(cluster.pod_groups.values()):
            if self.g_objs[i] is not g:
                raise _Fallback("gang-object-drift")
            start = (-1.0 if g.last_start_timestamp is None
                     else g.last_start_timestamp)
            stale_c = self.g_stale[i]
            stale_eq = ((g.stale_since is None and np.isnan(stale_c))
                        or (g.stale_since is not None
                            and stale_c == g.stale_since))
            if (bool(g.unschedulable) != bool(self.g_unsched[i])
                    or self.g_start[i] != start or not stale_eq
                    or self.g_tc[i] is not g.topology_constraint
                    or self.g_subs[i] is not g.sub_groups):
                self._encode_gang(i, g)
                dirty_gangs.add(i)
        # nodes: any drift at all → full rebuild (vocabularies, masks,
        # device tables and capacity all hang off the node section)
        sections("sweep.nodes")
        node_vals = [n for n in cluster.nodes.values()
                     if not n.unschedulable]
        if len(node_vals) != len(self._node_objs):
            raise _Fallback("node-membership-drift")
        for cached, n in zip(self._node_cache, node_vals):
            if (cached[0] is not n or cached[1] is not n.allocatable
                    or cached[2] is not n.labels
                    or cached[3] is not n.taints
                    or cached[4] is not n.extended
                    or cached[5] != n.accel_memory_gib):
                raise _Fallback("node-drift")

    def _patch(self, cluster, j, now, queue_usage):
        # each block's sections close inside its span, whatever raises
        sections = SpanSections(self._tracer)
        with self._span("patch.journal"):
            try:
                sections("journal.compact")
                if len(self.p_objs) > 2 * max(int(self.p_live.sum()), 64):
                    self._compact_pods()
                dirty_rows, dirty_gangs, gang_src = self._apply_journal(
                    cluster, j, sections)
            finally:
                sections.close()
        with self._span("patch.sweep"):
            try:
                self._sweep(cluster, dirty_rows, dirty_gangs, sections)
            finally:
                sections.close()
        self._last_dirty = (len(dirty_rows), len(dirty_gangs))
        if self._nonplain > 0:
            raise _Fallback("nonplain-pods")
        if self._present_twice > 0:
            raise _Fallback("inflight-move")
        live = int(self.p_live.sum())
        dirty_frac = max(
            len(dirty_rows) / max(live, 1),
            len(dirty_gangs) / max(len(self.g_objs), 1))
        if dirty_frac > self.dirty_threshold:
            raise _Fallback("dirty-threshold")
        cap = self._capacity
        if len(self.g_objs) > cap.gangs:
            raise _Fallback("overflow-gangs")
        if len(self._queue_names) != len(cluster.queues):
            raise _Fallback("queue-set-changed")
        host_old = self._host
        if now is None:
            order = self._order
            now = float(self.p_crea[order].max()) if len(order) else 0.0
        with self._span("patch.assemble"):
            try:
                return self._assemble(
                    cluster, dirty_gangs, gang_src, now, queue_usage,
                    host_old, sections)
            finally:
                sections.close()

    # -- assembly ----------------------------------------------------------

    def _assemble(self, cluster, dirty_gangs, gang_src, now, queue_usage,
                  old, sections):
        """The host ``ClusterState`` and index of a patched cycle.
        ``sections(name)`` marks where each group of sections starts:
        ``assemble.gather`` (the ledger's columns through ``order`` and
        ``run_rows``), ``assemble.tables`` (what is built whole each
        cycle), ``assemble.rederive`` (what is kept and derived again by
        key), ``assemble.index``."""
        cap = self._capacity
        G, T = cap.gangs, cap.tasks
        N, Q, M = cap.nodes, cap.queues, cap.running
        NG = len(self.g_objs)
        sections("assemble.gather")
        order = self._order
        eff = self.p_eff_status[order]
        grp_all = self.p_group[order]
        # --- the pinned vocabulary: a fresh build would number what it
        # lacks (a selector key of any pod, a label value of a pending
        # one, a spec of a pending or running one), so one rebuild pins
        # the larger vocabulary ---------------------------------------
        iid_all = self.p_iid[order]
        if self._intern_newkey[iid_all].any():
            raise _Fallback("vocab-growth")
        filtered = ((self._intern_cls != 0)
                    | (self._intern_sel != -1).any(axis=1))
        self._last_filtered = int(filtered[iid_all].sum())
        self._last_subgrouped = int(np.count_nonzero(self.p_sub[order]))
        # --- queues (always re-encoded; tiny) ----------------------------
        sections("assemble.tables")
        queues = list(cluster.queues.values())
        qt = build_queue_tables(queues, Q)
        if qt["queue_names"] != self._queue_names:
            raise _Fallback("queue-order-changed")
        # --- pending intake ----------------------------------------------
        pend = order[(eff == _PENDING) & (grp_all >= 0)]
        intake = pend[np.argsort(self.p_group[pend], kind="stable")]
        counts = (np.bincount(self.p_group[intake], minlength=NG)
                  if NG else np.zeros((0,), np.int64))
        if counts.size and int(counts.max()) > T:
            raise _Fallback("overflow-tasks")
        # fresh first-encounter type ids from the stable intern ids
        iid_seq = self.p_iid[intake]
        if ((self._intern_sel[iid_seq] == _UNKNOWN).any()
                or (self._intern_cls[iid_seq] == _UNKNOWN).any()):
            raise _Fallback("vocab-growth")
        if len(iid_seq):
            uniq, first, inv = np.unique(
                iid_seq, return_index=True, return_inverse=True)
            order_first = np.argsort(first, kind="stable")
            rank = np.empty(len(uniq), np.int64)
            rank[order_first] = np.arange(len(uniq))
            tid_seq = rank[inv]
            reps = uniq[order_first]
            Yn = len(uniq)
        else:
            tid_seq = np.zeros((0,), np.int64)
            reps = np.zeros((0,), np.int64)
            Yn = 0
        Y = _round_up(max(Yn, 1, cap.types), 4)
        if Y != cap.types and Yn > cap.types:
            raise _Fallback("overflow-types")
        # --- dirty-gang task rows -----------------------------------------
        og = old.gangs
        task_valid = np.asarray(og.task_valid)
        task_req = np.asarray(og.task_req)
        task_type_old = np.asarray(og.task_type)
        tnames = self._task_names_obj
        if gang_src is not None:
            # gang rows were closed up: the retained rows move with
            # their gangs, the rows vacated at the tail become padding
            task_valid = _gather_rows(task_valid, gang_src, False)
            task_req = _gather_rows(task_req, gang_src, 0.0)
            tnames = _gather_rows(tnames, gang_src, None)
        if dirty_gangs:
            dg = np.asarray(sorted(dirty_gangs), np.int64)
            if gang_src is None:
                task_valid = task_valid.copy()
                task_req = task_req.copy()
                tnames = tnames.copy()
            task_valid[dg] = False
            task_req[dg] = 0.0
            tnames[dg] = None
            dflag = np.zeros((NG,), bool)
            dflag[dg[dg < NG]] = True
            dsel = dflag[self.p_group[intake]]
            rows_d = intake[dsel]
            if len(rows_d):
                names_d = self.p_names[rows_d].astype(str)
                order_d = np.lexsort((
                    names_d, self.p_crea[rows_d], -self.p_prio[rows_d],
                    self.p_leader[rows_d], self.p_group[rows_d]))
                rows_s = rows_d[order_d]
                g_of = self.p_group[rows_s]
                first_g = np.ones(len(rows_s), bool)
                first_g[1:] = g_of[1:] != g_of[:-1]
                seg_start = np.nonzero(first_g)[0]
                seg = np.cumsum(first_g) - 1
                ti = (np.arange(len(rows_s)) - seg_start[seg]).astype(
                    np.int32)
                self.p_ti[rows_s] = ti
                task_valid[g_of, ti] = True
                task_req[g_of, ti] = self._intern_req[self.p_iid[rows_s]]
                tnames[g_of, ti] = self.p_names[rows_s]
        self._task_names_obj = tnames
        # task_type renumbers globally (dense first-encounter ids)
        # and with it the selector row and the filter class of a task
        K = self._intern_sel.shape[1]
        task_type = np.zeros((G, T), np.int32)
        task_selector = np.full((G, T, K), -1, np.int32)
        task_class = np.zeros((G, T), np.int32)
        task_sub = np.zeros((G, T), np.int32)
        if len(intake):
            slot = self.p_group[intake], self.p_ti[intake]
            task_type[slot] = tid_seq
            task_selector[slot] = self._intern_sel[iid_seq]
            task_class[slot] = self._intern_cls[iid_seq]
            task_sub[slot] = self.p_sub[intake]
        task_type = self._swap_if_equal(task_type, task_type_old)
        # --- type table ---------------------------------------------------
        type_req = np.zeros((Y, R), np.float32)
        type_selector = np.full((Y, K), -1, np.int32)
        type_class = np.zeros((Y,), np.int32)
        if Yn:
            type_req[:Yn] = self._intern_req[reps]
            type_selector[:Yn] = self._intern_sel[reps]
            type_class[:Yn] = self._intern_cls[reps]
        type_req = self._swap_if_equal(type_req, np.asarray(og.type_req))
        # --- gang scalar tables (vectorized over the ledger) -------------
        gk_valid = np.zeros((G,), bool)
        gk_valid[:NG] = counts > 0
        queue_col = np.zeros((G,), np.int32)
        queue_col[:NG] = self.g_queue[:NG]
        min_member = np.zeros((G,), np.int32)
        min_member[:NG] = self.g_minm[:NG]
        priority = np.zeros((G,), np.int32)
        priority[:NG] = self.g_prio[:NG]
        preemptible = np.zeros((G,), bool)
        preemptible[:NG] = self.g_preempt[:NG]
        creation = np.zeros((G,), np.int32)
        creation[:NG] = np.arange(NG, dtype=np.int32)
        backoff = np.zeros((G,), np.int32)
        backoff[:NG] = self.g_unsched[:NG].astype(np.int32)
        req_lvl = np.full((G,), -1, np.int32)
        req_lvl[:NG] = self.g_reqlvl[:NG]
        pref_lvl = np.full((G,), -1, np.int32)
        pref_lvl[:NG] = self.g_preflvl[:NG]
        S = cap.subgroups
        sub_valid = np.zeros((G, S), bool)
        sub_valid[:NG] = self.g_sub_valid[:NG]
        sub_minm = np.zeros((G, S), np.int32)
        sub_minm[:NG] = self.g_sub_minm[:NG]
        sub_rlvl = np.full((G, S), -1, np.int32)
        sub_rlvl[:NG] = self.g_sub_rlvl[:NG]
        stale_s = np.full((G,), -1.0, np.float32)
        has_stale = ~np.isnan(self.g_stale[:NG])
        stale_s[:NG] = np.where(
            has_stale,
            np.maximum(0.0, now - np.where(has_stale, self.g_stale[:NG],
                                           0.0)),
            -1.0).astype(np.float32)
        # --- running section ---------------------------------------------
        sections("assemble.gather")
        run_sel = (eff >= _BOUND) & (eff <= _RELEASING)
        run_rows = order[run_sel]
        Mu = len(run_rows)
        if Mu > M:
            raise _Fallback("overflow-running")
        r_node = self.p_eff_node[run_rows]
        r_grp = self.p_group[run_rows]
        r_rel = self.p_eff_status[run_rows] == _RELEASING
        r_req = self.p_req[run_rows].copy()
        r_cls = self._intern_cls[self.p_iid[run_rows]]
        if (r_cls == _UNKNOWN).any():
            raise _Fallback("vocab-growth")
        rk = dict(
            req=np.zeros((M, R), np.float32),
            node=np.full((M,), -1, np.int32),
            queue=np.zeros((M,), np.int32),
            gang=np.full((M,), -1, np.int32),
            priority=np.zeros((M,), np.int32),
            preemptible=np.zeros((M,), bool),
            valid=np.zeros((M,), bool),
            releasing=np.zeros((M,), bool),
            runtime_s=np.zeros((M,), np.float32),
            device=np.full((M,), -1, np.int32),
            devices_mask=np.zeros((M,), np.int32),
            accel_held=np.zeros((M,), np.float32),
            accel_mem=np.zeros((M,), np.float32),
            filter_class=np.zeros((M,), np.int32),
            extended=np.zeros((M, np.asarray(old.running.extended
                                             ).shape[1]), np.float32),
        )
        if Mu:
            rk["req"][:Mu] = r_req
            rk["node"][:Mu] = r_node
            rk["gang"][:Mu] = r_grp
            rk["valid"][:Mu] = True
            rk["releasing"][:Mu] = r_rel
            rk["filter_class"][:Mu] = r_cls
            has_grp = r_grp >= 0
            gsafe = np.maximum(r_grp, 0)
            if NG:
                rk["queue"][:Mu] = np.where(
                    has_grp, self.g_queue[:NG][gsafe], 0)
                rk["priority"][:Mu] = np.where(
                    has_grp, self.g_prio[:NG][gsafe], 0)
                rk["preemptible"][:Mu] = (has_grp
                                          & self.g_preempt[:NG][gsafe])
                started = self.g_start[:NG][gsafe]
                rk["runtime_s"][:Mu] = np.where(
                    has_grp & (started >= 0),
                    np.maximum(0.0, now - started), -1.0)
        # --- what is kept, and derived again by key ------------------------
        sections("assemble.rederive")
        gk_roll = dict(task_req=task_req, task_valid=task_valid,
                       queue=queue_col, valid=gk_valid,
                       task_extended=self._const["task_extended"])
        roll = self._rederive(
            gang_src, rk, gk_roll, run_rows, r_node, r_grp, r_rel,
            node_alloc=np.asarray(old.nodes.allocatable),
            queue_usage=queue_usage, qt=qt)
        kept = self._kept
        running_count = kept["running_count"]
        sub_running = kept["sub_running"]
        rk["devices_mask"][:Mu] = self.p_occ_mask[run_rows]
        rk["accel_held"][:Mu] = self.p_occ_held[run_rows]
        sections("assemble.tables")
        min_needed = np.maximum(min_member - running_count, 0)
        sub_min_needed = np.maximum(sub_minm - sub_running, 0)
        # --- scheduling signatures (same code as the builder) ------------
        big = np.int64(Y) * (S + 1) + 1
        comp = np.where(task_valid,
                        task_type.astype(np.int64) * (S + 1) + task_sub,
                        big)
        comp = np.sort(comp, axis=1)
        sub_mn = np.where(sub_valid, sub_min_needed, -2)
        sub_rl = np.where(sub_valid, sub_rlvl, -2)
        sig_mat = np.concatenate([
            comp, sub_mn, sub_rl,
            queue_col[:, None].astype(np.int64),
            min_needed[:, None], req_lvl[:, None],
            pref_lvl[:, None], self._const["anti_self_level"][:, None],
            preemptible[:, None].astype(np.int64),
            (~gk_valid[:, None]).astype(np.int64),
        ], axis=1, dtype=np.int64)
        sig = dense_row_ids(sig_mat).astype(np.int32)
        # --- hints (same expressions as the builder) ---------------------
        has_fracs = bool(self._const["task_portion"].any()
                         or self._const["task_accel_mem"].any()
                         or (rk["device"] >= 0).any())
        tvm = task_valid[:, :, None]
        uniform = (
            not has_fracs
            and self._subgrouped_gangs == 0
            and bool((self._const["task_nominated"] < 0).all())
            and bool((self._const["anti_self_level"] == -1).all())
            and bool((np.where(tvm, task_req, task_req[:, :1])
                      == task_req[:, :1]).all())
            and bool((np.where(tvm, task_selector, task_selector[:, :1])
                      == task_selector[:, :1]).all())
            and bool((np.where(task_valid, task_class, task_class[:, :1])
                      == task_class[:, :1]).all()))
        node_valid = np.asarray(old.nodes.valid)
        vocab = self._vocabulary
        dense = (
            not vocab.selector_keys and len(vocab.filter_specs) == 1
            and bool(np.asarray(old.nodes.filter_masks)[0][
                node_valid].all())
            and bool((self._const["anti_self_level"] < 0).all())
            and bool((sub_rlvl < 0).all()))
        # --- assemble host ClusterState ----------------------------------
        sw = self._swap_if_equal
        gangs = old.gangs.replace(
            queue=sw(queue_col, np.asarray(og.queue)),
            min_member=sw(min_member, np.asarray(og.min_member)),
            priority=sw(priority, np.asarray(og.priority)),
            preemptible=sw(preemptible, np.asarray(og.preemptible)),
            valid=sw(gk_valid, np.asarray(og.valid)),
            creation_order=sw(creation, np.asarray(og.creation_order)),
            backoff=sw(backoff, np.asarray(og.backoff)),
            task_req=sw(task_req, np.asarray(og.task_req)),
            task_valid=sw(task_valid, np.asarray(og.task_valid)),
            required_level=sw(req_lvl, np.asarray(og.required_level)),
            preferred_level=sw(pref_lvl,
                               np.asarray(og.preferred_level)),
            running_count=sw(running_count,
                             np.asarray(og.running_count)),
            min_needed=sw(min_needed, np.asarray(og.min_needed)),
            stale_s=sw(stale_s, np.asarray(og.stale_s)),
            task_type=sw(task_type, task_type_old),
            task_selector=sw(task_selector, np.asarray(og.task_selector)),
            task_filter_class=sw(task_class,
                                 np.asarray(og.task_filter_class)),
            task_subgroup=sw(task_sub, np.asarray(og.task_subgroup)),
            sig=sw(sig, np.asarray(og.sig)),
            type_req=type_req,
            type_selector=sw(type_selector, np.asarray(og.type_selector)),
            type_class=sw(type_class, np.asarray(og.type_class)),
            subgroup_valid=sw(sub_valid, np.asarray(og.subgroup_valid)),
            subgroup_min_member=sw(sub_minm,
                                   np.asarray(og.subgroup_min_member)),
            subgroup_min_needed=sw(sub_min_needed,
                                   np.asarray(og.subgroup_min_needed)),
            subgroup_required_level=sw(
                sub_rlvl, np.asarray(og.subgroup_required_level)),
        )
        orn = old.running
        running = old.running.replace(**{
            k: sw(v, np.asarray(getattr(orn, k)))
            for k, v in rk.items()})
        oq = old.queues
        queues_st = old.queues.replace(
            parent=sw(qt["q_parent"], np.asarray(oq.parent)),
            depth=sw(qt["q_depth"], np.asarray(oq.depth)),
            priority=sw(qt["q_priority"], np.asarray(oq.priority)),
            quota=sw(qt["q_quota"], np.asarray(oq.quota)),
            over_quota_weight=sw(qt["q_oqw"],
                                 np.asarray(oq.over_quota_weight)),
            limit=sw(qt["q_limit"], np.asarray(oq.limit)),
            allocated=sw(roll["q_alloc"], np.asarray(oq.allocated)),
            allocated_nonpreemptible=sw(
                roll["q_alloc_np"],
                np.asarray(oq.allocated_nonpreemptible)),
            request=sw(roll["q_request"], np.asarray(oq.request)),
            usage=sw(roll["q_usage"], np.asarray(oq.usage)),
            valid=sw(qt["q_valid"], np.asarray(oq.valid)),
            creation_order=sw(qt["q_creation"],
                              np.asarray(oq.creation_order)),
            preempt_min_runtime=sw(qt["q_preempt_mrt"],
                                   np.asarray(oq.preempt_min_runtime)),
            reclaim_min_runtime=sw(qt["q_reclaim_mrt"],
                                   np.asarray(oq.reclaim_min_runtime)),
            preempt_min_runtime_eff=sw(
                np.asarray(qt["q_preempt_eff"], np.float32),
                np.asarray(oq.preempt_min_runtime_eff)),
            reclaim_min_runtime_eff=sw(
                np.asarray(qt["q_reclaim_eff"], np.float32),
                np.asarray(oq.reclaim_min_runtime_eff)),
        )
        nodes_st = old.nodes.replace(
            free=sw(roll["node_free"], np.asarray(old.nodes.free)),
            releasing=sw(roll["node_rel"],
                         np.asarray(old.nodes.releasing)),
            device_free=sw(kept["dev_free"],
                           np.asarray(old.nodes.device_free)),
            device_releasing=sw(kept["dev_rel"],
                                np.asarray(old.nodes.device_releasing)),
        )
        host_new = _cs.ClusterState(
            nodes=nodes_st, queues=queues_st, gangs=gangs,
            running=running)
        # --- index --------------------------------------------------------
        sections("assemble.index")
        running_names = np.full((M,), "", object)
        running_names[:Mu] = self.p_names[run_rows]
        # the two long name tables go as the columnar views the commit
        # path gathers from (seeded below); their list forms are made
        # when something reads them
        index = _cs.SnapshotIndex(
            node_names=self._node_names,
            queue_names=qt["queue_names"],
            gang_names=list(self.g_names),
            task_names=None,
            running_pod_names=None,
            selector_keys=list(vocab.selector_keys),
            label_vocab=vocab.label_vocab,
            topology_levels=self._topo_levels,
            # the node section is the rebuild's: so are its domains
            topology_domains=self._index.topology_domains,
            needs_device_table=has_fracs,
            uniform_gangs=uniform,
            has_required_topology=bool((req_lvl >= 0).any()),
            has_preferred_topology=bool((pref_lvl >= 0).any()),
            has_subgroup_topology=bool((sub_rlvl >= 0).any()),
            has_extended_resources=False,
            extended_keys=[],
            has_reclaim_minruntime=bool((qt["q_reclaim_mrt"] > 0).any()),
            has_anti_groups=len(self._const["anti_term_level"]) > 0,
            num_anti_groups=len(self._const["anti_term_level"]),
            has_attract_groups=bool(
                (self._const["attract_needs"] >= 0).any()),
            max_queue_depth=int(qt["q_depth"].max(initial=0)),
            num_leaf_queues=int(
                (qt["q_valid"] & ~np.isin(
                    np.arange(Q),
                    qt["q_parent"][qt["q_parent"] >= 0])).sum()),
            num_pending_gangs=int(
                np.asarray(gangs.task_valid).any(axis=1).sum()),
            claims_by_pod={},
            host_tables={
                "task_portion": self._const["task_portion"],
                "task_accel_mem": self._const["task_accel_mem"],
                "task_req0": np.ascontiguousarray(task_req[:, :, 0]),
                "task_dra": self._const["task_dra"],
                "running_gang": rk["gang"],
                "queue_usage": roll["q_usage"],
                # the device-side gangs.valid mask (gangs with pending
                # tasks), host copy — kai-pulse starvation counters
                # advance against exactly what the kernel sees
                "gang_valid": np.asarray(gangs.valid),
            },
            dense_feasibility=dense,
            vocabulary=vocab,
        )
        # pre-seed the columnar name views (cached_property slots)
        index.task_names_arr = self._task_names_obj
        index.node_names_arr = self._node_names_arr
        index.running_pod_names_arr = running_names
        return host_new, index

    # -- the kept tables, derived again by key ------------------------------

    def _touched_masks(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``[N]``, ``[G]`` and ``[Q]`` masks of the keys to derive again
        — those ``_touch_row``, ``_encode_gang`` and ``_remove_gangs``
        named since the last patch — and every key where there is
        nothing kept, or pod or gang rows were moved in bulk."""
        cap = self._capacity
        every = self._kept is None or self._touch_all
        masks = []
        for keys, size in ((self._touched_nodes, cap.nodes),
                           (self._touched_gangs, cap.gangs),
                           (self._touched_queues, cap.queues)):
            mask = np.full((size,), every, bool)
            mask[list(keys)] = True
            masks.append(mask)
        self._forget_touched()
        return tuple(masks)

    def _seed_kept(self, host) -> None:
        """What the patch keeps, from the rebuild's own tables and the
        ledgers just rebuilt: the derivation by key with every key
        touched."""
        order = self._order
        eff = self.p_eff_status[order]
        run_rows = order[(eff >= _BOUND) & (eff <= _RELEASING)]
        hq = host.queues
        self._rederive(
            None,
            {k: np.asarray(getattr(host.running, k)) for k in (
                "valid", "node", "queue", "req", "releasing",
                "preemptible")},
            {k: np.asarray(getattr(host.gangs, k)) for k in (
                "task_req", "task_valid", "queue", "valid",
                "task_extended")},
            run_rows, self.p_eff_node[run_rows], self.p_group[run_rows],
            self.p_eff_status[run_rows] == _RELEASING,
            node_alloc=np.asarray(host.nodes.allocatable),
            queue_usage=None,
            qt=dict(q_index={}, q_parent=np.asarray(hq.parent),
                    q_depth=np.asarray(hq.depth),
                    queue_names=self._queue_names))

    def _rederive(self, gang_src, rk, gk, run_rows, r_node, r_grp, r_rel,
                  *, node_alloc, queue_usage, qt):
        """The tables keyed by a gang, a node or a leaf queue, out of
        last patch's (``self._kept``): an entry depends only on the
        running rows (and pending gangs) that belong to its key, so the
        touched keys' entries are computed again from their members,
        selected by a membership mask in row order, and every other
        entry is last cycle's.  With every key touched, and nothing
        kept, this is the whole derivation.  → ``derive_rollups``'
        result; the rest is left in ``self._kept``, and the device
        cells of each running pod in ``p_occ_mask`` / ``p_occ_held``."""
        t_nodes, t_gangs, t_queues = self._touched_masks()
        cap = self._capacity
        kept = self._kept
        if kept is None:
            kept = dict(
                running_count=np.zeros((cap.gangs,), np.int32),
                sub_running=np.zeros((cap.gangs, cap.subgroups), np.int32),
                dev_free=self._dev_template,
                dev_rel=np.zeros_like(self._dev_template),
                rollups=None)
        elif gang_src is not None:
            # gang rows were closed up: the kept rows move with them
            kept = dict(
                kept,
                running_count=_gather_rows(kept["running_count"],
                                           gang_src, 0),
                sub_running=_gather_rows(kept["sub_running"], gang_src, 0))
        Mu = len(run_rows)
        on_node = r_node >= 0
        has_grp = r_grp >= 0
        fed_node = on_node & t_nodes[r_node]
        fed_gang = has_grp & t_gangs[r_grp]
        fed_queue = t_queues[rk["queue"][:Mu]]
        # --- per gang -----------------------------------------------------
        running_count, sub_running = (kept["running_count"],
                                      kept["sub_running"])
        tg = np.flatnonzero(t_gangs)
        if len(tg):
            running_count, sub_running = (running_count.copy(),
                                          sub_running.copy())
            running_count[tg] = 0
            sub_running[tg] = 0
            active = np.flatnonzero(fed_gang & ~r_rel)
            np.add.at(running_count, r_grp[active], 1)
            np.add.at(sub_running,
                      (r_grp[active], self.p_sub[run_rows[active]]), 1)
        # --- per node: device cells ---------------------------------------
        dev_free, dev_rel = self._occupancy(
            kept["dev_free"], kept["dev_rel"], np.flatnonzero(t_nodes),
            run_rows[fed_node], r_node[fed_node], r_rel[fed_node])
        # --- per node and per queue: rollups (shared section builder) ----
        roll = derive_rollups(
            node_alloc=node_alloc,
            claim_used=np.zeros((cap.nodes, R), np.float32),
            rk=rk, gk=gk, g_of_ext=self._const["ext_accel"],
            r_mig=np.zeros((cap.running,), np.float32),
            queue_usage=queue_usage, q_index=qt["q_index"],
            q_parent=qt["q_parent"], q_depth=qt["q_depth"],
            num_queues=len(qt["queue_names"]), kept=kept["rollups"],
            touched_nodes=t_nodes, touched_queues=t_queues)
        self._kept = dict(
            running_count=running_count, sub_running=sub_running,
            dev_free=dev_free, dev_rel=dev_rel, rollups=roll["kept"])
        self._last_rederived = {
            "touched_nodes": int(t_nodes.sum()),
            "touched_gangs": len(tg),
            "touched_queues": int(t_queues.sum()),
            "rederived_rows": int(
                (fed_node | fed_gang | fed_queue).sum())}
        return roll

    @staticmethod
    def _swap_if_equal(new: np.ndarray, old: np.ndarray) -> np.ndarray:
        """Reuse the previous cycle's array object when the recomputed
        content is identical — downstream, `is` short-circuits both the
        ship compare and the device transfer."""
        if (new is old) or (new.shape == old.shape
                            and new.dtype == old.dtype
                            and np.array_equal(new, old)):
            return old
        return new

    # -- device occupancy (gated subset of the builder's section) ---------

    def _occupancy(self, dev_free, dev_rel, nodes, rows, node, rel):
        """Device cells of the node rows ``nodes`` → (``dev_free``,
        ``dev_rel``), the other nodes' as handed in, and the cells each
        pod holds into ``p_occ_mask`` / ``p_occ_held``.  ``rows``,
        ``node`` and ``rel`` are the running pods on those nodes in
        row order: their ledger rows, node rows and whether releasing.
        Occupancy is local to a node — first fit in row order over the
        pods it holds — so these pods give these nodes' cells and their
        own masks exactly."""
        if not len(nodes):
            return dev_free, dev_rel
        N, D = self._dev_template.shape
        dev_free, dev_rel = dev_free.copy(), dev_rel.copy()
        dev_free[nodes] = self._dev_template[nodes]
        dev_rel[nodes] = 0.0
        self.p_occ_mask[rows] = 0
        self.p_occ_held[rows] = 0.0
        whole_k = np.rint(self.p_req[rows, 0]).astype(np.int64)
        has_dev = self.p_hasdev[rows]
        touches = whole_k > 0
        special = touches & has_dev
        node_special = np.zeros((N,), bool)
        node_special[node[special]] = True
        vec = touches & ~special & ~node_special[node]
        vj = np.nonzero(vec)[0]
        if len(vj):
            accel_counts_a = self._accel_counts
            vn = node[vj]
            ordv = np.argsort(vn, kind="stable")
            vj, vn = vj[ordv], vn[ordv]
            vk = whole_k[vj]
            cum = np.cumsum(vk) - vk
            first = np.ones(len(vj), bool)
            first[1:] = vn[1:] != vn[:-1]
            grp = np.cumsum(first) - 1
            off = cum - cum[np.nonzero(first)[0]][grp]
            k_eff = np.clip(accel_counts_a[vn] - off, 0, vk)
            end = off + k_eff
            self.p_occ_mask[rows[vj]] = (
                (np.int64(1) << end) - (np.int64(1) << off)
            ).astype(np.int32)
            self.p_occ_held[rows[vj]] = k_eff.astype(np.float32)
            tot = int(k_eff.sum())
            if tot:
                rep = np.repeat(np.arange(len(vj)), k_eff)
                dpos = (np.arange(tot)
                        - np.repeat(np.cumsum(k_eff) - k_eff, k_eff)
                        + np.repeat(off, k_eff))
                nrep = vn[rep]
                dev_free[nrep, dpos] = 0.0
                relm = rel[vj][rep]
                dev_rel[nrep[relm], dpos[relm]] += 1.0
        rest = np.nonzero(touches & ~vec)[0]
        if len(rest):
            # exact vectorized path for recorded-device whole pods: a
            # debit is the template value and order is irrelevant UNLESS
            # the node hosts a first-fit pod (no recorded devices) or a
            # double-booked device cell — only those nodes' pods replay
            # the builder's sequential loop
            seq_nodes = np.zeros((N,), bool)
            seq_nodes[node[rest[~has_dev[rest]]]] = True
            vecr = rest[~seq_nodes[node[rest]]]
            masks = self.p_devmask[rows[vecr]]

            def held_cells(sub, sub_masks):
                """(node*D + dev) flat indices of every held device."""
                pj, dj = np.nonzero(
                    (sub_masks[:, None] >> np.arange(D)) & 1)
                return node[sub][pj] * D + dj

            cells = held_cells(vecr, masks)
            cnt = np.bincount(cells, minlength=N * D)
            booked_nodes = np.nonzero(
                (cnt.reshape(N, D) > 1).any(axis=1))[0]
            if len(booked_nodes):
                seq_nodes[booked_nodes] = True
                keep = ~seq_nodes[node[vecr]]
                vecr, masks = vecr[keep], masks[keep]
                cells = held_cells(vecr, masks)
                cnt = np.bincount(cells, minlength=N * D)
            if len(vecr):
                tmpl = self._dev_template
                dev_free -= tmpl * (cnt.reshape(N, D) > 0)
                self.p_occ_mask[rows[vecr]] = masks
                self.p_occ_held[rows[vecr]] = self.p_held[rows[vecr]]
                relj = vecr[rel[vecr]]
                if len(relj):
                    rel_cells = held_cells(relj,
                                           self.p_devmask[rows[relj]])
                    dev_rel += (tmpl.reshape(-1) * np.bincount(
                        rel_cells, minlength=N * D)).reshape(N, D)
            seq = rest[seq_nodes[node[rest]]]
            if len(seq):
                self._occupancy_sequential(
                    rows, node, rel, seq, whole_k, dev_free, dev_rel)
        return dev_free, dev_rel

    def _occupancy_sequential(self, rows, node, rel, rest, whole_k,
                              dev_free, dev_rel) -> None:
        """Builder-identical per-pod loop for order-dependent cases
        (first-fit pods on device-recorded nodes, double-booked cells)."""
        for jj in rest.tolist():
            pod = self.p_objs[rows[jj]]
            ni = int(node[jj])
            k = int(whole_k[jj])
            if pod.accel_devices:
                devs = list(pod.accel_devices)[:k]
            else:
                devs = list(np.nonzero(
                    dev_free[ni] >= 1.0 - 1e-6)[0][:k])
            mask = 0
            for d0 in devs:
                taken = min(1.0, dev_free[ni, d0])
                dev_free[ni, d0] -= taken
                if rel[jj]:
                    dev_rel[ni, d0] += taken
                mask |= 1 << int(d0)
            self.p_occ_mask[rows[jj]] = mask
            self.p_occ_held[rows[jj]] = float(len(devs))

    # -- shipping ----------------------------------------------------------

    def _ship(self, host_new):
        """Transfer only changed leaves; unchanged leaves keep their
        previous device buffers (and their previous host objects, so the
        next cycle's compares short-circuit on identity).  The transfer
        section is timed (and span-recorded) as the cycle's "upload"
        phase.

        All changed leaves ship in ONE batched ``device_put`` (a
        ``{keystr: array}`` dict, mirroring ``build_snapshot``'s
        one-shot pattern) through the kai-wire TransferLedger — the
        previous per-leaf loop cost one dispatch per changed leaf.  The
        ledger records both the
        would-have-been dispatch count (``leaves``) and the actual one
        (``dispatches`` == 1), keyed by the same leaf names the full
        build uses so redundancy tracking spans both paths.
        """
        t_ship = time.perf_counter()
        new_paths, treedef = jax.tree_util.tree_flatten_with_path(
            host_new)
        old_leaves = jax.tree_util.tree_leaves(self._host)
        dev_leaves = jax.tree_util.tree_leaves(self._dev)
        out_dev, out_host = list(dev_leaves), list(old_leaves)
        changed: dict[str, object] = {}
        slot: dict[str, int] = {}
        bytes_ = 0
        for i, ((path, new), old) in enumerate(zip(new_paths,
                                                   old_leaves)):
            # equal_nan on float leaves: a NaN-carrying leaf (e.g.
            # unset stale timestamps) must not read as "changed"
            # forever — the ledger would (rightly) flag the identical
            # re-upload as redundant bytes every cycle
            if new is old or (
                    getattr(new, "shape", None) == old.shape
                    and new.dtype == old.dtype
                    and np.array_equal(new, old,
                                       equal_nan=new.dtype.kind == "f")):
                continue
            name = jax.tree_util.keystr(path) or f"[{i}]"
            changed[name] = new
            slot[name] = i
            out_host[i] = new
            bytes_ += int(new.nbytes)
        leaves = len(changed)
        dispatches = 0
        if changed:
            dispatches = 1
            # leaf_names must follow FLATTEN order, and jax flattens
            # dict keys sorted — insertion (traversal) order would pair
            # names with the wrong leaves whenever a patch spans
            # sections (ClusterState fields don't sort alphabetically)
            shipped = _wire.LEDGER.device_put(
                changed, reason=_wire.REASON_JOURNAL_PATCH,
                leaf_names=sorted(changed))
            for name, dev in shipped.items():
                out_dev[slot[name]] = dev
        self._host = jax.tree_util.tree_unflatten(treedef, out_host)
        self._dev = jax.tree_util.tree_unflatten(treedef, out_dev)
        ship_s = time.perf_counter() - t_ship
        self.stats.leaves_shipped += leaves
        self.stats.bytes_shipped += bytes_
        self._last_ship = (leaves, bytes_, ship_s, dispatches)
        # NOT a device_sync span: jax.device_put is async, so this times
        # the transfer DISPATCH (flatten + compares + enqueue); the
        # transfer itself overlaps the solve and completion is absorbed
        # by the cycle's device_wait sync — exactly the async-attribution
        # rule the tracer exists to make explicit
        self._add_span("upload", t_ship, leaves=leaves, bytes=bytes_,
                       dispatches=dispatches)
        return self._dev

    # -- verification ------------------------------------------------------

    def _verify(self, cluster, now, queue_usage) -> None:
        """Assert the patched snapshot equals a fresh full rebuild,
        element-wise, including the index name maps."""
        # reason "verify" on the wire ledger: the reference rebuild's
        # transfer is deliberate re-upload, not patch-path redundancy
        with _wire.LEDGER.override_reason(_wire.REASON_VERIFY):
            _, fresh_index, fresh_host = _cs.build_snapshot(
                *cluster.snapshot_lists(), now=now,
                queue_usage=queue_usage,
                resource_claims=cluster.resource_claims,
                device_classes=cluster.device_classes,
                volume_claims=cluster.volume_claims,
                storage_classes=cluster.storage_classes,
                capacity=self._capacity, vocabulary=self._vocabulary,
                _return_host=True)
        paths_new = jax.tree_util.tree_flatten_with_path(self._host)[0]
        paths_ref = jax.tree_util.tree_flatten_with_path(fresh_host)[0]
        for (path, mine), (_, ref) in zip(paths_new, paths_ref):
            name = jax.tree_util.keystr(path)
            if mine.shape != ref.shape or mine.dtype != ref.dtype:
                raise IncrementalVerifyError(
                    f"leaf {name}: shape/dtype {mine.shape}/{mine.dtype}"
                    f" != {ref.shape}/{ref.dtype}")
            if not np.array_equal(np.asarray(mine), np.asarray(ref)):
                bad = np.nonzero(np.asarray(mine) != np.asarray(ref))
                raise IncrementalVerifyError(
                    f"leaf {name}: {len(bad[0])} mismatching elements "
                    f"(first at {[int(b[0]) for b in bad if len(b)]})")
        mine_i, ref_i = self._index, fresh_index
        for field in ("node_names", "queue_names", "gang_names",
                      "task_names", "running_pod_names", "selector_keys",
                      "label_vocab", "topology_levels", "topology_domains",
                      "needs_device_table", "uniform_gangs",
                      "has_required_topology", "has_preferred_topology",
                      "has_subgroup_topology", "has_extended_resources",
                      "extended_keys", "has_reclaim_minruntime",
                      "has_anti_groups", "has_attract_groups",
                      "max_queue_depth", "num_leaf_queues",
                      "num_pending_gangs",
                      "num_anti_groups", "claims_by_pod",
                      "dense_feasibility", "vocabulary"):
            if getattr(mine_i, field) != getattr(ref_i, field):
                raise IncrementalVerifyError(
                    f"index.{field}: {getattr(mine_i, field)!r} != "
                    f"{getattr(ref_i, field)!r}")
