"""Delta-document decomposition + sequential apply — ONE implementation
for both intake paths.

The kai-intake differential bar (ISSUE 12): a mutation storm routed
through the async lanes must produce a hub journal — and therefore
scheduling cycles — bit-identical to the same events applied
sequentially through the classic synchronous path.  The way to make
that provable rather than hopeful is to share the code: the classic
``POST /cluster/delta`` handler and the router's ``coalesce()`` both
decompose delta documents into the same ordered event stream
(:func:`decompose_delta`) and both replay it through the same
single-event applier (:func:`apply_events`).  The async path differs
ONLY in *when* events apply (at cycle boundaries, in global
sequence-number order) — never in *how*.

Journal marks batch through ``MutationJournal.merge`` (one lock
acquisition per chunk instead of one per event), with the mark mapping
owned by the gate (``intake/gate.py``, KAI091's choke point).
"""
from __future__ import annotations

import copy
import dataclasses
import enum
import gc
import math

import numpy as np

from ..apis import types as apis
from ..runtime import snapshot as snap
from . import gate

#: canonical apply order of a delta document's collections — the order
#: the classic handler has always used (dict-insertion order of its
#: parser table); the router assigns sequence numbers in this order so
#: the two paths replay identically
COLLECTIONS = gate.COLLECTIONS

_PARSERS = {
    "nodes": snap._node,
    "queues": snap._queue,
    "pod_groups": snap._pod_group,
    "pods": snap._pod,
    "bind_requests": snap._bind_request,
    "resource_claims": lambda d: apis.ResourceClaim(**d),
    "device_classes": lambda d: apis.DeviceClass(**d),
    "volume_claims": lambda d: apis.PersistentVolumeClaim(**d),
    "storage_classes": lambda d: apis.StorageClass(**d),
}

_DEFAULT_FACTORIES = {
    "nodes": lambda: apis.Node(name=""),
    "queues": lambda: apis.Queue(name=""),
    "pod_groups": lambda: apis.PodGroup(name="", queue=""),
    "pods": lambda: apis.Pod(name="", group=""),
    "bind_requests": lambda: apis.BindRequest(pod_name="",
                                              selected_node=""),
    "resource_claims": lambda: apis.ResourceClaim(name=""),
    "device_classes": lambda: apis.DeviceClass(name=""),
    "volume_claims": lambda: apis.PersistentVolumeClaim(name=""),
    "storage_classes": lambda: apis.StorageClass(name=""),
}


def _default_doc(coll: str) -> dict:
    """A FRESH default document per call — the parsers store some
    nested values (plain lists/dicts) verbatim on the constructed
    object, so a cached template would alias one container across
    every object ever defaulted from it."""
    return snap._to_jsonable(_DEFAULT_FACTORIES[coll]())


# -- fast pod construction (the storm-dominant create path) ---------------
#
# The generic path for a NEW object renders the default doc, merges,
# and re-parses EVERY field through the snapshot parser (~13 µs per
# pod) — the single biggest term in the 1M-event storm's coalesce.
# New *plain* pods skip it: shared immutable defaults + fresh mutable
# containers + the two converted fields, assembled directly.  The fast
# path must stay value-identical to ``_PARSERS["pods"](default|doc)``
# — ``tests/test_intake_router.py`` drift-guards it on randomized
# docs, and any doc touching a parser-converted irregular field
# (tolerations/affinity) or an unknown key falls back to the parser.

#: doc keys that force the generic parser (list-of-struct conversions)
_POD_SLOW_KEYS = frozenset({"tolerations", "node_affinity",
                            "pod_affinity"})


def _pod_fast_tables() -> tuple[dict, list, frozenset]:
    pod = _DEFAULT_FACTORIES["pods"]()
    shared: dict = {}
    fresh: list = []
    for f in dataclasses.fields(pod):
        v = getattr(pod, f.name)
        if isinstance(v, (list, dict, set)):
            fresh.append((f.name, type(v)))
        elif v is None or isinstance(v, (str, int, float, bool, tuple,
                                         enum.Enum)):
            shared[f.name] = v
        elif type(v)() == v:
            # default-constructed value object (ResourceVec()): a
            # fresh instance per pod, never shared across objects
            fresh.append((f.name, type(v)))
        else:
            # non-trivial non-scalar default: deep-copied per object
            fresh.append((f.name, lambda v=v: copy.deepcopy(v)))
    known = frozenset(shared) | frozenset(n for n, _f in fresh) \
        | {"resources", "status"}
    return shared, fresh, known


_POD_SHARED, _POD_FRESH, _POD_KNOWN_KEYS = None, None, None

#: pod upserts that took the generic parser since the process started
#: (a new pod the fast path refused, or an update of a stored one).  It
#: only counts on; whoever holds the commit lock over an apply reads it
#: before and after (``IntakeRouter.coalesce``)
PARSED_PODS = [0]


def _fast_new_pod(doc: dict):
    """A brand-new pod from a delta doc, bypassing the default-doc
    render + full re-parse.  Returns None when the doc needs the
    generic parser (irregular/unknown fields)."""
    global _POD_SHARED, _POD_FRESH, _POD_KNOWN_KEYS
    if _POD_SHARED is None:
        _POD_SHARED, _POD_FRESH, _POD_KNOWN_KEYS = _pod_fast_tables()
    keys = doc.keys()
    if not (keys <= _POD_KNOWN_KEYS) or keys & _POD_SLOW_KEYS:
        return None
    d = dict(_POD_SHARED)
    for name, factory in _POD_FRESH:
        if name not in keys:  # doc values land below; don't build twice
            d[name] = factory()
    for k, v in doc.items():
        if k == "resources":
            v = apis.ResourceVec(**v)
        elif k == "status":
            v = apis.PodStatus(v)
        d[k] = v
    obj = object.__new__(apis.Pod)
    obj.__dict__ = d
    return obj


class IntakeEvent:
    """One decomposed mutation: an upsert/delete of one object, or a
    clock advance.  ``seq`` is the router-assigned global sequence
    number (submission order); ``key`` the lane-routing key (the
    entity's identity — same entity, same lane, so per-entity ordering
    survives sharding)."""

    __slots__ = ("seq", "op", "coll", "key", "payload", "offered",
                 "in_coalesce")

    def __init__(self, seq: int, op: str, coll: str, key: str, payload,
                 offered: float = 0.0):
        self.seq = seq
        self.op = op          # "upsert" | "delete" | "now"
        self.coll = coll      # collection attr; "" for "now"
        self.key = key        # routing key; "" for "now"
        self.payload = payload  # upsert doc | delete name | now float
        #: ``perf_counter`` seconds of the submit that offered it (one
        #: read a submit); the coalesce that takes it books the wait
        self.offered = offered
        #: admitted by the coalesce's own pre-drain, on the cycle's
        #: thread, and not by a lane worker before it
        self.in_coalesce = False

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"IntakeEvent(seq={self.seq}, op={self.op!r}, "
                f"coll={self.coll!r}, key={self.key!r})")


def decompose_delta(delta: dict) -> list[tuple[str, str, str, object]]:
    """A delta document → ordered ``(op, coll, key, payload)`` list, in
    the canonical collection order (upserts before deletes per
    collection, matching the classic handler's iteration)."""
    out: list[tuple[str, str, str, object]] = []
    for coll in COLLECTIONS:
        for doc in delta.get(f"{coll}_upsert", []):
            key = ""
            if isinstance(doc, dict):
                key = doc.get("name") or doc.get("pod_name") or ""
            out.append(("upsert", coll, key, doc))
        for name in delta.get(f"{coll}_delete", []):
            out.append(("delete", coll, name, name))
    if "now" in delta:
        out.append(("now", "", "", delta["now"]))
    return out


def apply_event(cluster, op: str, coll: str, payload,
                marks: list) -> None:
    """Apply ONE event to the hub, appending its journal mark ops to
    ``marks`` (the caller merges them in batch).  Exactly the classic
    per-event semantics: partial upsert docs merge over the existing
    object when the key is stored, over defaults for new objects."""
    if op == "now":
        cluster.now = float(payload)
        marks.append(("time", ""))
        return
    store = getattr(cluster, coll)
    if op == "upsert":
        doc = payload
        key0 = doc.get("name") or doc.get("pod_name")
        obj = None
        if coll == "pods" and key0 not in store:
            obj = _fast_new_pod(doc)
        if obj is None:
            if coll == "pods":
                PARSED_PODS[0] += 1
            if key0 in store:
                full = snap._to_jsonable(store[key0])
            else:
                full = _default_doc(coll)  # fresh per call
            full.update(doc)
            obj = _PARSERS[coll](full)
        key = getattr(obj, "name", None) or obj.pod_name
        gate.upsert_marks(coll, key, obj, key in store, marks)
        store[key] = obj
    else:
        name = payload
        gate.delete_marks(coll, name, name in store, marks)
        store.pop(name, None)


#: flush journal marks every this-many events during a bulk apply so a
#: 1M-event coalesce never holds a million mark tuples at once
_MARK_CHUNK = 65536


def apply_events(cluster, events, errors: list | None = None) -> int:
    """Replay decomposed events against the hub in order, merging their
    journal marks in chunked batches.  ``events`` may be raw
    ``(op, coll, key, payload)`` tuples or :class:`IntakeEvent`\\ s.

    Error policy: with ``errors=None`` (the classic synchronous path)
    the first failing event raises — the caller gets its HTTP 400 and
    the applied prefix stays journaled.  With an ``errors`` list (the
    router's coalesce, where submitters were already acknowledged and
    one client's poisoned doc must never destroy other clients'
    accepted events) failing events are skipped and recorded as
    ``(seq, reason)``.

    The generational GC is suspended for the duration: a bulk apply
    allocates one long-lived object graph per event (pods, docs, mark
    tuples) and produces no reference cycles, but the allocation rate
    trips collection thresholds constantly — measured ~3x slowdown on
    a 100k-create storm with the collector left running."""
    journal = cluster.journal
    # kai-twin choke point: when a recorder is attached to the hub,
    # every event this call successfully applies is mirrored into its
    # stream (AFTER the journal merge below) — recording the APPLIED
    # sequence, never the offered one, is what makes a recorded stream
    # replayable bit-exact through this same function
    recorder = getattr(cluster, "twin_recorder", None)
    applied: list | None = [] if recorder is not None else None
    marks: list = []
    n = 0
    gc_was_on = gc.isenabled()
    if gc_was_on:
        gc.disable()
    try:
        for ev in events:
            if isinstance(ev, IntakeEvent):
                op, coll, key, payload = ev.op, ev.coll, ev.key, ev.payload
            else:
                op, coll, key, payload = ev
            if errors is None:
                apply_event(cluster, op, coll, payload, marks)
            else:
                try:
                    apply_event(cluster, op, coll, payload, marks)
                except Exception as exc:  # noqa: BLE001 — skip-and-
                    # record: the event was admitted, but admission is
                    # a door check, not a proof the applier accepts it
                    errors.append((getattr(ev, "seq", n), str(exc)))
                    n += 1
                    continue
            if applied is not None:
                applied.append((op, coll, key, payload))
            n += 1
            if len(marks) >= _MARK_CHUNK:
                # swap-before-merge: if the merge raises mid-chunk the
                # chunk is NOT retried (at-most-once — duplicate list
                # marks would corrupt cursors; the store marked its own
                # collections' writes as they landed)
                chunk, marks = marks, []
                gate.merge_marks(journal, chunk)
    finally:
        # the merge runs even when an event mid-batch raises (a
        # malformed doc aborting a delta): every store mutation that
        # DID apply must reach the journal, or the incremental
        # snapshotter serves a silently stale patch — the exact
        # invariant the per-event marking this replaced maintained.
        # The nested finally keeps gc.enable() unconditional: a merge
        # failure must never leave the process with the collector off.
        try:
            chunk, marks = marks, []
            gate.merge_marks(journal, chunk)
        finally:
            if gc_was_on:
                gc.enable()
        # record the applied PREFIX even when a classic-path event
        # raised mid-batch: what reached the journal is what the twin
        # must replay
        if recorder is not None and applied:
            recorder.record_events(applied)
            from ..framework import metrics
            metrics.twin_recorded_events.inc(by=len(applied))
    return n


def apply_cluster_delta(cluster, delta: dict) -> int:
    """The classic synchronous path: decompose + apply in one call
    (``POST /cluster/delta``'s body).  Returns the event count."""
    return apply_events(cluster, decompose_delta(delta))


# ---------------------------------------------------------------------------
# batched admission
# ---------------------------------------------------------------------------

#: scalar pod fields that must be finite and non-negative
_POD_SCALARS = ("accel_portion", "accel_memory_gib", "dra_accel_count")

#: an absurd per-object resource bound — a fat-fingered 1e30-CPU pod
#: must bounce at the door, not poison every fair-share division
RESOURCE_CAP = 1.0e9


def admit_batch(batch) -> tuple[list[bool], list[str | None]]:
    """Vectorized admission over one staged lane batch of
    :class:`IntakeEvent`\\ s.

    Structural checks (known collection, dict-shaped upsert doc,
    non-empty key) run per event; the numeric sanity sweep — every
    resource scalar finite, non-negative, below :data:`RESOURCE_CAP`,
    fractional shares within [0, 1] — gathers across the WHOLE batch
    into two flat arrays and judges them in one NumPy pass, replacing
    the per-request field-by-field checks the single-lock intake did.

    Returns ``(ok, reasons)`` aligned with ``batch`` (reason ``None``
    for admitted events).
    """
    n = len(batch)
    ok = [True] * n
    reasons: list[str | None] = [None] * n
    idx: list[int] = []
    vals: list[float] = []
    frac_idx: list[int] = []
    frac_vals: list[float] = []
    for i, ev in enumerate(batch):
        op, coll, key, payload = ev.op, ev.coll, ev.key, ev.payload
        if op == "now":
            try:
                t = float(payload)
            except (TypeError, ValueError):
                t = float("nan")
            if not math.isfinite(t):  # non-numeric / NaN / inf clock
                ok[i], reasons[i] = False, "now: not a finite number"
            continue
        if coll not in _PARSERS:
            ok[i], reasons[i] = False, f"unknown collection {coll!r}"
            continue
        if op == "delete":
            if not isinstance(payload, str) or not payload:
                ok[i], reasons[i] = False, "delete: empty name"
            continue
        doc = payload
        if not isinstance(doc, dict):
            ok[i], reasons[i] = False, "upsert: document must be a mapping"
            continue
        if not key:
            ok[i], reasons[i] = False, "upsert: missing name"
            continue
        try:
            # float() here, not at the np.asarray: a JSON integer wider
            # than a double (1e400 as an int literal) raises
            # OverflowError — per-event that is a clean rejection,
            # inside the batched asarray it would kill the whole batch
            # (and, unguarded, the lane's drain worker)
            bad_shape = False
            for field in ("resources", "allocatable", "capacity"):
                src = doc.get(field)
                if src is None:
                    continue
                if not isinstance(src, dict):
                    # a scalar where a vector doc belongs would pass
                    # admission and then crash the applier at coalesce
                    ok[i], reasons[i] = False, f"{field}: not a mapping"
                    bad_shape = True
                    break
                for v in src.values():
                    if isinstance(v, (int, float)):
                        idx.append(i)
                        vals.append(float(v))
            if bad_shape:
                continue
            for field in _POD_SCALARS:
                v = doc.get(field)
                if isinstance(v, (int, float)):
                    idx.append(i)
                    vals.append(float(v))
            v = doc.get("accel_portion")
            if isinstance(v, (int, float)):
                frac_idx.append(i)
                frac_vals.append(float(v))
        except OverflowError:
            ok[i], reasons[i] = False, "resource value out of range"
            continue
    # f64 on purpose (host-side, allowlisted): a float32 sweep has a
    # 64-unit ulp at the 1e9 cap, so RESOURCE_CAP + 63 (or a portion
    # of 1 + 1e-8) would round ONTO the bound and slip past the door
    # check — the exact class of input it exists to bounce
    if vals:
        arr = np.asarray(vals, dtype=np.float64)
        bad = ~np.isfinite(arr) | (arr < 0.0) | (arr > RESOURCE_CAP)
        for i in np.asarray(idx, dtype=np.int64)[bad].tolist():
            if ok[i]:
                ok[i] = False
                reasons[i] = "resource value out of range"
    if frac_vals:
        arr = np.asarray(frac_vals, dtype=np.float64)
        bad = ~np.isfinite(arr) | (arr < 0.0) | (arr > 1.0)
        for i in np.asarray(frac_idx, dtype=np.int64)[bad].tolist():
            if ok[i]:
                ok[i] = False
                reasons[i] = "accel_portion outside [0, 1]"
    return ok, reasons
