"""kai-intake — async, load-shedding, multi-lane mutation intake.

The reference scheduler targets thousands of nodes and "millions of
users"; at that rate the bottleneck moves from the solve to intake.
Until this module every cluster mutation serialized under
``SchedulerServer._state_lock`` — correct (PR 4), but a single-writer
wall: one slow POST convoys every other mutation behind the commit
lock, with no shed valve and no visibility.

:class:`IntakeRouter` decouples ingest from the scheduler cycle:

- **lanes** — submitted events hash-shard by entity key (pod/gang/node
  name) into N bounded lanes.  Same entity → same lane → FIFO, so
  per-entity ordering survives sharding; cross-entity ordering is
  restored at coalesce time by the global sequence number every event
  gets at submission.
- **workers** — one daemon thread per lane drains queued events in
  batches: structural validation plus a NumPy pass over the whole
  batch's resource scalars (:func:`~.apply.admit_batch`) replaces the
  old per-request checks.  Admitted events stage in the lane, off the
  commit path.
- **coalesce** — at cycle boundaries (the ``POST /cycle/stored``
  handler, under the now commit-side-only ``_state_lock``) the staged
  events of every lane merge, sort by sequence number, and replay
  through the SAME single-event applier as the classic synchronous
  path (``intake/apply.py``), with journal marks bulk-merged into the
  hub ``MutationJournal`` one lock acquisition per chunk.  PR 1's
  journal semantics and PR 11's packed-delta path see an ordinary —
  just batched — mutation stream.
- **backpressure** — a lane is bounded by ``lane_capacity`` counting
  queued AND staged events.  Overflow either sheds (the whole offered
  group, atomically — a shed request never half-writes; HTTP maps it
  to 429) or degrades to sync (``policy="sync"``: the submitter drains
  the lanes inline, flushes a coalesce through the server's commit
  lock, and retries — the old single-writer behavior, now the
  *fallback* instead of the steady state).  Shed/depth/degrade are
  metered (``kai_intake_*``) and served by ``GET /debug/intake``.

The differential bar — a storm through the lanes must yield a hub
journal and next-cycle binds/DecisionLog bit-identical to the same
events applied sequentially through the classic path — holds by
construction (shared applier, global seq order) and is pinned by
``tests/test_intake_router.py``.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import zlib
from operator import attrgetter

from ..framework import metrics
from ..runtime.tracing import annotation, span_of
from . import apply as _apply
from .apply import IntakeEvent


@dataclasses.dataclass(frozen=True)
class IntakeConfig:
    """Router knobs (``SchedulerConfig.intake_*`` / conf ``intake.*``)."""

    #: hash-shard lane count (one drain worker per lane)
    lanes: int = 4
    #: per-lane bound on queued + staged events; overflow sheds or
    #: degrades to sync
    lane_capacity: int = 65536
    #: overflow policy: "shed" (atomic per-group refusal, HTTP 429) or
    #: "sync" (drain inline + flush a coalesce, then retry — degrade to
    #: the classic single-writer behavior instead of dropping)
    policy: str = "shed"
    #: max events a worker pops per drain round (the admission batch —
    #: the NumPy sweep vectorizes over it)
    batch: int = 512

    def __post_init__(self):
        if self.lanes < 1:
            raise ValueError("intake lanes must be >= 1")
        if self.lane_capacity < 1:
            raise ValueError("intake lane_capacity must be >= 1")
        if self.policy not in ("shed", "sync"):
            raise ValueError(f"unknown intake policy {self.policy!r}")
        if self.batch < 1:
            raise ValueError("intake batch must be >= 1")


class _Lane:
    """One bounded intake lane.  All mutable state lives under the
    lane's own lock; holders never call out while holding it (no
    nested locks, no blocking calls — kai-race KAI103/KAI105)."""

    __slots__ = ("idx", "capacity", "wake", "drain_lock", "_lock",
                 "queued", "staged", "inflight", "accepted", "shed",
                 "rejected", "errors")

    #: bounded per-lane ring of recent admission rejections
    ERROR_RING = 32

    def __init__(self, idx: int, capacity: int):
        self.idx = idx
        self.capacity = capacity
        #: drain worker's doorbell (sync object, not shared state)
        self.wake = threading.Event()
        #: serializes whole pop→admit→stage drain rounds: with the
        #: lane's worker and an inline helper (drain_inline, the sync
        #: degrade path) draining concurrently, a later batch could
        #: stage BEFORE an earlier in-flight one — and a coalesce
        #: landing in that gap would apply same-key events out of
        #: order across windows.  One drainer at a time keeps stage
        #: order == pop order == FIFO; parallelism is across lanes.
        self.drain_lock = threading.Lock()
        self._lock = threading.Lock()
        self.queued: list = []      # kai-race: guarded-by=_lock
        self.staged: list = []      # kai-race: guarded-by=_lock
        #: events popped by a worker but not yet staged (quiesce gate)
        self.inflight = 0           # kai-race: guarded-by=_lock
        self.accepted = 0           # kai-race: guarded-by=_lock
        self.shed = 0               # kai-race: guarded-by=_lock
        self.rejected = 0           # kai-race: guarded-by=_lock
        self.errors: list = []      # kai-race: guarded-by=_lock

    def would_fit(self, n: int) -> bool:
        """Capacity probe for the all-or-nothing submit: the caller
        holds the router lock — as do every other submission path AND
        coalesce's take→restage window (the only operation that can
        GROW a lane's load from outside a submit) — so between a
        positive probe and the offer the load can only shrink, and a
        probe-then-offer can't oversubscribe or half-accept."""
        with self._lock:
            load = len(self.queued) + len(self.staged) + self.inflight
            return load + n <= self.capacity

    def offer(self, events: list) -> bool:
        """Queue a group of events atomically: either the whole group
        fits under the lane bound or the whole group is shed — a
        backpressured request never half-lands (and therefore never
        half-journals).  Shed ACCOUNTING is the router's job
        (:meth:`count_shed`): a refusal the sync degrade path then
        delivers must not show up as dropped events."""
        with self._lock:
            load = len(self.queued) + len(self.staged) + self.inflight
            if load + len(events) > self.capacity:
                return False
            self.queued.extend(events)
            self.accepted += len(events)
        self.wake.set()
        return True

    def count_shed(self, n: int) -> None:
        with self._lock:
            self.shed += n

    def take_queued(self, limit: int) -> list:
        with self._lock:
            batch = self.queued[:limit]
            del self.queued[:len(batch)]
            self.inflight += len(batch)
            return batch

    def stage(self, admitted: list, errors: list, taken: int) -> None:
        """Land one drained batch: admitted events append to the staged
        list (seq-ascending — the queue was FIFO), rejections count."""
        with self._lock:
            self.staged.extend(admitted)
            self.rejected += len(errors)
            self.inflight -= taken
            if errors:
                self.errors.extend(errors)
                del self.errors[:-self.ERROR_RING]

    def take_staged(self) -> list:
        with self._lock:
            out = self.staged
            self.staged = []
            return out

    def restage(self, events: list) -> None:
        """Put taken-but-deferred events back at the FRONT of the
        staged list (the coalesce watermark cut): they carry the
        lane's lowest outstanding seqs, so prepending preserves the
        list's seq-ascending order."""
        with self._lock:
            self.staged[:0] = events

    def snapshot(self) -> dict:
        """Point-in-time stats (its own lock only — a scrape can never
        block behind the commit lock or another lane)."""
        with self._lock:
            return {
                "lane": self.idx,
                "queued": len(self.queued) + self.inflight,
                "staged": len(self.staged),
                "capacity": self.capacity,
                "accepted": self.accepted,
                "shed": self.shed,
                "rejected": self.rejected,
                "errors": [{"seq": s, "reason": r}
                           for s, r in self.errors[-8:]],
            }

    def quiet(self) -> bool:
        with self._lock:
            return not self.queued and self.inflight == 0

    def backlog(self) -> int:
        """Events submitted but not yet staged — the coalesce
        pre-drain's per-lane bound."""
        with self._lock:
            return len(self.queued) + self.inflight


class IntakeRouter:
    """The multi-lane front end.  See the module docstring.

    ``sync_flush`` (optional) is the degrade-to-sync valve: a callable
    that runs ``coalesce`` against the owning cluster under its commit
    lock.  The server wires it; a router without one sheds even under
    ``policy="sync"`` (counted, never silent).

    ``tracer`` (optional) is the owning server's ``CycleTracer``: a
    submit and a coalesce record their spans under whatever request is
    open on the calling thread (``docs/TRACING.md``); without one, or
    outside a request, they record nothing.
    """

    def __init__(self, config: IntakeConfig | None = None,
                 sync_flush=None, tracer=None):
        self.config = config or IntakeConfig()
        self._tracer = tracer
        self._lanes = tuple(
            _Lane(i, self.config.lane_capacity)
            for i in range(self.config.lanes))
        self._sync_flush = sync_flush
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._seq = 0              # kai-race: guarded-by=_lock
        self._coalesces = 0        # kai-race: guarded-by=_lock
        self._coalesced_events = 0  # kai-race: guarded-by=_lock
        self._sync_degrades = 0    # kai-race: guarded-by=_lock
        self._apply_errors = 0     # kai-race: guarded-by=_lock
        #: of the events coalesces took: admitted before, off the
        #: cycle's thread / by the coalesce's own pre-drain; and their
        #: waits in a lane, from offer to take
        self._by_workers = 0       # kai-race: guarded-by=_lock
        self._in_coalesce = 0      # kai-race: guarded-by=_lock
        self._wait_sum = 0.0       # kai-race: guarded-by=_lock
        self._wait_max = 0.0       # kai-race: guarded-by=_lock
        #: drain workers; started/stopped from the owning thread only,
        #: handler-thread reads are liveness probes on the list binding
        self._threads: list = []   # kai-race: guarded-by=single-writer

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "IntakeRouter":
        if self._threads:
            return self
        self._stop.clear()
        for lane in self._lanes:
            t = threading.Thread(target=self._worker, args=(lane,),
                                 daemon=True,
                                 name=f"kai-intake-lane-{lane.idx}")
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        for lane in self._lanes:
            lane.wake.set()
        for t in self._threads:
            t.join(timeout=5)
        self._threads = []

    # -- submission (producer side) ------------------------------------------

    def _lane_index(self, key: str) -> int:
        return zlib.crc32(key.encode("utf-8")) % len(self._lanes)

    def _lane_of(self, key: str) -> _Lane:
        return self._lanes[self._lane_index(key)]

    # NOTE: these one-line wrappers are deliberate, not dead seams —
    # kai-race resolves attribute accesses through annotated
    # parameters, and `self._lanes[idx].offer(...)` (a subscript) is
    # opaque to it.  Routing every lane call through a `lane: _Lane`
    # annotated helper is what keeps the lane lock discipline on the
    # analyzer's surface (tests/test_analysis.py pins that coverage).

    def _offer(self, lane: _Lane, events: list) -> bool:
        return lane.offer(events)

    def _count_shed(self, lane: _Lane, n: int) -> None:
        lane.count_shed(n)

    def _lane_backlog(self, lane: _Lane) -> int:
        return lane.backlog()

    def _restage(self, lane: _Lane, events: list) -> None:
        lane.restage(events)

    def _would_fit(self, lane: _Lane, n: int) -> bool:
        return lane.would_fit(n)

    def _submit_atomic(self, ops, all_or_nothing: bool = False
                       ) -> tuple[int, list, int]:
        """Assign the sequence block AND offer every lane group while
        holding the router lock, so offer order == seq order globally.
        Without that atomicity two racing submitters could offer out of
        seq order, and a coalesce landing between their offers would
        apply a later-seq same-key event a window before an earlier
        one — inverting the order a sequential replay would produce.
        Offers are pure list appends; nothing blocks under the lock,
        and the O(n) prep — lane hashing, event construction — happens
        BEFORE it so racing submitters convoy only on seq stamping and
        the appends themselves."""
        order: list = []
        groups: dict[int, list] = {}
        offered = time.perf_counter()
        for op, coll, key, payload in ops:
            ev = IntakeEvent(0, op, coll, key, payload, offered)
            order.append(ev)
            groups.setdefault(self._lane_index(key), []).append(ev)
        with self._lock:
            if all_or_nothing:
                # the HTTP contract: a 429 means NOTHING of the request
                # was queued, so a client's blind full retry can never
                # double-apply a partially accepted delta.  Probing is
                # sound under the router lock: submits AND coalesce's
                # restage serialize here, and drains only free capacity.
                # Lanes that actually overflowed are flagged so shed
                # accounting blames the saturated lane, not the healthy
                # ones collaterally refused with it.
                causing = [idx for idx, events in sorted(groups.items())
                           if not self._would_fit(self._lanes[idx],
                                                  len(events))]
                if causing:
                    return 0, [(idx, events, idx in causing)
                               for idx, events in sorted(groups.items())
                               ], len(groups)
            base = self._seq
            self._seq = base + len(order)
            for off, ev in enumerate(order):
                ev.seq = base + off
            shed_groups = []
            accepted = 0
            for idx, events in sorted(groups.items()):
                if self._offer(self._lanes[idx], events):
                    accepted += len(events)
                else:
                    # a per-lane refusal is always its own lane's doing
                    shed_groups.append((idx, events, True))
        return accepted, shed_groups, len(groups)

    def submit_ops(self, ops, all_or_nothing: bool = False) -> dict:
        """Queue decomposed ``(op, coll, key, payload)`` operations.

        Sequence numbers are assigned in list order, atomically with
        the lane offers (see ``_submit_atomic``), so a later coalesce
        restores exactly this submission order across lanes.  Each
        lane's slice is offered atomically; ``all_or_nothing=True``
        (the HTTP boundary) extends that to the whole request, so a
        429 guarantees nothing was queued and a blind full retry is
        safe.  In-process callers keep per-lane partial accept and
        retry the ``shed_ops`` echo exactly."""
        n = len(ops)
        accepted, shed_groups, lanes = self._submit_atomic(
            ops, all_or_nothing)
        if shed_groups and self.config.policy == "sync" \
                and self._sync_flush is not None:
            # degrade to sync: become the old single-writer intake for
            # one request — drain every lane inline, flush a coalesce
            # through the commit lock, then retry on the emptied lanes.
            # The retry re-enters _submit_atomic, so it gets FRESH
            # sequence numbers: everything staged before the flush has
            # already applied, and a retry keeping its pre-flush seqs
            # would claim an ordering the hub no longer honors.
            self.drain_inline()
            self._sync_flush()
            with self._lock:
                self._sync_degrades += 1
            metrics.intake_sync_degrades.inc()
            retry_ops = [(e.op, e.coll, e.key, e.payload)
                         for _idx, events, _causing in shed_groups
                         for e in events]
            more, shed_groups, _ = self._submit_atomic(retry_ops,
                                                       all_or_nothing)
            accepted += more
        # shed accounting happens HERE, on the final outcome only — a
        # refusal the degrade path then delivered is not a drop.  The
        # per-lane counters blame only CAUSING lanes (the saturated
        # ones): an all-or-nothing refusal also refuses groups bound
        # for healthy lanes, and charging those lanes would point an
        # operator at the wrong place.  The request-level `shed` count
        # is the full refusal either way.
        shed = sum(len(events) for _idx, events, _causing in shed_groups)
        for idx, events, causing in shed_groups:
            if causing:
                self._count_shed(self._lanes[idx], len(events))
                metrics.intake_shed.inc(str(idx),
                                        by=float(len(events)))
        if accepted:
            metrics.intake_accepted.inc(by=float(accepted))
        # shed_ops: exactly the refused operations (sheds are atomic
        # per lane group, so a mixed-lane submit can be PARTIALLY
        # accepted — callers that retry must retry these, not guess)
        return {"accepted": accepted, "shed": shed, "total": n,
                "lanes": lanes,
                "shed_ops": [(e.op, e.coll, e.key, e.payload)
                             for _idx, events, _causing in shed_groups
                             for e in events]}

    def submit_delta(self, delta: dict,
                     all_or_nothing: bool = False) -> dict:
        """Queue one delta document (the ``POST /intake`` body — the
        same schema ``POST /cluster/delta`` applies synchronously)."""
        with span_of(self._tracer, "intake.submit") as sp:
            out = self.submit_ops(_apply.decompose_delta(delta),
                                  all_or_nothing)
            sp.attrs.update(events=out["total"], lanes=out["lanes"],
                            shed=out["shed"])
        return out

    # -- drain (worker side) --------------------------------------------------

    def _worker(self, lane: _Lane) -> None:
        """One lane's drain loop (daemon thread, one per lane)."""
        while not self._stop.is_set():
            lane.wake.clear()
            if self._drain_lane(lane)[0] == 0:
                lane.wake.wait(0.05)

    def _drain_lane(self, lane: _Lane, in_coalesce: bool = False
                    ) -> tuple[int, int]:
        """Pop one batch, admission-check it (vectorized), stage the
        admitted events — one whole round under the lane's drain lock
        (see ``_Lane.drain_lock``).  Returns the events popped and, of
        them, admitted.  ``in_coalesce`` says the round runs on the
        cycle's own thread (``coalesce``'s pre-drain) and not on a lane
        worker's: the admitted events carry it to the take."""
        with lane.drain_lock:
            batch = lane.take_queued(self.config.batch)
            if not batch:
                return 0, 0
            try:
                with annotation("lane.admit"):
                    ok, reasons = _apply.admit_batch(batch)
            except Exception as exc:  # noqa: BLE001 — a poisoned batch
                # must never kill the lane's worker (the lane would
                # stop draining forever) or leak the inflight count:
                # reject the whole batch, with the reason on the ring
                ok = [False] * len(batch)
                reasons = [f"admission error: {exc}"] * len(batch)
            admitted = [ev for ev, good in zip(batch, ok) if good]
            errors = [(ev.seq, reasons[i])
                      for i, ev in enumerate(batch) if not ok[i]]
            if in_coalesce:
                for ev in admitted:
                    ev.in_coalesce = True
            lane.stage(admitted, errors, len(batch))
        if errors:
            metrics.intake_rejected.inc(str(lane.idx),
                                        by=float(len(errors)))
        return len(batch), len(admitted)

    def drain_inline(self, timeout: float = 30.0) -> bool:
        """Quiesce the queues from the calling thread: help-drain every
        lane until nothing is queued or in flight (used by the sync
        degrade path, tests, and the bench's honest end-to-end clock).
        Safe alongside live workers — whoever pops a batch stages it."""
        deadline = time.monotonic() + timeout
        while True:
            moved = 0
            for lane in self._lanes:
                moved += self._drain_lane(lane)[0]
            if moved == 0 and all(lane.quiet() for lane in self._lanes):
                return True
            if time.monotonic() > deadline:
                return False

    # -- coalesce (commit side) -----------------------------------------------

    def _take_staged(self, lane: _Lane) -> list:
        return lane.take_staged()

    def coalesce(self, cluster) -> dict:
        """Merge every lane's staged events into the hub, in global
        sequence order, through the shared applier.  The caller holds
        the cluster's commit lock (``SchedulerServer._state_lock``) —
        this is the ONLY point where intake touches shared cluster
        state, which is what lets ``_state_lock`` shrink from
        per-mutation to per-cycle-boundary."""
        t0 = time.perf_counter()
        # the watermark is the window's cut: a submit is atomic (seq
        # block + every lane offer under the router lock), so every
        # event with seq < watermark was FULLY offered before this
        # boundary and every event >= watermark belongs wholly to the
        # next window — a racing submit can never have half its delta
        # in this cycle and half in the next, whichever lanes the
        # sweep visits first.
        with self._lock:
            watermark = self._seq
        # pre-drain: everything submitted BEFORE this boundary joins
        # this window.  Without it, one delta's events could split
        # across cycles by worker timing (pods staged from one lane, a
        # still-queued gang in another) — a state the sequential
        # classic path can never produce.  Bounded by each lane's
        # backlog at entry: events racing in DURING the coalesce go to
        # the next window, so a sustained storm cannot livelock the
        # cycle.  Draining waits on a mid-round worker (drain_lock),
        # so nothing submitted-before-boundary is left in flight.
        with span_of(self._tracer, "coalesce.drain") as sp:
            admitted_here = 0
            for lane in self._lanes:
                target = self._lane_backlog(lane)
                moved = 0
                while moved < target:
                    n, admitted = self._drain_lane(lane, in_coalesce=True)
                    if n == 0:
                        break
                    moved += n
                    admitted_here += admitted
            sp.attrs["events"] = admitted_here
        # the take→cut→restage window runs under the ROUTER lock: the
        # all-or-nothing probe's soundness premise is that between its
        # capacity check and the offer, lane load can only shrink —
        # restage grows it, so restage must serialize with the probes
        # (both sit under the same lock; lane-lock nesting stays
        # router→lane, the one direction used everywhere)
        staged: list = []
        with span_of(self._tracer, "coalesce.take") as sp:
            with self._lock:
                for lane in self._lanes:
                    taken = self._take_staged(lane)
                    cut = len(taken)
                    while cut > 0 and taken[cut - 1].seq >= watermark:
                        cut -= 1
                    if cut < len(taken):
                        self._restage(lane, taken[cut:])
                    staged.extend(taken[:cut])
            staged.sort(key=attrgetter("seq"))
            # each taken event's wait in its lane, from offer to here
            now = time.perf_counter()
            waits = [now - ev.offered for ev in staged]
            wait_sum, wait_max = sum(waits), max(waits, default=0.0)
            in_coalesce = sum(ev.in_coalesce for ev in staged)
            lanes = {"admitted_by_workers": len(staged) - in_coalesce,
                     "admitted_in_coalesce": in_coalesce,
                     "lane_wait_seconds": {
                         "mean": wait_sum / len(waits) if waits else 0.0,
                         "max": wait_max}}
            sp.attrs["events"] = len(staged)
        apply_errors: list = []
        parsed0 = _apply.PARSED_PODS[0]
        with span_of(self._tracer, "coalesce.apply") as sp:
            n = _apply.apply_events(cluster, staged, errors=apply_errors)
            applied = n - len(apply_errors)
            parsed = _apply.PARSED_PODS[0] - parsed0
            sp.attrs.update(events=applied, parsed_pods=parsed,
                            errors=len(apply_errors))
        dt = time.perf_counter() - t0
        with self._lock:
            self._coalesces += 1
            self._coalesced_events += applied
            self._apply_errors += len(apply_errors)
            self._by_workers += lanes["admitted_by_workers"]
            self._in_coalesce += in_coalesce
            self._wait_sum += wait_sum
            self._wait_max = max(self._wait_max, wait_max)
        if applied:
            metrics.intake_coalesced.inc(by=float(applied))
        if apply_errors:
            # admitted-but-unappliable docs: skipped so one client's
            # poisoned event can never destroy other clients' accepted
            # mutations or fail the scheduling cycle
            metrics.intake_apply_errors.inc(by=float(len(apply_errors)))
        metrics.intake_coalesce_seconds.observe(value=dt)
        for lane in self._lanes:
            snap = lane.snapshot()
            metrics.intake_lane_depth.set(
                str(snap["lane"]),
                value=float(snap["queued"] + snap["staged"]))
        return {"events": applied, "seconds": dt, "parsed_pods": parsed,
                "lanes": lanes, "apply_errors": apply_errors[:8]}

    # -- observability ----------------------------------------------------------

    def _totals(self, lanes: list[dict]) -> dict:
        """Aggregate one pass of lane snapshots + router counters."""
        with self._lock:
            coalesces = self._coalesces
            merged = self._coalesced_events
            degrades = self._sync_degrades
            apply_errors = self._apply_errors
            by_workers, in_coalesce = self._by_workers, self._in_coalesce
            wait_sum, wait_max = self._wait_sum, self._wait_max
        taken = by_workers + in_coalesce
        return {
            "lanes": len(lanes),
            "queued": sum(s["queued"] for s in lanes),
            "staged": sum(s["staged"] for s in lanes),
            "accepted": sum(s["accepted"] for s in lanes),
            "shed": sum(s["shed"] for s in lanes),
            "rejected": sum(s["rejected"] for s in lanes),
            "coalesces": coalesces,
            "coalesced_events": merged,
            "apply_errors": apply_errors,
            "sync_degrades": degrades,
            # of the events coalesces took: who admitted them, and how
            # long they waited in a lane from offer to take
            "admitted_by_workers": by_workers,
            "admitted_in_coalesce": in_coalesce,
            "lane_wait_seconds": {
                "mean": wait_sum / taken if taken else 0.0,
                "max": wait_max},
        }

    def health(self) -> dict:
        """The ``/healthz`` intake slice: totals only, cheap."""
        return self._totals([lane.snapshot() for lane in self._lanes])

    def debug_doc(self) -> dict:
        """The ``GET /debug/intake`` document.  Reads only per-lane and
        router locks — never the server's commit lock, so a scrape can
        never block behind intake lanes or a running cycle.  Each lane
        is snapshotted ONCE and the totals derive from those same
        snapshots, so the document is internally consistent: its
        top-level counts always equal the sum of its lane rows."""
        lanes = [lane.snapshot() for lane in self._lanes]
        doc = self._totals(lanes)
        doc.update(
            policy=self.config.policy,
            lane_capacity=self.config.lane_capacity,
            batch=self.config.batch,
            workers_alive=sum(t.is_alive() for t in self._threads),
            lane_stats=lanes,
        )
        return doc
