"""Scheduler sidecar server — the PluginServer + the snapshot-in /
placements-out wire boundary.

Reference surfaces collapse into one stdlib HTTP server:

- ``GET /job-order``  — the reflectjoborder plugin
  (``plugins/reflectjoborder``): the computed job order of the last (or
  an on-demand) session, for debugging fairness.
- ``GET /snapshot``   — the snapshot plugin (``plugins/snapshot``):
  the full cluster state as JSON, replayable by ``snapshot_tool.py``.
- ``POST /cycle``     — the sidecar protocol (SURVEY.md §7d): POST a
  cluster snapshot document, receive the cycle's commit set.  This is
  the cache→session boundary as a wire protocol, so a host harness in
  another language can mount the TPU solver behind its own registries.
- ``GET /metrics``    — Prometheus text exposition
  (``pkg/scheduler/metrics``).
- ``GET /debug/trace``  — the kai-trace flight recorder
  (``runtime/tracing.py``): the last N cycles' phase-attributed span
  trees as Chrome-trace JSON (``?cycles=`` bounds the window).
- ``GET /debug/events`` — per-gang decision events
  (``runtime/events.py``): every considered gang's cycle outcome
  (allocated / fit-failure / quota-gate / preempted-for);
  ``?gang=<name>`` filters to one pod group.
- ``GET /debug/wire``   — the kai-wire transfer ledger + compile
  watcher (``runtime/wire_ledger.py`` / ``runtime/compile_watch.py``):
  per-cycle, per-leaf host→device upload events with redundancy
  accounting, the device-residency gauge, and per-entry jit cache-miss
  attribution (``?cycles=`` bounds the ring window).
- ``GET /debug/cluster`` — the kai-pulse cluster-health document
  (``ops/analytics.py``): fragmentation (gang ladder, stranded
  capacity, free histograms), utilization/goodput, fairness drift, and
  the starvation top-K table of the latest analytics cycle.
- ``GET /debug/repack`` — the kai-repack defragmentation solver
  (``ops/repack.py``): trigger knobs, live trigger state (consecutive
  high-fragmentation cycles, cooldown remaining), and the last
  firing's bounded migration plan.
- ``GET /debug/intake`` — the kai-intake multi-lane mutation front end
  (``intake/router.py``): per-lane queued/staged depth, accepted/shed/
  rejected counters, recent admission rejections, coalesce totals.
- ``POST /intake``      — queue a delta document through the async
  lanes instead of applying it under the commit lock: hash-sharded by
  entity key, admission-checked in vectorized batches, coalesced into
  the hub journal at the next cycle boundary.  Lane overflow sheds
  with 429 (atomically per lane group — nothing journaled) or
  degrades to sync, per ``SchedulerConfig.intake_policy``.
- ``GET /debug``        — machine-readable index of every debug
  surface with one-line descriptions and live query params, so
  operators stop grepping this file.

The server is deliberately dependency-free (http.server); a production
deployment would front it with gRPC — the payloads are already the
stable JSON documents of ``runtime/snapshot.py``.

``docs/TRACING.md`` lists every span, device scope, counter and
``/healthz`` field by name, and how to get them out of a running server.
"""
from __future__ import annotations

import copy
import cProfile
import json
import pstats
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from ..intake import apply as intake_apply
from ..intake.router import IntakeConfig, IntakeRouter
from ..runtime import (compile_cache, compile_watch, malloc_tune,
                       wire_ledger)
from ..runtime.cluster import Cluster
from ..runtime.snapshot import dump_cluster, load_cluster
from ..runtime.tracing import GcWatch
from . import metrics
from .scheduler import Scheduler
from .session import Session


#: every debug surface the server mounts, with live query params — the
#: ``GET /debug`` index payload (an endpoint test pins this list
#: against the actual routes, so it cannot rot)
DEBUG_SURFACES = (
    {"path": "/debug", "params": (),
     "desc": "this index: every debug surface with query params"},
    {"path": "/debug/trace", "params": ("cycles",),
     "desc": ("kai-trace flight recorder: retained cycles' "
              "phase-attributed span trees as Chrome-trace JSON")},
    {"path": "/debug/events", "params": ("gang",),
     "desc": ("per-gang decision events: allocated / fit-failure / "
              "quota-gate / preempted-for / starved")},
    {"path": "/debug/wire", "params": ("cycles",),
     "desc": ("kai-wire transfer ledger + compile watcher: per-leaf "
              "uploads, redundancy accounting, device residency, "
              "per-entry jit cache misses")},
    {"path": "/debug/cluster", "params": (),
     "desc": ("kai-pulse cluster health: fragmentation gang ladder + "
              "stranded capacity, utilization/goodput, fairness "
              "drift, starvation top-K (latest analytics cycle)")},
    {"path": "/debug/repack", "params": (),
     "desc": ("kai-repack defragmentation solver: trigger knobs + live "
              "trigger state (frag streak, cooldown) and the last "
              "firing's bounded migration plan")},
    {"path": "/debug/intake", "params": (),
     "desc": ("kai-intake multi-lane mutation front end: per-lane "
              "queued/staged depth, accepted/shed/rejected counters, "
              "recent admission rejections, coalesce totals, worker "
              "liveness")},
    {"path": "/debug/twin", "params": ("stream",),
     "desc": ("kai-twin digital twin: stream recorder status "
              "(attached/events/dropped) and the last differential-"
              "oracle replay verdict; ?stream=1 inlines the full "
              "recorded stream document")},
    {"path": "/debug/pprof", "params": (),
     "desc": ("one profiled cycle (cProfile): hottest host functions "
              "+ kai-trace phase breakdown")},
    {"path": "/debug/pprof/continuous", "params": (),
     "desc": ("continuous-profiler folded-stack windows (404 while "
              "the sampler is off)")},
)


def job_order(cluster: Cluster, scheduler: Scheduler) -> list[dict]:
    """The fairness-ordered gang list a cycle would attempt —
    reflectjoborder's payload."""
    from ..ops import ordering
    session = Session.open(*cluster.snapshot_lists(),
                           config=scheduler.config.session,
                           now=cluster.now)
    st = session.state
    perm = np.asarray(ordering.job_order_perm(
        st.gangs, st.queues, st.queues.allocated, st.queues.fair_share,
        st.total_capacity, st.gangs.valid))
    valid = np.asarray(st.gangs.valid)
    queues = np.asarray(st.gangs.queue)
    out = []
    for gi in perm.tolist():
        if gi < len(session.index.gang_names) and valid[gi]:
            out.append({
                "pod_group": session.index.gang_names[gi],
                "queue": session.index.queue_names[queues[gi]],
            })
    return out


def profile_cycle(cluster: Cluster, scheduler: Scheduler,
                  top: int = 25) -> dict:
    """One scheduling cycle under cProfile — the pprof
    ``/debug/pprof/profile`` analogue (ref ``cmd/scheduler/profiling``):
    returns the hottest host-side functions plus the cycle's kai-trace
    phase breakdown (``CycleResult.phase_seconds`` — the tracer's
    attribution, not ad-hoc timers; device time is the ``device_wait``
    phase)."""
    # profile against private copies: a profiling GET must never write
    # bind requests or evictions into the server's stored cluster, and
    # the synthetic cProfile-inflated cycle must not pollute the LIVE
    # scheduler's trace ring / decision log or repoint its warm
    # incremental snapshotter at the throwaway deepcopy
    cluster = copy.deepcopy(cluster)
    scheduler = Scheduler(scheduler.config,
                          usage_lister=scheduler.usage_lister)
    prof = cProfile.Profile()
    prof.enable()
    result = scheduler.run_once(cluster)
    prof.disable()
    stats = pstats.Stats(prof)
    stats.sort_stats("cumulative")
    rows = []
    for func, (cc, nc, tt, ct, _) in stats.stats.items():  # type: ignore
        fname, line, name = func
        rows.append({"function": f"{fname}:{line}({name})",
                     "calls": nc, "total_s": round(tt, 6),
                     "cumulative_s": round(ct, 6)})
    rows.sort(key=lambda r: -r["cumulative_s"])
    return {
        "phases": dict(result.phase_seconds),
        "total_seconds": result.session_seconds,
        "action_seconds": result.action_seconds,
        "hottest": rows[:top],
    }


def apply_cluster_delta(cluster: Cluster, delta: dict) -> None:
    """Apply an incremental update to the stored cluster — the
    delta/incremental wire protocol: instead of shipping the full
    cluster document every cycle (tens of MB at 10k nodes × 50k pods),
    a sidecar PATCHes only what changed.  Collections accept
    ``{collection}_upsert`` (object docs, partial docs merge over the
    stored object) and ``{collection}_delete`` (names); ``now``
    advances the clock.

    This is the CLASSIC synchronous path — it delegates to the same
    decompose + apply pipeline the kai-intake router's coalesce replays
    (``intake/apply.py``), which is what makes the async lanes'
    storm-vs-sequential differential bar a shared-code identity rather
    than a parallel reimplementation."""
    intake_apply.apply_cluster_delta(cluster, delta)


def run_cycle_doc(doc: dict, scheduler: Scheduler | None = None) -> dict:
    """POST /cycle body → commit-set document (the sidecar protocol)."""
    cluster = load_cluster(doc)
    scheduler = scheduler or Scheduler()
    result = scheduler.run_once(cluster)
    return _commit_doc(result)


def _commit_doc(result) -> dict:
    return {
        "bind_requests": [{
            "pod": br.pod_name, "node": br.selected_node,
            "type": br.received_resource_type.value,
            "accel_count": br.received_accel_count,
            "accel_portion": br.received_accel_portion,
            "accel_memory_gib": br.received_accel_memory_gib,
            "accel_groups": br.selected_accel_groups,
        } for br in result.bind_requests],
        "evictions": [{
            "pod": ev.pod_name, "group": ev.group, "move_to": ev.move_to,
        } for ev in result.evictions],
        "action_seconds": result.action_seconds,
    }


#: how many requests are served at once; further ones wait their turn
HANDLER_THREADS = 16

#: the POST routes, each a key of ``last_cycle.requests`` once it has
#: served a request; any other path is booked as ``other``
POST_ROUTES = frozenset((
    "/cycle", "/cluster", "/cluster/delta", "/intake", "/cycle/stored",
    "/twin/record", "/twin/replay"))


class SchedulerServer:
    """Serve the debug/sidecar endpoints for one cluster + scheduler.

    Concurrency model: ``ThreadingHTTPServer`` runs every request in a
    thread beside the others — one of ``HANDLER_THREADS`` threads that
    live as long as the server (``_serve_on_pool``) — so the stored
    cluster document and the (stateful) Scheduler are shared mutable
    state.  All handler access to them is
    serialized under ``_state_lock`` — payloads are computed under the
    lock and written to the socket after releasing it, so a slow client
    never stalls the next request's state access.  ``GET /healthz``
    serves ``_cycle_stats``, an immutable per-cycle stats document
    swapped (never mutated) for each cycle run through the server, once
    the cycle's reply is encoded and before it is written.  Every
    ``POST`` is a trace of its own on the scheduler's tracer (root span
    ``request``; ``docs/TRACING.md``), with the cycle's root under it.
    The cluster/scheduler pair handed to a running server is owned by
    it: driving ``run_once`` on the same objects from another thread
    bypasses this lock.

    kai-intake (PR 12) shrinks what the lock serializes: mutations
    posted to ``POST /intake`` shard into the router's bounded lanes
    (their own locks), admission-check off the commit path, and touch
    ``_state_lock`` only at the cycle-boundary ``coalesce`` inside
    ``POST /cycle/stored``.  The classic ``POST /cluster/delta`` stays
    the synchronous reference path (same applier, applied immediately
    under the lock).
    """

    def __init__(self, cluster: Cluster, scheduler: Scheduler | None = None,
                 port: int = 0):
        self._state_lock = threading.Lock()
        self.cluster = cluster  # kai-race: guarded-by=_state_lock
        self.scheduler = scheduler or Scheduler()
        #: the scheduler's tracer: handler threads open their request
        #: traces on it (read-only binding after init)
        self._tracer = self.scheduler.tracer
        #: per handler thread: when ``_serve_on_pool`` handed it the
        #: connection it is serving (``perf_counter`` seconds)
        self._handed = threading.local()
        # kai-intake multi-lane front end: lanes/capacity/policy come
        # from the scheduler config (conf `intake.*` document keys).
        # The sync_flush valve lets policy="sync" degrade an overflowing
        # request to the classic behavior: quiesce the lanes and run a
        # coalesce under the commit lock, then retry.
        icfg = self.scheduler.config
        self.intake = IntakeRouter(
            IntakeConfig(lanes=icfg.intake_lanes,
                         lane_capacity=icfg.intake_lane_capacity,
                         policy=icfg.intake_policy,
                         batch=icfg.intake_batch),
            sync_flush=self._intake_flush, tracer=self._tracer)
        #: immutable per-cycle stats document (GET /healthz); handler
        #: threads swap in a fresh dict under _state_lock, readers take
        #: the current binding without it
        self._cycle_stats: dict | None = None  # kai-race: guarded-by=atomic-swap
        #: serializes the publication's compare-and-swap: a document is
        #: finished outside ``_state_lock`` (after the reply's encode),
        #: and an older cycle's must not replace a newer one's
        self._publish_lock = threading.Lock()
        #: cycles run through the server, and the first one's phases
        self._cycles = 0  # kai-race: guarded-by=_state_lock
        self._first_phases = None  # kai-race: guarded-by=_state_lock
        #: times the process's garbage collections while the server
        #: runs (start() installs the hook, stop() removes it)
        self._gc_watch = GcWatch()
        # kai-twin stream recorder: attached to the stored cluster so
        # the shared intake applier (intake/apply.py choke point)
        # mirrors every applied mutation; /cycle/stored appends cycle
        # marks.  The recorder is internally locked; the last oracle
        # verdict is an immutable atomic-swapped doc, so GET
        # /debug/twin and the healthz twin slice never take
        # _state_lock.
        self.recorder = None
        self._twin_doc: dict | None = None  # kai-race: guarded-by=atomic-swap
        if getattr(self.scheduler.config, "twin_record", False):
            from ..twin import stream as twin_stream
            self.recorder = twin_stream.StreamRecorder()
            self._twin_attach(cluster)
        # continuous profiling (the Pyroscope analogue) — created here,
        # STARTED in start() so a never-started server leaks no sampler
        self.profiler = None
        cfg = self.scheduler.config
        hz = getattr(cfg, "profiler_sample_hz", None)
        addr = getattr(cfg, "pyroscope_address", "")
        # an address with an UNSET rate defaults to 100 Hz; an explicit
        # rate of 0 keeps the sampler off even with an address
        if (hz or 0) > 0 or (addr and hz is None):
            from ..runtime.profiling import ContinuousProfiler
            self.profiler = ContinuousProfiler(
                sample_hz=hz if hz else 100.0,
                server_address=addr,
            )
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def _send_text(self, body: bytes,
                           ctype: str = "text/plain",
                           code: int = 200) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _send(self, payload, code=200):
                self._send_text(json.dumps(payload).encode(),
                                "application/json", code)

            def do_GET(self):  # noqa: N802
                # cluster/scheduler reads happen under the state lock;
                # the response is written AFTER release so a slow client
                # cannot hold every other endpoint hostage
                if self.path == "/job-order":
                    with outer._state_lock:
                        payload = job_order(outer.cluster, outer.scheduler)
                    self._send(payload)
                elif self.path == "/snapshot":
                    with outer._state_lock:
                        payload = dump_cluster(outer.cluster)
                    self._send(payload)
                elif self.path == "/healthz":
                    # _cycle_stats is swapped atomically (never mutated
                    # in place), so this read needs no lock; the
                    # kai-intake slice reads only lane/router locks —
                    # a health scrape never blocks behind the commit
                    # lock or a full intake lane
                    stats = outer._cycle_stats
                    self._send({"ok": True, "last_cycle": stats,
                                "intake": outer.intake.health(),
                                "twin": outer._twin_health()})
                elif self.path.startswith("/debug/trace"):
                    # kai-trace flight recorder: the retained cycle ring
                    # as Chrome-trace JSON.  Only the scheduler HANDLE
                    # is read under the state lock; the export itself
                    # runs outside it — the tracer rings only COMPLETED,
                    # immutable traces under its own lock, so the export
                    # can never tear and must not stall cycle POSTs.
                    params = urllib.parse.parse_qs(
                        urllib.parse.urlparse(self.path).query)
                    try:
                        cycles = (int(params["cycles"][0])
                                  if "cycles" in params else None)
                    except ValueError:
                        self.send_error(400, "cycles must be an integer")
                        return
                    with outer._state_lock:
                        tracer = outer.scheduler.tracer
                    self._send(tracer.export_chrome(cycles=cycles))
                elif self.path.startswith("/debug/events"):
                    # per-gang decision events: ?gang=<name> filters.
                    # Same discipline as /debug/trace: handle under the
                    # lock, the (internally locked) log reads outside
                    params = urllib.parse.parse_qs(
                        urllib.parse.urlparse(self.path).query)
                    gang = params.get("gang", [None])[0]
                    with outer._state_lock:
                        log = outer.scheduler.decisions
                    self._send({"gang": gang,
                                "events": log.events(gang=gang),
                                "summary": log.summary()})
                elif self.path.startswith("/debug/wire"):
                    # kai-wire transfer ledger + compile watcher: the
                    # rolled per-cycle upload ring (?cycles= bounds),
                    # residency gauge, and per-entry compile-miss
                    # attribution.  Computed OUTSIDE _state_lock —
                    # ledger/watcher are process-global and internally
                    # locked, ring entries are immutable once rolled,
                    # so the document can never tear and never stalls
                    # a concurrent cycle POST.
                    params = urllib.parse.parse_qs(
                        urllib.parse.urlparse(self.path).query)
                    try:
                        cycles = (int(params["cycles"][0])
                                  if "cycles" in params else None)
                    except ValueError:
                        self.send_error(400, "cycles must be an integer")
                        return
                    doc = wire_ledger.LEDGER.wire_doc(cycles=cycles)
                    doc["compile"] = compile_watch.WATCHER.report()
                    self._send(doc)
                elif self.path.startswith("/debug/cluster"):
                    # kai-pulse cluster-health document: the LAST
                    # analytics cycle's immutable doc.  Only the
                    # scheduler handle is read under the state lock;
                    # the doc itself is atomic-swapped by the cycle
                    # thread and never mutated after publication, so
                    # this can never tear and never stalls a cycle.
                    with outer._state_lock:
                        sched = outer.scheduler
                    doc = sched.last_analytics
                    self._send({
                        "analytics": doc,
                        "analytics_every":
                            sched.config.analytics_every,
                        "starvation_alarm_cycles":
                            sched.config.starvation_alarm_cycles,
                        "ok": bool(doc)})
                elif self.path.startswith("/debug/intake"):
                    # kai-intake lane document: per-lane depth/shed/
                    # rejection stats + coalesce totals.  Computed from
                    # the router's own per-lane and router locks ONLY —
                    # never _state_lock — so a scrape can never block
                    # behind a running cycle or a full intake lane.
                    self._send(outer.intake.debug_doc())
                elif self.path.startswith("/debug/repack"):
                    # kai-repack status: knobs + trigger state + the
                    # LAST firing's plan doc.  Same discipline as
                    # /debug/cluster — only the scheduler handle is
                    # read under the state lock; the plan doc is
                    # atomic-swapped by the cycle thread and never
                    # mutated after publication, the trigger counters
                    # are single-writer ints (GIL-atomic reads).
                    with outer._state_lock:
                        sched = outer.scheduler
                    self._send(sched.repack_status())
                elif self.path.startswith("/debug/twin"):
                    # kai-twin status: recorder stats + the last
                    # differential-oracle verdict; ?stream=1 inlines
                    # the recorded stream document.  NO _state_lock —
                    # the recorder is internally locked and the
                    # verdict doc is atomic-swapped, so this scrape
                    # can never block behind a running cycle.
                    params = urllib.parse.parse_qs(
                        urllib.parse.urlparse(self.path).query)
                    rec = outer.recorder
                    twin = outer._twin_doc or {}
                    doc = {"recording": rec is not None
                           and rec.attached,
                           "recorder": rec.stats() if rec else None,
                           "last_replay": twin.get("last_replay")}
                    if rec is not None and params.get("stream"):
                        doc["stream"] = rec.doc()
                    self._send(doc)
                elif self.path in ("/debug", "/debug/"):
                    # index of every debug surface — static doc plus
                    # which optional surfaces are live right now
                    surfaces = [dict(s, params=list(s["params"]))
                                for s in DEBUG_SURFACES]
                    for s in surfaces:
                        if s["path"] == "/debug/pprof/continuous":
                            s["live"] = outer.profiler is not None
                        else:
                            s["live"] = True
                    self._send({"surfaces": surfaces})
                elif self.path.startswith("/debug/pprof/continuous"):
                    # the continuous-profiling (Pyroscope) analogue:
                    # retained folded-stack windows (profiler state is
                    # internally locked)
                    if outer.profiler is None:
                        self.send_error(404, "continuous profiler off")
                        return
                    self._send_text(outer.profiler.render().encode())
                elif self.path.startswith("/debug/pprof"):
                    # the --enable-profiler pprof endpoint analogue
                    with outer._state_lock:
                        payload = profile_cycle(outer.cluster,
                                                outer.scheduler)
                    self._send(payload)
                elif self.path == "/metrics":
                    # Registry.render snapshots each metric under its
                    # own lock — the text is a consistent point-in-time
                    # view even while a cycle thread observes
                    self._send_text(metrics.registry.render().encode(),
                                    "text/plain; version=0.0.4")
                else:
                    self.send_error(404)

            def _reply(self, request, encode, ctype: str, code: int = 200,
                       stats: dict | None = None) -> None:
                """A POST's answer, in the request's spans:
                ``reply.encode`` (``encode()`` makes the body), then,
                where the request ran a cycle, the publication of its
                ``stats`` — the document has to be whole before the
                reply leaves, or a client that reads ``/healthz`` the
                moment it has the reply reads the cycle before —
                and ``reply.write``."""
                tracer = outer._tracer
                with tracer.span("reply.encode"):
                    body = encode()
                request.root.attrs.update(status=code, bytes_out=len(body))
                if stats is not None:
                    with tracer.span("record"):
                        outer._publish(stats, request)
                with tracer.span("reply.write"):
                    self._send_text(body, ctype, code)

            def _reply_json(self, request, make, code=200, stats=None):
                self._reply(request, lambda: json.dumps(make()).encode(),
                            "application/json", code, stats)

            def _reply_pb(self, request, make, stats=None):
                self._reply(request, lambda: make().SerializeToString(),
                            "application/x-protobuf", stats=stats)

            def _refuse(self, request, code: int, message=None) -> None:
                request.root.attrs["status"] = code
                self.send_error(code, message)

            def do_POST(self):  # noqa: N802
                # the sidecar protocol speaks two framings over the same
                # endpoints: the stable JSON documents, and the typed
                # protobuf schema (wire/sidecar.proto — SURVEY §7d's
                # proto boundary; HTTP Content-Length is the length
                # prefix).  Content-Type selects.
                proto = self.headers.get(
                    "Content-Type", "").startswith("application/x-protobuf")
                # a request is a trace (docs/TRACING.md): its root opens
                # where _serve_on_pool handed the connection to this
                # thread, and everything below is a span under it
                with outer._tracer.request(
                        self.path if self.path in POST_ROUTES else "other",
                        start=outer._handed.__dict__.pop("at", None),
                        framing="protobuf" if proto else "json") as request:
                    # (the routes stand here and in no method of their
                    # own, and this frame holds the locals it held: a
                    # cycle runs as deep in its thread's stack as it did,
                    # ``_serve``)
                    try:
                        # socket read happens before taking the state lock;
                        # the reply goes out after releasing it
                        with outer._tracer.span("http.read"):
                            body = self.rfile.read(int(
                                self.headers.get("Content-Length", 0)))
                        request.root.attrs["bytes_in"] = len(body)
                        if proto:
                            from ..wire import codec, sidecar_pb2 as pb
                            if self.path == "/cycle":
                                with outer._tracer.span("body.parse"):
                                    doc = pb.ClusterDoc()
                                    doc.ParseFromString(body)
                                    # deserialize outside the lock (a
                                    # tens-of-MB snapshot must not stall
                                    # other endpoints)
                                    doc = codec.cluster_from_msg(doc)
                                result = outer._run_cycle(doc, request)
                                self._reply_pb(
                                    request,
                                    lambda: codec.commit_to_msg(result[0]),
                                    result[1])
                            elif self.path == "/cluster":
                                with outer._tracer.span("body.parse"):
                                    doc = pb.ClusterDoc()
                                    doc.ParseFromString(body)
                                    # (no lock)
                                    doc = codec.cluster_from_msg(doc)
                                outer._replace_cluster(doc)
                                self._reply_pb(request, pb.CommitSet)
                            elif self.path == "/cluster/delta":
                                with outer._tracer.span("body.parse"):
                                    doc = pb.ClusterDelta()
                                    doc.ParseFromString(body)
                                outer._apply_delta(
                                    codec.apply_delta_msg, doc, dict(
                                        (f.name, len(v))
                                        for f, v in doc.ListFields()
                                        if hasattr(v, "__len__")))
                                self._reply_pb(request, pb.CommitSet)
                            elif self.path == "/cycle/stored":
                                result = outer._run_stored_cycle(request)
                                self._reply_pb(
                                    request,
                                    lambda: codec.commit_to_msg(result[0]),
                                    result[1])
                            else:
                                self._refuse(request, 404)
                            return
                        if self.path == "/cycle":
                            with outer._tracer.span("body.parse"):
                                doc = load_cluster(json.loads(body.decode()))
                            result = outer._run_cycle(doc, request)
                            self._reply_json(
                                request, lambda: _commit_doc(result[0]),
                                stats=result[1])
                        elif self.path == "/cluster":
                            # replace the stored cluster (upload once ...)
                            with outer._tracer.span("body.parse"):
                                doc = load_cluster(json.loads(body.decode()))
                            outer._replace_cluster(doc)
                            self._reply_json(request, lambda: {"ok": True})
                        elif self.path == "/cluster/delta":
                            # ... then PATCH deltas instead of re-shipping
                            # the full document every cycle
                            with outer._tracer.span("body.parse"):
                                doc = json.loads(body.decode())
                            outer._apply_delta(
                                apply_cluster_delta, doc, dict(
                                    (k, len(v)) for k, v in doc.items()
                                    if isinstance(v, list)))
                            self._reply_json(request, lambda: {"ok": True})
                        elif self.path == "/intake":
                            # kai-intake: queue the delta through the async
                            # multi-lane front end instead of applying it
                            # under the commit lock.  Parse + lane offers
                            # touch NO server state lock; the staged events
                            # coalesce into the hub at the next cycle
                            # boundary.  A backpressured (shed) request
                            # reports 429 with the per-request counts —
                            # atomically refused per lane group, nothing
                            # journaled.
                            with outer._tracer.span("body.parse"):
                                doc = json.loads(body.decode())
                            # all-or-nothing at the HTTP boundary: a 429
                            # means NOTHING was queued, so a client's
                            # blind full retry can never double-apply a
                            # partially accepted delta.  Counts only on
                            # the wire — the shed ops echo is for
                            # in-process retriers.
                            out = outer.intake.submit_delta(
                                doc, all_or_nothing=True)
                            self._reply_json(
                                request, lambda: {"accepted": out["accepted"],
                                                  "shed": out["shed"],
                                                  "total": out["total"]},
                                code=429 if out["shed"] else 200)
                        elif self.path == "/cycle/stored":
                            # run a cycle against the stored cluster: the
                            # incremental sidecar protocol's execute step.
                            # Cycle boundary = the kai-intake coalesce
                            # point: staged lane events merge into the hub
                            # journal (global seq order) before the cycle
                            # snapshots it.
                            result = outer._run_stored_cycle(request)
                            self._reply_json(
                                request, lambda: _commit_doc(result[0]),
                                stats=result[1])
                        elif self.path == "/twin/record":
                            # kai-twin recorder control: start re-anchors
                            # the stream at the CURRENT stored cluster,
                            # stop freezes it (the stream stays readable
                            # through /debug/twin?stream=1)
                            doc = json.loads(body.decode()) if body else {}
                            action = doc.get("action", "start")
                            if outer.recorder is None:
                                self._refuse(
                                    request, 400, "twin recording disabled "
                                                  "(twinRecord: false)")
                                return
                            with outer._state_lock:
                                if action in ("start", "reset"):
                                    outer._twin_attach(outer.cluster)
                                elif action == "stop":
                                    outer.recorder.detach()
                                    outer.cluster.twin_recorder = None
                                else:
                                    self._refuse(
                                        request, 400,
                                        f"unknown action {action!r}")
                                    return
                            self._reply_json(request, lambda: {
                                "ok": True, "action": action,
                                "recorder": outer.recorder.stats()})
                        elif self.path == "/twin/replay":
                            # differential-oracle replay of the recorded
                            # stream: snapshot the stream under the
                            # recorder's own lock, replay it twice OUTSIDE
                            # _state_lock (a long replay must never stall
                            # the live scheduler), then atomic-swap the
                            # verdict for /debug/twin and healthz.
                            if (outer.recorder is None
                                    or not outer.recorder.attached):
                                self._refuse(
                                    request, 400, "no twin stream recorded")
                                return
                            from ..twin import replay as twin_replay
                            verdict = twin_replay.oracle(
                                outer.recorder.stream())
                            outer._twin_doc = {"last_replay": verdict}
                            self._reply_json(request, lambda: verdict)
                        else:
                            self._refuse(request, 404)
                    except Exception as exc:  # noqa: BLE001
                        self._refuse(request, 400, str(exc))

            def log_message(self, *args):
                pass

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self._pool = ThreadPoolExecutor(
            max_workers=HANDLER_THREADS, thread_name_prefix="kai-http")
        self._httpd.process_request = self._serve_on_pool
        self.port = self._httpd.server_address[1]
        self._thread: threading.Thread | None = None

    def _twin_attach(self, cluster: Cluster) -> None:
        """(Re-)anchor the recorder: snapshot the stored cluster as the
        stream header and hook the shared applier.  Called at
        construction and whenever ``POST /cluster`` replaces the
        stored document (under ``_state_lock`` there)."""
        if self.recorder is None:
            return
        from .. import conf as conf_mod
        cfg = self.scheduler.config
        self.recorder.attach(dump_cluster(cluster), seed=cfg.seed,
                             config=conf_mod.effective_config_doc(cfg))
        cluster.twin_recorder = self.recorder

    def _twin_health(self) -> dict:
        """The healthz twin slice — recorder + last-oracle state, no
        ``_state_lock`` (recorder is internally locked, the verdict
        doc is atomic-swapped)."""
        if self.recorder is None:
            return {"recording": False}
        out = dict(self.recorder.stats())
        twin = self._twin_doc
        if twin and twin.get("last_replay"):
            out["last_replay_ok"] = twin["last_replay"]["ok"]
            out["last_replay_divergences"] = len(
                twin["last_replay"]["divergences"])
        return out

    def _apply_delta(self, apply, delta, counts: dict) -> None:
        """``POST /cluster/delta``, either framing: ``apply(cluster,
        delta)`` under the state lock, as the span ``delta.apply`` with
        the length of each of the delta's lists."""
        t0 = time.perf_counter()
        with self._state_lock:
            self._tracer.add_span("lock_wait", t0, time.perf_counter())
            with self._tracer.span("delta.apply", **counts):
                apply(self.cluster, delta)

    def _replace_cluster(self, fresh: Cluster) -> None:
        """``POST /cluster``, either framing."""
        t0 = time.perf_counter()
        with self._state_lock:
            self._tracer.add_span("lock_wait", t0, time.perf_counter())
            self.cluster = fresh
            self._twin_attach(fresh)

    def _run_cycle(self, cycle_cluster: Cluster, request) -> tuple:
        """``POST /cycle``, either framing: one cycle over the posted
        document → (result, its stats document, to be published once
        the reply is encoded)."""
        t0 = time.perf_counter()
        with self._state_lock:
            self._tracer.add_span("lock_wait", t0, time.perf_counter())
            result = self.scheduler.run_once(cycle_cluster)
            with self._tracer.span("record"):
                stats = self._record_cycle(result, request)
        return result, stats

    def _run_stored_cycle(self, request) -> tuple:
        """``POST /cycle/stored``, either framing: take the commit lock,
        merge what the intake lanes staged into the hub journal (global
        seq order: the cycle boundary is the kai-intake coalesce
        point), run the cycle → (result, its stats document, to be
        published once the reply is encoded).  The wait for the lock
        and the coalesce are spans of the request, beside the cycle's
        root; ``entry_seconds`` serves their seconds."""
        tracer = self._tracer
        t0 = time.perf_counter()
        with self._state_lock:
            tracer.add_span("lock_wait", t0, time.perf_counter())
            with tracer.span("coalesce"):
                merged = self.intake.coalesce(self.cluster)
            result = self.scheduler.run_once(self.cluster)
            with tracer.span("record"):
                stats = self._record_cycle(result, request, merged)
            if self.recorder is not None:
                self.recorder.record_cycle()
        return result, stats

    def _record_cycle(self, result, request,
                      merged: dict | None = None) -> dict:
        """A fresh per-cycle stats document, but for what only the end
        of the request knows (``_publish``).  Called under
        ``_state_lock``, inside ``request``, the open trace of the POST
        that ran the cycle; ``merged`` is what its coalesce returned."""
        self._cycles += 1
        stats = {"cycles": self._cycles}
        if result is not None:
            stats.update(
                total_seconds=result.session_seconds,
                phase_seconds=dict(result.phase_seconds),
                decisions=self.scheduler.decisions.summary(),
                bind_requests=len(result.bind_requests),
                evictions=len(result.evictions),
                # kai-wire summary of the cycle: bytes on the wire by
                # reason, redundant re-uploads, device residency
                wire=dict(result.wire))
            trace = result.trace
            if trace is not None:
                # the cycle from the inside (docs/TRACING.md): self time
                # of every span, what the snapshotter did, the
                # collector's pauses, what the entry spent before the
                # cycle opened.  Start-up is the first cycle's phases,
                # frozen, and the compile stages, which count on: a
                # recompile in steady state shows
                if self._first_phases is None:
                    self._first_phases = dict(result.phase_seconds)
                # the request's spans beside the cycle's root
                entry = {name: sum(sp.seconds
                                   for sp in request.root.children
                                   if sp.name == name)
                         for name in ("lock_wait", "coalesce")}
                stats.update(
                    span_self_seconds=trace.self_seconds(),
                    snapshot=next(
                        (dict(sp.attrs) for sp in trace.root.children
                         if sp.name == "snapshot"), {}),
                    gc=trace.gc,
                    entry_seconds=entry,
                    intake_parsed_pods=(merged["parsed_pods"]
                                        if merged else 0),
                    victim_actions_skipped=dict(
                        result.victim_actions_skipped),
                    kernels=dict(result.kernels),
                    topology=dict(result.topology),
                    startup={"phase_seconds": self._first_phases,
                             **compile_watch.WATCHER.stage_seconds()})
                if merged:
                    # who admitted the coalesce's events, and their waits
                    stats["lanes"] = merged["lanes"]
            # kai-pulse slice: the headline cluster-health gauges of
            # the latest analytics cycle (this one, or — on cycles the
            # cadence skipped — the last one that ran)
            pulse = (result.analytics
                     or self.scheduler.last_analytics)
            if pulse:
                stats["cluster"] = {
                    "fragmentation_score":
                        pulse["fragmentation"]["score"],
                    "largest_rack_unit_pods":
                        pulse["fragmentation"]["largest_rack_unit_pods"],
                    "goodput": pulse["goodput"],
                    "utilization": dict(pulse["utilization"]),
                    "fairness_drift_max":
                        pulse["fairness"]["drift_max"],
                    "pending_gangs":
                        pulse["starvation"]["pending_gangs"],
                    "oldest_pending_age_cycles": max(
                        [o["age_cycles"] for o
                         in pulse["starvation"]["oldest"]], default=0),
                }
            # kai-repack slice: present only on cycles the trigger fired
            if result.repack:
                stats["repack"] = {
                    "feasible": result.repack["feasible"],
                    "target_gang": result.repack["target_gang"],
                    "migrations_executed":
                        result.repack["migrations_executed"],
                }
        return stats

    def _publish(self, stats: dict, request) -> None:
        """Finish a cycle's stats document with what its iteration's
        requests did (``requests``, ``gc_iteration``; the tracer's
        ``close_iteration``) and swap it in whole (``GET /healthz``;
        readers take the current binding with no lock, the dict is never
        mutated after publication).  Called by the handler of
        ``request`` once the reply is encoded, before it is written."""
        stats.update(self._tracer.close_iteration(request, stats.get("gc")))
        with self._publish_lock:
            prev = self._cycle_stats
            if prev is None or prev["cycles"] < stats["cycles"]:
                self._cycle_stats = stats

    def _intake_flush(self) -> None:
        """Degrade-to-sync valve (``intake_policy="sync"``): coalesce
        everything staged into the stored cluster under the commit lock
        so an overflowing lane empties.  Called by the router from the
        submitting handler thread, which holds NO lane locks here."""
        with self._state_lock:
            with self._tracer.span("coalesce"):
                self.intake.coalesce(self.cluster)

    def _serve_on_pool(self, request, client_address) -> None:
        """In place of ``ThreadingHTTPServer``'s new thread a request.

        glibc gives a new thread an arena of its own as long as the
        thread before it has not quite exited, up to eight arenas a
        core.  A patched cycle's 23 MB of host temporaries then came
        out of a heap that had to be mapped and faulted in first, about
        35 ms of a cycle of 150, until, a hundred threads on, new
        threads began to share the arenas that were there: whole runs
        of the idle cell sat on one of two levels by how that race went
        (PERF.md, PR 30).  A thread that stays keeps its arena, and the
        arena its heap (``runtime/malloc_tune.py``)."""
        self._pool.submit(self._serve, (time.perf_counter(), request),
                          client_address)

    def _serve(self, handed, client_address) -> None:
        """On a pool thread: note when the connection was handed over
        (``handed`` = (``perf_counter`` seconds, the connection); the
        request's root span starts there: what it waited for a free
        thread is its ``accept_wait``), then serve it.

        The body is ``ThreadingMixIn.process_request_thread``'s, here
        and not called, with as many locals: the handler runs exactly
        as deep in its thread's stack as it did.  CPython 3.12 keeps a
        thread's frames in 16 KiB chunks and maps and unmaps a chunk
        each time a call crosses into a new one and returns, so a frame
        more, or a few words more in one, at a thread's base moves
        which calls of a cycle (JAX's dispatch path, 60–80 frames down)
        pay that (PERF.md §6, PR 36)."""
        self._handed.at = handed[0]
        try:
            self._httpd.finish_request(handed[1], client_address)
        except Exception:  # noqa: BLE001 — as socketserver reports it
            self._httpd.handle_error(handed[1], client_address)
        finally:
            self._httpd.shutdown_request(handed[1])

    def start(self) -> "SchedulerServer":
        compile_cache.enable()
        # a serving process owns its allocator: cycles of one level, not
        # of whichever the process's earlier frees left it on
        malloc_tune.fix_thresholds()
        compile_watch.WATCHER.listen()
        self.scheduler.tracer.gc_watch = self._gc_watch.install()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()
        self.intake.start()
        if self.profiler is not None:
            self.profiler.start()
        return self

    def stop(self) -> None:
        if self.profiler is not None:
            self.profiler.stop()
        self.intake.stop()
        self._gc_watch.uninstall()
        self._httpd.shutdown()
        self._pool.shutdown(wait=False)
        if self._thread is not None:
            self._thread.join(timeout=5)
