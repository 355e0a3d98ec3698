"""kai-trace tests — cycle flight recorder, per-gang decision events,
and the debug endpoints (ISSUE 6 tentpole).

Covers the acceptance properties directly:

* the cycle's phase breakdown (snapshot / upload / solve_dispatch /
  device_wait / host_decode / commit) partitions the measured wall time
  (contiguous checkpoints on one clock — within 10% by construction);
* ``GET /debug/trace`` returns valid Chrome-trace JSON (loadable by
  ``json.loads``) whose events are strictly nested per lane;
* ``GET /debug/events?gang=`` answers "why is my job not running";
* the endpoints never serve torn documents under a concurrent cycle
  hammer (the kai-race cleanliness half lives in tests/test_analysis.py,
  which lints the new modules with the rest of the package).
"""
import json
import time
import urllib.request

import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.framework.scheduler import Scheduler, SchedulerConfig
from kai_scheduler_tpu.framework.server import SchedulerServer
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.runtime.events import DecisionLog, GangDecision
from kai_scheduler_tpu.runtime.tracing import CycleTracer
from served_round import churn_cluster as _churn_cluster
from served_round import post_round as _round
from served_round import start_server as _start_server

PHASES = {"snapshot", "upload", "solve_dispatch", "device_wait",
          "host_decode", "commit"}


def _small_cluster():
    nodes = [apis.Node("n0", apis.ResourceVec(8, 64, 256))]
    queues = [apis.Queue("q", accel=apis.QueueResource(quota=8))]
    groups = [apis.PodGroup("g", queue="q", min_member=1),
              apis.PodGroup("toobig", queue="q", min_member=1)]
    pods = [apis.Pod("p", "g", apis.ResourceVec(1, 1, 1)),
            apis.Pod("pb", "toobig", apis.ResourceVec(64, 1, 1))]
    return Cluster.from_objects(nodes, queues, groups, pods)


def _preempt_cluster():
    """One node saturated by a low-priority gang, a boosted pending
    gang — preempt must evict (mirrors test_metrics_logging)."""
    nodes = [apis.Node("n0", apis.ResourceVec(8, 64, 256))]
    queues = [apis.Queue("q", accel=apis.QueueResource(quota=8))]
    low = apis.PodGroup("low", queue="q", min_member=1, priority=1,
                        last_start_timestamp=0.0)
    high = apis.PodGroup("high", queue="q", min_member=2, priority=9,
                         creation_timestamp=1.0)
    pods = [apis.Pod(f"v{i}", "low", apis.ResourceVec(1, 1, 4),
                     status=apis.PodStatus.RUNNING, node="n0")
            for i in range(8)]
    pods += [apis.Pod(f"h{i}", "high", apis.ResourceVec(2, 1, 4),
                      creation_timestamp=1.0) for i in range(2)]
    cluster = Cluster.from_objects(nodes, queues, [low, high], pods)
    cluster.now = 100.0
    return cluster


def _assert_strictly_nested(doc: dict) -> int:
    """Chrome-trace "X" events must nest per (pid, tid) lane: any two
    either disjoint or one containing the other.  Returns the event
    count checked."""
    lanes: dict = {}
    for e in doc["traceEvents"]:
        if e.get("ph") != "X":
            continue
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(e)
        lanes.setdefault((e["pid"], e["tid"]), []).append(e)
    eps = 0.5  # us of float-rounding slack
    total = 0
    for evs in lanes.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in evs:
            while stack and e["ts"] >= (stack[-1]["ts"]
                                        + stack[-1]["dur"] - eps):
                stack.pop()
            if stack:
                parent = stack[-1]
                assert (e["ts"] + e["dur"]
                        <= parent["ts"] + parent["dur"] + eps), (
                    f"partial overlap: {e['name']} vs {parent['name']}")
            stack.append(e)
            total += 1
    return total


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------


def test_tracer_nesting_ring_and_detached_spans():
    tr = CycleTracer(retain_cycles=3)
    # a span outside any cycle records nothing (bench/CLI paths)
    with tr.span("orphan") as sp:
        sp.attrs["x"] = 1
    assert tr.last() == [] and tr.export_chrome()["traceEvents"]
    for i in range(5):
        with tr.cycle(n=i) as trace:
            with tr.span("a"):
                with tr.span("b", device_sync=True):
                    pass
            tr.add_span("c", trace.root.start, trace.root.start + 0.001,
                        leaves=2)
    ring = tr.last(10)
    assert len(ring) == 3  # bounded
    assert [t.cycle_id for t in ring] == [2, 3, 4]
    t = ring[-1]
    assert [s.name for s in t.root.children] == ["a", "c"]
    assert t.root.children[0].children[0].device_sync is True
    assert t.phase_seconds().keys() == {"a", "c"}
    doc = tr.export_chrome(cycles=2)
    json.loads(json.dumps(doc))  # fully JSON-serializable
    assert _assert_strictly_nested(doc) >= 6
    # the device-sync marker survives export
    marks = [e for e in doc["traceEvents"]
             if e.get("args", {}).get("device_sync")]
    assert marks and all(e["name"] == "b" for e in marks)


def test_tracer_thread_local_recording():
    """Two threads recording cycles concurrently never corrupt each
    other's span trees (the open trace is thread-local; only completed
    traces ring)."""
    import threading

    tr = CycleTracer(retain_cycles=64)
    errors = []

    def run(tag):
        try:
            for _ in range(20):
                with tr.cycle(tag=tag):
                    with tr.span(f"{tag}-outer"):
                        with tr.span(f"{tag}-inner"):
                            pass
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=run, args=(f"t{i}",))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    for trace in tr.last(64):
        tag = trace.root.attrs["tag"]
        assert [s.name for s in trace.root.children] == [f"{tag}-outer"]
        assert ([s.name for s in trace.root.children[0].children]
                == [f"{tag}-inner"])
    _assert_strictly_nested(tr.export_chrome())


def test_decision_log_bounds_and_query():
    log = DecisionLog(retain_cycles=2, max_events_per_cycle=3)
    evs = [GangDecision(gang=f"g{i}", queue="q", outcome="allocated")
           for i in range(5)]
    log.record_cycle(0, evs, dropped=1)
    log.record_cycle(1, [GangDecision(gang="g0", queue="q",
                                      outcome="fit-failure",
                                      detail="no node")])
    log.record_cycle(2, [])
    s = log.summary()
    assert s["cycle"] == 2 and s["events"] == 0
    got = log.events(gang="g0")
    # newest cycle first; cycle 0 fell off the 2-cycle ring
    assert [e["cycle"] for e in got] == [1]
    assert got[0]["outcome"] == "fit-failure"
    # the per-cycle cap adds to the producer's dropped count
    log.record_cycle(3, evs, dropped=2)
    assert log.summary()["dropped"] == 2 + 2 and log.summary()["events"] == 3


# ---------------------------------------------------------------------------
# the instrumented cycle
# ---------------------------------------------------------------------------


def test_phase_breakdown_partitions_wall_time():
    cluster = _small_cluster()
    sched = Scheduler()
    sched.run_once(cluster)           # compile
    res = sched.run_once(cluster)     # measured cycle
    assert set(res.phase_seconds) == PHASES
    total = sum(res.phase_seconds.values())
    # contiguous checkpoints on one clock: the phases partition the
    # cycle wall (well inside the 10% acceptance bar)
    assert total <= res.session_seconds * 1.001 + 1e-6
    assert total >= res.session_seconds * 0.9
    # legacy wall fields still line up with the phase view
    assert abs(res.open_seconds
               - (res.phase_seconds["snapshot"]
                  + res.phase_seconds["upload"])) < 1e-6
    assert res.commit_seconds >= res.phase_seconds["device_wait"]


def test_trace_and_result_phase_surfaces_agree():
    """The two phase-attribution surfaces — CycleResult.phase_seconds
    (contiguous checkpoints) and CycleTrace.phase_seconds() (span-
    derived, with the upload child promoted) — must agree per phase, so
    /debug/trace numbers and the metrics/healthz/bench numbers can be
    cross-checked.  Guards against a phase added to one surface only."""
    cluster = _small_cluster()
    sched = Scheduler()
    sched.run_once(cluster)           # compile
    cluster.tick()
    res = sched.run_once(cluster)     # warm cycle
    trace_phases = sched.tracer.last(1)[0].phase_seconds()
    for phase, secs in res.phase_seconds.items():
        got = trace_phases.get(phase, 0.0)
        # spans bracket the work tightly while checkpoints partition the
        # timeline, so tiny inter-phase slivers are tolerated
        assert abs(got - secs) < max(0.005, 0.05 * secs), (
            phase, got, secs)
    stray = set(trace_phases) - set(res.phase_seconds) - {"cycle"}
    assert not stray, f"span-only phases missing from the result: {stray}"


def test_cycle_trace_spans_and_chrome_export():
    cluster = _small_cluster()
    sched = Scheduler()
    sched.run_once(cluster)
    sched.run_once(cluster)
    traces = sched.tracer.last(2)
    assert len(traces) == 2
    names = {s.name for s in traces[-1].root.children}
    assert {"snapshot", "solve_dispatch", "device_wait", "host_decode",
            "commit"} <= names
    # the device-sync marker brackets the first blocking transfer
    dw = [s for s in traces[-1].root.children if s.name == "device_wait"]
    assert dw and dw[0].device_sync
    # snapshot span carries the journal-delta attribution
    snap = [s for s in traces[-1].root.children if s.name == "snapshot"]
    assert snap[0].attrs.get("mode") in ("patched", "full", "open")
    doc = sched.tracer.export_chrome()
    parsed = json.loads(json.dumps(doc))
    assert _assert_strictly_nested(parsed) >= 10
    evnames = {e["name"] for e in parsed["traceEvents"]
               if e.get("ph") == "X"}
    assert {"cycle", "snapshot", "solve_dispatch", "device_wait",
            "host_decode", "commit"} <= evnames


def test_cycle_phase_metrics_populated():
    from kai_scheduler_tpu.framework import metrics
    cluster = _small_cluster()
    before = metrics.cycle_phase_seconds.count("device_wait")
    Scheduler().run_once(cluster)
    assert metrics.cycle_phase_seconds.count("device_wait") == before + 1
    text = metrics.registry.render()
    assert "kai_cycle_phase_seconds" in text
    # profiler counters are registered even while idle (satellite)
    assert "kai_profiler_pushed_windows_total" in text
    assert "kai_profiler_push_errors_total" in text


def test_decision_events_fit_failure_and_allocated():
    cluster = _small_cluster()
    sched = Scheduler()
    sched.run_once(cluster)
    events = sched.decisions.events()
    by_gang = {e["gang"]: e for e in events}
    assert by_gang["g"]["outcome"] == "allocated"
    assert by_gang["toobig"]["outcome"] in ("fit-failure", "quota-gate")
    assert by_gang["toobig"]["detail"]  # FIT_REASONS text, not a code
    s = sched.decisions.summary()
    assert s["outcomes"].get("allocated", 0) >= 1
    assert sum(s["outcomes"].values()) == s["events"]


def test_decision_events_preempted_for():
    cluster = _preempt_cluster()
    sched = Scheduler()
    res = sched.run_once(cluster)
    assert res.evictions  # preempt actually fired
    events = sched.decisions.events(gang="low")
    assert events and events[0]["outcome"] == "preempted-for"
    high = sched.decisions.events(gang="high")
    assert high and high[0]["outcome"] == "allocated"


def test_incremental_snapshot_span_attribution():
    """The snapshot span records the journal-delta stats (mode, dirty
    rows, leaves/bytes uploaded) once the incremental path warms up."""
    cluster = _small_cluster()
    sched = Scheduler()
    sched.run_once(cluster)
    cluster.tick()  # journaled time advance -> patchable delta
    sched.run_once(cluster)
    snap = [s for s in sched.tracer.last(1)[0].root.children
            if s.name == "snapshot"][0]
    assert snap.attrs["mode"] in ("patched", "full")
    if snap.attrs["mode"] == "patched":
        assert {"leaves_shipped", "bytes_shipped",
                "fallback_reason"} <= set(snap.attrs)
        child_names = [c.name for c in snap.children]
        assert "snapshot.patch" in child_names


# ---------------------------------------------------------------------------
# server endpoints
# ---------------------------------------------------------------------------


def _get_json(base, path):
    return json.load(urllib.request.urlopen(f"{base}{path}", timeout=10))


def test_debug_trace_and_events_endpoints():
    server = SchedulerServer(_small_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        # before any cycle: valid, empty-ish documents
        doc = _get_json(base, "/debug/trace")
        assert "traceEvents" in doc
        req = urllib.request.Request(
            f"{base}/cycle/stored", data=b"{}",
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=30)
        doc = _get_json(base, "/debug/trace?cycles=1")
        assert _assert_strictly_nested(doc) >= 5
        names = {e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        assert {"cycle", "device_wait", "commit"} <= names
        ev = _get_json(base, "/debug/events?gang=toobig")
        assert ev["gang"] == "toobig"
        assert ev["events"][0]["outcome"] in ("fit-failure", "quota-gate")
        allg = _get_json(base, "/debug/events")
        assert allg["summary"]["events"] >= 2
        # /healthz folds the phase breakdown + decision summary in
        health = _get_json(base, "/healthz")
        stats = health["last_cycle"]
        assert set(stats["phase_seconds"]) == PHASES
        assert stats["decisions"]["events"] >= 2
        # ... and one flag per victim action: was its gate closed
        assert set(stats["victim_actions_skipped"]) == {
            "reclaim", "preempt", "consolidation"}
        assert set(stats["victim_actions_skipped"].values()) <= {0, 1}
    finally:
        server.stop()


def test_profile_cycle_reuses_tracer_phases():
    from kai_scheduler_tpu.framework.server import profile_cycle
    cluster = _small_cluster()
    sched = Scheduler()
    sched.run_once(cluster)  # compile outside the profiled cycle
    doc = profile_cycle(cluster, sched, top=5)
    assert set(doc["phases"]) == PHASES
    assert doc["total_seconds"] >= sum(doc["phases"].values()) * 0.9
    assert doc["hottest"]


def test_debug_endpoints_hammer_no_torn_documents():
    """Cycles run while /debug/trace, /debug/events and
    /debug/pprof/continuous are scraped concurrently: every response
    must be a complete, valid document (tracer rings only immutable
    completed traces; the decision log rings immutable tuples)."""
    import concurrent.futures

    sched = Scheduler(SchedulerConfig(profiler_sample_hz=50.0))
    server = SchedulerServer(_small_cluster(), sched).start()
    base = f"http://127.0.0.1:{server.port}"

    def post_cycle(_i):
        req = urllib.request.Request(
            f"{base}/cycle/stored", data=b"{}",
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=60).status

    def get_trace(_i):
        doc = _get_json(base, "/debug/trace")
        _assert_strictly_nested(doc)
        return 200

    def get_events(_i):
        doc = _get_json(base, "/debug/events")
        assert {"events", "summary"} <= set(doc)
        for e in doc["events"]:
            assert {"cycle", "gang", "outcome"} <= set(e)
        return 200

    def get_prof(_i):
        return urllib.request.urlopen(
            f"{base}/debug/pprof/continuous", timeout=60).status

    try:
        post_cycle(0)  # compile before the storm
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futures = []
            for i in range(8):
                futures.append(pool.submit(post_cycle, i))
                futures.append(pool.submit(get_trace, i))
                futures.append(pool.submit(get_events, i))
                futures.append(pool.submit(get_prof, i))
            statuses = [f.result() for f in futures]
        assert all(s == 200 for s in statuses)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# spans inside the snapshot, self times, one clock, GC (ISSUE 26)
# ---------------------------------------------------------------------------


def _find(span, name):
    """First span called ``name`` in the tree under ``span``, or None."""
    for child in span.children:
        if child.name == name:
            return child
        hit = _find(child, name)
        if hit is not None:
            return hit
    return None


def _full_then_patched():
    """(trace of a rebuilt cycle, trace of a patched cycle)."""
    cluster = _small_cluster()
    sched = Scheduler()
    sched.run_once(cluster)           # cold: rebuilds
    full = sched.tracer.last(1)[0]
    for _ in range(3):                # a time advance alone patches
        cluster.tick()
        sched.run_once(cluster)
        patched = sched.tracer.last(1)[0]
        if _find(patched.root, "snapshot.patch") is not None:
            break
    return full, patched


@pytest.mark.parametrize("which, span", [
    (0, "snapshot.full_build"), (1, "snapshot.patch")])
def test_self_seconds_partition_the_cycle(which, span):
    trace = _full_then_patched()[which]
    assert _find(trace.root, span) is not None
    selfs = trace.self_seconds()
    assert abs(sum(selfs.values()) - trace.root.seconds) < 1e-6
    assert all(v >= 0.0 for v in selfs.values())
    assert "cycle" in selfs and "cycle/snapshot" in selfs
    # every path starts at the root and names a span of the tree
    for path in selfs:
        node = trace.root
        for part in path.split("/")[1:]:
            node = next(c for c in node.children if c.name == part)


def test_full_build_spans_nested_in_order():
    full, _ = _full_then_patched()
    build = _find(full.root, "snapshot.full_build")
    assert build.attrs["fallback_reason"] == "cold"
    assert [c.name for c in build.children] == [
        "snapshot.lists", "snapshot.encode", "snapshot.transfer",
        "snapshot.ledgers"]
    sections = [c.name for c in _find(build, "snapshot.encode").children]
    # the filter evaluation has a section of its own, in the middle of
    # what used to be one: ``encode.rollups`` opens again after it
    # (repeats of one path add up in ``self_seconds``)
    assert sections == ["encode.vocab", "encode.nodes", "encode.queues",
                        "encode.gangs", "encode.running", "encode.rollups",
                        "encode.filters", "encode.rollups"]
    transfer = _find(build, "snapshot.transfer")
    assert transfer.attrs["bytes"] > 0 and transfer.attrs["leaves"] > 0
    # the rest of the snapshot phase and of commit have spans too
    assert _find(full.root, "snapshot.session") is not None
    commit = next(c for c in full.root.children if c.name == "commit")
    assert [c.name for c in commit.children] == [
        "writes", "status_updates", "commit.decisions", "commit.metrics"]


def test_patched_cycle_spans_nested_in_order():
    _, patched = _full_then_patched()
    patch = _find(patched.root, "snapshot.patch")
    assert patch is not None and patch.attrs["mode"] == "patched"
    assert [c.name for c in patch.children] == [
        "patch.journal", "patch.sweep", "patch.assemble"]
    # the ledger's columns are gathered twice (through ``order``, then
    # through ``run_rows``) and the tables built whole come before and
    # after what is derived by key: repeats of one path add up
    assert [c.name for c in _find(patch, "patch.assemble").children] == [
        "assemble.gather", "assemble.tables", "assemble.gather",
        "assemble.rederive", "assemble.tables", "assemble.index"]
    snap = next(c for c in patched.root.children if c.name == "snapshot")
    # the upload stays the snapshot phase's own child, after the patch
    assert [c.name for c in snap.children][:2] == ["snapshot.patch",
                                                   "upload"]
    assert _find(patched.root, "snapshot.full_build") is None


def test_empty_delta_touches_no_key():
    """The counters of what a patch derived again: a cycle whose only
    change is the clock feeds no key, and the patch span and
    ``stats.last`` (``/healthz`` ``last_cycle.snapshot``) say so (a key
    that is touched: ``tests/test_incremental.py``)."""
    cluster = _small_cluster()
    cluster.bind_pod("p", "n0")
    sched = Scheduler()
    sched.run_once(cluster)
    counters = ("touched_nodes", "touched_gangs", "touched_queues",
                "rederived_rows")
    for _ in range(4):   # until a cycle whose only change is the clock
        cluster.tick()
        sched.run_once(cluster)
        last = sched._snapshotter.stats.last
        if last["mode"] == "patched" and not last["dirty_pods"]:
            break
    assert last["mode"] == "patched"
    assert [last[k] for k in counters] == [0, 0, 0, 0]
    patch = _find(sched.tracer.last(1)[0].root, "snapshot.patch")
    assert [patch.attrs[k] for k in counters] == [0, 0, 0, 0]


@pytest.mark.parametrize("metric, floor", [
    ("patch_assemble_ms", 0.0), ("patch_sweep_ms", 0.0),
    ("assemble_rederived_rows", -1), ("store_marks", -1)])
def test_benchmark_reads_the_patch_from_healthz(metric, floor):
    """``benchmark/layer_metrics/<metric>.py`` over ``last_cycle`` as
    ``/healthz`` serves it (``span_self_seconds``, ``snapshot``): a
    patched cycle gives each a number; a program from before the spans'
    children and the counter were added gives nothing, not an error."""
    import importlib.util
    import os
    import sys
    import types
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            metric, os.path.join(bench, "layer_metrics", f"{metric}.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(bench)
    _, patched = _full_then_patched()
    patch = _find(patched.root, "snapshot.patch")
    health = {"span_self_seconds": patched.self_seconds(),
              "snapshot": dict(patch.attrs)}
    run = types.SimpleNamespace(cycles=[{"health": health}] * 2)
    value = reader.read(run)
    assert value is not None and value > floor
    if metric == "patch_assemble_ms":
        # the span with its children, and nothing of its siblings
        under = 1e3 * sum(
            secs for path, secs in health["span_self_seconds"].items()
            if "patch.assemble" in path.split("/"))
        assert value == pytest.approx(under)
        assert value < 1e3 * patch.seconds
    older = types.SimpleNamespace(cycles=[{"health": {
        "phase_seconds": {}, "snapshot": {"mode": "patched"}}}])
    assert reader.read(older) is None


def test_build_snapshot_without_a_tracer_records_nothing():
    from kai_scheduler_tpu.state import build_snapshot
    tr = CycleTracer()
    cluster = _small_cluster()
    with tr.cycle() as trace:
        build_snapshot(*cluster.snapshot_lists())
        assert trace.root.children == []
        build_snapshot(*cluster.snapshot_lists(), tracer=tr)
    assert [c.name for c in trace.root.children] == [
        "snapshot.encode", "snapshot.transfer"]


def test_each_action_has_a_device_scope():
    """Metadata only: the lowered five-action program names every
    action in its operations' ``op_name`` locations, and the scopes one
    level down where the device time is."""
    from kai_scheduler_tpu.framework.scheduler import _fused_pipeline
    from kai_scheduler_tpu.framework.session import Session
    cfg = SchedulerConfig()
    session = Session.open(*_preempt_cluster().snapshot_lists(),
                           config=cfg.session, now=100.0)
    sc = session.config
    text = _fused_pipeline.__kai_jit__.lower(
        session.state, session.state.queues.fair_share,
        actions=tuple(cfg.actions), num_levels=sc.num_levels,
        acfg=sc.allocate, vcfg=sc.victims,
        grace_s=sc.stale_grace_s).as_text(debug_info=True)
    locs = [ln for ln in text.splitlines() if ln.startswith("#loc")]
    for scope in ("allocate", "consolidation", "reclaim", "preempt",
                  "stalegangeviction", "placement_loop", "unit_tables",
                  "wavefront"):
        assert any(f"/{scope}/" in ln or f"/{scope}\"" in ln
                   for ln in locs), scope


CYCLE_PHASES = ["kai:cycle", "kai:snapshot", "kai:solve_dispatch",
                "kai:device_wait", "kai:host_decode", "kai:commit"]


def _run_once_twice(entered, start_recording):
    cluster = _small_cluster()
    sched = Scheduler()
    sched.run_once(cluster)           # compile outside the recording
    start_recording()
    cluster.tick()
    sched.run_once(cluster)
    trace = sched.tracer.last(1)[0]
    # a cycle with no request around it enters no request's annotation
    assert not [n for n in entered if n.startswith("kai:request")]
    assert abs(trace.wall_start_ns / 1e9 - time.time()) < 600.0
    return CYCLE_PHASES


def _served_rounds(entered, start_recording):
    """Two rounds of what the harness posts; the second patches."""
    server, base = _start_server(_churn_cluster())
    try:
        _round(base, 0)               # compile outside the recording
        start_recording()
        _round(base, 1)
    finally:
        server.stop()
    req = [n for n in entered if n.startswith("kai:request:")]
    assert req == ["kai:request:/cluster/delta", "kai:request:/intake",
                   "kai:request:/cycle/stored"]
    # every span of the requests, of the patch's blocks and of the
    # dispatch is in the capture, and the lanes' admission beside them
    for name in ("kai:http.read", "kai:body.parse", "kai:delta.apply",
                 "kai:intake.submit", "kai:coalesce", "kai:coalesce.drain",
                 "kai:coalesce.take", "kai:coalesce.apply", "kai:record",
                 "kai:reply.encode", "kai:reply.write", "kai:lane.admit",
                 "kai:journal.compact", "kai:journal.removed",
                 "kai:journal.gangs", "kai:journal.pods",
                 "kai:patch.sweep", "kai:dispatch.init_result"):
        assert name in entered, name
    # the request of the cycle encloses it: coalesce before the cycle's
    # root, the document and the reply after its last phase
    at = entered[entered.index("kai:request:/cycle/stored"):].index
    assert (at("kai:coalesce") < at("kai:cycle") < at("kai:commit")
            < at("kai:record") < at("kai:reply.encode")
            < at("kai:reply.write"))
    return CYCLE_PHASES


@pytest.mark.parametrize("drive", [_run_once_twice, _served_rounds])
def test_cycle_and_spans_enter_profiler_annotations(monkeypatch, drive):
    """One clock with the device: with ``TraceAnnotation`` replaced by a
    recorder (no profiler session: one a process, seconds to start), a
    cycle enters ``kai:cycle`` and its phases in order, a served round
    its three requests around them, and nothing under a name the
    benchmark harness filters by."""
    import jax.profiler

    entered = []

    class Recorder:
        def __init__(self, name, **_):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    want = drive(entered, lambda: monkeypatch.setattr(
        jax.profiler, "TraceAnnotation", Recorder))
    assert all(n.startswith("kai:") for n in entered)
    assert [n for n in entered if n in want] == want
    assert not {"churn_post", "cycle_post"} & set(entered)


def test_forced_full_collection_is_one_gc_pause_span():
    import gc

    from kai_scheduler_tpu.runtime.tracing import GcWatch
    watch = GcWatch().install()
    tr = CycleTracer(gc_watch=watch)
    gc.disable()                      # only the forced collection
    try:
        with tr.cycle() as trace:
            with tr.span("snapshot"):
                with tr.span("snapshot.encode"):
                    gc.collect()
    finally:
        gc.enable()
        watch.uninstall()
    assert trace.gc["collections"] == [0, 0, 1]
    assert trace.gc["pause_seconds"][2] > 0.0
    encode = _find(trace.root, "snapshot.encode")
    pauses = [c for c in encode.children if c.name == "gc.pause"]
    assert len(pauses) == 1 and "collected" in pauses[0].attrs
    assert abs(pauses[0].seconds - trace.gc["pause_seconds"][2]) < 1e-6
    assert sum(1 for p in trace.self_seconds() if "gc.pause" in p) == 1
    assert watch._on_gc not in gc.callbacks
    # without a watch a cycle books zeros
    with CycleTracer().cycle() as bare:
        pass
    assert bare.gc == {"collections": [0, 0, 0],
                       "pause_seconds": [0.0, 0.0, 0.0]}


def test_gc_pause_across_a_span_boundary_is_cut_there():
    """A full collection another thread set off can straddle two spans
    of the cycle: each part goes to the span it lies in, and self times
    still partition the cycle."""
    from kai_scheduler_tpu.runtime.tracing import GcWatch
    watch = GcWatch()
    tr = CycleTracer(gc_watch=watch)
    with tr.cycle() as trace:
        with tr.span("a") as a:
            pass
        with tr.span("b") as b:
            pass
        # as the hook would have booked it, from inside a to inside b
        mid_a = (a.start + a.end) / 2
        mid_b = (b.start + b.end) / 2
        watch.collections[2] += 1
        watch.seconds[2] += mid_b - mid_a
        watch.recent_full[0] = (mid_a, mid_b, 7)
    pieces = {path: secs for path, secs in trace.self_seconds().items()
              if path.endswith("gc.pause")}
    assert set(pieces) == {"cycle/a/gc.pause", "cycle/gc.pause",
                           "cycle/b/gc.pause"}
    assert abs(sum(pieces.values()) - (mid_b - mid_a)) < 1e-9
    assert abs(sum(trace.self_seconds().values())
               - trace.root.seconds) < 1e-9
    _assert_strictly_nested(tr.export_chrome())


def test_jit_miss_inside_a_cycle_is_a_compile_span():
    import jax
    import jax.numpy as jnp

    from kai_scheduler_tpu.runtime import compile_watch
    watcher = compile_watch.CompileWatcher()
    toy = watcher.wrap("toy", jax.jit(lambda x: x * 2 + 1))
    compile_watch.WATCHER.listen()
    before = compile_watch.WATCHER.stage_seconds()
    assert set(before) == {"trace_s", "lower_s", "backend_compile_s",
                           "cache_load_s"}
    tr = CycleTracer()
    with tr.cycle() as trace:
        with tr.span("solve_dispatch"):
            toy(jnp.arange(7))        # miss: traced, lowered, compiled
            toy(jnp.arange(7))        # hit: no span
    dispatch = trace.root.children[0]
    assert [c.name for c in dispatch.children] == ["compile:toy"]
    assert "signature" in dispatch.children[0].attrs
    after = compile_watch.WATCHER.stage_seconds()
    assert after["trace_s"] > before["trace_s"]
    assert all(after[k] >= before[k] for k in before)
    toy(jnp.arange(9))                # a miss outside any cycle: no-op


# ---------------------------------------------------------------------------
# a request is a trace; the collector wherever it runs (ISSUE 36)
# ---------------------------------------------------------------------------


def _bare_request(tr):
    with tr.request("/intake", framing="json") as req:
        with tr.span("http.read"):
            pass
        with tr.span("intake.submit"):
            with tr.span("coalesce"):
                pass
    return req


def _handed_over_request(tr):
    with tr.request("/cluster/delta",
                    start=time.perf_counter() - 0.005) as req:
        with tr.span("delta.apply"):
            pass
    assert req.root.children[0].name == "accept_wait"
    assert req.root.children[0].seconds >= 0.005
    return req


def _request_around_a_cycle(tr):
    with tr.request("/cycle/stored") as req:
        with tr.span("coalesce"):
            pass
        with tr.cycle():
            with tr.span("snapshot"):
                with tr.span("snapshot.patch"):
                    pass
        with tr.span("reply.write"):
            pass
    # the nested cycle counts whole: its own trace has its inside
    assert "request/cycle" in req.self_seconds()
    assert not [p for p in req.self_seconds() if "snapshot" in p]
    return req


def _published_request(tr):
    with tr.request("/cycle") as req:
        with tr.cycle() as trace:
            pass
        with tr.span("record"):
            doc = tr.close_iteration(req, trace.gc)
            mine = doc["requests"]["/cycle"]
            # as it stood: its spans partition what it had spent
            assert mine["count"] == 1
            assert abs(sum(mine["span_self_seconds"].values())
                       - mine["total_seconds"]) < 1e-9
        with tr.span("reply.write"):
            time.sleep(0.002)
    # what followed is the next iteration's, under its own key
    with tr.request("/cycle") as nxt:
        with tr.span("record"):
            after = tr.close_iteration(nxt, trace.gc)["requests"]["/cycle"]
    assert after["count"] == 1
    assert after["previous_reply_write_seconds"] >= 0.002
    return req


@pytest.mark.parametrize("make", [
    _bare_request, _handed_over_request, _request_around_a_cycle,
    _published_request])
def test_request_self_seconds_partition_its_root(make):
    tr = CycleTracer()
    req = make(tr)
    selfs = req.self_seconds()
    assert abs(sum(selfs.values()) - req.root.seconds) < 1e-9
    assert all(v >= 0.0 for v in selfs.values())
    assert all(p.split("/")[0] == "request" for p in selfs)
    assert req.root.attrs["path"] == req.path
    assert tr.last_requests(8)[0] is req
    _assert_strictly_nested(tr.export_chrome())


def _phases_of(tr, inside_request: bool):
    import contextlib
    around = (tr.request("/cycle/stored") if inside_request
              else contextlib.nullcontext())
    with around as req:
        with tr.cycle(tag="t") as trace:
            with tr.span("snapshot"):
                with tr.span("snapshot.patch"):
                    pass
                tr.add_span("upload", time.perf_counter(),
                            time.perf_counter())
            with tr.span("device_wait", device_sync=True):
                pass
    return req, trace


@pytest.mark.parametrize("inside_request", [False, True])
def test_cycle_is_the_same_trace_inside_a_request_and_without(
        inside_request):
    """A cycle nested in a request keeps its ``cycle/...`` paths and its
    phases to the digit, and closes into the cycle ring as the trace it
    is; with no request around it nothing is different."""
    tr = CycleTracer()
    req, trace = _phases_of(tr, inside_request)
    assert tr.last(1) == [trace] and trace.root.name == "cycle"
    assert set(trace.self_seconds()) == {
        "cycle", "cycle/snapshot", "cycle/snapshot/snapshot.patch",
        "cycle/snapshot/upload", "cycle/device_wait"}
    assert set(trace.phase_seconds()) == {"snapshot", "upload",
                                          "device_wait"}
    assert abs(sum(trace.self_seconds().values())
               - trace.root.seconds) < 1e-9
    if inside_request:
        # the same spans, read through the request's tree
        hung = [c for c in req.root.children if c.name == "cycle"]
        assert hung == [trace.root]
        from kai_scheduler_tpu.runtime.tracing import CycleTrace
        assert (CycleTrace(0, hung[0]).phase_seconds()
                == trace.phase_seconds())
        assert tr.last_requests(1) == [req]
        lanes = {e["args"]["name"] for e in tr.export_chrome()["traceEvents"]
                 if e.get("name") == "thread_name"}
        assert lanes == {"cycle-0", "request-0 /cycle/stored"}
    else:
        assert req is None and tr.last_requests(1) == []


def test_forced_collection_outside_the_cycle_is_the_requests():
    """A full collection inside a request but outside its cycle's root
    is a ``gc.pause`` under the span it fell in and ``in_requests`` of
    the iteration; the cycle's own ``gc`` does not hold it, and the
    three parts add up to what the watch counted."""
    import gc

    from kai_scheduler_tpu.runtime.tracing import GcWatch
    watch = GcWatch().install()
    tr = CycleTracer(gc_watch=watch)
    gc.disable()                      # only the forced collections
    try:
        gc.collect()                  # before any request: between
        with tr.request("/cycle/stored") as req:
            with tr.span("coalesce"):
                with tr.span("coalesce.apply"):
                    gc.collect()
            with tr.cycle() as trace:
                with tr.span("snapshot"):
                    gc.collect()
            with tr.span("record"):
                doc = tr.close_iteration(req, trace.gc)
        total = watch.read()
    finally:
        gc.enable()
        watch.uninstall()
    parts = doc["gc_iteration"]
    assert [parts[k]["collections"] for k in (
        "in_cycle", "in_requests", "between_requests")] == [
            [0, 0, 1], [0, 0, 1], [0, 0, 1]]
    assert trace.gc["collections"] == [0, 0, 1]
    for gen in range(3):
        assert sum(parts[k]["collections"][gen] for k in parts) \
            == total[0][gen]
        assert abs(sum(parts[k]["pause_seconds"][gen] for k in parts)
                   - total[1][gen]) < 1e-9
    selfs = doc["requests"]["/cycle/stored"]["span_self_seconds"]
    pause = selfs["request/coalesce/coalesce.apply/gc.pause"]
    assert abs(pause - parts["in_requests"]["pause_seconds"][2]) < 1e-9
    # the cycle's pause is the cycle's: one span, in its own tree
    assert [p for p in selfs if p.endswith("gc.pause")] == [
        "request/coalesce/coalesce.apply/gc.pause"]
    assert "cycle/snapshot/gc.pause" in trace.self_seconds()
    assert abs(sum(req.self_seconds().values()) - req.root.seconds) < 1e-9


# ---------------------------------------------------------------------------
# the benchmark's readers of the request traces (ISSUE 36)
# ---------------------------------------------------------------------------

#: the per-layer metrics ISSUE 36 added, with the cells their entry lists
#: (None: every cell)
_CHURN_CELLS = ["reclaim-10k.steady", "alloc-10k.churn", "pools-10k.churn",
                "kubeflow-10k.churn", "topology-10k.churn"]
REQUEST_METRICS = {
    "entry_request_gap_ms": None, "reply_encode_ms": None,
    "coalesce_drain_ms": _CHURN_CELLS, "coalesce_apply_ms": _CHURN_CELLS,
    "churn_parse_ms": _CHURN_CELLS, "delta_apply_ms": _CHURN_CELLS,
    "intake_submit_ms": _CHURN_CELLS, "lane_wait_ms": _CHURN_CELLS,
    "lane_admitted_in_coalesce": _CHURN_CELLS,
    "gc_outside_cycle_ms": None, "gc_full_outside_cycle": None,
    "patch_journal_ms": None}


@pytest.fixture(scope="module")
def served_healths():
    """``last_cycle`` of two served rounds at 64 nodes (the second
    patches), each read directly after its cycle's reply."""
    server, base = _start_server(_churn_cluster())
    try:
        return [_round(base, cyc) for cyc in range(2)]
    finally:
        server.stop()


@pytest.mark.parametrize("metric", sorted(REQUEST_METRICS))
def test_benchmark_reads_the_requests_from_healthz(metric, served_healths):
    """Every per-layer entry ISSUE 36 added to ``BENCHMARK.json`` has a
    reader file; over ``last_cycle`` of a served round it gives a
    number, over a document without ``requests`` (a program from before
    them) nothing, and not an error."""
    import importlib.util
    import os
    import sys
    import types
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        entry = next(m for m in json.load(fh)["per_layer"]
                     if m["name"] == metric)
    assert entry["moves"] == "cycle_ms" and entry["better"] == "lower"
    assert entry.get("workloads") == REQUEST_METRICS[metric]
    bench = os.path.join(root, "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            metric, os.path.join(bench, "layer_metrics", f"{metric}.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(bench)
    run = types.SimpleNamespace(cycles=[
        {"health": h, "cycle_post_s": h["requests"]["/cycle/stored"][
            "total_seconds"] + 0.001} for h in served_healths])
    value = reader.read(run)
    assert value is not None and value >= 0.0
    if metric == "entry_request_gap_ms":
        assert value == pytest.approx(1.0)
    if metric in ("reply_encode_ms", "churn_parse_ms", "delta_apply_ms",
                  "intake_submit_ms", "coalesce_apply_ms", "lane_wait_ms",
                  "patch_journal_ms"):
        assert value > 0.0
    older = types.SimpleNamespace(cycles=[{
        "cycle_post_s": 0.1, "health": {
            k: v for k, v in served_healths[-1].items()
            if k not in ("requests", "gc_iteration", "lanes")}}])
    assert reader.read(older) is None
