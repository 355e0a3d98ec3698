"""Sidecar/PluginServer tests — ref ``plugins/reflectjoborder``,
``plugins/snapshot`` HTTP endpoints and the snapshot-in/placements-out
wire boundary (SURVEY.md §7d)."""
import json
import time
import urllib.request

import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.framework.server import SchedulerServer, run_cycle_doc
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.runtime.snapshot import dump_cluster
from kai_scheduler_tpu.state import make_cluster


def _cluster():
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=4, node_accel=8.0, num_gangs=4, tasks_per_gang=2)
    return Cluster.from_objects(nodes, queues, groups, pods, topo)


def test_run_cycle_doc_round_trip():
    doc = dump_cluster(_cluster())
    out = run_cycle_doc(doc)
    assert len(out["bind_requests"]) == 8
    assert out["evictions"] == []
    # deterministic across calls on the same document
    assert run_cycle_doc(doc)["bind_requests"] == out["bind_requests"]


def test_http_endpoints():
    server = SchedulerServer(_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        order = json.load(urllib.request.urlopen(f"{base}/job-order"))
        assert len(order) == 4 and {"pod_group", "queue"} <= set(order[0])

        snap = json.load(urllib.request.urlopen(f"{base}/snapshot"))
        assert len(snap["nodes"]) == 4

        req = urllib.request.Request(
            f"{base}/cycle", data=json.dumps(snap).encode(),
            headers={"Content-Type": "application/json"})
        cycle = json.load(urllib.request.urlopen(req))
        assert len(cycle["bind_requests"]) == 8

        metrics_text = urllib.request.urlopen(
            f"{base}/metrics").read().decode()
        assert "kai_e2e_scheduling_latency_seconds" in metrics_text
    finally:
        server.stop()


class TestContinuousProfiler:
    """The Pyroscope analogue (ref cmd/scheduler/profiling/pyroscope.go
    + the pyroscope-address / profiler-rate flags, options.go:110-113):
    a wall-stack sampler with windowed retain + push."""

    def test_sampler_folds_and_rolls_windows(self):
        import threading
        import time as _t

        from kai_scheduler_tpu.runtime.profiling import ContinuousProfiler

        stop = threading.Event()

        def busy_beacon():
            while not stop.is_set():
                _t.sleep(0.001)

        t = threading.Thread(target=busy_beacon, daemon=True)
        t.start()
        prof = ContinuousProfiler(sample_hz=200, window_s=0.2).start()
        _t.sleep(0.7)
        prof.stop()
        stop.set()
        t.join(timeout=1)
        assert len(prof.windows) >= 2  # rolled at least twice
        body = prof.render()
        assert "busy_beacon" in body  # the beacon thread was sampled
        # folded format: "frame;frame;... count"
        line = next(ln for ln in body.splitlines()
                    if "busy_beacon" in ln)
        assert line.rsplit(" ", 1)[1].isdigit()

    def test_stop_start_cycle_resumes_sampling(self):
        """start() must clear the stop event a previous stop() left set,
        or the re-started sampler thread exits immediately and
        profiling silently stops (ADVICE r5)."""
        import threading
        import time as _t

        from kai_scheduler_tpu.runtime.profiling import ContinuousProfiler

        stop = threading.Event()

        def busy_beacon():
            while not stop.is_set():
                _t.sleep(0.001)

        t = threading.Thread(target=busy_beacon, daemon=True)
        t.start()
        prof = ContinuousProfiler(sample_hz=200, window_s=10.0).start()
        _t.sleep(0.2)
        prof.stop()
        assert prof._thread is None and prof._stop.is_set()
        prof.start()   # restart: must clear the event and sample again
        _t.sleep(0.3)
        assert prof._thread is not None and prof._thread.is_alive()
        prof.stop()
        stop.set()
        t.join(timeout=1)
        # the post-restart window saw the beacon thread
        assert "busy_beacon" in prof.render_folded(prof.windows[-1][2])

    def test_push_hits_ingest_endpoint(self):
        import http.server
        import threading
        import time as _t

        from kai_scheduler_tpu.runtime.profiling import ContinuousProfiler

        received = []

        class Sink(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                received.append((self.path, self.rfile.read(n)))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        httpd = http.server.HTTPServer(("127.0.0.1", 0), Sink)
        port = httpd.server_address[1]
        st = threading.Thread(target=httpd.serve_forever, daemon=True)
        st.start()
        try:
            prof = ContinuousProfiler(
                sample_hz=200, window_s=0.15,
                server_address=f"http://127.0.0.1:{port}",
                app_name="kai-test").start()
            _t.sleep(0.5)
            prof.stop()
            assert prof.pushed >= 1, (prof.pushed, prof.push_errors)
            path, body = received[0]
            assert "name=kai-test" in path and "format=folded" in path
            assert b";" in body or b" " in body
        finally:
            httpd.shutdown()

    def test_server_endpoint_serves_retained_windows(self):
        import dataclasses
        import json
        import time as _t
        import urllib.request

        from kai_scheduler_tpu.apis import types as apis
        from kai_scheduler_tpu.framework.scheduler import (Scheduler,
                                                           SchedulerConfig)
        from kai_scheduler_tpu.framework.server import SchedulerServer
        from kai_scheduler_tpu.runtime.cluster import Cluster

        cluster = Cluster.from_objects(
            [apis.Node("n0", apis.ResourceVec(1, 4, 16))],
            [apis.Queue("q", accel=apis.QueueResource(quota=1))], [], [])
        sched = Scheduler(SchedulerConfig(profiler_sample_hz=100.0))
        server = SchedulerServer(cluster, sched).start()
        try:
            _t.sleep(0.3)
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/debug/pprof/continuous",
                timeout=5).read().decode()
            assert "# window" in body
            # print-config surfaces the flags
            from kai_scheduler_tpu import conf
            doc = json.loads(conf.dumps_effective(sched.config))
            assert doc["profilerSampleHz"] == 100.0
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# Concurrency (PR 4): serialized handler state access, the /healthz
# per-cycle stats snapshot, and the profiler stop/start lifecycle
# ---------------------------------------------------------------------------


def test_healthz_serves_swapped_cycle_stats():
    server = SchedulerServer(_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        doc = json.load(urllib.request.urlopen(f"{base}/healthz"))
        assert doc["ok"] is True and doc["last_cycle"] is None
        req = urllib.request.Request(
            f"{base}/cycle/stored", data=b"{}",
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req)
        doc = json.load(urllib.request.urlopen(f"{base}/healthz"))
        stats = doc["last_cycle"]
        assert stats["cycles"] == 1
        assert stats["bind_requests"] == 8
        assert stats["total_seconds"] >= 0.0
    finally:
        server.stop()


def test_concurrent_deltas_and_reads_stay_consistent():
    """ThreadingHTTPServer runs handlers on per-request threads; deltas
    mutating the stored cluster must serialize against snapshot/metrics
    reads instead of tearing the document (pre-PR-4 a delta could
    resize dicts mid-GET)."""
    import concurrent.futures

    server = SchedulerServer(_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"

    def post_delta(i):
        body = json.dumps({"pods_upsert": [{
            "name": f"stress-{i}", "group": "gang-0"}]}).encode()
        req = urllib.request.Request(
            f"{base}/cluster/delta", data=body,
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=10).status

    def get_snapshot(_i):
        snap = json.load(urllib.request.urlopen(
            f"{base}/snapshot", timeout=10))
        # a torn document would lose invariants like this one
        assert {"nodes", "pods", "pod_groups"} <= set(snap)
        return 200

    def get_metrics(_i):
        urllib.request.urlopen(f"{base}/metrics", timeout=10).read()
        return 200

    try:
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futures = []
            for i in range(12):
                futures.append(pool.submit(post_delta, i))
                futures.append(pool.submit(get_snapshot, i))
                futures.append(pool.submit(get_metrics, i))
            statuses = [f.result() for f in futures]
        assert all(s == 200 for s in statuses)
        # every delta landed exactly once
        snap = json.load(urllib.request.urlopen(f"{base}/snapshot"))
        names = {p["name"] for p in snap["pods"]}
        assert {f"stress-{i}" for i in range(12)} <= names
    finally:
        server.stop()


def test_profiler_second_start_after_failed_join_raises():
    """stop() joins with a timeout; if the sampler refuses to die, a
    second start() must raise instead of leaking a second daemon
    sampler writing into the same windows (PR-4 satellite)."""
    import threading
    import time as _t

    import pytest as _pytest

    from kai_scheduler_tpu.runtime.profiling import ContinuousProfiler

    prof = ContinuousProfiler(sample_hz=50, window_s=10.0)
    release = threading.Event()

    class _Stubborn(threading.Thread):
        """Stands in for a wedged sampler: ignores the stop event until
        released."""

        def run(self):
            release.wait(10.0)

    stub = _Stubborn(daemon=True)
    stub.start()
    prof._thread = stub
    prof.stop(timeout=0.05)  # join times out — sampler still alive
    assert prof._thread is stub  # the straggler is NOT forgotten
    with _pytest.raises(RuntimeError, match="has not stopped"):
        prof.start()
    release.set()
    stub.join(timeout=5)
    # once the straggler exits, start() recovers cleanly
    prof.start()
    _t.sleep(0.05)
    assert prof._thread is not None and prof._thread.is_alive()
    prof.stop()
    assert prof._thread is None


# ---------------------------------------------------------------------------
# the cycle from the inside, on /healthz (ISSUE 26; docs/TRACING.md)
# ---------------------------------------------------------------------------


def _post_cycle(base, framing="json"):
    headers = {"Content-Type": "application/json" if framing == "json"
               else "application/x-protobuf"}
    req = urllib.request.Request(
        f"{base}/cycle/stored", data=b"{}" if framing == "json" else b"",
        headers=headers)
    urllib.request.urlopen(req, timeout=120).read()
    return json.load(urllib.request.urlopen(f"{base}/healthz"))[
        "last_cycle"]


def test_healthz_carries_the_cycle_from_the_inside():
    server = SchedulerServer(_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        first = _post_cycle(base)
        second = _post_cycle(base, framing="proto")
    finally:
        server.stop()
    for stats in (first, second):
        assert {"span_self_seconds", "snapshot", "gc", "entry_seconds",
                "startup"} <= set(stats)
        selfs = stats["span_self_seconds"]
        assert "cycle/snapshot" in selfs and "cycle/device_wait" in selfs
        # the spans partition the cycle, and the cycle is the session
        assert abs(sum(selfs.values()) - stats["total_seconds"]) < 1e-3
        assert stats["snapshot"]["mode"] in ("full", "patched")
        assert "fallback_reason" in stats["snapshot"]
        assert len(stats["gc"]["collections"]) == 3
        assert len(stats["gc"]["pause_seconds"]) == 3
        assert set(stats["entry_seconds"]) == {"lock_wait", "coalesce"}
        assert all(v >= 0.0 for v in stats["entry_seconds"].values())
    # start-up is the first cycle's, frozen; the compile stages count on
    assert first["startup"]["phase_seconds"] == first["phase_seconds"]
    assert second["startup"]["phase_seconds"] == first["phase_seconds"]
    assert second["phase_seconds"] != first["phase_seconds"]
    stages = ("trace_s", "lower_s", "backend_compile_s", "cache_load_s")
    assert set(first["startup"]) == {"phase_seconds", *stages}
    assert all(second["startup"][k] >= first["startup"][k] >= 0.0
               for k in stages)


def test_server_installs_and_removes_the_gc_hook():
    import gc
    before = len(gc.callbacks)
    server = SchedulerServer(_cluster()).start()
    try:
        assert len(gc.callbacks) == before + 1
        assert server.scheduler.tracer.gc_watch is not None
    finally:
        server.stop()
    assert len(gc.callbacks) == before


def test_metrics_offer_no_series_that_nothing_feeds():
    from kai_scheduler_tpu.framework import metrics
    text = metrics.registry.render()
    for dead in ("kai_plugin_scheduling_latency_seconds",
                 "kai_pod_scheduling_latency_seconds",
                 "kai_scenarios_simulated_total",
                 "kai_scenarios_filtered_total",
                 "kai_preemption_attempts_total"):
        assert dead not in text
    assert "kai_e2e_scheduling_latency_seconds" in text


# ---------------------------------------------------------------------------
# gang turnover, served: group deletes patch (ISSUE 27)
# ---------------------------------------------------------------------------


def _post_json(base, path, doc):
    req = urllib.request.Request(
        base + path, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    return json.load(urllib.request.urlopen(req, timeout=300))


def test_served_gang_turnover_patches_over_deleted_groups():
    """What a shim sends between cycles of a busy cluster, at 256 nodes
    half full: finished gangs' pods, bind requests and pod GROUPS
    deleted through ``/cluster/delta``, new gangs with groups of their
    own through ``/intake``.  Every cycle after the first patches its
    snapshot (``verify_incremental`` holds each to a fresh rebuild) and
    says on ``/healthz`` how many gang rows it closed up."""
    from kai_scheduler_tpu.framework.scheduler import (Scheduler,
                                                       SchedulerConfig)
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=256, node_accel=8.0, num_gangs=128, tasks_per_gang=8,
        running_fraction=1.0)
    cluster = Cluster.from_objects(nodes, queues, groups, pods, topo)
    placed = {g.name: [p.name for p in pods if p.group == g.name]
              for g in groups}
    bound_by_commit: set = set()
    server = SchedulerServer(
        cluster, Scheduler(SchedulerConfig(verify_incremental=True))
    ).start()
    base = f"http://127.0.0.1:{server.port}"
    snaps = []
    try:
        for cyc in range(5):
            # three of the first hour and the newest: from the second
            # cycle on that is a gang the last commit bound
            finished = list(placed)[cyc::17][:3] + [list(placed)[-1]]
            gone = [p for g in finished for p in placed.pop(g)]
            _post_json(base, "/cluster/delta", {
                "now": float(cyc + 1), "pods_delete": gone,
                "pod_groups_delete": finished,
                "bind_requests_delete": [
                    p for p in gone if p in bound_by_commit]})
            names = [f"job-{cyc}-{i}" for i in range(4)]
            _post_json(base, "/intake", {
                "pod_groups_upsert": [
                    {"name": n, "queue": "queue-0-0", "min_member": 8}
                    for n in names],
                "pods_upsert": [
                    {"name": f"{n}-{t}", "group": n,
                     "resources": {"accel": 1.0, "cpu": 1.0,
                                   "memory": 4.0}}
                    for n in names for t in range(8)]})
            commit = _post_json(base, "/cycle/stored", {})
            assert len(commit["bind_requests"]) == 32, cyc
            assert commit["evictions"] == []
            for br in commit["bind_requests"]:
                placed.setdefault(br["pod"].rsplit("-", 1)[0],
                                  []).append(br["pod"])
                bound_by_commit.add(br["pod"])
            snaps.append(json.load(urllib.request.urlopen(
                f"{base}/healthz"))["last_cycle"]["snapshot"])
        stored = json.load(urllib.request.urlopen(f"{base}/snapshot"))
    finally:
        server.stop()
    assert snaps[0]["mode"] == "full"  # the cold build
    for cyc, snap in enumerate(snaps[1:], start=1):
        assert snap["mode"] == "patched", (cyc, snap)
        assert snap["gangs_removed"] == 4 and snap["pods_removed"] == 32
        # the four arrivals and the three gangs of the last commit's
        # four that are still there: of the rows that only moved, none
        assert snap["dirty_gangs"] == 7
    # later cycles finished gangs that earlier commits had bound
    assert "job-0-3" not in placed and bound_by_commit
    assert {g["name"] for g in stored["pod_groups"]} == set(placed)
    assert len(stored["pod_groups"]) == 128


def test_fixed_thresholds_keep_a_freed_array_mapped():
    """What ``SchedulerServer.start`` sets: a large array freed and
    allocated again comes from the heap that is already mapped, so the
    second one faults in (almost) no page — the patched cycle's arrays,
    every cycle on one level."""
    import resource

    import numpy as np
    import pytest

    from kai_scheduler_tpu.runtime import malloc_tune
    if not malloc_tune.fix_thresholds():
        pytest.skip("no glibc mallopt in this process")
    assert malloc_tune.fix_thresholds()  # again: the same answer

    def faults_of_one_array():
        before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        np.ones((24 << 20,), np.uint8)  # under the 32 MiB threshold
        return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before

    faults_of_one_array()
    pages = (24 << 20) // resource.getpagesize()
    # (read here: 0 with the thresholds fixed, 480 of 6 144 without)
    assert faults_of_one_array() < pages // 64


def test_server_start_fixes_the_thresholds(monkeypatch):
    from kai_scheduler_tpu.runtime import malloc_tune
    calls = []
    monkeypatch.setattr(malloc_tune, "fix_thresholds",
                        lambda: calls.append(1) or True)
    server = SchedulerServer(_cluster()).start()
    server.stop()
    assert calls == [1]


def test_requests_run_on_threads_that_stay():
    """A request gets no thread of its own: however many arrive, one
    after another or at once, ``HANDLER_THREADS`` threads that live as
    long as the server answer them all, and go when it stops."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from kai_scheduler_tpu.framework import server as server_mod

    def handlers():
        return {t.ident for t in threading.enumerate()
                if t.name.startswith("kai-http")}

    before = handlers()
    server = SchedulerServer(_cluster()).start()
    url = f"http://127.0.0.1:{server.port}/healthz"
    try:
        for _ in range(3 * server_mod.HANDLER_THREADS):
            assert urllib.request.urlopen(url).status == 200
        serial = handlers() - before
        assert 1 <= len(serial) <= server_mod.HANDLER_THREADS
        with ThreadPoolExecutor(4 * server_mod.HANDLER_THREADS) as clients:
            statuses = list(clients.map(
                lambda _: urllib.request.urlopen(url).status,
                range(8 * server_mod.HANDLER_THREADS)))
        assert statuses == [200] * (8 * server_mod.HANDLER_THREADS)
        mine = handlers() - before
        assert serial <= mine
        assert len(mine) <= server_mod.HANDLER_THREADS
    finally:
        server.stop()
    deadline = time.monotonic() + 5
    while handlers() - before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not handlers() - before


# ---------------------------------------------------------------------------
# an iteration is three requests (ISSUE 36; docs/TRACING.md)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("framing", ["json", "protobuf"])
def test_round_leaves_three_requests_on_healthz(framing):
    """One ``/cluster/delta`` + ``/intake`` + ``/cycle/stored`` round:
    the document a client reads directly after the cycle's reply is that
    cycle's, whole, with the three requests of its iteration."""
    from served_round import (PATHS, closed_requests, post_round,
                              start_server)
    server, base = start_server()
    try:
        docs = [post_round(base, cyc, framing) for cyc in range(2)]
        ring = closed_requests(server, 6)
    finally:
        server.stop()
    for n, doc in enumerate(docs, start=1):
        assert doc["cycles"] == n     # this cycle's, not the one before
        assert set(doc["requests"]) == set(PATHS)
        for path, req in doc["requests"].items():
            assert req["count"] == 1, path
            # (an empty protobuf CommitSet is no byte at all)
            assert req["bytes_in"] >= 0 and req["bytes_out"] >= 0
            assert abs(sum(req["span_self_seconds"].values())
                       - req["total_seconds"]) < 1e-6, path
        mine = doc["requests"]["/cycle/stored"]
        # published before the reply left: its write is the next one's
        assert "request/reply.write" not in mine["span_self_seconds"]
        assert ("previous_reply_write_seconds" in mine) == (n == 2)
        assert mine["total_seconds"] >= doc["total_seconds"]
        assert mine["bytes_out"] > 0 or framing == "protobuf"
        # entry_seconds reads the request's spans
        under = sum(v for p, v in mine["span_self_seconds"].items()
                    if "coalesce" in p.split("/"))
        assert doc["entry_seconds"]["coalesce"] == pytest.approx(under)
        assert doc["entry_seconds"]["lock_wait"] == pytest.approx(
            mine["span_self_seconds"]["request/lock_wait"])
        assert doc["lanes"]["admitted_by_workers"] \
            + doc["lanes"]["admitted_in_coalesce"] == 18
        assert doc["lanes"]["lane_wait_seconds"]["max"] > 0.0
        # what point 6 took off the document
        assert "commit_seconds" not in doc and "open_seconds" not in doc
    assert [r.path for r in ring] == list(PATHS) * 2
    framings = {r.root.attrs["framing"] for r in ring
                if r.path != "/intake"}
    assert framings == {framing}
    assert all(r.root.attrs["status"] == 200 for r in ring)
    stored = ring[-1]
    assert [c.name for c in stored.root.children
            if c.name != "accept_wait"] == [
        "http.read", "lock_wait", "coalesce", "cycle", "record",
        "reply.encode", "record", "reply.write"]
    coalesce = next(c for c in stored.root.children
                    if c.name == "coalesce")
    assert [c.name for c in coalesce.children] == [
        "coalesce.drain", "coalesce.take", "coalesce.apply"]
    assert coalesce.children[2].attrs["events"] == 18
    assert coalesce.seconds == pytest.approx(
        docs[-1]["entry_seconds"]["coalesce"])
    delta = ring[-3]
    apply_span = next(c for c in delta.root.children
                      if c.name == "delta.apply")
    assert apply_span.attrs["pods_delete"] == 8
    assert apply_span.attrs["pod_groups_delete"] == 1


@pytest.mark.parametrize("where, part", [
    ("coalesce.apply", "in_requests"), ("cycle", "in_cycle"),
    ("client", "between_requests")])
def test_forced_collection_lands_in_its_part_of_the_iteration(
        monkeypatch, where, part):
    """A full collection forced inside the coalesce's apply, inside the
    cycle, or by the client between two requests: ``gc_iteration`` books
    it in that part alone, the three parts add up to what the watch
    counted between two publications, and the one in the coalesce is a
    ``gc.pause`` under ``coalesce.apply``, not in ``last_cycle.gc``."""
    import gc

    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.intake import apply as intake_apply
    from served_round import closed_requests, post_round, start_server
    server, base = start_server()
    gc.disable()                      # only the forced collection
    try:
        post_round(base, 0)           # compiles; lanes and pool are up
        assert len(closed_requests(server, 3)) == 3
        apply_events = intake_apply.apply_events
        record_metrics = Scheduler._record_metrics

        def collecting(fn):
            def wrapped(*args, **kwargs):
                # the coalesce hands its applier an error list; the
                # classic path of ``/cluster/delta`` does not
                if fn is record_metrics or kwargs.get("errors") is not None:
                    gc.collect()
                return fn(*args, **kwargs)
            return wrapped

        if where == "coalesce.apply":
            monkeypatch.setattr(intake_apply, "apply_events",
                                collecting(apply_events))
        elif where == "cycle":
            monkeypatch.setattr(Scheduler, "_record_metrics",
                                collecting(record_metrics))
        # nothing collects on its own, so the watch's totals stand
        # still from the first round's publication to here
        before = server._gc_watch.read()
        first = json.load(urllib.request.urlopen(
            f"{base}/healthz"))["last_cycle"]
        if where == "client":
            gc.collect()
        doc = post_round(base, 1)
        after = server._gc_watch.read()
    finally:
        gc.enable()
        server.stop()
    assert first["cycles"] == 1 and doc["cycles"] == 2
    parts = doc["gc_iteration"]
    assert set(parts) == {"in_cycle", "in_requests", "between_requests"}
    for name, booked in parts.items():
        assert booked["collections"] == (
            [0, 0, 1] if name == part else [0, 0, 0]), name
    assert parts["in_cycle"] == doc["gc"]
    for gen in range(3):
        assert sum(p["collections"][gen] for p in parts.values()) \
            == after[0][gen] - before[0][gen]
        assert sum(p["pause_seconds"][gen] for p in parts.values()) \
            == pytest.approx(after[1][gen] - before[1][gen])
    selfs = doc["requests"]["/cycle/stored"]["span_self_seconds"]
    pause = "request/coalesce/coalesce.apply/gc.pause"
    if where == "coalesce.apply":
        assert selfs[pause] == pytest.approx(
            parts["in_requests"]["pause_seconds"][2])
        assert doc["gc"]["collections"] == [0, 0, 0]
        assert not [p for p in doc["span_self_seconds"]
                    if p.endswith("gc.pause")]
    else:
        assert pause not in selfs
