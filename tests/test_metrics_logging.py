"""Metrics registry + leveled logging tests (ref
``pkg/scheduler/metrics/metrics.go``, ``pkg/scheduler/log/log.go``)."""
from kai_scheduler_tpu.utils.logging import InfraLogger
from kai_scheduler_tpu.utils.metrics import Registry


def test_counter_gauge_histogram_and_exposition():
    reg = Registry()
    c = reg.counter("kai_podgroups_scheduled_total", "x", ("action",))
    g = reg.gauge("kai_queue_fair_share", "y", ("queue", "resource"))
    h = reg.histogram("kai_e2e_scheduling_latency_seconds", "z",
                      buckets=(0.01, 0.1, 1.0))
    c.inc("allocate")
    c.inc("allocate", by=2)
    g.set("team-a", "accel", value=4.5)
    h.observe(value=0.05)
    h.observe(value=5.0)
    assert c.value("allocate") == 3
    assert g.value("team-a", "accel") == 4.5
    assert h.count() == 2
    text = reg.render()
    assert 'kai_podgroups_scheduled_total{action="allocate"} 3' in text
    assert 'kai_queue_fair_share{queue="team-a",resource="accel"} 4.5' in text
    assert 'kai_e2e_scheduling_latency_seconds_bucket{le="0.1"} 1' in text
    assert 'kai_e2e_scheduling_latency_seconds_bucket{le="+Inf"} 2' in text
    assert "# TYPE kai_queue_fair_share gauge" in text


def test_scheduler_cycle_populates_metrics():
    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.framework import metrics
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.runtime.cluster import Cluster

    nodes = [apis.Node("n0", apis.ResourceVec(8, 64, 256))]
    queues = [apis.Queue("q", accel=apis.QueueResource(quota=8))]
    groups = [apis.PodGroup("g", queue="q", min_member=1)]
    pods = [apis.Pod("p", "g", apis.ResourceVec(1, 1, 1))]
    cluster = Cluster.from_objects(nodes, queues, groups, pods)
    before = metrics.podgroups_scheduled.value("all")
    Scheduler().run_once(cluster)
    assert metrics.podgroups_scheduled.value("all") >= before + 1
    assert metrics.queue_fair_share.value("q", "accel") > 0
    assert metrics.e2e_latency.count() >= 1
    assert "kai_queue_fair_share" in metrics.registry.render()


def test_victim_wavefront_gauges_populated():
    """PR-5 observability: a cycle whose preempt action runs chunks
    must surface chunk count, lane occupancy, and the sparse-path
    fallback count through /metrics (``wavefront_stats`` rides the
    packed commit transfer)."""
    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.framework import metrics
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.runtime.cluster import Cluster

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
    res = Scheduler().run_once(cluster)
    assert len(res.evictions) > 0          # preempt actually fired
    assert metrics.victim_wavefront_chunks.value("preempt") >= 1
    occ = metrics.victim_wavefront_lane_occupancy.value("preempt")
    assert 0 < occ <= 1.0
    assert metrics.victim_wavefront_sparse_fallbacks.value("preempt") == 0
    assert (metrics.victim_wavefront_leftover_demotions.value("preempt")
            >= 0)
    # preempt's gate opened; reclaim had nobody to serve from one queue
    assert metrics.victim_action_skipped.value("preempt") == 0
    assert metrics.victim_action_skipped.value("reclaim") == 1
    assert res.victim_actions_skipped == {
        "reclaim": 1, "preempt": 0, "consolidation": 1}
    text = metrics.registry.render()
    for name in ("kai_victim_action_skipped",
                 "kai_victim_wavefront_chunks",
                 "kai_victim_wavefront_lane_occupancy",
                 "kai_victim_wavefront_sparse_fallbacks",
                 "kai_victim_wavefront_leftover_demotions"):
        assert name in text


def test_starvation_alarm_gauge_and_decision_event():
    """PR-9 kai-pulse: a gang pending past ``starvation_alarm_cycles``
    fires exactly one ``starved`` DecisionLog event carrying the
    FIT_REASONS text of its blocker, and the top-K
    ``kai_gang_starvation_age_cycles`` gauge tracks its pending age."""
    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.framework import metrics
    from kai_scheduler_tpu.framework.scheduler import (Scheduler,
                                                       SchedulerConfig)
    from kai_scheduler_tpu.framework.session import FIT_REASONS
    from kai_scheduler_tpu.runtime import events as gang_events
    from kai_scheduler_tpu.runtime.cluster import Cluster

    nodes = [apis.Node("n0", apis.ResourceVec(8, 64, 256))]
    queues = [apis.Queue("q", accel=apis.QueueResource(quota=8))]
    groups = [apis.PodGroup("hungry", queue="q", min_member=1)]
    # requests no node can ever satisfy — the gang starves forever
    pods = [apis.Pod("p0", "hungry", apis.ResourceVec(64, 1, 1))]
    cluster = Cluster.from_objects(nodes, queues, groups, pods)
    sched = Scheduler(SchedulerConfig(starvation_alarm_cycles=2))
    for _ in range(3):
        res = sched.run_once(cluster)
    assert res.bind_requests == []
    # gauge: the top-K table carries the gang at its current age
    assert metrics.gang_starvation_age.value("hungry") == 3.0
    # the /debug/cluster starvation family agrees
    starv = res.analytics["starvation"]
    assert starv["oldest"][0]["gang"] == "hungry"
    assert starv["oldest"][0]["age_cycles"] == 3
    assert starv["oldest"][0]["blocker"] == FIT_REASONS[1]
    # exactly ONE starved event, fired at the crossing, blocker text in
    # the detail
    evs = [e for e in sched.decisions.events(gang="hungry")
           if e["outcome"] == gang_events.OUTCOME_STARVED]
    assert len(evs) == 1
    assert FIT_REASONS[1] in evs[0]["detail"]
    assert "pending 2 cycles" in evs[0]["detail"]
    # the starved outcome is counted in the cycle summary it fired in
    assert any(
        c[3].get(gang_events.OUTCOME_STARVED) == 1
        for c in sched.decisions._cycles)
    text = metrics.registry.render()
    assert "kai_gang_starvation_age_cycles" in text
    assert "kai_cluster_fragmentation_score" in text


def test_infra_logger_verbosity_and_scope(capsys):
    log = InfraLogger(name="kai-test", verbosity=3)
    scoped = log.with_scope(session=7, action="allocate")
    scoped.V(2).infof("placed %d pods", 5)
    scoped.V(5).infof("should not appear")
    err = capsys.readouterr().err
    assert "placed 5 pods" in err
    assert "session=7" in err and "action=allocate" in err
    assert "should not appear" not in err


def test_render_consistent_under_concurrent_observation():
    """A /metrics scrape renders while the cycle thread observes.
    Pre-PR-4 the histogram renderer iterated the LIVE bucket lists and
    read ``_sums`` afterwards, so a scrape overlapping observes could
    expose sum != count * value — a torn, never-was state.  The locked
    snapshot pins sum == count exactly (every observed value is 1.0)."""
    import threading

    reg = Registry()
    hist = reg.histogram("h_seconds", "h", buckets=(0.5, 2.0))
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            hist.observe(value=1.0)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    torn = []
    try:
        for _ in range(400):
            text = reg.render()
            got_sum = got_count = None
            for line in text.splitlines():
                if line.startswith("h_seconds_sum"):
                    got_sum = float(line.rsplit(" ", 1)[1])
                elif line.startswith("h_seconds_count"):
                    got_count = float(line.rsplit(" ", 1)[1])
            if got_sum is not None and got_sum != got_count:
                torn.append((got_sum, got_count))
    finally:
        stop.set()
        t.join(timeout=10)
    assert not torn, f"torn expositions: {torn[:3]}"


def test_benchmark_reads_skipped_victim_actions_from_healthz():
    """``benchmark/layer_metrics/victim_actions_skipped.py`` over
    ``last_cycle`` documents as ``/healthz`` serves them: the flags of a
    cycle summed, then the mean over the window's cycles; a program
    from before the counter (no such key) gives nothing, not an error."""
    import importlib.util
    import os
    import sys
    import types
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "victim_actions_skipped", os.path.join(
                bench, "layer_metrics", "victim_actions_skipped.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(bench)

    def run_of(*healths):
        return types.SimpleNamespace(
            cycles=[{"health": h} for h in healths])

    closed = {"reclaim": 1, "preempt": 1, "consolidation": 1}
    opened = {"reclaim": 0, "preempt": 1, "consolidation": 1}
    assert reader.read(run_of(
        {"victim_actions_skipped": closed},
        {"victim_actions_skipped": opened})) == 2.5
    assert reader.read(run_of({"phase_seconds": {}}, {})) is None
    assert reader.read(run_of()) is None
