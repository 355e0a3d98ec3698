"""kai-intake tests (PR 12) — the async multi-lane mutation front end.

The load-bearing assertion is the DIFFERENTIAL: a randomized storm of
interleaved creates/deletes/updates (including same-key races, which
lane-sharding must confine to one lane) routed through the
IntakeRouter's queue → admit → stage → coalesce pipeline yields a hub
cluster, a hub journal (cursor-for-cursor), and a next scheduling
cycle's binds/evictions/DecisionLog **bit-identical** to the same
events applied sequentially through the classic synchronous path.
Plus: atomic shed (429, nothing journaled), degrade-to-sync,
vectorized admission rejections, the /intake + /debug/intake server
surfaces, and a storm-vs-scrapes endpoint hammer.
"""
import copy
import json
import random
import threading
import urllib.error
import urllib.request

import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.framework.scheduler import Scheduler, SchedulerConfig
from kai_scheduler_tpu.framework.server import SchedulerServer
from kai_scheduler_tpu.intake import apply as intake_apply
from kai_scheduler_tpu.intake.router import IntakeConfig, IntakeRouter
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.runtime.snapshot import dump_cluster
from kai_scheduler_tpu.state import make_cluster
from kai_scheduler_tpu.state.incremental import MutationJournal

pytestmark = pytest.mark.core

CURSOR_FIELDS = ("pods_dirty", "pods_added", "pods_removed",
                 "gangs_dirty", "gangs_added", "gangs_removed",
                 "nodes_dirty", "structural", "time_dirty")


def _cluster():
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=4, node_accel=8.0, num_gangs=4, tasks_per_gang=2)
    return Cluster.from_objects(nodes, queues, groups, pods, topo)


def _assert_cursor_equal(batch_a, batch_b):
    for field in CURSOR_FIELDS:
        va, vb = getattr(batch_a, field), getattr(batch_b, field)
        assert va == vb, (field, va, vb)


def _storm_deltas(rng: random.Random, n: int) -> list[dict]:
    """Interleaved creates / partial updates / deletes / clock ticks
    over a small key space, so same-key races (update-after-delete,
    delete-then-recreate, a group deleted under its pods and created
    again) occur by construction."""
    deltas = []
    for i in range(n):
        kind = rng.randrange(6)
        pid = rng.randrange(12)
        pod = f"storm-p{pid}"
        gang = f"storm-g{pid % 5}"
        if kind == 0:  # create (gang + pod)
            deltas.append({
                "pod_groups_upsert": [
                    {"name": gang, "queue": "queue-0-0", "min_member": 1}],
                "pods_upsert": [{
                    "name": pod, "group": gang,
                    "resources": {"accel": 1.0, "cpu": 1.0,
                                  "memory": 1.0}}]})
        elif kind == 1:  # partial update over whatever is stored
            deltas.append({"pods_upsert": [
                {"name": pod, "priority": rng.randrange(3)}]})
        elif kind == 2:  # delete (possibly of a never-created key)
            deltas.append({"pods_delete": [pod]})
        elif kind == 3:  # clock advance
            deltas.append({"now": float(i)})
        elif kind == 5:  # a gang finishes: group, and often its pod
            doc = {"pod_groups_delete": [gang]}
            if rng.randrange(3):
                doc["pods_delete"] = [pod]
            deltas.append(doc)
        else:  # mixed multi-collection document
            deltas.append({
                "pods_upsert": [{"name": pod, "group": gang}],
                "pods_delete": [f"storm-p{(pid + 1) % 12}"],
            })
    return deltas


# ---------------------------------------------------------------------------
# journal merge
# ---------------------------------------------------------------------------


def test_journal_merge_identical_to_sequential_marks():
    """MutationJournal.merge replays (kind, name) batches with the
    exact per-mark semantics — including the order-sensitive
    pod-readded and gang-readded structural escalations — under one
    lock acquisition."""
    j_seq, j_merge = MutationJournal(), MutationJournal()
    cur_seq, cur_merge = j_seq.register(), j_merge.register()
    ops = [("pod", "a"), ("pod_added", "b"), ("pod_removed", "c"),
           ("pod_added", "c"),           # removed-then-readded
           ("gang", "g"), ("gang_added", "h"), ("node", "n"),
           ("structural", "why"), ("time", ""), ("pod_added", "a"),
           ("gang_removed", "k"), ("gang_removed", "h"),
           ("gang_added", "h"),          # removed-then-readded
           ("gang_added", "m"), ("gang_removed", "m")]
    j_seq.mark_pod("a")
    j_seq.mark_pod_added("b")
    j_seq.mark_pod_removed("c")
    j_seq.mark_pod_added("c")
    j_seq.mark_gang("g")
    j_seq.mark_gang_added("h")
    j_seq.mark_node("n")
    j_seq.mark_structural("why")
    j_seq.mark_time()
    j_seq.mark_pod_added("a")
    j_seq.mark_gang_removed("k")
    j_seq.mark_gang_removed("h")
    j_seq.mark_gang_added("h")
    j_seq.mark_gang_added("m")
    j_seq.mark_gang_removed("m")
    j_merge.merge(ops)
    assert j_seq.generation == j_merge.generation == len(ops)
    got_seq, got_merge = cur_seq.consume(), cur_merge.consume()
    _assert_cursor_equal(got_seq, got_merge)
    assert got_merge.gangs_removed == {"k", "h", "m"}
    assert got_merge.gangs_added == ["h", "m"]
    assert got_merge.structural == ["pod-readded", "why", "pod-readded",
                                    "gang-readded"]

    with pytest.raises(ValueError, match="unknown journal mark"):
        j_merge.merge([("bogus", "x")])


# ---------------------------------------------------------------------------
# lane routing
# ---------------------------------------------------------------------------


def test_same_key_events_route_to_one_lane():
    router = IntakeRouter(IntakeConfig(lanes=4, lane_capacity=1000))
    ops = [("upsert", "pods", "same-pod", {"name": "same-pod",
                                           "group": "g"})] * 16
    router.submit_ops(ops)
    occupied = [s for s in router.debug_doc()["lane_stats"]
                if s["queued"] or s["staged"]]
    assert len(occupied) == 1 and occupied[0]["accepted"] == 16

    many = [("upsert", "pods", f"p{i}", {"name": f"p{i}", "group": "g"})
            for i in range(64)]
    router.submit_ops(many)
    spread = [s for s in router.debug_doc()["lane_stats"]
              if s["queued"] or s["staged"]]
    assert len(spread) >= 3  # 64 keys over 4 hash lanes


# ---------------------------------------------------------------------------
# THE differential: storm through lanes == sequential classic path
# ---------------------------------------------------------------------------


def test_storm_vs_sequential_bit_identical():
    """Randomized 4-lane storm (creates/deletes/updates/clock, same-key
    races included) → drain → coalesce must produce a hub cluster, a
    hub journal, and a next cycle's binds + evictions + DecisionLog
    bit-identical to applying the same deltas sequentially through the
    classic path."""
    c_classic = _cluster()
    c_intake = copy.deepcopy(c_classic)
    cur_classic = c_classic.journal.register()
    cur_intake = c_intake.journal.register()

    rng = random.Random(1234)
    deltas = _storm_deltas(rng, 400)

    for d in deltas:
        intake_apply.apply_cluster_delta(c_classic, d)

    router = IntakeRouter(IntakeConfig(lanes=4, lane_capacity=100000,
                                       batch=64)).start()
    try:
        for d in deltas:
            out = router.submit_delta(d)
            assert out["shed"] == 0
        assert router.drain_inline(timeout=30)
        summary = router.coalesce(c_intake)
    finally:
        router.stop()
    assert summary["events"] > 400  # multi-op documents decompose

    # hub journal: cursor-for-cursor and generation bit-identical
    _assert_cursor_equal(cur_classic.consume(), cur_intake.consume())
    assert c_classic.journal.generation == c_intake.journal.generation
    # hub document: object-for-object identical
    assert dump_cluster(c_classic) == dump_cluster(c_intake)

    # next cycle: binds / evictions / DecisionLog bit-identical
    s_classic, s_intake = Scheduler(), Scheduler()
    r_classic = s_classic.run_once(c_classic)
    r_intake = s_intake.run_once(c_intake)
    assert r_classic.bind_requests == r_intake.bind_requests
    assert r_classic.evictions == r_intake.evictions

    def last_events(sched):
        evs = sched.decisions.events(limit=100000)
        if not evs:
            return []
        last = max(e["cycle"] for e in evs)
        return sorted((e["gang"], e["queue"], e["outcome"], e["detail"])
                      for e in evs if e["cycle"] == last)

    assert last_events(s_classic) == last_events(s_intake)


def test_group_delete_storm_patches_identically_on_both_paths():
    """Gang turnover (groups deleted with their pods, new gangs with
    groups of their own) between two cycles: through the lanes and
    through the classic path the journals agree mark for mark, both
    snapshotters PATCH over the closed-up ledger (``verify_incremental``
    holds each to a fresh rebuild), and the cycles' results agree."""
    c_classic = _cluster()
    c_intake = copy.deepcopy(c_classic)
    cfg = SchedulerConfig(verify_incremental=True,
                          incremental_dirty_threshold=1.0)
    s_classic, s_intake = Scheduler(cfg), Scheduler(cfg)
    assert s_classic.run_once(c_classic).bind_requests \
        == s_intake.run_once(c_intake).bind_requests
    cur_classic = c_classic.journal.register()
    cur_intake = c_intake.journal.register()

    rng = random.Random(99)
    names = list(c_classic.pod_groups)
    rng.shuffle(names)
    deltas = []
    for k, gang in enumerate(names[:3]):
        pods = [p.name for p in c_classic.pods.values() if p.group == gang]
        deltas.append({"pod_groups_delete": [gang], "pods_delete": pods,
                       "bind_requests_delete": pods})
        deltas.append({
            "pod_groups_upsert": [{"name": f"turn-{k}",
                                   "queue": "queue-0-0", "min_member": 2}],
            "pods_upsert": [{"name": f"turn-{k}-{t}", "group": f"turn-{k}",
                             "resources": {"accel": 1.0, "cpu": 1.0,
                                           "memory": 1.0}}
                            for t in range(2)]})
        deltas.append({"now": float(k + 1)})

    for d in deltas:
        intake_apply.apply_cluster_delta(c_classic, d)
    router = IntakeRouter(IntakeConfig(lanes=4, lane_capacity=1000,
                                       batch=4)).start()
    try:
        for d in deltas:
            assert router.submit_delta(d)["shed"] == 0
        assert router.drain_inline(timeout=30)
        router.coalesce(c_intake)
    finally:
        router.stop()

    got_classic, got_intake = cur_classic.consume(), cur_intake.consume()
    _assert_cursor_equal(got_classic, got_intake)
    assert got_intake.gangs_removed == set(names[:3])
    assert not got_intake.structural
    assert dump_cluster(c_classic) == dump_cluster(c_intake)

    r_classic = s_classic.run_once(c_classic)
    r_intake = s_intake.run_once(c_intake)
    assert r_classic.bind_requests == r_intake.bind_requests
    assert len(r_intake.bind_requests) == 6
    assert r_classic.evictions == r_intake.evictions
    for sched in (s_classic, s_intake):
        last = sched._snapshotter.stats.last
        assert last["mode"] == "patched", last
        assert last["gangs_removed"] == 3 and last["pods_removed"] == 6


def test_concurrent_producers_storm_converges():
    """4 producer threads with disjoint key spaces hammer the router
    while workers drain; after coalesce every accepted event landed
    exactly once (per-key ordering is lane-FIFO by construction)."""
    cluster = Cluster()
    cluster.queues["q"] = apis.Queue("q")
    router = IntakeRouter(IntakeConfig(lanes=4, lane_capacity=200000,
                                       batch=256)).start()
    per_producer = 300

    def produce(tid: int):
        for i in range(per_producer):
            router.submit_delta({"pods_upsert": [{
                "name": f"t{tid}-p{i}", "group": f"t{tid}-g"}]})

    try:
        threads = [threading.Thread(target=produce, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert router.drain_inline(timeout=30)
        router.coalesce(cluster)
    finally:
        router.stop()
    assert len(cluster.pods) == 4 * per_producer
    health = router.health()
    assert health["accepted"] == health["coalesced_events"] \
        == 4 * per_producer
    assert health["shed"] == health["rejected"] == 0


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------


def test_shed_is_atomic_and_never_half_journals():
    """A lane-overflowing group is refused WHOLE: kai_intake_shed_total
    increments, nothing reaches the queue, nothing ever reaches the
    journal — no partial write."""
    from kai_scheduler_tpu.framework import metrics
    cluster = Cluster()
    cursor = cluster.journal.register()
    gen0 = cluster.journal.generation
    # no workers started: the queue can only fill
    router = IntakeRouter(IntakeConfig(lanes=2, lane_capacity=4))
    shed_before = sum(
        s["shed"] for s in router.debug_doc()["lane_stats"])
    assert shed_before == 0
    ops = [("upsert", "pods", "hot-key",
            {"name": "hot-key", "priority": i}) for i in range(6)]
    metric_before = metrics.intake_shed.value(
        str(router._lane_of("hot-key").idx))
    out = router.submit_ops(ops)
    assert (out["accepted"], out["shed"], out["total"]) == (0, 6, 6)
    # the shed echo names exactly the refused ops, for exact retries
    assert [o[2] for o in out["shed_ops"]] == ["hot-key"] * 6
    lane = router._lane_of("hot-key")
    assert metrics.intake_shed.value(str(lane.idx)) \
        == metric_before + 6
    # nothing queued, nothing staged, nothing journaled
    assert router.health()["queued"] == 0
    router.coalesce(cluster)
    assert cluster.journal.generation == gen0
    batch = cursor.consume()
    for field in CURSOR_FIELDS:
        assert not getattr(batch, field), field
    # a smaller group still fits afterwards
    assert router.submit_ops(ops[:3])["shed"] == 0


def test_all_or_nothing_submit_refuses_whole_request():
    """The HTTP boundary's contract: with all_or_nothing=True a shed
    refuses the WHOLE request even when other lanes had room — a 429
    means nothing was queued, so a client's blind full retry can never
    double-apply a partially accepted delta."""
    router = IntakeRouter(IntakeConfig(lanes=4, lane_capacity=4))
    router.submit_ops([("upsert", "pods", "hot",
                        {"name": "hot", "priority": i})
                       for i in range(4)])  # fill hot's lane
    assert router.health()["queued"] == 4
    ops = [("upsert", "pods", f"aon-{i}", {"name": f"aon-{i}"})
           for i in range(3)] + [("upsert", "pods", "hot",
                                  {"name": "hot"})]
    out = router.submit_ops(ops, all_or_nothing=True)
    assert out["accepted"] == 0 and out["shed"] == 4
    assert router.health()["queued"] == 4  # nothing new anywhere
    # shed blame lands on the saturated lane only — healthy lanes
    # collaterally refused with it must not be charged
    hot_idx = router._lane_of("hot").idx
    for s in router.debug_doc()["lane_stats"]:
        assert (s["shed"] > 0) == (s["lane"] == hot_idx), s
    # without the flag, the fitting lanes' slices are accepted and the
    # shed echo names exactly the refused portion
    out = router.submit_ops(ops)
    assert out["shed"] >= 1
    assert {o[2] for o in out["shed_ops"]} <= {"hot", "aon-0",
                                               "aon-1", "aon-2"}


def test_sync_policy_degrades_instead_of_shedding():
    """policy="sync" + an overflowing lane: the submitter quiesces the
    lanes, flushes a coalesce through the (caller-supplied) commit
    valve, and retries — every event lands, nothing sheds, the degrade
    is counted."""
    cluster = Cluster()
    flushes = []

    router = IntakeRouter(
        IntakeConfig(lanes=2, lane_capacity=8, policy="sync"),
        sync_flush=lambda: flushes.append(router.coalesce(cluster)))
    total = 0
    for i in range(10):
        out = router.submit_ops([
            ("upsert", "pods", f"sync-p{i}-{j}",
             {"name": f"sync-p{i}-{j}", "group": "g"})
            for j in range(6)])
        assert out["shed"] == 0
        total += out["accepted"]
    router.drain_inline(timeout=10)
    router.coalesce(cluster)
    assert total == 60 and len(cluster.pods) == 60
    assert flushes, "overflow never exercised the sync valve"
    health = router.health()
    assert health["sync_degrades"] == len(flushes)
    # a refusal the degrade path then DELIVERED is not a drop: both
    # shed surfaces (health totals and per-lane stats) must stay zero
    assert health["shed"] == 0
    assert all(s["shed"] == 0 for s in router.debug_doc()["lane_stats"])


# ---------------------------------------------------------------------------
# vectorized admission
# ---------------------------------------------------------------------------


def test_admission_rejects_bad_events_in_batch():
    cluster = Cluster()
    router = IntakeRouter(IntakeConfig(lanes=2, lane_capacity=100))
    bad = [
        ("upsert", "pods", "neg",
         {"name": "neg", "resources": {"cpu": -1.0}}),
        ("upsert", "pods", "nan",
         {"name": "nan", "resources": {"accel": float("nan")}}),
        ("upsert", "pods", "huge",
         {"name": "huge", "resources": {"memory": 1e12}}),
        ("upsert", "pods", "frac",
         {"name": "frac", "accel_portion": 1.5}),
        # one float32 ulp past the bounds: a single-precision sweep
        # would round these ONTO the cap / 1.0 and admit them
        ("upsert", "pods", "ulp-cap",
         {"name": "ulp-cap", "resources": {"cpu": 1.0e9 + 63.0}}),
        ("upsert", "pods", "ulp-frac",
         {"name": "ulp-frac", "accel_portion": 1.0 + 1e-8}),
        ("upsert", "frobs", "x", {"name": "x"}),
        ("upsert", "pods", "", {"group": "g"}),
        ("delete", "pods", "", ""),
        ("now", "", "", "not-a-clock"),
    ]
    good = [
        ("upsert", "pods", "ok-1",
         {"name": "ok-1", "group": "g",
          "resources": {"accel": 1.0, "cpu": 1.0, "memory": 1.0}}),
        ("upsert", "pods", "ok-2",
         {"name": "ok-2", "group": "g", "accel_portion": 0.5}),
        ("delete", "pods", "ok-1", "ok-1"),
        ("now", "", "", 7.5),
    ]
    out = router.submit_ops(bad + good)
    assert out["shed"] == 0
    router.drain_inline(timeout=10)
    router.coalesce(cluster)
    assert set(cluster.pods) == {"ok-2"}
    assert cluster.now == 7.5
    health = router.health()
    assert health["rejected"] == len(bad)
    assert health["coalesced_events"] == len(good)
    # the rejection ring surfaces reasons on /debug/intake
    reasons = {e["reason"]
               for s in router.debug_doc()["lane_stats"]
               for e in s["errors"]}
    assert any("out of range" in r for r in reasons)
    assert any("unknown collection" in r for r in reasons)


def test_oversized_int_resource_rejected_without_killing_worker():
    """A JSON integer wider than a double (1e400 as an int literal)
    must reject per-event — unguarded it raised OverflowError inside
    the batched np.asarray, killing the lane's drain worker forever
    and leaking the inflight count."""
    cluster = Cluster()
    router = IntakeRouter(IntakeConfig(lanes=1, lane_capacity=100)).start()
    try:
        out = router.submit_ops([
            ("upsert", "pods", "fat",
             {"name": "fat", "resources": {"cpu": 10 ** 400}}),
            ("upsert", "pods", "ok",
             {"name": "ok", "group": "g"}),
        ])
        assert out["shed"] == 0
        assert router.drain_inline(timeout=10)
        router.coalesce(cluster)
        assert set(cluster.pods) == {"ok"}
        assert router.health()["rejected"] == 1
        # the worker survived and the lane still drains
        assert router.debug_doc()["workers_alive"] == 1
        router.submit_ops([("upsert", "pods", "after",
                            {"name": "after", "group": "g"})])
        assert router.drain_inline(timeout=10)
        router.coalesce(cluster)
        assert "after" in cluster.pods
    finally:
        router.stop()


def test_mid_batch_failure_journals_applied_prefix():
    """An event that raises mid-delta must not discard the journal
    marks of events already applied: the store and the journal would
    silently diverge and the incremental snapshotter would serve a
    stale patch (the per-event marking this code replaced kept them
    consistent)."""
    cluster = Cluster()
    cursor = cluster.journal.register()
    with pytest.raises(TypeError):
        intake_apply.apply_cluster_delta(cluster, {"pods_upsert": [
            {"name": "good", "group": "g"},
            {"name": "bad", "resources": {"bogus_axis": 1.0}},
        ]})
    assert "good" in cluster.pods and "bad" not in cluster.pods
    batch = cursor.consume()
    assert batch.pods_added == ["good"]


def test_admitted_but_unappliable_event_skipped_not_fatal():
    """An event that passes the admission door check but fails object
    construction at coalesce must be skipped and counted — never abort
    the coalesce and destroy later-seq accepted events (clients were
    already acknowledged), and never fail the cycle.  Non-dict
    resources docs are now rejected at admission outright."""
    cluster = Cluster()
    router = IntakeRouter(IntakeConfig(lanes=1, lane_capacity=100))
    out = router.submit_ops([
        ("upsert", "pods", "good-a", {"name": "good-a", "group": "g"}),
        # passes admission (values numeric) but ResourceVec(**v)
        # rejects the unknown axis at apply time
        ("upsert", "pods", "poison",
         {"name": "poison", "resources": {"bogus_axis": 1.0}}),
        ("upsert", "pods", "good-b", {"name": "good-b", "group": "g"}),
    ])
    assert out["shed"] == 0
    summary = router.coalesce(cluster)
    assert summary["events"] == 2
    assert [s for s, _r in summary["apply_errors"]] == [out["total"] - 2]
    assert set(cluster.pods) == {"good-a", "good-b"}
    assert router.health()["apply_errors"] == 1
    # scalar-where-vector docs bounce at the door instead
    out = router.submit_ops([
        ("upsert", "pods", "scalar", {"name": "scalar", "resources": 5})])
    router.drain_inline(timeout=10)
    router.coalesce(cluster)
    assert "scalar" not in cluster.pods
    assert router.health()["rejected"] == 1


def test_coalesce_watermark_defers_post_boundary_events():
    """The coalesce window is cut by a seq watermark taken at entry:
    staged events at-or-after it are put back (in order) for the next
    window, so a submit racing the lane sweep can never have half its
    delta in this cycle and half in the next."""
    from kai_scheduler_tpu.intake.apply import IntakeEvent
    cluster = Cluster()
    router = IntakeRouter(IntakeConfig(lanes=1, lane_capacity=100))
    router.submit_ops([("upsert", "pods", "pre",
                        {"name": "pre", "group": "g"})])
    assert router.drain_inline(timeout=10)
    lane = router._lanes[0]
    # simulate a racing submit: an event stamped AT the watermark
    # (== router._seq) lands in staged — after "pre", preserving the
    # lane's seq-ascending staging order — before the sweep reads it
    lane.stage([IntakeEvent(router._seq, "upsert", "pods", "post",
                            {"name": "post", "group": "g"})], [], 0)
    summary = router.coalesce(cluster)
    assert summary["events"] == 1
    assert set(cluster.pods) == {"pre"}  # "post" deferred, not lost
    # once the seq clock passes it, the next boundary applies it
    router.submit_ops([("upsert", "pods", "later",
                        {"name": "later", "group": "g"})])
    summary = router.coalesce(cluster)
    assert summary["events"] == 2
    assert set(cluster.pods) == {"pre", "post", "later"}


def test_coalesce_predrains_submitted_backlog():
    """A cycle boundary must sweep everything submitted before it even
    if no worker has drained yet — otherwise one delta's events can
    split across cycles by worker timing (pods placed a cycle before
    their gang document exists, a state the sequential path can never
    produce)."""
    cluster = Cluster()
    router = IntakeRouter(IntakeConfig(lanes=4))  # workers NOT started
    router.submit_delta({
        "pod_groups_upsert": [{"name": "pg", "queue": "q"}],
        "pods_upsert": [{"name": f"pg-{i}", "group": "pg"}
                        for i in range(8)]})
    assert router.health()["staged"] == 0  # nothing drained yet
    summary = router.coalesce(cluster)
    assert summary["events"] == 9
    assert "pg" in cluster.pod_groups and len(cluster.pods) == 8


def test_concurrent_drainers_preserve_lane_fifo():
    """A lane's stage order must equal its pop order even when an
    inline helper (the sync degrade path) races the lane's worker —
    ``_Lane.drain_lock`` serializes whole drain rounds.  Without it, a
    later batch can stage before an earlier in-flight one and a
    coalesce landing in the gap applies same-key events out of order
    across windows."""
    router = IntakeRouter(IntakeConfig(lanes=1, lane_capacity=100000,
                                       batch=16))
    lane = router._lanes[0]
    for _round in range(5):
        router.submit_ops([
            ("upsert", "pods", "k", {"name": "k", "priority": i})
            for i in range(800)])
        threads = [threading.Thread(
            target=lambda: [router._drain_lane(lane)
                            for _ in range(80)]) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        router.drain_inline(timeout=10)
        seqs = [e.seq for e in lane.take_staged()]
        assert seqs == sorted(seqs)
        assert len(seqs) == 800


def test_fast_pod_construction_matches_generic_parser():
    """The storm-rate create path builds new plain pods directly
    (shared immutable defaults + fresh containers); it must stay
    value-identical to the generic default-doc + parser path on every
    eligible doc, bail (None) on irregular ones, and never alias a
    mutable container between pods."""
    rng = random.Random(7)
    for i in range(300):
        doc = {"name": f"fp{i}", "group": f"g{i % 5}"}
        if rng.random() < 0.6:
            doc["resources"] = {"accel": float(rng.randrange(4)),
                                "cpu": 2.0, "memory": 4.0}
        if rng.random() < 0.3:
            doc["priority"] = rng.randrange(5)
        if rng.random() < 0.2:
            doc["status"] = rng.choice([0, 1, 2])
        if rng.random() < 0.2:
            doc["accel_devices"] = [0, 1]
        if rng.random() < 0.2:
            doc["labels"] = {"tier": "x"}
        fast = intake_apply._fast_new_pod(doc)
        full = intake_apply._default_doc("pods")
        full.update(doc)
        slow = intake_apply._PARSERS["pods"](full)
        assert fast == slow, doc
    # irregular / unknown fields take the generic parser
    assert intake_apply._fast_new_pod(
        {"name": "x", "tolerations": []}) is None
    assert intake_apply._fast_new_pod({"name": "x", "bogus": 1}) is None
    # defaulted containers are per-object, never shared
    a = intake_apply._fast_new_pod({"name": "a", "group": "g"})
    b = intake_apply._fast_new_pod({"name": "b", "group": "g"})
    assert a.accel_devices is not b.accel_devices
    assert a.labels is not b.labels
    assert a.resources is not b.resources


# ---------------------------------------------------------------------------
# server surfaces
# ---------------------------------------------------------------------------


def _get_json(base, path):
    return json.load(urllib.request.urlopen(base + path, timeout=30))


def _post(base, path, doc):
    req = urllib.request.Request(
        base + path, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=60)


def test_intake_endpoint_shed_429_and_debug_doc():
    cfg = SchedulerConfig(intake_lanes=1, intake_lane_capacity=4)
    server = SchedulerServer(Cluster(), Scheduler(cfg))
    # only the HTTP thread runs — intake workers stay off so the lane
    # can only fill and the overflow path is deterministic
    server_thread = threading.Thread(
        target=server._httpd.serve_forever, daemon=True)
    server_thread.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        doc = {"pods_upsert": [{"name": f"e{i}", "group": "g"}
                               for i in range(3)]}
        with _post(base, "/intake", doc) as resp:
            assert resp.status == 200
            assert json.load(resp) == {"accepted": 3, "shed": 0,
                                       "total": 3}
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(base, "/intake", doc)
        assert err.value.code == 429
        assert json.load(err.value) == {"accepted": 0, "shed": 3,
                                        "total": 3}
        dbg = _get_json(base, "/debug/intake")
        assert dbg["policy"] == "shed" and dbg["lanes"] == 1
        assert dbg["queued"] == 3 and dbg["shed"] == 3
        health = _get_json(base, "/healthz")
        assert health["intake"]["shed"] == 3
        index = _get_json(base, "/debug")
        assert "/debug/intake" in {s["path"] for s in index["surfaces"]}
    finally:
        server._httpd.shutdown()
        server_thread.join(timeout=5)


def test_intake_coalesces_at_cycle_boundary_e2e():
    """POST /intake queues; POST /cycle/stored coalesces the staged
    events into the stored cluster and schedules them in the SAME
    request — the cycle boundary is the commit point."""
    server = SchedulerServer(_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        with _post(base, "/intake", {
                "pod_groups_upsert": [
                    {"name": "late-gang", "queue": "queue-0-0",
                     "min_member": 1}],
                "pods_upsert": [{
                    "name": "late-pod", "group": "late-gang",
                    "resources": {"accel": 1.0, "cpu": 1.0,
                                  "memory": 1.0}}]}) as resp:
            assert resp.status == 200
        with _post(base, "/cycle/stored", {}) as resp:
            cycle = json.load(resp)
        bound = {b["pod"] for b in cycle["bind_requests"]}
        assert "late-pod" in bound
        snap = _get_json(base, "/snapshot")
        assert "late-pod" in {p["name"] for p in snap["pods"]}
        assert _get_json(base, "/healthz")["intake"]["staged"] == 0
    finally:
        server.stop()


def test_endpoint_hammer_storm_vs_scrapes():
    """Concurrent storm POSTs vs /healthz, /debug/wire and
    /debug/intake scrapes and stored-cycle runs: every response is a
    complete document; scrapes never block behind intake lanes (they
    read only router/lane locks) and never tear."""
    import concurrent.futures

    server = SchedulerServer(_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"

    def post_storm(i):
        doc = {"pods_upsert": [
            {"name": f"hammer-{i}-{j}", "group": f"hammer-g{i}",
             "resources": {"accel": 1.0, "cpu": 1.0, "memory": 1.0}}
            for j in range(20)]}
        with _post(base, "/intake", doc) as resp:
            return resp.status

    def post_cycle(_i):
        with _post(base, "/cycle/stored", {}) as resp:
            return resp.status

    def get_intake(_i):
        doc = _get_json(base, "/debug/intake")
        assert {"lanes", "queued", "staged", "accepted", "shed",
                "rejected", "policy", "lane_stats",
                "workers_alive"} <= set(doc)
        assert len(doc["lane_stats"]) == doc["lanes"]
        return 200

    def get_health(_i):
        doc = _get_json(base, "/healthz")
        assert "intake" in doc
        return 200

    def get_wire(_i):
        doc = _get_json(base, "/debug/wire")
        assert {"cycles", "window", "residency", "compile"} <= set(doc)
        return 200

    try:
        post_cycle(0)  # compile before the storm
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futures = []
            for i in range(10):
                futures.append(pool.submit(post_storm, i))
                futures.append(pool.submit(get_intake, i))
                futures.append(pool.submit(get_health, i))
                futures.append(pool.submit(get_wire, i))
                if i % 5 == 0:
                    futures.append(pool.submit(post_cycle, i))
            statuses = [f.result() for f in futures]
        assert all(s == 200 for s in statuses)
        # a final boundary lands everything the storm queued
        post_cycle(99)
        snap = _get_json(base, "/snapshot")
        names = {p["name"] for p in snap["pods"]}
        assert {f"hammer-{i}-0" for i in range(10)} <= names
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


def test_conf_intake_keys_round_trip():
    from kai_scheduler_tpu import conf
    cfg = conf.load_config({"intake": {"lanes": 8, "laneCapacity": 1024,
                                       "policy": "sync", "batch": 128}})
    assert (cfg.intake_lanes, cfg.intake_lane_capacity,
            cfg.intake_policy, cfg.intake_batch) == (8, 1024, "sync", 128)
    doc = conf.effective_config_doc(cfg)
    assert doc["intake"] == {"lanes": 8, "laneCapacity": 1024,
                             "policy": "sync", "batch": 128}
    with pytest.raises(ValueError):
        IntakeConfig(policy="yolo")
    with pytest.raises(ValueError):
        IntakeConfig(lanes=0)


# ---------------------------------------------------------------------------
# the lanes, counted: who admitted an event, how long it waited (ISSUE 36)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("drained_first", [False, True])
def test_coalesce_says_who_admitted_and_how_long_events_waited(
        drained_first):
    """With the workers stopped every event of a submit is admitted by
    the coalesce's own pre-drain, on the cycle's thread: the cycle's
    ``lanes``, the ``coalesce.drain`` span and the router's totals all
    count it.  With the lanes run to quiescence first none is.  Either
    way an event's wait runs from the submit that offered it to the
    coalesce that took it."""
    import time

    from kai_scheduler_tpu.runtime.tracing import CycleTracer
    tracer = CycleTracer()
    cluster = Cluster()
    router = IntakeRouter(IntakeConfig(lanes=4), tracer=tracer)  # no workers
    with tracer.request("/intake") as posted:
        out = router.submit_delta({
            "pod_groups_upsert": [{"name": "pg", "queue": "q"}],
            "pods_upsert": [{"name": f"pg-{i}", "group": "pg"}
                            for i in range(8)]})
    assert out["accepted"] == 9
    submit = posted.root.children[0]
    assert submit.name == "intake.submit"
    assert submit.attrs["events"] == 9 and submit.attrs["shed"] == 0
    assert 1 <= submit.attrs["lanes"] <= 4
    if drained_first:
        assert router.drain_inline()
    time.sleep(0.05)
    with tracer.request("/cycle/stored") as req:
        with tracer.span("coalesce"):
            summary = router.coalesce(cluster)
    here, before = (0, 9) if drained_first else (9, 0)
    lanes = summary["lanes"]
    assert lanes["admitted_in_coalesce"] == here
    assert lanes["admitted_by_workers"] == before
    assert lanes["lane_wait_seconds"]["max"] >= 0.05
    assert 0.05 <= lanes["lane_wait_seconds"]["mean"] \
        <= lanes["lane_wait_seconds"]["max"]
    drain, take, apply_span = req.root.children[0].children
    assert [drain.name, take.name, apply_span.name] == [
        "coalesce.drain", "coalesce.take", "coalesce.apply"]
    assert drain.attrs["events"] == here
    assert take.attrs["events"] == 9
    assert apply_span.attrs == {"events": 9, "parsed_pods": 0, "errors": 0}
    # the totals on /debug/intake count on
    doc = router.debug_doc()
    assert doc["admitted_in_coalesce"] == here
    assert doc["admitted_by_workers"] == before
    assert doc["lane_wait_seconds"] == lanes["lane_wait_seconds"]
    # an empty coalesce takes nothing and waits for nothing
    empty = router.coalesce(cluster)["lanes"]
    assert empty == {"admitted_by_workers": 0, "admitted_in_coalesce": 0,
                     "lane_wait_seconds": {"mean": 0.0, "max": 0.0}}
    assert router.debug_doc()["admitted_in_coalesce"] == here
