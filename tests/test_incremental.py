"""Incremental snapshot engine tests — journaled dirty-set refresh
(``state/incremental.py``).

The load-bearing property: a PATCHED snapshot must be element-wise
identical to a fresh full ``build_snapshot`` — every ``ClusterState``
leaf and every ``SnapshotIndex`` name map.  ``IncrementalSnapshotter``
(verify=True) asserts exactly that after every patch, so these tests
drive churn through it and then check the patch path actually engaged
(a fallback-to-full would pass verification vacuously).
"""
import dataclasses

import jax
import numpy as np
import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.binder import Binder
from kai_scheduler_tpu.framework.scheduler import Scheduler, SchedulerConfig
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.state import make_cluster
from kai_scheduler_tpu.state.incremental import (
    IncrementalSnapshotter,
    MutationJournal,
)

pytestmark = pytest.mark.core


def build(num_nodes=8, num_gangs=6, tasks_per_gang=2, **kw) -> Cluster:
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=num_nodes, num_gangs=num_gangs,
        tasks_per_gang=tasks_per_gang, **kw)
    return Cluster.from_objects(nodes, queues, groups, pods, topo)


def refresh(snap, cluster):
    return snap.refresh(cluster, now=cluster.now)


# ---------------------------------------------------------------------------
# Journal
# ---------------------------------------------------------------------------


class TestJournal:
    def test_cursor_consume_resets(self):
        j = MutationJournal()
        cur = j.register()
        j.mark_pod("a")
        j.mark_pod_added("b")
        j.mark_gang("g")
        j.mark_time()
        got = cur.consume()
        assert got.pods_dirty == {"a"}
        assert got.pods_added == ["b"]
        assert got.gangs_dirty == {"g"}
        assert got.time_dirty
        empty = cur.consume()
        assert not empty.pods_dirty and not empty.pods_added
        assert not empty.time_dirty

    def test_multiple_consumers_each_see_all_marks(self):
        j = MutationJournal()
        c1, c2 = j.register(), j.register()
        j.mark_pod("p")
        assert c1.consume().pods_dirty == {"p"}
        # c2's view is independent — not drained by c1
        assert c2.consume().pods_dirty == {"p"}

    def test_cluster_ops_are_journaled(self):
        cluster = build()
        cur = cluster.journal.register()
        pod = next(p for p in cluster.pods.values()
                   if p.status == apis.PodStatus.PENDING)
        cluster.bind_pod(pod.name, list(cluster.nodes)[0])
        cluster.evict_pod(pod.name)
        cluster.tick()
        got = cur.consume()
        assert pod.name in got.pods_dirty
        assert pod.name in got.pods_removed  # reaped by the tick
        assert got.time_dirty

    def test_submit_appends(self):
        cluster = build()
        cur = cluster.journal.register()
        g = apis.PodGroup(name="new-gang", queue="queue-0-0",
                          min_member=1)
        cluster.submit(g, [apis.Pod(name="new-pod", group="new-gang")])
        got = cur.consume()
        assert got.gangs_added == ["new-gang"]
        assert got.pods_added == ["new-pod"]


# ---------------------------------------------------------------------------
# Patch equivalence (verify=True asserts bit-identity internally)
# ---------------------------------------------------------------------------


class TestPatchEquivalence:
    def test_bind_evict_submit_cycle_patches_identically(self):
        cluster = build(num_nodes=8, num_gangs=6, tasks_per_gang=2)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        # bind two pods, evict one, submit a new gang, tick — patched
        pend = [p for p in cluster.pods.values()
                if p.status == apis.PodStatus.PENDING]
        cluster.bind_pod(pend[0].name, "node-0")
        cluster.bind_pod(pend[1].name, "node-1")
        refresh(snap, cluster)
        cluster.evict_pod(pend[0].name)
        cluster.tick()
        refresh(snap, cluster)
        g = apis.PodGroup(name="late", queue="queue-0-0", min_member=1)
        cluster.submit(g, [apis.Pod(
            name="late-0", group="late",
            resources=apis.ResourceVec(1, 1, 4))])
        refresh(snap, cluster)
        assert snap.stats.patched == 3
        assert snap.stats.full_builds == 1  # the cold build only

    def test_direct_status_mutation_is_swept_and_patched(self):
        """Un-journaled in-place writes (tests/controllers do this) are
        detected by the drift sweep and patched correctly."""
        cluster = build(num_gangs=4, running_fraction=0.5)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        pod = next(p for p in cluster.pods.values()
                   if p.status == apis.PodStatus.RUNNING)
        pod.status = apis.PodStatus.SUCCEEDED  # direct, no journal
        refresh(snap, cluster)
        assert snap.stats.patched == 1

    def test_randomized_churn_property(self):
        """Randomized bind/evict/submit/delete/tick streams over many
        cycles: every patched snapshot must equal a fresh full rebuild
        (asserted by verify=True), including forced-fallback cycles."""
        rng = np.random.default_rng(42)
        cluster = build(num_nodes=8, num_gangs=8, tasks_per_gang=2,
                        running_fraction=0.25,
                        topology_levels=(2, 2))
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        submitted = 0
        for cycle in range(12):
            for _ in range(int(rng.integers(1, 4))):
                op = rng.choice(["bind", "evict", "submit", "tick",
                                 "mutate"])
                pods = list(cluster.pods.values())
                if op == "bind":
                    pend = [p for p in pods
                            if p.status == apis.PodStatus.PENDING]
                    if pend:
                        p = pend[int(rng.integers(len(pend)))]
                        node = f"node-{rng.integers(8)}"
                        try:
                            cluster.bind_pod(p.name, node)
                        except RuntimeError:
                            pass
                elif op == "evict":
                    run = [p for p in pods if p.status in
                           (apis.PodStatus.BOUND, apis.PodStatus.RUNNING)]
                    if run:
                        cluster.evict_pod(
                            run[int(rng.integers(len(run)))].name)
                elif op == "submit":
                    submitted += 1
                    name = f"extra-{submitted}"
                    g = apis.PodGroup(name=name, queue="queue-0-0",
                                      min_member=1)
                    cluster.submit(g, [apis.Pod(
                        name=f"{name}-p{i}", group=name,
                        resources=apis.ResourceVec(1, 1, 4))
                        for i in range(int(rng.integers(1, 3)))])
                elif op == "tick":
                    cluster.tick()
                else:
                    run = [p for p in pods if p.status
                           == apis.PodStatus.RUNNING]
                    if run:
                        run[int(rng.integers(len(run)))].status = \
                            apis.PodStatus.SUCCEEDED
            refresh(snap, cluster)
        # the stream must exercise the patch path, not just fall back
        assert snap.stats.patched >= 8, snap.stats

    def test_patch_through_binder_devices(self):
        """Binder-bound pods carry concrete accel devices — the
        recorded-device occupancy path must patch identically."""
        cluster = build(num_nodes=4, num_gangs=4, tasks_per_gang=2)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        sched = Scheduler(SchedulerConfig(incremental=False))
        binder = Binder()
        refresh(snap, cluster)
        sched.run_once(cluster)
        binder.reconcile(cluster)
        refresh(snap, cluster)
        cluster.tick()
        refresh(snap, cluster)
        assert snap.stats.patched == 2

    def test_shapes_stay_pinned_across_churn(self):
        """Capacity floors keep every compiled shape identical across
        patched cycles (shape changes would recompile the kernels)."""
        cluster = build(num_nodes=8, num_gangs=6, tasks_per_gang=2)
        snap = IncrementalSnapshotter(dirty_threshold=1.0)
        state0, _ = refresh(snap, cluster)
        shapes0 = [leaf.shape for leaf in
                   __import__("jax").tree_util.tree_leaves(state0)]
        pend = [p.name for p in cluster.pods.values()
                if p.status == apis.PodStatus.PENDING]
        for i, name in enumerate(pend[:4]):
            cluster.bind_pod(name, f"node-{i % 8}")
        cluster.tick()
        state1, _ = refresh(snap, cluster)
        shapes1 = [leaf.shape for leaf in
                   __import__("jax").tree_util.tree_leaves(state1)]
        assert shapes0 == shapes1
        assert snap.stats.patched == 1

    def test_unchanged_leaves_reuse_device_buffers(self):
        cluster = build(num_nodes=8, num_gangs=6, tasks_per_gang=2)
        snap = IncrementalSnapshotter(dirty_threshold=1.0)
        state0, _ = refresh(snap, cluster)
        pod = next(p for p in cluster.pods.values()
                   if p.status == apis.PodStatus.PENDING)
        cluster.bind_pod(pod.name, "node-0")
        state1, _ = refresh(snap, cluster)
        # node labels/topology never changed — same device buffer
        assert state1.nodes.labels is state0.nodes.labels
        assert state1.nodes.topology is state0.nodes.topology
        assert state1.nodes.allocatable is state0.nodes.allocatable
        # the running table did change
        assert state1.running.valid is not state0.running.valid


# ---------------------------------------------------------------------------
# Fallback triggers
# ---------------------------------------------------------------------------


class TestFallbacks:
    def test_structural_node_change_falls_back(self):
        cluster = build()
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        cluster.nodes["node-extra"] = apis.Node(
            name="node-extra",
            allocatable=apis.ResourceVec(8, 64, 256))
        refresh(snap, cluster)
        assert snap.stats.patched == 0
        assert "node-membership-drift" in snap.stats.fallbacks

    def test_queue_set_change_falls_back(self):
        cluster = build()
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        cluster.queues["q-late"] = apis.Queue(name="q-late",
                                              parent="dept-0")
        refresh(snap, cluster)
        assert snap.stats.patched == 0
        assert "queue-set-changed" in snap.stats.fallbacks

    def test_feature_pod_falls_back(self):
        """Fractional-share pods ride the irregular intake paths — the
        snapshotter must fall back, not mis-patch."""
        cluster = build()
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        g = apis.PodGroup(name="frac-gang", queue="queue-0-0",
                          min_member=1)
        cluster.submit(g, [apis.Pod(
            name="frac-pod", group="frac-gang", accel_portion=0.5,
            resources=apis.ResourceVec(0, 1, 1))])
        refresh(snap, cluster)
        assert "nonplain-pods" in snap.stats.fallbacks
        # once the feature pod leaves, patching resumes
        cluster.evict_pod("frac-pod")
        cluster.tick()
        refresh(snap, cluster)  # full (ledger had the nonplain pod)
        pod = next(p for p in cluster.pods.values()
                   if p.status == apis.PodStatus.PENDING)
        cluster.bind_pod(pod.name, "node-0")
        refresh(snap, cluster)
        assert snap.stats.patched >= 1

    def test_dirty_threshold_falls_back(self):
        cluster = build()
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=0.0)
        refresh(snap, cluster)
        pod = next(p for p in cluster.pods.values()
                   if p.status == apis.PodStatus.PENDING)
        cluster.bind_pod(pod.name, "node-0")
        refresh(snap, cluster)
        assert snap.stats.patched == 0
        assert "dirty-threshold" in snap.stats.fallbacks

    def test_fallback_rebuild_keeps_compiled_shapes(self):
        """A churn-driven fallback rebuilds in full but must not also
        move a padded axis: shapes are jit cache keys, and the fused
        pipeline takes minutes to compile at 10k nodes.  Capacity only
        grows — a shrunken cluster keeps the axes it compiled for, an
        overflowing one re-pads."""
        cluster = build(num_gangs=8)
        snap = IncrementalSnapshotter(verify=False, dirty_threshold=0.1)
        state, _ = refresh(snap, cluster)
        cap0 = snap._capacity
        shapes0 = [leaf.shape for leaf in jax.tree.leaves(state)]
        # shrink: half the pods bind (pending tasks and gangs drop)
        for p in list(cluster.pods.values())[:len(cluster.pods) // 2]:
            cluster.bind_pod(p.name, "node-0")
        state, _ = refresh(snap, cluster)
        assert snap.stats.last["mode"] == "full"
        assert snap.stats.last["fallback_reason"] == "dirty-threshold"
        assert snap._capacity == cap0
        assert [leaf.shape for leaf in jax.tree.leaves(state)] == shapes0
        # overflow: more gangs than the pinned axis holds re-pads it
        for i in range(cap0.gangs):
            cluster.submit(
                apis.PodGroup(f"grow-{i}", queue="queue-0-0",
                              min_member=1),
                [apis.Pod(f"grow-{i}-p", f"grow-{i}",
                          apis.ResourceVec(1.0, 1.0, 1.0))])
        refresh(snap, cluster)
        assert snap._capacity.gangs > cap0.gangs
        assert snap._capacity.nodes == cap0.nodes

    def test_topology_swap_falls_back(self):
        cluster = build(topology_levels=(2, 2))
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        cluster.topology = dataclasses.replace(cluster.topology)
        refresh(snap, cluster)
        assert snap.stats.patched == 0
        assert "topology-changed" in snap.stats.fallbacks


# ---------------------------------------------------------------------------
# Scheduler integration (the verify_incremental flag end-to-end)
# ---------------------------------------------------------------------------


class TestSchedulerIntegration:
    def test_multi_cycle_e2e_with_verify_incremental(self):
        """Scheduler + binder over several cycles with
        ``verify_incremental`` on: every patched cycle is asserted
        identical to a fresh rebuild, and scheduling results flow."""
        cluster = build(num_nodes=4, node_accel=8.0, num_gangs=4,
                        tasks_per_gang=2)
        cfg = SchedulerConfig(verify_incremental=True,
                              incremental_dirty_threshold=1.0)
        sched, binder = Scheduler(cfg), Binder()
        r1 = sched.run_once(cluster)
        assert len(r1.bind_requests) == 8
        assert len(binder.reconcile(cluster).bound) == 8
        cluster.tick()
        r2 = sched.run_once(cluster)
        assert r2.bind_requests == []
        # drain one gang and let the next cycle re-place capacity
        for p in list(cluster.pods.values())[:2]:
            p.status = apis.PodStatus.SUCCEEDED
        cluster.tick()
        g = apis.PodGroup(name="late", queue="queue-0-0", min_member=2)
        cluster.submit(g, [apis.Pod(
            name=f"late-{i}", group="late",
            resources=apis.ResourceVec(1, 1, 4)) for i in range(2)])
        r3 = sched.run_once(cluster)
        assert len(r3.bind_requests) == 2
        snap = sched._snapshotter
        assert snap is not None and snap.verify
        assert snap.stats.patched >= 1, snap.stats

    def test_incremental_off_uses_plain_session_open(self):
        cluster = build(num_nodes=4, num_gangs=2)
        sched = Scheduler(SchedulerConfig(incremental=False))
        r = sched.run_once(cluster)
        assert sched._snapshotter is None
        assert len(r.bind_requests) == 4

    def test_sharded_scheduler_bypasses_incremental(self):
        shard = apis.SchedulingShard(name="s0",
                                     partition_label_value=None)
        cluster = build(num_nodes=4, num_gangs=2)
        sched = Scheduler(SchedulerConfig(shard=shard))
        sched.run_once(cluster)
        assert sched._snapshotter is None


class TestBindRequestPresentation:
    def test_direct_bind_request_clear_is_swept(self):
        """A Pending BindRequest presents its pod as bound; clearing the
        store directly (no journal) must still flip the presentation
        back — the sweep covers the BR table too."""
        cluster = build(num_nodes=4, num_gangs=4, tasks_per_gang=2)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        pod = next(p for p in cluster.pods.values()
                   if p.status == apis.PodStatus.PENDING)
        cluster.create_bind_request(apis.BindRequest(
            pod_name=pod.name, selected_node="node-0"))
        state, _ = refresh(snap, cluster)
        assert int(np.asarray(state.running.valid).sum()) == 1
        cluster.bind_requests.clear()  # direct, unjournaled
        state, _ = refresh(snap, cluster)
        assert int(np.asarray(state.running.valid).sum()) == 0
        assert snap.stats.patched == 2


# ---------------------------------------------------------------------------
# Concurrency: journal marks racing the snapshotter's consume (PR 4)
# ---------------------------------------------------------------------------


class TestJournalConcurrency:
    """The journal is marked from binder / status-updater / HTTP
    handler threads while the scheduler thread drains cursors.  Before
    the journal lock, ``consume()``'s field swap could drop a mark that
    raced it — and a dropped mark for an in-place field mutation the
    drift sweep does not compare (e.g. pod priority) silently serves a
    stale snapshot."""

    def test_marks_hammered_from_thread_patched_equals_fresh(self):
        import threading

        from kai_scheduler_tpu.state import cluster_state as cs

        cluster = build(num_nodes=6, num_gangs=4, tasks_per_gang=2)
        snap = IncrementalSnapshotter()
        refresh(snap, cluster)  # warm (full build + ledgers)

        pending = [p for p in cluster.pods.values()
                   if p.status == apis.PodStatus.PENDING]
        assert pending
        stop = threading.Event()
        rounds = {"n": 0}

        def hammer():
            # in-place priority bumps + marks: the exact write the
            # sweep cannot attribute without the journal entry
            i = 0
            while not stop.is_set():
                pod = pending[i % len(pending)]
                pod.priority += 1
                cluster.journal.mark_pod(pod.name)
                cluster.journal.mark_time()
                rounds["n"] += 1
                i += 1

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        # drain the journal under full contention: every consume races
        # in-flight marks
        for _ in range(15):
            refresh(snap, cluster)
        stop.set()
        t.join(timeout=10)
        assert not t.is_alive()
        assert rounds["n"] > 0  # the hammer actually contended

        # with every mark retained, one quiet refresh must converge to
        # a state element-wise identical to a fresh full rebuild
        state, index = refresh(snap, cluster)
        _fresh_state, fresh_index, fresh_host = cs.build_snapshot(
            *cluster.snapshot_lists(), now=cluster.now,
            capacity=snap._capacity, _return_host=True)
        import jax
        for (path, mine), (_, ref) in zip(
                jax.tree_util.tree_flatten_with_path(snap._host)[0],
                jax.tree_util.tree_flatten_with_path(fresh_host)[0]):
            assert np.array_equal(np.asarray(mine), np.asarray(ref)), (
                f"leaf {jax.tree_util.keystr(path)} diverged after "
                f"concurrent journal marks")
        assert index.gang_names == fresh_index.gang_names
        assert index.task_names == fresh_index.task_names

    def test_consume_is_atomic_under_concurrent_marks(self):
        """No mark may vanish: every mark made before a consume returns
        is either in that batch or in a later one."""
        import threading

        j = MutationJournal()
        cur = j.register()
        total = 2000
        seen: set[str] = set()
        done = threading.Event()

        def writer():
            for i in range(total):
                j.mark_pod(f"p{i}")
            done.set()

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        while not done.is_set():
            seen |= cur.consume().pods_dirty
        t.join(timeout=10)
        seen |= cur.consume().pods_dirty
        assert len(seen) == total  # zero lost marks
