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
import types

import jax
import numpy as np
import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.binder import Binder
from kai_scheduler_tpu.framework.scheduler import Scheduler, SchedulerConfig
from kai_scheduler_tpu.intake import apply as intake_apply
from kai_scheduler_tpu.intake import gate
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.runtime.store import Collection
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


def marks_of(batch) -> list:
    """A drained batch as the ``(kind, name)`` marks that make it."""
    return ([("pod", n) for n in sorted(batch.pods_dirty)]
            + [("pod_added", n) for n in batch.pods_added]
            + [("pod_removed", n) for n in sorted(batch.pods_removed)]
            + [("gang", n) for n in sorted(batch.gangs_dirty)]
            + [("gang_added", n) for n in batch.gangs_added]
            + [("gang_removed", n) for n in sorted(batch.gangs_removed)]
            + [("node", n) for n in sorted(batch.nodes_dirty)]
            + [("structural", r) for r in batch.structural]
            + [("drift", r) for r in batch.drift]
            + [("time", "")] * batch.time_dirty)


def refresh(snap, cluster):
    return snap.refresh(cluster, now=cluster.now)


def delete_groups(cluster, names, pods=True) -> None:
    """What a shim posts when gangs finish or are evicted whole: the
    groups and — unless ``pods`` is False — their pods and the bind
    requests that placed them, in one delta document."""
    names = list(names)
    delta = {"pod_groups_delete": names}
    if pods:
        gone = [p.name for p in cluster.pods.values() if p.group in names]
        delta["pods_delete"] = gone
        delta["bind_requests_delete"] = [
            n for n in gone if n in cluster.bind_requests]
    intake_apply.apply_cluster_delta(cluster, delta)


def submit_groups(cluster, names, tasks=2) -> None:
    """New gangs with pod groups of their own, as one delta document."""
    intake_apply.apply_cluster_delta(cluster, {
        "pod_groups_upsert": [
            {"name": n, "queue": "queue-0-0", "min_member": tasks}
            for n in names],
        "pods_upsert": [
            {"name": f"{n}-p{t}", "group": n,
             "resources": {"accel": 1.0, "cpu": 1.0, "memory": 4.0}}
            for n in names for t in range(tasks)]})


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

    def test_gang_removed_is_a_mark_of_its_own(self):
        j = MutationJournal()
        cur = j.register()
        gate.gang_removed(j, "g0")
        j.mark_gang_added("g1")
        got = cur.consume()
        assert got.gangs_removed == {"g0"} and not got.structural
        assert got.gangs_added == ["g1"]
        # removed, then added again inside one window: the name moved
        # to the end of the store — as pod-readded, too subtle to patch
        j.mark_gang_removed("g2")
        j.mark_gang_added("g2")
        got = cur.consume()
        assert got.structural == ["gang-readded"]
        assert got.gangs_removed == {"g2"} and not got.gangs_added

    @pytest.mark.parametrize("coll,mark", [
        ("pods", ("pod_removed", "x")),
        ("pod_groups", ("gang_removed", "x")),
        ("bind_requests", ("pod", "x")),
        ("nodes", ("structural", "nodes-delete")),
        ("queues", ("structural", "queues-delete")),
        ("resource_claims", ("structural", "resource_claims-delete")),
    ])
    def test_delete_marks_by_collection(self, coll, mark):
        """What a delete of ``x`` marks: the store marks its own
        collections' as the delete lands, a delta document the rest."""
        out: list = []
        gate.delete_marks(coll, "x", True, out)
        if coll in ("pods", "pod_groups", "bind_requests"):
            assert out == []
            j = MutationJournal()
            cur = j.register()
            store = Collection(coll, j, {"x": types.SimpleNamespace()})
            cur.consume()
            del store["x"]
            assert marks_of(cur.consume()) == [mark]
            out = [mark]
        assert out == [mark]
        gate.delete_marks(coll, "x", False, out)  # nothing was stored
        assert out == [mark]


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
        """In-place writes of a watched field (tests/controllers do
        this) mark the journal through the store and are patched
        correctly."""
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
        submitted = subgrouped = 0
        for cycle in range(12):
            for _ in range(int(rng.integers(1, 4))):
                op = rng.choice(["bind", "evict", "submit", "tick",
                                 "mutate", "delete"])
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
                    shape = rng.choice(["plain", *sorted(SUBGROUPED)])
                    if shape != "plain":
                        # a job with declared subgroups
                        submit_subgrouped(cluster, name, str(shape))
                        subgrouped += 1
                        continue
                    g = apis.PodGroup(name=name, queue="queue-0-0",
                                      min_member=1)
                    cluster.submit(g, [apis.Pod(
                        name=f"{name}-p{i}", group=name,
                        resources=apis.ResourceVec(1, 1, 4))
                        for i in range(int(rng.integers(1, 3)))])
                elif op == "tick":
                    cluster.tick()
                elif op == "delete":
                    names = list(cluster.pod_groups)
                    k = min(len(names) - 1, int(rng.integers(1, 3)))
                    delete_groups(cluster, [
                        names[i] for i in rng.choice(
                            len(names), size=k, replace=False)])
                else:
                    run = [p for p in pods if p.status
                           == apis.PodStatus.RUNNING]
                    if run:
                        run[int(rng.integers(len(run)))].status = \
                            apis.PodStatus.SUCCEEDED
            refresh(snap, cluster)
        # the stream must exercise the patch path, not just fall back
        assert snap.stats.patched >= 8, snap.stats
        assert subgrouped >= 3

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
# Gang removal: the ledger closes up, the cycle patches (ISSUE 27)
# ---------------------------------------------------------------------------


def only_cold(snap) -> bool:
    return snap.stats.fallbacks == {"cold": 1}


class TestGangRemoval:
    """A pod-group delete is a journal mark the patch path consumes.
    ``verify=True`` holds every patched state to a fresh rebuild, leaf
    for leaf and name for name; the tests then check that the patch
    path engaged, for a fallback would pass that vacuously."""

    def warm(self, **kw):
        cluster = build(num_nodes=8, num_gangs=8, tasks_per_gang=2,
                        running_fraction=0.5, **kw)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        return cluster, snap

    @pytest.mark.parametrize("rows", [(0,), (7,), (1, 4, 5), (0, 7),
                                      (0, 1, 2, 3, 4, 5, 6)])
    def test_rows_close_up(self, rows):
        cluster, snap = self.warm()
        names = list(cluster.pod_groups)
        delete_groups(cluster, [names[i] for i in rows])
        _, index = refresh(snap, cluster)
        assert snap.stats.patched == 1 and only_cold(snap), snap.stats
        assert snap.stats.last["gangs_removed"] == len(rows)
        assert snap.stats.last["pods_removed"] == 2 * len(rows)
        # rows that only moved are not dirty
        assert snap.stats.last["dirty_gangs"] == 0
        kept = list(cluster.pod_groups)
        assert index.gang_names[:len(kept)] == kept
        # and a second window over the closed-up ledger patches too
        cluster.tick()
        refresh(snap, cluster)
        assert snap.stats.patched == 2 and only_cold(snap)

    def test_removal_and_arrival_in_one_window(self):
        cluster, snap = self.warm()
        names = list(cluster.pod_groups)
        delete_groups(cluster, [names[2], names[6]])
        submit_groups(cluster, ["late-a", "late-b", "late-c"])
        _, index = refresh(snap, cluster)
        assert snap.stats.patched == 1 and only_cold(snap), snap.stats
        assert snap.stats.last["gangs_removed"] == 2
        assert snap.stats.last["dirty_gangs"] == 3  # the arrivals only
        assert index.gang_names[:9] == list(cluster.pod_groups)

    def test_orphans_stay_until_their_pods_go(self):
        """The group goes a cycle ahead of its pods: they are encoded
        as a rebuild encodes a pod of no known group."""
        cluster, snap = self.warm()
        name = list(cluster.pod_groups)[3]
        delete_groups(cluster, [name], pods=False)
        refresh(snap, cluster)
        assert snap.stats.patched == 1 and only_cold(snap), snap.stats
        assert snap.stats.last["gangs_removed"] == 1
        assert snap.stats.last["pods_removed"] == 0
        assert snap.stats.last["dirty_pods"] == 2  # the orphans
        orphans = [p.name for p in cluster.pods.values()
                   if p.group == name]
        intake_apply.apply_cluster_delta(
            cluster, {"pods_delete": orphans})
        refresh(snap, cluster)
        assert snap.stats.patched == 2 and only_cold(snap), snap.stats
        assert snap.stats.last["pods_removed"] == 2

    def test_orphans_resolve_when_their_group_returns_later(self):
        cluster, snap = self.warm()
        name = list(cluster.pod_groups)[0]
        queue = cluster.pod_groups[name].queue
        delete_groups(cluster, [name], pods=False)
        refresh(snap, cluster)
        intake_apply.apply_cluster_delta(cluster, {"pod_groups_upsert": [
            {"name": name, "queue": queue, "min_member": 2}]})
        _, index = refresh(snap, cluster)
        assert snap.stats.patched == 2 and only_cold(snap), snap.stats
        assert index.gang_names[7] == name  # back, at the store's end

    def test_added_then_removed_in_one_window(self):
        cluster, snap = self.warm()
        submit_groups(cluster, ["blink", "stays"])
        delete_groups(cluster, ["blink"])
        _, index = refresh(snap, cluster)
        assert snap.stats.patched == 1 and only_cold(snap), snap.stats
        # it never had a row, so none was closed up
        assert snap.stats.last["gangs_removed"] == 0
        assert "blink" not in index.gang_names
        assert "stays" in index.gang_names

    def test_removed_then_readded_escalates(self):
        cluster, snap = self.warm()
        name = list(cluster.pod_groups)[1]
        delete_groups(cluster, [name])
        submit_groups(cluster, [name])
        refresh(snap, cluster)
        assert snap.stats.patched == 0
        assert snap.stats.last["fallback_reason"] \
            == "structural:gang-readded"
        # the rebuild re-anchors the ledger: the next removal patches
        delete_groups(cluster, [name])
        refresh(snap, cluster)
        assert snap.stats.patched == 1

    def test_touched_then_removed_in_one_window(self):
        cluster, snap = self.warm()
        name = list(cluster.pod_groups)[5]
        gate.gang_touched(cluster.journal, name)
        delete_groups(cluster, [name])
        refresh(snap, cluster)
        assert snap.stats.patched == 1 and only_cold(snap), snap.stats

    def test_removal_does_not_trip_dirty_threshold(self):
        """Seven of eight rows move when the first goes; none is dirty,
        so even a threshold of nothing lets the patch through."""
        cluster = build(num_nodes=8, num_gangs=8, tasks_per_gang=2,
                        running_fraction=0.5)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=0.0)
        refresh(snap, cluster)
        delete_groups(cluster, [list(cluster.pod_groups)[0]])
        refresh(snap, cluster)
        assert snap.stats.patched == 1 and only_cold(snap), snap.stats
        assert snap.stats.last["dirty_pods"] == 0
        assert snap.stats.last["dirty_gangs"] == 0

    def test_shapes_hold_over_turnover(self):
        """8 gangs out and 8 in, 20 cycles: the gang axis never fills
        (a ledger that only appended would overflow it within the run)
        and no padded shape moves."""
        cluster = build(num_nodes=16, num_gangs=32, tasks_per_gang=2,
                        running_fraction=0.5)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        state, _ = refresh(snap, cluster)
        shapes = [leaf.shape for leaf in jax.tree.leaves(state)]
        cap = snap._capacity
        assert cap.gangs < 32 + 20 * 8
        rng = np.random.default_rng(7)
        for cyc in range(20):
            names = list(cluster.pod_groups)
            delete_groups(cluster, [names[i] for i in rng.choice(
                len(names), size=8, replace=False)])
            submit_groups(cluster, [f"t{cyc}-{i}" for i in range(8)])
            state, index = refresh(snap, cluster)
            assert [leaf.shape for leaf in jax.tree.leaves(state)] \
                == shapes
            assert index.gang_names[:32] == list(cluster.pod_groups)
        assert snap.stats.patched == 20 and only_cold(snap), snap.stats
        assert snap._capacity == cap

    def test_pod_ledger_compacts_in_place(self):
        """Appends only ever take new pod rows; once the dead ones
        outnumber the live the ledger closes up over them — it used to
        rebuild (``ledger-compaction``)."""
        cluster = build(num_nodes=16, num_gangs=40, tasks_per_gang=2,
                        running_fraction=0.5)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        live = len(cluster.pods)
        longest = 0
        for cyc in range(24):
            delete_groups(cluster, list(cluster.pod_groups)[:4])
            submit_groups(cluster, [f"c{cyc}-{i}" for i in range(4)])
            refresh(snap, cluster)
            longest = max(longest, len(snap.p_objs))
            assert len(snap.p_objs) <= 2 * live + 8
        assert longest > 2 * live - 8  # the threshold was reached
        assert len(snap.p_objs) < longest  # and the ledger closed up
        assert snap.stats.patched == 24 and only_cold(snap), snap.stats


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
# Tolerations and node selectors ride the patch (ISSUE 31)
# ---------------------------------------------------------------------------

TAINT = apis.Taint("nvidia.com/gpu", "present", "NoSchedule")
TOLERATES = apis.Toleration("nvidia.com/gpu", "Exists", effect="NoSchedule")
#: what a pod of each kind carries beside its request
FILTERED = {
    "tolerating": {"tolerations": [TOLERATES]},
    "selecting": {"node_selector": {"gpu.type": "volta"}},
    "both": {"tolerations": [TOLERATES],
             "node_selector": {"gpu.type": "pascal"}},
}


def pool(**kw) -> Cluster:
    """The small cluster as an accelerator pool: nodes 0-3 tainted
    ``volta``, 4-5 tainted ``pascal``, 6-7 as built."""
    cluster = build(num_nodes=8, num_gangs=6, tasks_per_gang=2, **kw)
    for i, gpu in enumerate(["volta"] * 4 + ["pascal"] * 2):
        node = cluster.nodes[f"node-{i}"]
        node.labels = {**node.labels, "gpu.type": gpu}
        node.taints = [TAINT]
    return cluster


def submit_filtered(cluster, name, kind, tasks=2) -> list[str]:
    cluster.submit(
        apis.PodGroup(name, queue="queue-0-0", min_member=tasks),
        [apis.Pod(f"{name}-p{t}", name, apis.ResourceVec(1, 1, 4),
                  **{k: type(v)(v) for k, v in FILTERED[kind].items()})
         for t in range(tasks)])
    return [f"{name}-p{t}" for t in range(tasks)]


def warm_pool(**kw):
    """A pool whose cold build has met every kind of ``FILTERED``: two
    specs, one selector key, two label values."""
    cluster = pool(**kw)
    for kind in FILTERED:
        submit_filtered(cluster, f"seed-{kind}", kind)
    snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
    refresh(snap, cluster)
    return cluster, snap


def patched_and_fresh(snap, cluster):
    state, index = refresh(snap, cluster)
    assert snap.stats.last["mode"] == "patched", snap.stats.last
    _assert_fresh(state, snap, cluster)
    return state, index


class TestFilteredPodsPatch:
    """A toleration and a node selector are per-pod constants once the
    filter-class and label numbering survives from cycle to cycle:
    ``verify=True`` and ``_assert_fresh`` hold every patched state to a
    fresh build under the pinned vocabulary."""

    @pytest.mark.parametrize("kind", sorted(FILTERED))
    def test_a_filtered_gang_lives_and_dies_in_patched_cycles(self, kind):
        cluster, snap = warm_pool(running_fraction=0.5)
        vocab0 = snap._vocabulary
        # arrives
        pods = submit_filtered(cluster, "job", kind)
        state, index = patched_and_fresh(snap, cluster)
        gi = index.gang_names.index("job")
        want = FILTERED[kind]
        cls = np.asarray(state.gangs.task_filter_class)[gi, :2]
        sel = np.asarray(state.gangs.task_selector)[gi, :2, 0]
        assert (cls == (1 if "tolerations" in want else 0)).all()
        gpu = want.get("node_selector", {}).get("gpu.type")
        assert (sel == (index.label_vocab[("gpu.type", gpu)]
                        if gpu else -1)).all()
        assert snap.stats.last["filter_classes"] == 2
        assert snap.stats.last["selector_keys"] == 1
        assert snap.stats.last["nonplain_pods"] == 0
        # the seeds and this gang, two pods each
        assert snap.stats.last["filtered_pods"] == 8
        # binds (a bind request presents it bound, then the pod is)
        node = "node-5" if gpu == "pascal" else "node-0"
        for name in pods:
            cluster.bind_pod(name, node)
        state, index = patched_and_fresh(snap, cluster)
        rows = [index.running_pod_names.index(n) for n in pods]
        assert (np.asarray(state.running.filter_class)[rows]
                == (1 if "tolerations" in want else 0)).all()
        # one pod is evicted and reaped, the gang is short again
        cluster.evict_pod(pods[0])
        patched_and_fresh(snap, cluster)
        cluster.tick()
        patched_and_fresh(snap, cluster)
        # the gang finishes: the group goes with its pods
        delete_groups(cluster, ["job"])
        patched_and_fresh(snap, cluster)
        assert snap.stats.last["filtered_pods"] == 6
        assert snap.stats.fallbacks == {"cold": 1}
        assert snap._vocabulary == vocab0

    @pytest.mark.parametrize("grows,pod", [
        ("spec", {"tolerations": [apis.Toleration(
            "dedicated", "Equal", "ml", "NoSchedule")]}),
        ("key", {"node_selector": {"zone": "a"}}),
        # a value no node carries: the pod can go nowhere, and the
        # build still numbers it
        ("value", {"node_selector": {"gpu.type": "hopper"}}),
    ])
    def test_growth_rebuilds_once_then_patches(self, grows, pod):
        cluster, snap = warm_pool()
        before = snap._vocabulary
        cluster.submit(
            apis.PodGroup("new", queue="queue-0-0", min_member=1),
            [apis.Pod("new-p", "new", apis.ResourceVec(1, 1, 4), **pod)])
        state, _ = refresh(snap, cluster)
        assert snap.stats.last["mode"] == "full"
        assert snap.stats.last["fallback_reason"] == "vocab-growth"
        _assert_fresh(state, snap, cluster)
        after = snap._vocabulary
        grew = {"spec": len(after.filter_specs) - len(before.filter_specs),
                "key": len(after.selector_keys) - len(before.selector_keys),
                "value": len(after.label_vocab) - len(before.label_vocab)}
        assert grew[grows] == 1, grew
        # ids already given stay
        assert after.filter_specs[:2] == before.filter_specs
        assert after.selector_keys[:1] == before.selector_keys
        assert before.label_vocab.items() <= after.label_vocab.items()
        # the cycle after patches, and so does a second pod like it
        patched_and_fresh(snap, cluster)
        cluster.submit(
            apis.PodGroup("next", queue="queue-0-0", min_member=1),
            [apis.Pod("next-p", "next", apis.ResourceVec(1, 1, 4), **pod)])
        patched_and_fresh(snap, cluster)
        assert snap.stats.fallbacks == {"cold": 1, "vocab-growth": 1}

    def test_the_last_selecting_pod_leaving_changes_no_shape(self):
        """``K`` and ``X`` are compiled shapes and ``dense_feasibility``
        a static argument of the solve: the vocabulary only grows."""
        cluster, snap = warm_pool()
        state0, index0 = refresh(snap, cluster)
        delete_groups(cluster, [f"seed-{kind}" for kind in FILTERED])
        state, index = patched_and_fresh(snap, cluster)
        assert snap.stats.last["filtered_pods"] == 0
        assert snap.stats.last["filter_classes"] == 2
        assert snap.stats.last["selector_keys"] == 1
        # ... and a rebuild keeps them too
        cluster.journal.mark_structural("test")
        rebuilt, rebuilt_index = refresh(snap, cluster)
        assert snap.stats.last["mode"] == "full"
        for st, ix in ((state, index), (rebuilt, rebuilt_index)):
            assert ix.selector_keys == index0.selector_keys == ["gpu.type"]
            assert ix.label_vocab == index0.label_vocab
            assert ix.dense_feasibility is index0.dense_feasibility is False
            assert ([leaf.shape for leaf in jax.tree.leaves(st)]
                    == [leaf.shape for leaf in jax.tree.leaves(state0)])
        patched_and_fresh(snap, cluster)

    def test_a_spec_that_reads_running_pods_is_not_pinned(self):
        """A host port's mask changes with the running pods, so its row
        is not inert once its pod has left: the pod stays a blocker, and
        the vocabulary forgets its spec with it."""
        cluster, snap = warm_pool()
        cluster.submit(
            apis.PodGroup("web", queue="queue-0-0", min_member=1),
            [apis.Pod("web-p", "web", apis.ResourceVec(1, 1, 4),
                      host_ports=[8080], tolerations=[TOLERATES])])
        for _ in range(2):
            refresh(snap, cluster)
            assert snap.stats.last["fallback_reason"] == "nonplain-pods"
            assert snap.stats.last["filter_classes"] == 3
            assert snap.stats.last["nonplain_pods"] == 1
            assert len(snap._vocabulary.filter_specs) == 2
        delete_groups(cluster, ["web"])
        refresh(snap, cluster)  # the ledger still held the pod
        patched_and_fresh(snap, cluster)
        assert snap.stats.last["filter_classes"] == 2

    def test_a_running_pod_selecting_an_unknown_value_does_not_rebuild(
            self):
        """The builder numbers label values of nodes and of pending
        pods only.  A running pod whose value no node carries (its node
        was relabelled) has none, before and after any rebuild, so it
        must not be refused as growth cycle after cycle."""
        cluster = pool(running_fraction=0.5)
        ghost = next(p for p in cluster.pods.values()
                     if p.status == apis.PodStatus.RUNNING)
        ghost.node_selector = {"gpu.type": "ghost"}
        submit_filtered(cluster, "seed", "selecting")
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        _, index = refresh(snap, cluster)
        assert ("gpu.type", "ghost") not in index.label_vocab
        patched_and_fresh(snap, cluster)
        # restarted and pending again, its value is one a build numbers
        cluster.evict_pod(ghost.name, restart=True)
        patched_and_fresh(snap, cluster)
        cluster.tick()
        _, index = refresh(snap, cluster)
        assert ghost.status == apis.PodStatus.PENDING
        assert snap.stats.last["fallback_reason"] == "vocab-growth"
        assert ("gpu.type", "ghost") in index.label_vocab
        patched_and_fresh(snap, cluster)

# ---------------------------------------------------------------------------
# Declared subgroups ride the patch
# ---------------------------------------------------------------------------

ACCEL = apis.ResourceVec(1, 1, 4)
NO_ACCEL = apis.ResourceVec(0, 1, 2)
ROLE = "training.kubeflow.org/job-role"
#: required levels of ``build(topology_levels=(2, 2))``: 0 and 2
BLOCK = apis.TopologyConstraint("default", required_level="topo/level0")
HOST = apis.TopologyConstraint(
    "default", required_level="kubernetes.io/hostname")


def _sub(name, min_member, tc=None):
    return apis.SubGroup(name, min_member=min_member,
                         topology_constraint=tc)


#: shape -> (the group's fields, [(a pod's subgroup, its request, its
#: job-role label)], and the ``[S]`` rows a build gives the gang: each
#: pod's slot, ``subgroup_min_member``, ``subgroup_required_level``)
SUBGROUPED = {
    # a master and workers, every pod with an accelerator
    "pytorch": (
        {"sub_groups": [_sub("master", 1), _sub("worker", 3)]},
        [("master", ACCEL, "master")] + [("worker", ACCEL, "worker")] * 3,
        [1, 2, 2, 2], [0, 1, 3, 0], [-1, -1, -1, -1]),
    # a launcher that asks for no accelerator, and workers
    "mpi": (
        {"sub_groups": [_sub("launcher", 1), _sub("worker", 2)]},
        [("launcher", NO_ACCEL, "launcher")]
        + [("worker", ACCEL, "worker")] * 2,
        [1, 2, 2], [0, 1, 2, 0], [-1, -1, -1, -1]),
    # a subgroup with a required topology level of its own
    "own-level": (
        {"sub_groups": [_sub("a", 1), _sub("b", 2, HOST)]},
        [("a", ACCEL, None)] + [("b", ACCEL, None)] * 2,
        [1, 2, 2], [0, 1, 2, 0], [-1, -1, 2, -1]),
    # the gang's level, inherited by every slot without one
    "inherited-level": (
        {"sub_groups": [_sub("a", 1), _sub("b", 1, HOST)],
         "topology_constraint": BLOCK},
        [("a", ACCEL, None), ("b", ACCEL, None), ("a", ACCEL, None)],
        [1, 2, 1], [0, 1, 1, 0], [0, 0, 2, 0]),
    # a pod that names a subgroup its gang does not declare, and one
    # that names none: the default slot
    "unknown-name": (
        {"sub_groups": [_sub("a", 1)]},
        [("a", ACCEL, None), ("ghost", ACCEL, None), (None, ACCEL, None)],
        [1, 0, 0], [0, 1, 0, 0], [-1, -1, -1, -1]),
    # equal pods, and a subgroup that may start short of its pods
    "short": (
        {"sub_groups": [_sub("master", 1), _sub("worker", 2)]},
        [("master", ACCEL, "master")] + [("worker", ACCEL, "worker")] * 3,
        [1, 2, 2, 2], [0, 1, 2, 0], [-1, -1, -1, -1]),
}

#: shape -> ``GangState.uniform`` of its gang when it arrives and once
#: its first two pods are bound: the whole-gang kernel places it only
#: while every pending pod of it is equal and needed for every quorum
#: (the MPIJob's pods left pending are its workers alone)
UNIFORM = {"pytorch": (True, True), "mpi": (False, True),
           "own-level": (False, False), "inherited-level": (False, False),
           "unknown-name": (False, False), "short": (False, False)}


def submit_subgrouped(cluster, name, shape) -> list[str]:
    fields, pods, *_ = SUBGROUPED[shape]
    # a list of its own a gang: the store tells writes apart by identity
    fields = {**fields, "sub_groups": list(fields["sub_groups"])}
    cluster.submit(
        apis.PodGroup(name, queue="queue-0-0", min_member=len(pods),
                      **fields),
        [apis.Pod(f"{name}-p{t}", name, req, subgroup=sub,
                  labels={ROLE: role} if role else {})
         for t, (sub, req, role) in enumerate(pods)])
    return [f"{name}-p{t}" for t in range(len(pods))]


def task_slots(state, index, gang) -> dict:
    """Pending pod name -> its entry of ``task_subgroup``."""
    gi = index.gang_names.index(gang)
    return dict(zip(index.task_names[gi],
                    np.asarray(state.gangs.task_subgroup)[gi].tolist()))


def warm_topo(**kw):
    cluster = build(num_nodes=8, num_gangs=6, tasks_per_gang=2,
                    topology_levels=(2, 2), **kw)
    snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
    refresh(snap, cluster)
    return cluster, snap


class TestSubgroupedGangsPatch:
    """A declared subgroup is a slot in the gang's ``[S]`` rows and a
    number on each of its pods: ``verify=True`` and ``_assert_fresh``
    hold every patched state to a fresh build under the pinned ``S``."""

    @pytest.mark.parametrize("shape", sorted(SUBGROUPED))
    def test_a_subgrouped_gang_lives_and_dies_in_patched_cycles(
            self, shape):
        cluster, snap = warm_topo(running_fraction=0.5)
        _state, index0 = refresh(snap, cluster)
        assert index0.uniform_gangs is True
        cap0 = snap._capacity
        _fields, _pods, slots, minm, rlvl = SUBGROUPED[shape]
        # arrives
        pods = submit_subgrouped(cluster, "job", shape)
        state, index = patched_and_fresh(snap, cluster)
        gi = index.gang_names.index("job")
        g = state.gangs
        by_name = task_slots(state, index, "job")
        assert [by_name[n] for n in pods] == slots
        assert np.asarray(g.subgroup_valid)[gi].tolist() == [
            s <= len(_fields["sub_groups"]) for s in range(4)]
        assert np.asarray(g.subgroup_min_member)[gi].tolist() == minm
        assert np.asarray(g.subgroup_min_needed)[gi].tolist() == minm
        assert np.asarray(g.subgroup_required_level)[gi].tolist() == rlvl
        assert index.uniform_gangs is False
        assert index.has_subgroup_topology is any(x >= 0 for x in rlvl)
        last = snap.stats.last
        assert last["nonplain_pods"] == last["nonplain_gangs"] == 0
        assert last["subgrouped_gangs"] == 1
        assert last["subgrouped_pods"] == sum(s > 0 for s in slots)
        # the first two pods bind: their subgroups need that many fewer
        for name in pods[:2]:
            cluster.bind_pod(name, "node-0")
        state, index = patched_and_fresh(snap, cluster)
        gi = index.gang_names.index("job")
        need = list(minm)
        for s in slots[:2]:
            need[s] = max(need[s] - 1, 0)
        assert np.asarray(
            state.gangs.subgroup_min_needed)[gi].tolist() == need
        assert snap.stats.last["subgrouped_pods"] == sum(
            s > 0 for s in slots)
        # one of them is evicted and reaped: its subgroup is short again
        cluster.evict_pod(pods[1])
        patched_and_fresh(snap, cluster)
        cluster.tick()
        state, index = patched_and_fresh(snap, cluster)
        need[slots[1]] = minm[slots[1]]
        assert np.asarray(state.gangs.subgroup_min_needed)[
            index.gang_names.index("job")].tolist() == need
        # the job finishes: the group goes with its pods
        delete_groups(cluster, ["job"])
        _state, index = patched_and_fresh(snap, cluster)
        assert index.uniform_gangs is True
        assert snap.stats.last["subgrouped_gangs"] == 0
        assert snap.stats.last["subgrouped_pods"] == 0
        assert only_cold(snap), snap.stats
        assert snap._capacity == cap0

    @pytest.mark.parametrize("shape", sorted(SUBGROUPED))
    def test_the_patch_marks_the_gangs_of_the_whole_gang_kernel(self, shape):
        """``GangState.uniform`` rides the patch: the subgrouped gang's
        entry is what ``UNIFORM`` says, as a build gives it
        (``patched_and_fresh``), every other pending gang's is true, and
        the index counts the pending gangs whose entry is false."""
        cluster, snap = warm_topo(running_fraction=0.5)
        pods = submit_subgrouped(cluster, "job", shape)
        for step, expected in enumerate(UNIFORM[shape]):
            if step:
                for name in pods[:2]:
                    cluster.bind_pod(name, "node-0")
            state, index = patched_and_fresh(snap, cluster)
            col = np.asarray(state.gangs.uniform)
            gi = index.gang_names.index("job")
            assert bool(col[gi]) is expected
            others = np.asarray(state.gangs.task_valid).any(1)
            others[gi] = False
            assert col[others].all()
            assert index.num_nonuniform_gangs == int(not expected)
            assert index.uniform_gangs is False

    # "sweep": the write alone, which the store marks (the id is older
    # than the store's marks); "journal": the write and an explicit mark
    @pytest.mark.parametrize("by", ["sweep", "journal"])
    @pytest.mark.parametrize("status", ["pending", "running"])
    def test_growing_and_shedding_subgroups_reslots_the_gangs_pods(
            self, status, by):
        """The pods name a subgroup all along; the slot it means is the
        gang's to give and to take, written with or without a mark."""
        cluster = build(num_nodes=8, num_gangs=6, tasks_per_gang=2,
                        running_fraction=0.5)
        want = (apis.PodStatus.PENDING if status == "pending"
                else apis.PodStatus.RUNNING)
        first = next(p for p in cluster.pods.values()
                     if p.status == want)
        group = cluster.pod_groups[first.group]
        mine = [p for p in cluster.pods.values() if p.group == group.name]
        for p in mine:
            p.subgroup = "late"
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)

        def write(sub_groups):
            group.sub_groups = sub_groups
            if by == "journal":
                gate.gang_touched(cluster.journal, group.name)

        def slots(state, index):
            gi = index.gang_names.index(group.name)
            if status == "pending":
                return np.asarray(
                    state.gangs.task_subgroup)[gi, :2].tolist()
            # where the running pods count: needed = min_member - running
            return (np.asarray(state.gangs.subgroup_min_member)[gi]
                    - np.asarray(state.gangs.subgroup_min_needed)[gi]
                    ).tolist()

        state, index = patched_and_fresh(snap, cluster)
        assert snap.stats.last["subgrouped_pods"] == 0
        assert index.uniform_gangs is True
        write([_sub("early", 2), _sub("late", 2)])
        state, index = patched_and_fresh(snap, cluster)
        assert slots(state, index) == ([2, 2] if status == "pending"
                                       else [0, 0, 2, 0])
        assert snap.stats.last["subgrouped_pods"] == 2
        assert snap.stats.last["subgrouped_gangs"] == 1
        assert index.uniform_gangs is False
        write([_sub("late", 2)])
        state, index = patched_and_fresh(snap, cluster)
        assert slots(state, index) == ([1, 1] if status == "pending"
                                       else [0, 2, 0, 0])
        write([])
        state, index = patched_and_fresh(snap, cluster)
        assert slots(state, index) == ([0, 0] if status == "pending"
                                       else [2, 0, 0, 0])
        assert snap.stats.last["subgrouped_pods"] == 0
        assert snap.stats.last["subgrouped_gangs"] == 0
        assert index.uniform_gangs is True
        assert only_cold(snap), snap.stats

    def test_a_pod_ahead_of_its_group_takes_its_slot_when_it_arrives(
            self):
        cluster, snap = warm_topo()
        _fields, pods, slots, *_ = SUBGROUPED["mpi"]
        intake_apply.apply_cluster_delta(cluster, {"pods_upsert": [
            {"name": f"early-p{t}", "group": "early", "subgroup": sub,
             "resources": {"accel": req.accel, "cpu": 1.0, "memory": 2.0}}
            for t, (sub, req, _role) in enumerate(pods)]})
        patched_and_fresh(snap, cluster)
        assert snap.stats.last["subgrouped_pods"] == 0
        intake_apply.apply_cluster_delta(cluster, {"pod_groups_upsert": [
            {"name": "early", "queue": "queue-0-0", "min_member": 3,
             "sub_groups": [{"name": "launcher", "min_member": 1},
                            {"name": "worker", "min_member": 2}]}]})
        state, index = patched_and_fresh(snap, cluster)
        by_name = task_slots(state, index, "early")
        assert [by_name[f"early-p{t}"] for t in range(3)] == slots
        assert snap.stats.last["subgrouped_pods"] == 3
        # and loses it when the group goes ahead of its pods
        delete_groups(cluster, ["early"], pods=False)
        patched_and_fresh(snap, cluster)
        assert snap.stats.last["subgrouped_pods"] == 0
        assert only_cold(snap), snap.stats

    def test_more_subgroups_than_slots_rebuild_once_and_S_stays(self):
        """``S`` is a compiled shape, pinned with the capacity: it grows
        in one rebuild and never shrinks."""
        cluster, snap = warm_topo()
        assert snap._capacity.subgroups == 4
        submit_subgrouped(cluster, "fits", "pytorch")
        patched_and_fresh(snap, cluster)

        def wide(name):
            cluster.submit(
                apis.PodGroup(name, queue="queue-0-0", min_member=4,
                              sub_groups=[_sub(f"r{i}", 1)
                                          for i in range(4)]),
                [apis.Pod(f"{name}-p{i}", name, ACCEL, subgroup=f"r{i}")
                 for i in range(4)])

        wide("wide")
        state, _ = refresh(snap, cluster)
        assert snap.stats.last["fallback_reason"] == "overflow-subgroups"
        assert snap._capacity.subgroups == 8
        assert np.asarray(state.gangs.subgroup_valid).shape[1] == 8
        wide("wider")   # a second one like it patches
        state, index = patched_and_fresh(snap, cluster)
        by_name = task_slots(state, index, "wider")
        assert [by_name[f"wider-p{i}"] for i in range(4)] == [1, 2, 3, 4]
        # the last subgrouped gang leaves, and a rebuild after it: the
        # axis stays, the hint is the fresh build's
        delete_groups(cluster, ["fits", "wide", "wider"])
        state, index = patched_and_fresh(snap, cluster)
        assert index.uniform_gangs is True
        cluster.journal.mark_structural("test")
        rebuilt, rebuilt_index = refresh(snap, cluster)
        assert snap.stats.last["mode"] == "full"
        assert rebuilt_index.uniform_gangs is True
        assert ([leaf.shape for leaf in jax.tree.leaves(rebuilt)]
                == [leaf.shape for leaf in jax.tree.leaves(state)])
        assert snap._capacity.subgroups == 8
        assert snap.stats.fallbacks == {
            "cold": 1, "overflow-subgroups": 1, "structural": 1}

    def test_the_last_subgrouped_gang_leaving_changes_no_shape(self):
        """``uniform_gangs`` is a static argument of the solve and the
        builder's expression: it flips when the first subgrouped gang
        arrives and back when the last leaves, exactly as a fresh build
        flips it, and no leaf changes shape either way."""
        cluster, snap = warm_topo(running_fraction=0.5)
        state0, index0 = refresh(snap, cluster)
        shapes = [leaf.shape for leaf in jax.tree.leaves(state0)]
        assert index0.uniform_gangs is True
        for name, shape in (("a", "pytorch"), ("b", "mpi")):
            submit_subgrouped(cluster, name, shape)
        state, index = patched_and_fresh(snap, cluster)
        assert index.uniform_gangs is False
        assert [leaf.shape for leaf in jax.tree.leaves(state)] == shapes
        delete_groups(cluster, ["a"])
        state, index = patched_and_fresh(snap, cluster)
        assert index.uniform_gangs is False
        assert snap.stats.last["subgrouped_gangs"] == 1
        delete_groups(cluster, ["b"])
        state, index = patched_and_fresh(snap, cluster)
        assert index.uniform_gangs is True
        assert [leaf.shape for leaf in jax.tree.leaves(state)] == shapes
        assert only_cold(snap), snap.stats


class TestVocabularyNumbering:
    def lists(self):
        cluster = pool()
        for kind in ("selecting", "both", "tolerating"):
            submit_filtered(cluster, f"job-{kind}", kind)
        return cluster.snapshot_lists()

    def test_without_a_vocabulary_numbers_by_first_encounter(self):
        """What every caller but the snapshotter gets, as before: keys
        in pod order, values over nodes then pending types, specs over
        pending tasks; the empty spec is row 0."""
        from kai_scheduler_tpu.state.cluster_state import (
            SnapshotVocabulary, build_snapshot)
        from kai_scheduler_tpu.state.node_filters import EMPTY_SPEC
        state, index = build_snapshot(*self.lists())
        assert index.selector_keys == ["gpu.type"]
        assert index.label_vocab == {("gpu.type", "volta"): 0,
                                     ("gpu.type", "pascal"): 1}
        assert np.asarray(state.nodes.filter_masks).shape[0] == 2
        assert index.vocabulary.filter_specs[0] == EMPTY_SPEC
        assert index.vocabulary.selector_keys == ("gpu.type",)
        assert index.vocabulary.label_vocab == index.label_vocab
        again, index2 = build_snapshot(
            *self.lists(), vocabulary=SnapshotVocabulary())
        assert index2.vocabulary == index.vocabulary
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(again)):
            assert np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True)

    def test_a_pinned_vocabulary_keeps_its_ids_and_appends(self):
        from kai_scheduler_tpu.state.cluster_state import (
            SnapshotVocabulary, build_snapshot)
        from kai_scheduler_tpu.state.node_filters import (
            EMPTY_SPEC, pod_filter_spec)
        other = apis.Pod("", "", tolerations=[apis.Toleration(
            "dedicated", "Exists")])
        pinned = SnapshotVocabulary(
            selector_keys=("zone",),
            label_vocab={("zone", "a"): 0, ("gpu.type", "pascal"): 1},
            filter_specs=(EMPTY_SPEC, pod_filter_spec(other)),
            spec_pods={pod_filter_spec(other): other})
        state, index = build_snapshot(*self.lists(), vocabulary=pinned)
        assert index.selector_keys == ["zone", "gpu.type"]
        assert index.label_vocab == {
            ("zone", "a"): 0, ("gpu.type", "pascal"): 1,
            ("gpu.type", "volta"): 2}
        assert index.vocabulary.filter_specs[:2] == pinned.filter_specs
        masks = np.asarray(state.nodes.filter_masks)
        assert masks.shape[0] == 3
        # row 1, which no pod carries, tolerates nothing of this pool;
        # row 2 is the pool's own toleration
        assert not masks[1, :6].any() and masks[1, 6:8].all()
        assert masks[2, :8].all()
        labels = np.asarray(state.nodes.labels)
        assert (labels[:8, 0] == -1).all()
        assert labels[:6, 1].tolist() == [2, 2, 2, 2, 1, 1]
        assert not index.dense_feasibility
        gi = index.gang_names.index("job-both")
        assert np.asarray(state.gangs.task_filter_class)[gi, 0] == 2
        assert np.asarray(state.gangs.task_selector)[gi, 0].tolist() == [
            -1, 1]


# ---------------------------------------------------------------------------
# Every named refusal: reached, booked, rebuilt right, and left behind
# ---------------------------------------------------------------------------


def _pending(cluster, n=1):
    out = [p for p in cluster.pods.values()
           if p.status == apis.PodStatus.PENDING][:n]
    assert len(out) == n
    return out


def _node_with_mig(c):
    c.nodes["node-0"].extended = {"nvidia.com/mig-1g.5gb": 2}


def _node_without_mig(c):
    c.nodes["node-0"].extended = {}


def _start_move(c):
    # a consolidation move in flight: the pod releases its node while a
    # Pending bind request holds its target
    name = next(p.name for p in c.pods.values()
                if p.status == apis.PodStatus.RUNNING)
    c.evict_pod(name, restart=True)
    c.create_bind_request(apis.BindRequest(name, "node-1"))


def _submit_five_subgroups(c):
    # one more than the cold build's four slots hold beside slot 0
    c.submit(apis.PodGroup("sg", queue="queue-0-0", min_member=2,
                           sub_groups=[apis.SubGroup(f"r{i}", min_member=0)
                                       for i in range(5)]),
             [apis.Pod(f"sg-{i}", "sg", apis.ResourceVec(1, 1, 4),
                       subgroup=f"r{i}") for i in range(2)])


def _add_storage_class(c):
    c.storage_classes["fast"] = apis.StorageClass("fast")


def _cordon_by_delta(c):
    intake_apply.apply_cluster_delta(c, {"nodes_upsert": [
        {"name": "node-3", "unschedulable": True,
         "allocatable": {"accel": 8.0, "cpu": 64.0, "memory": 256.0}}]})


def _swap_topology(c):
    c.topology = dataclasses.replace(c.topology)


def _drop_journal(c):
    c.journal = None


def _replace_pod_object(c):
    pod = _pending(c)[0]
    c.pods[pod.name] = dataclasses.replace(pod)


def _replace_gang_object(c):
    name = next(iter(c.pod_groups))
    c.pod_groups[name] = dataclasses.replace(c.pod_groups[name])


def _rewrite_node_allocatable(c):
    n = c.nodes["node-2"]
    n.allocatable = dataclasses.replace(n.allocatable)


def _swap_last_queues(c):
    items = list(c.queues.items())
    items[-1], items[-2] = items[-2], items[-1]
    c.queues = dict(items)


def _many_new_groups(c):
    for i in range(64):
        c.submit(apis.PodGroup(f"wave-{i}", queue="queue-0-0",
                               min_member=1), [])


def _one_wide_gang(c):
    c.submit(apis.PodGroup("wide", queue="queue-0-0", min_member=1),
             [apis.Pod(f"wide-{i}", "wide", apis.ResourceVec(1, 1, 4))
              for i in range(12)])


def _resize_pods_apart(c):
    for i, pod in enumerate(_pending(c, 6)):
        pod.resources = apis.ResourceVec(1, 1 + i, 4)
        gate.pod_touched(c.journal, pod.name)


def _bind_most(c):
    for i, pod in enumerate(_pending(c, 40)):
        c.bind_pod(pod.name, f"node-{i % 8}")


def _resubmit_deleted_pod(c):
    pod = _pending(c)[0]
    del c.pods[pod.name]
    c.submit(c.pod_groups[pod.group], [pod])


def _resubmit_deleted_group(c):
    name = next(iter(c.pod_groups))
    group = c.pod_groups.pop(name)
    c.submit(group, [])


def _evict_then_drop_pod(c):
    pod = _pending(c)[0]
    c.evict_pod(pod.name)
    del c.pods[pod.name]


def _touch_then_drop_group(c):
    name = next(iter(c.pod_groups))
    c.submit(c.pod_groups[name], [])
    del c.pod_groups[name]


def _drop_pod_unseen(c):
    del c.pods[_pending(c)[0].name]


def _drop_group_unseen(c):
    del c.pod_groups[next(reversed(c.pod_groups))]


def _submit_tolerating(c):
    # a toleration no pod of the cluster has carried: a filter spec the
    # pinned vocabulary lacks
    c.submit(apis.PodGroup("tol", queue="queue-0-0", min_member=1),
             [apis.Pod("tol-p", "tol", apis.ResourceVec(1, 1, 4),
                       tolerations=[apis.Toleration(
                           "dedicated", "Exists", effect="NoSchedule")])])


def _drop_node_unseen(c):
    del c.nodes["node-7"]


#: reason -> (what the cluster is built with, what is done to it before
#: the first refresh, the named mutation, what undoes a lasting cause).
#: A replaced ``cluster.topology`` has one name, ``topology-changed``:
#: ``_patch_blockers`` tests it before anything of the patch runs.
_REFUSALS = {
    "vocab-residue": ({}, _node_with_mig, None, _node_without_mig),
    "vocab-growth": ({}, None, _submit_tolerating, None),
    "inflight-move": ({"running_fraction": 0.5}, None, _start_move,
                      lambda c: c.tick()),
    "feature-stores": ({}, None, _add_storage_class,
                       lambda c: c.storage_classes.clear()),
    "node-dirty": ({}, None, _cordon_by_delta, None),
    "topology-changed": ({"topology_levels": (2, 2)}, None,
                         _swap_topology, None),
    "no-journal": ({}, _drop_journal, None, None),
    "pod-object-drift": ({}, None, _replace_pod_object, None),
    "gang-object-drift": ({}, None, _replace_gang_object, None),
    "node-drift": ({}, None, _rewrite_node_allocatable, None),
    "queue-order-changed": ({}, None, _swap_last_queues, None),
    "overflow-gangs": ({}, None, _many_new_groups, None),
    "overflow-subgroups": ({}, None, _submit_five_subgroups, None),
    "overflow-tasks": ({}, None, _one_wide_gang, None),
    "overflow-types": ({}, None, _resize_pods_apart, None),
    "overflow-running": ({"num_gangs": 24}, None, _bind_most, None),
    "pod-add-drift": ({}, None, _resubmit_deleted_pod, None),
    "gang-add-drift": ({}, None, _resubmit_deleted_group, None),
    "pod-removed-unjournaled": ({}, None, _evict_then_drop_pod, None),
    "gang-removed-unjournaled": ({}, None, _touch_then_drop_group, None),
    "pod-membership-drift": ({}, None, _drop_pod_unseen, None),
    "gang-membership-drift": ({}, None, _drop_group_unseen, None),
    "node-membership-drift": ({}, None, _drop_node_unseen, None),
}

#: the mutations above that the store now marks as they land
#: (``runtime/store.py``), and what became of their refusal: None, the
#: patch carries the write; else the name the journal refuses it by (a
#: name deleted and stored again inside one window moved to the end of
#: its collection).  The names themselves still refuse a write that goes
#: round the store (``tests/test_store_marks.py``).
_NOW_MARKED = {
    "pod-object-drift": None,
    "gang-object-drift": None,
    "pod-membership-drift": None,
    "gang-membership-drift": None,
    "pod-removed-unjournaled": None,
    "gang-removed-unjournaled": None,
    "pod-add-drift": "structural:pod-readded",
    "gang-add-drift": "structural:gang-readded",
}


def _assert_fresh(state, snap, cluster):
    """``state`` equals a fresh ``build_snapshot`` of the cluster as it
    stands under the pinned capacity and vocabulary, leaf for leaf."""
    from kai_scheduler_tpu.state.cluster_state import build_snapshot
    fresh, _ = build_snapshot(
        *cluster.snapshot_lists(), now=cluster.now,
        resource_claims=cluster.resource_claims,
        device_classes=cluster.device_classes,
        volume_claims=cluster.volume_claims,
        storage_classes=cluster.storage_classes,
        capacity=snap._capacity, vocabulary=snap._vocabulary)
    mine = jax.tree_util.tree_flatten_with_path(state)[0]
    ref = jax.tree_util.tree_leaves(fresh)
    assert len(mine) == len(ref)
    for (path, a), b in zip(mine, ref):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, path
        assert np.array_equal(a, b, equal_nan=a.dtype.kind == "f"), (
            jax.tree_util.keystr(path))


@pytest.mark.parametrize("reason", sorted(_REFUSALS))
def test_refusal_is_named_rebuilt_right_and_left_behind(reason):
    """Each reason ``refresh`` refuses to patch for: a named mutation
    of a small cluster reaches it, ``stats.fallbacks`` books it under
    that name, what comes back is a fresh build of the cluster as it
    now stands, and once the cause is gone a quiet cycle patches.  A
    mutation the store now marks (``_NOW_MARKED``) is patched fresh, or
    refused by the journal's name for it."""
    kw, before, mutate, undo = _REFUSALS[reason]
    cluster = build(**{"num_gangs": 8, **kw})
    snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
    if before is not None:
        before(cluster)
    refresh(snap, cluster)
    if before is None:
        # the cluster as built patches: the refusal is the mutation's
        refresh(snap, cluster)
        assert snap.stats.last["mode"] == "patched"
    booked = dict(snap.stats.fallbacks)
    if mutate is not None:
        mutate(cluster)
    state, _ = refresh(snap, cluster)
    expect = _NOW_MARKED.get(reason, reason)
    if expect is None:
        assert snap.stats.last["mode"] == "patched", snap.stats.last
        assert snap.stats.fallbacks == booked
    else:
        assert snap.stats.last["mode"] == "full"
        assert snap.stats.last["fallback_reason"] == expect
        key = expect.split(":")[0]
        assert snap.stats.fallbacks.get(key, 0) == booked.get(key, 0) + 1
    _assert_fresh(state, snap, cluster)
    if reason == "no-journal":
        # nothing to patch from, ever: the same objects under a store
        # that keeps a journal are a new document to the snapshotter
        cluster = Cluster.from_objects(*cluster.snapshot_lists())
    elif undo is not None:
        undo(cluster)
    refresh(snap, cluster)
    state, _ = refresh(snap, cluster)
    assert snap.stats.last["mode"] == "patched", snap.stats.last
    _assert_fresh(state, snap, cluster)


# ---------------------------------------------------------------------------
# The kept tables: a patch derives again what a dirty row feeds
# ---------------------------------------------------------------------------


def kept_tables(snap) -> dict:
    """What the snapshotter keeps across patches, flat: the tables by
    gang, node and leaf queue, and each live pod's device cells."""
    kept = snap._kept
    live = snap.p_live
    return dict(
        {k: v for k, v in kept.items() if k != "rollups"},
        **kept["rollups"],
        p_occ_mask=np.where(live, snap.p_occ_mask, 0),
        p_occ_held=np.where(live, snap.p_occ_held, 0))


def assert_kept_is_whole_derivation(snap) -> None:
    """The kept tables equal those of the derivation by key with every
    key touched (``_seed_kept``: nothing kept, so every entry is summed
    again from the ledgers and the current host tables).  ``verify``
    cannot see all of them: ``free`` and the quorums are clamped at 0
    over ``node_used`` and ``sub_running``, and the queue tables ship
    with their parents added."""
    by_key = kept_tables(snap)
    snap._kept = None
    snap._seed_kept(snap._host)
    whole = kept_tables(snap)
    assert by_key.keys() == whole.keys()
    for name, table in whole.items():
        np.testing.assert_array_equal(by_key[name], table, err_msg=name)


def rederived(snap) -> tuple:
    last = snap.stats.last
    return (last["touched_nodes"], last["touched_gangs"],
            last["touched_queues"], last["rederived_rows"])


def running(cluster) -> list:
    return [p for p in cluster.pods.values()
            if p.status == apis.PodStatus.RUNNING]


class TestRederivedByKey:
    """One case per way a key is touched.  ``verify=True`` holds every
    leaf and name to a fresh rebuild; each case also holds the kept
    tables to the whole derivation and checks that the patch derived
    the few keys it should, not the cluster."""

    def warm(self, **kw):
        # requests that float32 adds inexactly: order shows
        kw = dict(dict(num_nodes=8, num_gangs=12, tasks_per_gang=2,
                       running_fraction=0.75, task_cpu=0.3,
                       task_mem=1.37), **kw)
        cluster = build(**kw)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        return cluster, snap

    def patched(self, snap, cluster, n=1):
        out = refresh(snap, cluster)
        assert snap.stats.patched == n and only_cold(snap), snap.stats
        assert_kept_is_whole_derivation(snap)
        return out

    def test_empty_delta_derives_nothing(self):
        cluster, snap = self.warm()
        cluster.tick()
        host_before = snap._host
        self.patched(snap, cluster)
        assert rederived(snap) == (0, 0, 0, 0)
        # nothing was touched, so last cycle's tables came back as the
        # objects they were and nothing of them was compared or shipped
        for leaf in ("free", "releasing", "device_free"):
            assert getattr(snap._host.nodes, leaf) is getattr(
                host_before.nodes, leaf)
        assert snap._host.gangs.running_count \
            is host_before.gangs.running_count

    def test_bound_pod_turns_releasing(self):
        cluster, snap = self.warm()
        pod = running(cluster)[5]
        cluster.evict_pod(pod.name)
        state, index = self.patched(snap, cluster)
        # its node, its gang, its queue; the rows that fed them
        nodes, gangs, queues, rows = rederived(snap)
        assert (nodes, gangs, queues) == (1, 1, 1)
        assert 0 < rows < len(running(cluster)) + 1
        ni = index.node_names.index(pod.node)
        assert np.asarray(state.nodes.releasing)[ni, 0] == 1.0

    def test_pod_deleted_from_the_middle_shifts_every_later_row(self):
        cluster, snap = self.warm()
        run = running(cluster)
        gone, last = run[3], run[-1]
        before = snap._index.running_pod_names.index(last.name)
        intake_apply.apply_cluster_delta(
            cluster, {"pods_delete": [gone.name]})
        state, index = self.patched(snap, cluster)
        assert index.running_pod_names.index(last.name) == before - 1
        assert rederived(snap)[:3] == (1, 1, 1)
        # the rows that moved kept their cells
        assert np.asarray(state.running.devices_mask)[before - 1] != 0

    def test_pod_moves_node(self):
        cluster, snap = self.warm()
        pod = running(cluster)[0]
        old = pod.node
        new = next(n for n in cluster.nodes if n != old)
        pod.node = new  # direct write: the store marks it
        state, index = self.patched(snap, cluster)
        assert rederived(snap)[:3] == (2, 1, 1)  # both nodes
        free = np.asarray(state.nodes.free)
        used = {n: sum(p.resources.accel for p in running(cluster)
                       if p.node == n) for n in (old, new)}
        for n in (old, new):
            assert free[index.node_names.index(n), 0] == 8.0 - used[n]

    def test_gang_deleted_and_rows_close_up(self):
        cluster, snap = self.warm()
        names = list(cluster.pod_groups)
        delete_groups(cluster, [names[1], names[4]])
        state, index = self.patched(snap, cluster)
        assert snap.stats.last["gangs_removed"] == 2
        nodes, gangs, queues, rows = rederived(snap)
        # the gangs that went have no row left to derive: the rows
        # that closed up brought their counts with them
        assert gangs == 0 and nodes == 4 and queues == 2
        counts = np.asarray(state.gangs.running_count)
        for gi, name in enumerate(cluster.pod_groups):
            assert counts[gi] == cluster.group_running_count(name)

    def test_bind_of_a_pending_gang_in_a_queue_that_held_nothing(self):
        cluster, snap = self.warm(num_gangs=6, running_fraction=0.5,
                                  queues_per_department=4)
        empty = "queue-1-3"
        assert not any(g.queue == empty
                       for g in cluster.pod_groups.values())
        intake_apply.apply_cluster_delta(cluster, {
            "pod_groups_upsert": [
                {"name": "first", "queue": empty, "min_member": 2}],
            "pods_upsert": [
                {"name": f"first-p{t}", "group": "first",
                 "resources": {"accel": 1.0, "cpu": 0.3, "memory": 1.37}}
                for t in range(2)]})
        state, index = self.patched(snap, cluster)
        qi = index.queue_names.index(empty)
        assert np.asarray(state.queues.request)[qi, 0] == 2.0
        assert np.asarray(state.queues.allocated)[qi, 0] == 0.0
        for t in range(2):
            cluster.create_bind_request(apis.BindRequest(
                pod_name=f"first-p{t}", selected_node=f"node-{t}"))
        state, index = self.patched(snap, cluster, n=2)
        assert rederived(snap)[:3] == (2, 1, 1)
        assert np.asarray(state.queues.allocated)[qi, 0] == 2.0

    def test_eviction_moves_the_queues_parent(self):
        cluster, snap = self.warm()
        pod = running(cluster)[2]
        leaf = cluster.pod_groups[pod.group].queue
        parent = cluster.queues[leaf].parent
        pi = snap._index.queue_names.index(parent)
        before = np.asarray(snap._host.queues.allocated)[pi].copy()
        intake_apply.apply_cluster_delta(
            cluster, {"pods_delete": [pod.name]})
        state, _ = self.patched(snap, cluster)
        # one leaf derived again; the parents are summed every cycle
        assert rederived(snap)[2] == 1
        after = np.asarray(state.queues.allocated)[pi]
        assert after[0] == before[0] - 1.0 and (after < before).all()

    def test_first_fit_pod_beside_recorded_devices_gains_and_loses(self):
        """A node whose pods hold recorded devices, with one pod beside
        them that records none: that node's cells are order-dependent
        and replay the builder's loop (``_occupancy_sequential``), and
        only that node's."""
        cluster, snap = self.warm(num_gangs=4, running_fraction=1.0)
        on_node = [p for p in running(cluster) if p.node == "node-0"]
        assert len(on_node) == 1
        on_node[0].accel_devices = [3]
        for p in running(cluster):
            if p.node == "node-1":
                p.accel_devices = [0]
        cluster.journal.mark_pod(on_node[0].name)
        self.patched(snap, cluster)
        # gains: a pod bound by a bind request records no device
        submit_groups(cluster, ["late"], tasks=2)
        for t in range(2):
            cluster.create_bind_request(apis.BindRequest(
                pod_name=f"late-p{t}", selected_node="node-0"))
        state, index = self.patched(snap, cluster, n=2)
        assert rederived(snap)[0] == 1
        ni = index.node_names.index("node-0")
        # first fit goes round the recorded cell
        assert np.asarray(state.nodes.device_free)[ni].tolist() == [
            0.0, 0.0, 1.0, 0.0, 1.0, 1.0, 1.0, 1.0]
        # loses: the recorded-device pod goes, the first-fit pods stay
        intake_apply.apply_cluster_delta(
            cluster, {"pods_delete": [on_node[0].name]})
        state, index = self.patched(snap, cluster, n=3)
        assert rederived(snap)[0] == 1
        assert np.asarray(state.nodes.device_free)[ni].tolist() == [
            0.0, 0.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0]

    def test_compaction_cycle_derives_every_key(self):
        cluster, snap = self.warm(num_gangs=40, num_nodes=16)
        names = list(cluster.pod_groups)
        # churn until the ledger holds over twice its live rows
        for i in range(0, 36, 4):
            delete_groups(cluster, names[i:i + 4])
            submit_groups(cluster, [f"new-{i + k}" for k in range(4)])
            refresh(snap, cluster)
        rows = len(snap.p_objs)
        for i in range(8):
            delete_groups(cluster, [f"new-{i}"])
            refresh(snap, cluster)
            if len(snap.p_objs) < rows:
                break
            rows = len(snap.p_objs)
        else:
            pytest.fail("the pod ledger was never compacted")
        assert only_cold(snap), snap.stats
        assert_kept_is_whole_derivation(snap)
        cap = snap._capacity
        assert rederived(snap)[:3] == (cap.nodes, cap.gangs, cap.queues)
        # and the cycle after is by key again
        cluster.tick()
        refresh(snap, cluster)
        assert rederived(snap) == (0, 0, 0, 0)


@pytest.mark.parametrize("shape", ["flat", "racks", "wide_gangs"])
def test_twenty_cycles_of_churn_keep_the_tables_whole(shape):
    """``churn``-like deltas at 1 000 nodes: gangs finish (pods, bind
    requests and groups deleted), gangs arrive, last cycle's arrivals
    are bound, a few pods are evicted and reported deleted.  After every
    patch the kept tables equal the derivation with every key
    touched."""
    kw = {"flat": {}, "racks": dict(topology_levels=(4, 10)),
          "wide_gangs": dict(tasks_per_gang=8, num_gangs=375)}[shape]
    kw = dict(dict(num_nodes=1000, num_gangs=1500, tasks_per_gang=2,
                   running_fraction=1.0, queues_per_department=50,
                   task_cpu=0.3, task_mem=1.37), **kw)
    cluster = build(**kw)
    tasks = kw["tasks_per_gang"]
    snap = IncrementalSnapshotter(verify=True)
    refresh(snap, cluster)
    rng = np.random.default_rng(35)
    leaves = [q.name for q in cluster.queues.values() if q.parent]
    pending: list[str] = []
    for cycle in range(20):
        placed = [g for g in cluster.pod_groups if any(
            p.node for p in cluster.pods_of_group(g))]
        delete_groups(cluster, [placed[i] for i in rng.choice(
            len(placed), size=6, replace=False)])
        for name in pending:
            if name in cluster.pods:
                cluster.create_bind_request(apis.BindRequest(
                    pod_name=name,
                    selected_node=f"node-{rng.integers(1000)}"))
        evicted = [p.name for p in cluster.pods.values()
                   if p.status == apis.PodStatus.RELEASING]
        for p in rng.choice(running(cluster), size=3, replace=False):
            cluster.evict_pod(p.name)
        new = [f"job-{cycle}-{i}" for i in range(6)]
        intake_apply.apply_cluster_delta(cluster, {
            "now": float(cycle + 1), "pods_delete": evicted,
            "pod_groups_upsert": [
                {"name": n, "queue": leaves[int(rng.integers(len(leaves)))],
                 "min_member": tasks} for n in new],
            "pods_upsert": [
                {"name": f"{n}-p{t}", "group": n,
                 "resources": {"accel": 1.0, "cpu": 0.1 * (1 + t % 7),
                               "memory": 1.37}}
                for n in new for t in range(tasks)]})
        pending = [f"{n}-p{t}" for n in new for t in range(tasks)]
        refresh(snap, cluster)
        if snap.stats.last["mode"] == "patched":
            nodes, gangs, queues, rows = rederived(snap)
            assert 0 < rows < len(running(cluster)) // 2
            assert nodes < 100 and queues < 30
        assert_kept_is_whole_derivation(snap)
    # the first arrivals outgrow the pinned task axis, once
    assert snap.stats.patched >= 18, snap.stats


def _random_sections(rng, N=60, Q=40, G=120, T=5, M=3000):
    """Running and pending tables with requests float32 adds inexactly,
    over a three-level queue tree (4 roots, 12 middles, 24 leaves)."""
    R = apis.NUM_RESOURCES
    parent = np.full((Q,), -1, np.int32)
    parent[4:16] = np.arange(12) % 4
    parent[16:] = 4 + np.arange(24) % 12
    depth = np.where(parent < 0, 0, np.where(parent < 4, 1, 2)).astype(
        np.int32)
    rk = dict(
        valid=rng.random(M) < 0.9,
        node=rng.integers(-1, N, M).astype(np.int32),
        queue=rng.integers(4, Q, M).astype(np.int32),
        req=rng.random((M, R)).astype(np.float32),
        releasing=rng.random(M) < 0.2,
        preemptible=rng.random(M) < 0.5)
    gk = dict(
        task_req=rng.random((G, T, R)).astype(np.float32),
        task_valid=rng.random((G, T)) < 0.6,
        queue=rng.integers(16, Q, G).astype(np.int32),
        valid=rng.random(G) < 0.8,
        task_extended=np.zeros((G, T, 0), np.float32))
    fixed = dict(
        node_alloc=np.full((N, R), 64.0, np.float32),
        claim_used=np.zeros((N, R), np.float32),
        g_of_ext=np.zeros((0,), np.float32),
        r_mig=np.zeros((M,), np.float32), queue_usage=None, q_index={},
        q_parent=parent, q_depth=depth, num_queues=Q)
    return rk, gk, fixed


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_rollups_by_key_equal_the_whole_and_the_loop(seed):
    """``derive_rollups`` sums a touched key again from its members in
    row order, and the parents by depth level: both must give, to the
    bit, what summing every key gives and what the loop over every
    queue that stood there gave."""
    from kai_scheduler_tpu.state.cluster_state import derive_rollups
    rng = np.random.default_rng(seed)
    rk, gk, fixed = _random_sections(rng)
    whole = derive_rollups(rk=rk, gk=gk, **fixed)
    # the loop the level sums replaced, kept here as the reference
    parent, depth = fixed["q_parent"], fixed["q_depth"]
    for name in ("q_alloc", "q_alloc_np", "q_request"):
        ref = whole["kept"][name].copy()
        for i in sorted(range(len(parent)), key=lambda i: -depth[i]):
            if parent[i] >= 0:
                ref[parent[i]] += ref[i]
        np.testing.assert_array_equal(whole[name], ref, err_msg=name)
    # move some running rows and some pending gangs; derive their keys
    rows = rng.choice(len(rk["node"]), 40, replace=False)
    gangs = rng.choice(len(gk["queue"]), 6, replace=False)
    touched_nodes = np.zeros(fixed["node_alloc"].shape[0], bool)
    touched_queues = np.zeros(len(parent), bool)
    touched_nodes[rk["node"][rows][rk["node"][rows] >= 0]] = True
    touched_queues[rk["queue"][rows]] = True
    touched_queues[gk["queue"][gangs]] = True
    rk["node"][rows] = rng.integers(-1, len(touched_nodes), len(rows))
    rk["queue"][rows] = rng.integers(4, len(parent), len(rows))
    rk["req"][rows] = rng.random((len(rows), 3)).astype(np.float32)
    rk["releasing"][rows] = ~rk["releasing"][rows]
    gk["task_valid"][gangs] = ~gk["task_valid"][gangs]
    touched_nodes[rk["node"][rows][rk["node"][rows] >= 0]] = True
    touched_queues[rk["queue"][rows]] = True
    assert not touched_nodes.all() and not touched_queues.all()
    by_key = derive_rollups(rk=rk, gk=gk, **fixed, kept=whole["kept"],
                            touched_nodes=touched_nodes,
                            touched_queues=touched_queues)
    again = derive_rollups(rk=rk, gk=gk, **fixed)
    for name in ("node_free", "node_rel", "q_alloc", "q_alloc_np",
                 "q_request", "q_usage"):
        np.testing.assert_array_equal(by_key[name], again[name],
                                      err_msg=name)
    for name, table in again["kept"].items():
        np.testing.assert_array_equal(by_key["kept"][name], table,
                                      err_msg=name)
    # no key touched: last cycle's tables come back as they were
    same = derive_rollups(
        rk=rk, gk=gk, **fixed, kept=again["kept"],
        touched_nodes=np.zeros_like(touched_nodes),
        touched_queues=np.zeros_like(touched_queues))
    assert all(same["kept"][k] is v for k, v in again["kept"].items())


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
        store directly (no explicit mark) must still flip the
        presentation back — the store marks the BR table too."""
        cluster = build(num_nodes=4, num_gangs=4, tasks_per_gang=2)
        snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
        refresh(snap, cluster)
        pod = next(p for p in cluster.pods.values()
                   if p.status == apis.PodStatus.PENDING)
        cluster.create_bind_request(apis.BindRequest(
            pod_name=pod.name, selected_node="node-0"))
        state, _ = refresh(snap, cluster)
        assert int(np.asarray(state.running.valid).sum()) == 1
        cluster.bind_requests.clear()  # direct, marked by the store
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
    raced it — and a dropped mark for an in-place write of a field the
    store does not watch (e.g. pod priority) silently serves a stale
    snapshot."""

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
            # store cannot attribute without the journal entry
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
