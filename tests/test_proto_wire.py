"""Sidecar protobuf wire protocol (SURVEY §7d; VERDICT r3 item 7).

The same endpoints the JSON sidecar uses accept/emit the typed protobuf
schema of ``wire/sidecar.proto`` when Content-Type is
``application/x-protobuf``: upload a ClusterDoc, PATCH ClusterDeltas,
drive cycles, get CommitSets back.
"""
import urllib.request

import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.framework.server import SchedulerServer
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.wire import codec
from kai_scheduler_tpu.wire import sidecar_pb2 as pb


def _cluster():
    nodes = [apis.Node(name=f"n{i}",
                       allocatable=apis.ResourceVec(4.0, 64.0, 256.0),
                       labels={"kubernetes.io/hostname": f"n{i}"})
             for i in range(2)]
    queues = [apis.Queue(name="dept"),
              apis.Queue(name="q0", parent="dept",
                         accel=apis.QueueResource(quota=8.0))]
    groups = [apis.PodGroup(name="g0", queue="q0", min_member=2)]
    pods = [apis.Pod(name=f"g0-{i}", group="g0",
                     resources=apis.ResourceVec(1.0, 1.0, 1.0),
                     labels={"app": "x"},
                     tolerations=[apis.Toleration(key="k")],
                     pod_affinity=[apis.PodAffinityTerm(
                         match_labels=(("app", "x"),), anti=False,
                         required=False)])
            for i in range(2)]
    return Cluster.from_objects(nodes, queues, groups, pods, None)


def _post(port, path, msg, resp_cls):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=msg.SerializeToString(),
        headers={"Content-Type": "application/x-protobuf"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        assert resp.headers["Content-Type"] == "application/x-protobuf"
        out = resp_cls()
        out.ParseFromString(resp.read())
        return out


def test_codec_roundtrip_preserves_objects():
    cluster = _cluster()
    doc = codec.cluster_to_msg(cluster)
    back = codec.cluster_from_msg(doc)
    assert sorted(back.nodes) == sorted(cluster.nodes)
    p0 = back.pods["g0-0"]
    assert p0.tolerations[0].key == "k"
    assert p0.pod_affinity[0].match_labels == (("app", "x"),)
    assert back.pod_groups["g0"].min_member == 2
    assert back.queues["q0"].accel.quota == 8.0


def test_cycle_roundtrip_through_proto_framing():
    """Upload the cluster as proto, run a cycle, check the CommitSet —
    and that the commit matches the JSON wire's result."""
    cluster = _cluster()
    server = SchedulerServer(_cluster()).start()
    try:
        doc = codec.cluster_to_msg(cluster)
        commit = _post(server.port, "/cycle", doc, pb.CommitSet)
        binds = {b.pod_name: b.selected_node for b in commit.bind_requests}
        assert set(binds) == {"g0-0", "g0-1"}
        assert all(n in ("n0", "n1") for n in binds.values())
        assert len(commit.evictions) == 0
    finally:
        server.stop()


def test_stored_cluster_and_delta_through_proto():
    server = SchedulerServer(_cluster()).start()
    try:
        cluster = _cluster()
        _post(server.port, "/cluster", codec.cluster_to_msg(cluster),
              pb.CommitSet)
        # delta: add a second gang (complete objects)
        delta = pb.ClusterDelta()
        codec.to_msg(apis.PodGroup(name="g1", queue="q0", min_member=1),
                     delta.pod_groups_upsert.add())
        codec.to_msg(apis.Pod(name="g1-0", group="g1",
                              resources=apis.ResourceVec(1.0, 1.0, 1.0)),
                     delta.pods_upsert.add())
        delta.now = 5.0
        _post(server.port, "/cluster/delta", delta, pb.CommitSet)
        commit = _post(server.port, "/cycle/stored", pb.ClusterDoc(),
                       pb.CommitSet)
        binds = {b.pod_name for b in commit.bind_requests}
        assert "g1-0" in binds and "g0-0" in binds
    finally:
        server.stop()


def test_proto_delta_group_delete_is_a_patchable_mark():
    """The proto delta applier marks through the same gate as the JSON
    one: a pod-group delete is ``gang_removed``, not structural, and the
    snapshot of the cycle after it patches."""
    from kai_scheduler_tpu.state.incremental import IncrementalSnapshotter
    plain = _cluster()
    cluster = Cluster.from_objects(
        list(plain.nodes.values()), list(plain.queues.values()),
        [apis.PodGroup(name=g, queue="q0", min_member=2)
         for g in ("g0", "g1")],
        [apis.Pod(name=f"{g}-{i}", group=g,
                  resources=apis.ResourceVec(1.0, 1.0, 1.0))
         for g in ("g0", "g1") for i in range(2)], None)
    snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
    snap.refresh(cluster, now=cluster.now)
    cursor = cluster.journal.register()
    delta = pb.ClusterDelta()
    delta.pod_groups_delete.append("g0")
    delta.pods_delete.extend(["g0-0", "g0-1"])
    codec.apply_delta_msg(cluster, delta)
    batch = cursor.consume()
    assert batch.gangs_removed == {"g0"} and not batch.structural
    assert batch.pods_removed == {"g0-0", "g0-1"}
    _, index = snap.refresh(cluster, now=cluster.now)
    assert snap.stats.last["mode"] == "patched"
    assert snap.stats.last["gangs_removed"] == 1
    assert index.gang_names[0] == "g1"
