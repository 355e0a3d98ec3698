"""The store marks its own writes (``runtime/store.py``).

Every write the incremental snapshot depends on marks the journal as it
lands: a mutation of ``Cluster.pods``, ``pod_groups``, ``nodes`` or
``bind_requests``, and a write to a watched field of what they hold
(``apis.types.WATCHED_FIELDS``).  Nothing compares the cluster with the
snapshotter's ledger any more, so each write here is made the way a test
or a controller makes it — directly, with no explicit mark — and the
next refresh under ``verify=True`` is either patched and equal to a
fresh ``build_snapshot``, or refused by its name and rebuilt fresh.
"""
import ast
import copy
import dataclasses
import inspect
import textwrap

import jax
import numpy as np
import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.controllers.podgroup_controller import (
    PodGroupController)
from kai_scheduler_tpu.framework.scheduler import Scheduler, SchedulerConfig
from kai_scheduler_tpu.intake import gate
from kai_scheduler_tpu.runtime.cluster import STORE_COLLECTIONS, Cluster
from kai_scheduler_tpu.runtime.status_updater import AsyncStatusUpdater
from kai_scheduler_tpu.state import incremental, make_cluster
from kai_scheduler_tpu.state.cluster_state import build_snapshot
from kai_scheduler_tpu.state.incremental import IncrementalSnapshotter

pytestmark = pytest.mark.core

PENDING, RUNNING = apis.PodStatus.PENDING, apis.PodStatus.RUNNING


def build() -> Cluster:
    """Eight nodes under a two-level tree, four running gangs and four
    pending, and one pending pod presented bound by a bind request."""
    c = Cluster.from_objects(*make_cluster(
        num_nodes=8, num_gangs=8, tasks_per_gang=2, running_fraction=0.5,
        topology_levels=(2, 2)))
    c.create_bind_request(apis.BindRequest(_pending_pod(c).name, "node-3"))
    return c


def refresh(snap, cluster):
    return snap.refresh(cluster, now=cluster.now)


def warm(cluster) -> IncrementalSnapshotter:
    snap = IncrementalSnapshotter(verify=True, dirty_threshold=1.0)
    refresh(snap, cluster)
    refresh(snap, cluster)
    assert snap.stats.last["mode"] == "patched"
    return snap


def assert_fresh(state, snap, cluster) -> None:
    fresh, _ = build_snapshot(
        *cluster.snapshot_lists(), now=cluster.now,
        capacity=snap._capacity, vocabulary=snap._vocabulary)
    mine = jax.tree_util.tree_flatten_with_path(state)[0]
    ref = jax.tree_util.tree_leaves(fresh)
    assert len(mine) == len(ref)
    for (path, a), b in zip(mine, ref):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, path
        assert np.array_equal(a, b, equal_nan=a.dtype.kind == "f"), (
            jax.tree_util.keystr(path))


def served(snap, cluster, refused: str | None = None) -> dict:
    """The next refresh patches (``refused`` None) or rebuilds for
    ``refused``; either way it is fresh, and a quiet one after it
    patches.  Returns what the store marked for the first."""
    state, _ = refresh(snap, cluster)
    last = snap.stats.last
    if refused is None:
        assert last["mode"] == "patched", last
    else:
        assert last["mode"] == "full" and last["fallback_reason"] == refused
    assert_fresh(state, snap, cluster)
    marks = last["store_marks"]
    state, _ = refresh(snap, cluster)
    assert snap.stats.last["mode"] == "patched", snap.stats.last
    assert snap.stats.last["store_marks"] == dict.fromkeys(marks, 0)
    assert_fresh(state, snap, cluster)
    return marks


def _pending_pod(c, i=0):
    return [p for p in c.pods.values()
            if p.status == PENDING and p.name not in c.bind_requests][i]


def _running_pod(c):
    return next(p for p in c.pods.values() if p.status == RUNNING)


def _pending_gang(c):
    return c.pod_groups[_pending_pod(c).group]


def _running_gang(c):
    return c.pod_groups[_running_pod(c).group]


def _node(c):
    return c.nodes["node-5"]


def _bind_request(c):
    return next(iter(c.bind_requests.values()))


#: every watched field: the stored object it is written on, and a value
#: the snapshot reads differently
_WRITES = {
    (apis.Pod, "status"): (_running_pod, apis.PodStatus.RELEASING),
    (apis.Pod, "node"): (_running_pod, "node-7"),
    (apis.PodGroup, "unschedulable"): (_pending_gang, True),
    (apis.PodGroup, "last_start_timestamp"): (_pending_gang, 5.0),
    (apis.PodGroup, "stale_since"): (_running_gang, 3.0),
    (apis.PodGroup, "topology_constraint"): (
        _pending_gang, apis.TopologyConstraint(
            topology="default", required_level="topo/level1")),
    (apis.PodGroup, "sub_groups"): (
        _pending_gang, [apis.SubGroup("a", min_member=1)]),
    (apis.Node, "allocatable"): (_node, apis.ResourceVec(4, 64, 256)),
    (apis.Node, "labels"): (_node, {"kubernetes.io/hostname": "node-5",
                                    "topo/level0": "level0-1",
                                    "topo/level1": "level1-0"}),
    (apis.Node, "taints"): (_node, [apis.Taint("dedicated")]),
    (apis.Node, "extended"): (_node, {}),
    (apis.Node, "accel_memory_gib"): (_node, 32.0),
    (apis.Node, "unschedulable"): (_node, True),
    (apis.BindRequest, "phase"): (_bind_request, "Failed"),
    (apis.BindRequest, "selected_node"): (_bind_request, "node-6"),
}

#: the name each class's store counts its marks under
_COUNTED = {apis.Pod: "pods", apis.PodGroup: "gangs", apis.Node: "nodes",
            apis.BindRequest: "bind_requests"}

_WATCHED = [(cls, name) for cls, fields in apis.WATCHED_FIELDS.items()
            for name, _by_identity in fields]


def test_every_watched_field_has_a_write_here():
    assert sorted(_WRITES, key=str) == sorted(_WATCHED, key=str)


@pytest.mark.parametrize("cls,name", _WATCHED,
                         ids=[f"{c.__name__}.{n}" for c, n in _WATCHED])
def test_watched_field_write_is_served_fresh_or_refused(cls, name):
    """A node written in place rebuilds, as ``node-drift``; anything
    else the store marks is patched."""
    cluster = build()
    snap = warm(cluster)
    pick, value = _WRITES[cls, name]
    obj = pick(cluster)
    setattr(obj, name, value)
    marks = served(snap, cluster,
                   "node-drift" if cls is apis.Node else None)
    assert marks[_COUNTED[cls]] == 1
    assert sum(marks.values()) == 1


def _equal(value):
    """``value`` again, as another object where a value can be one."""
    if isinstance(value, str):
        return "".join(list(value))
    if type(value) is float:
        return value + 0.0
    return value


@pytest.mark.parametrize("cls,name", _WATCHED,
                         ids=[f"{c.__name__}.{n}" for c, n in _WATCHED])
def test_writing_what_is_there_marks_nothing(cls, name):
    cluster = build()
    snap = warm(cluster)
    obj = _WRITES[cls, name][0](cluster)
    by_identity = dict(apis.WATCHED_FIELDS[cls])[name]
    old = getattr(obj, name)
    setattr(obj, name, old if by_identity else _equal(old))
    refresh(snap, cluster)
    last = snap.stats.last
    assert last["mode"] == "patched"
    assert last["dirty_pods"] == last["dirty_gangs"] == 0
    assert sum(last["store_marks"].values()) == 0


# -- the collections' mutators ----------------------------------------------


def _new(c, coll):
    """An object ``coll`` does not hold yet, and its key."""
    if coll == "pods":
        pod = apis.Pod("late-0", _pending_gang(c).name,
                       apis.ResourceVec(1, 1, 4))
        return pod.name, pod
    if coll == "pod_groups":
        return "late", apis.PodGroup("late", queue="queue-0-0")
    if coll == "bind_requests":
        name = _pending_pod(c, 1).name
        return name, apis.BindRequest(name, "node-2")
    node = dataclasses.replace(c.nodes["node-0"], name="node-late", labels={
        **c.nodes["node-0"].labels, "kubernetes.io/hostname": "node-late"})
    return node.name, node


def _old(c, coll):
    """A key ``coll`` holds."""
    return {"pods": lambda: _pending_pod(c).name,
            "pod_groups": lambda: _pending_gang(c).name,
            "bind_requests": lambda: next(iter(c.bind_requests)),
            "nodes": lambda: "node-5"}[coll]()


def _setitem_new(c, store, coll):
    store.__setitem__(*_new(c, coll))


def _setitem_over(c, store, coll):
    key = _old(c, coll)
    store[key] = dataclasses.replace(store[key])


def _delitem(c, store, coll):
    del store[_old(c, coll)]


def _pop(c, store, coll):
    store.pop(_old(c, coll))


def _popitem(c, store, coll):
    store.popitem()


def _update(c, store, coll):
    store.update(dict([_new(c, coll)]))


def _setdefault(c, store, coll):
    store.setdefault(*_new(c, coll))


def _ior(c, store, coll):
    store |= dict([_new(c, coll)])
    setattr(c, coll, store)  # what ``c.pods |= ...`` does after


def _clear(c, store, coll):
    store.clear()


_MUTATORS = {f.__name__[1:]: f for f in (
    _setitem_new, _setitem_over, _delitem, _pop, _popitem, _update,
    _setdefault, _ior, _clear)}

#: how a node mutation is refused: a node stored over another is the
#: delta's ``node`` mark; any other changes the node set
_NODE_REFUSAL = {"setitem_over": "node-dirty"}


@pytest.mark.parametrize("how", sorted(_MUTATORS))
@pytest.mark.parametrize("coll", STORE_COLLECTIONS)
def test_collection_mutator_is_served_fresh_or_refused(coll, how):
    cluster = build()
    snap = warm(cluster)
    _MUTATORS[how](cluster, getattr(cluster, coll), coll)
    refused = None
    if coll == "nodes":
        refused = _NODE_REFUSAL.get(how, "node-membership-drift")
    marks = served(snap, cluster, refused)
    counted = gate.STORE_MARKS[coll][1]
    assert marks[counted] >= 1
    assert sum(marks.values()) == marks[counted]


@pytest.mark.parametrize("coll", STORE_COLLECTIONS)
def test_a_collection_replaced_whole_rebuilds(coll):
    cluster = build()
    snap = warm(cluster)
    old = getattr(cluster, coll)
    setattr(cluster, coll, dict(old))
    served(snap, cluster, f"structural:{coll}-replaced")
    # the old collection is no longer part of the store: its objects,
    # held by the new one, mark only through it
    obj = next(iter(getattr(cluster, coll).values()))
    assert old.journal is None and len(obj._held_by) == 2


# -- writes that go round the store still refuse by name ----------------------


def _round_the_store_pod(c):
    dict.__delitem__(c.pods, _pending_pod(c).name)


def _round_the_store_gang(c):
    dict.__delitem__(c.pod_groups, next(reversed(c.pod_groups)))


def _round_the_store_node(c):
    dict.__delitem__(c.nodes, "node-7")


def _marked_then_gone_pod(c):
    name = _pending_pod(c).name
    gate.pod_touched(c.journal, name)
    dict.__delitem__(c.pods, name)


def _marked_then_gone_gang(c):
    name = _pending_gang(c).name
    gate.gang_touched(c.journal, name)
    dict.__delitem__(c.pod_groups, name)


def _marked_added_pod(c):
    gate.pod_added(c.journal, _pending_pod(c).name)


def _marked_added_gang(c):
    gate.gang_added(c.journal, _pending_gang(c).name)


#: a write the store cannot see (round its methods), or an explicit mark
#: the store contradicts -> the name the snapshotter refuses it by
_ROUND_THE_STORE = {
    "pod-membership-drift": _round_the_store_pod,
    "gang-membership-drift": _round_the_store_gang,
    "node-membership-drift": _round_the_store_node,
    "pod-removed-unjournaled": _marked_then_gone_pod,
    "gang-removed-unjournaled": _marked_then_gone_gang,
    "pod-add-drift": _marked_added_pod,
    "gang-add-drift": _marked_added_gang,
}


@pytest.mark.parametrize("reason", sorted(_ROUND_THE_STORE))
def test_a_write_round_the_store_refuses_by_name(reason):
    cluster = build()
    snap = warm(cluster)
    _ROUND_THE_STORE[reason](cluster)
    served(snap, cluster, reason)


# -- who holds an object ------------------------------------------------------


def test_an_object_in_two_stores_marks_both_and_a_copy_neither():
    one = build()
    del one.bind_requests[next(iter(one.bind_requests))]
    two = Cluster.from_objects(*one.snapshot_lists())
    snaps = [warm(one), warm(two)]
    twin = copy.deepcopy(one)
    twin_cursor = twin.journal.register()
    pod = _running_pod(one)
    assert two.pods[pod.name] is pod
    assert twin.pods[pod.name] is not pod
    pod.node = "node-7"
    for snap, cluster in zip(snaps, (one, two)):
        assert served(snap, cluster)["pods"] == 1
    assert not twin_cursor.consume().pods_dirty
    # a deep-copied twin's object marks its own store only, and a copy
    # made by ``dataclasses.replace`` (a presentation) marks nothing
    twin.pods[pod.name].node = "node-6"
    dataclasses.replace(pod).node = "node-6"
    copy.copy(pod).node = "node-6"
    for snap, cluster in zip(snaps, (one, two)):
        refresh(snap, cluster)
        assert sum(snap.stats.last["store_marks"].values()) == 0
        assert snap.stats.last["dirty_pods"] == 0
    assert twin_cursor.consume().pods_dirty == {pod.name}


# -- the program's own writers, with nothing to sweep after them -------------


def _scheduler(pool):
    return Scheduler(SchedulerConfig(verify_incremental=True,
                                     incremental_dirty_threshold=1.0),
                     status_updater=pool)


def _cycle(sched, pool, cluster) -> dict:
    sched.run_once(cluster)
    assert pool.flush()
    return sched._snapshotter.stats.last


def test_status_pool_fail_and_podgroup_controller_are_served_fresh():
    """``Scheduler._record_fit_status``'s ``fail`` closure on the status
    pool, and ``PodGroupController.reconcile``, write a pod group in
    place; the cycle after each patches, and ``verify_incremental``
    holds it to a fresh build."""
    nodes = [apis.Node("n0", apis.ResourceVec(2, 64, 256))]
    queues = [apis.Queue("q", accel=apis.QueueResource(quota=100))]
    groups = [apis.PodGroup("huge", queue="q", min_member=1,
                            scheduling_backoff=1),
              apis.PodGroup("fits", queue="q", min_member=1)]
    pods = [apis.Pod("hp", "huge", apis.ResourceVec(16, 1, 1)),
            apis.Pod("fp", "fits", apis.ResourceVec(1, 1, 1))]
    cluster = Cluster.from_objects(nodes, queues, groups, pods)
    pool = AsyncStatusUpdater(workers=2)
    sched, ctl = _scheduler(pool), PodGroupController()
    huge = cluster.pod_groups["huge"]
    ctl.reconcile(cluster)
    _cycle(sched, pool, cluster)  # cold: ``fail`` marks ``huge``
    assert huge.unschedulable
    last = _cycle(sched, pool, cluster)
    assert last["mode"] == "patched" and last["store_marks"]["gangs"] == 1
    # a second pending pod clears the condition in the controller
    cluster.pods["hp2"] = apis.Pod("hp2", "huge", apis.ResourceVec(1, 1, 1))
    ctl.reconcile(cluster)
    assert not huge.unschedulable
    last = _cycle(sched, pool, cluster)
    assert last["mode"] == "patched"
    assert last["store_marks"]["pods"] == 1  # hp2
    assert last["store_marks"]["gangs"] == 1  # the controller's clear


def test_status_pool_reset_is_served_fresh():
    """A gang that failed to fit and then places has its status reset
    by the ``reset`` closure: ``unschedulable`` is written False over
    False and marks nothing, and the cycles after it patch."""
    nodes = [apis.Node("n0", apis.ResourceVec(2, 64, 256))]
    queues = [apis.Queue("q", accel=apis.QueueResource(quota=100))]
    groups = [apis.PodGroup("old", queue="q", min_member=1),
              apis.PodGroup("later", queue="q", min_member=1)]
    pods = [apis.Pod("op", "old", apis.ResourceVec(1, 1, 1),
                     status=RUNNING, node="n0"),
            apis.Pod("lp", "later", apis.ResourceVec(2, 1, 1))]
    cluster = Cluster.from_objects(nodes, queues, groups, pods)
    pool = AsyncStatusUpdater(workers=2)
    sched = _scheduler(pool)
    later = cluster.pod_groups["later"]
    _cycle(sched, pool, cluster)  # cold: ``fail`` counts a failure
    assert later.fit_failures == 1 and not later.unschedulable
    cluster.evict_pod("op")
    cluster.tick()  # the running pod is gone: ``later`` fits now
    last = _cycle(sched, pool, cluster)
    assert last["mode"] == "patched" and "lp" in cluster.bind_requests
    assert later.fit_failures == 0  # ``reset`` ran
    last = _cycle(sched, pool, cluster)
    assert last["mode"] == "patched"
    assert last["store_marks"]["gangs"] == 0


# -- the watched set, in one place --------------------------------------------


#: the fields the snapshotter compared with its ledger every cycle before
#: the store marked its writes; each must stay watched
_COMPARED_BY_THE_SWEEP = {
    apis.Pod: {"status", "node"},
    apis.PodGroup: {"unschedulable", "last_start_timestamp", "stale_since",
                    "topology_constraint", "sub_groups"},
    apis.Node: {"allocatable", "labels", "taints", "extended",
                "accel_memory_gib", "unschedulable"},
    apis.BindRequest: {"phase", "selected_node"},
}

#: the fields the patch's encoders read that no store watches: the spec,
#: changed by storing another object under the key, and what a writer
#: changes in place with an explicit mark (``accel_devices``, by
#: ``Cluster.bind_pod`` and ``tick``)
_READ_UNWATCHED = {
    apis.Pod: {"name", "group", "subgroup", "resources", "priority",
               "creation_timestamp", "labels", "node_selector",
               "tolerations", "accel_devices", "node_affinity",
               "pod_affinity", "extended", "resource_claims",
               "volume_claims", "host_ports", "nominated_node",
               "accel_portion", "accel_memory_gib", "dra_accel_count"},
    apis.PodGroup: {"name", "queue", "min_member", "priority",
                    "preemptibility"},
    apis.BindRequest: set(),
}

#: the encoders, and the parameter or local each reads an object through
_ENCODERS = {
    "_encode_pod": {"pod": apis.Pod, "br": apis.BindRequest},
    "_encode_gang": {"g": apis.PodGroup},
    "_is_plain_pod": {"pod": apis.Pod},
    "_slot_of": {"pod": apis.Pod},
}


def _reads(fn, names: dict) -> dict:
    """The attributes read off each of ``names`` in ``fn``'s source."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
    out: dict = {cls: set() for cls in names.values()}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in names):
            out[names[node.value.id]].add(node.attr)
    return out


def test_every_field_the_sweep_compared_is_watched():
    for cls, compared in _COMPARED_BY_THE_SWEEP.items():
        watched = {name for name, _ in apis.WATCHED_FIELDS[cls]}
        assert compared <= watched, cls.__name__


def test_every_field_an_encoder_reads_is_watched_or_named_unwatched():
    """A field the encoders come to read fails here until it is either
    hooked (``WATCHED_FIELDS``) or named above as one its writers
    replace the object for, or mark by hand."""
    for fname, names in _ENCODERS.items():
        fn = getattr(IncrementalSnapshotter, fname, None) or getattr(
            incremental, fname)
        for cls, read in _reads(fn, names).items():
            watched = {name for name, _ in apis.WATCHED_FIELDS[cls]}
            fields = {f.name for f in dataclasses.fields(cls)}
            assert read <= watched | _READ_UNWATCHED[cls], (
                fname, cls.__name__, read - watched - _READ_UNWATCHED[cls])
            assert read <= fields | {"as_tuple"}, (fname, read - fields)
