"""Served cycles against the plain placement oracle
(``tests/oracles/placement_oracle.py``: upstream ``TaintToleration`` and
``nodeSelector`` over the wire's documents).

Clusters as accelerator pools are deployed: tainted nodes of two GPU
types named by a label, a few untainted nodes; gangs that tolerate the
taint or do not, that select a type or none.  Intake takes the generic
parser for a tolerating pod, the cold build numbers the filter specs
and the selector key, every refresh after it patches against that
pinned vocabulary (``state/incremental.py``), and the device places by
selector match, filter-class mask and feasible rank
(``dense_feasibility`` false).  The default wavefront and the ``B=1``
sequential scan are both held to the oracle, and to each other.
"""
import functools
import importlib.util
import json
import os
import urllib.request

import numpy as np
import pytest

from oracles import placement_oracle as oracle

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.framework.scheduler import Scheduler, SchedulerConfig
from kai_scheduler_tpu.framework.server import SchedulerServer
from kai_scheduler_tpu.framework.session import SessionConfig
from kai_scheduler_tpu.intake import apply as intake_apply
from kai_scheduler_tpu.ops.allocate import AllocateConfig
from kai_scheduler_tpu.ops.victims import VictimConfig
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.runtime.snapshot import load_cluster

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TAINT = {"key": "nvidia.com/gpu", "value": "present", "effect": "NoSchedule"}
TOLERATION = {"key": "nvidia.com/gpu", "operator": "Exists",
              "effect": "NoSchedule"}
TASKS = 4
#: (tolerates the taint, the GPU type it selects)
KINDS = {"tv": (True, "volta"), "tp": (True, "pascal"), "tn": (True, None),
         "nn": (False, None), "nv": (False, "volta")}
#: of every 64 nodes: tainted volta, tainted pascal; the rest untainted
SPLITS = {"3to1": (42, 14), "1to1": (28, 28), "1to3": (14, 42)}


def _gang(name, kind, queue, created, nodes=None):
    tolerating, gpu = KINDS[kind]
    group = {"name": name, "queue": queue, "min_member": TASKS,
             "priority": 0, "preemptibility": "Preemptible",
             "phase": "Pending", "creation_timestamp": created,
             "last_start_timestamp": 0.0 if nodes else None}
    pods = []
    for t in range(TASKS):
        pod = {"name": f"{name}-pod-{t}", "group": name, "status": 0,
               "resources": {"accel": 1.0, "cpu": 1.0, "memory": 4.0},
               "creation_timestamp": created}
        if tolerating:
            pod["tolerations"] = [dict(TOLERATION)]
        if gpu:
            pod["node_selector"] = {"gpu.type": gpu}
        if nodes:
            pod["status"], pod["node"] = 2, nodes[t]
        pods.append(pod)
    return group, pods


def _cluster(seed, split, nodes=64, fill_pascal=False):
    """Nodes of 4 accelerators dealt into pools by the seed; running
    gangs hold half of each tainted pool (all of ``pascal`` with
    ``fill_pascal``)."""
    rng = np.random.default_rng(seed)
    volta, pascal = (share * nodes // 64 for share in SPLITS[split])
    dealt = [int(i) for i in rng.permutation(nodes)]
    pools = {"volta": dealt[:volta], "pascal": dealt[volta:volta + pascal],
             None: dealt[volta + pascal:]}
    node_docs = [{"name": f"node-{i}",
                  "allocatable": {"accel": 4.0, "cpu": 32.0,
                                  "memory": 128.0},
                  "labels": {"kubernetes.io/hostname": f"node-{i}"}}
                 for i in range(nodes)]
    for gpu, members in pools.items():
        for i in members:
            if gpu:
                node_docs[i]["labels"]["gpu.type"] = gpu
                node_docs[i]["taints"] = [dict(TAINT)]
    res = {"quota": -1.0, "over_quota_weight": 1.0, "limit": -1.0}
    queues = [{"name": "dept", "parent": None, "accel": dict(res),
               "cpu": dict(res), "memory": dict(res),
               "creation_timestamp": 0.0}]
    queues += [{"name": f"team-{j}", "parent": "dept", "accel": dict(res),
                "cpu": dict(res), "memory": dict(res),
                "creation_timestamp": float(j)} for j in range(4)]
    groups, pods = [], []
    for gpu, kind in (("volta", "tv"), ("pascal", "tp")):
        members = pools[gpu]
        gangs = len(members) if fill_pascal and gpu == "pascal" \
            else len(members) // 2
        for g in range(gangs):   # gang g holds node g of its pool whole
            grp, gp = _gang(f"run-{gpu}-{g}", kind, f"team-{g % 4}",
                            float(len(groups)),
                            [f"node-{members[g]}"] * TASKS)
            groups.append(grp)
            pods += gp
    return {"version": 1, "now": 0.0, "nodes": node_docs, "queues": queues,
            "pod_groups": groups, "pods": pods, "topology": None}, pools


def _arrivals(cycle, kinds, created0):
    groups, pods = [], []
    for i, kind in enumerate(kinds):
        grp, gp = _gang(f"job-{cycle}-{i}-{kind}", kind, f"team-{i % 4}",
                        float(created0 + i))
        groups.append(grp)
        pods += gp
    return {"pod_groups_upsert": groups, "pods_upsert": pods}


#: the ``B=1`` sequential scan, the judge of every wavefront
SCAN = SessionConfig(
    allocate=AllocateConfig(batch_size=1),
    victims=VictimConfig(batch_size=1, batch_size_preempt=1,
                         placement=AllocateConfig(batch_size=1)))


def _post(base, path, doc):
    req = urllib.request.Request(base + path, data=json.dumps(doc).encode(),
                                 headers={"Content-Type": "application/json"})
    return json.load(urllib.request.urlopen(req))


@functools.lru_cache(maxsize=None)
def _served(seed, split, nodes=64, scan=False, fill_pascal=False):
    """Three served cycles over the seeded cluster, with verification of
    the incremental refresh on.  Returns per cycle the oracle's counts,
    the commit, and ``/healthz``'s ``last_cycle``."""
    doc, pools = _cluster(seed, split, nodes, fill_pascal)
    model = oracle.Oracle(doc)
    config = SchedulerConfig(verify_incremental=True,
                             **({"session": SCAN} if scan else {}))
    server = SchedulerServer(load_cluster(doc), Scheduler(config),
                             port=0).start()
    base = f"http://127.0.0.1:{server.port}"
    rng = np.random.default_rng([seed, 7])
    kinds = ["tv", "tv", "tp", "tn", "nn", "nv"]
    out = []
    try:
        for cycle in range(1, 4):
            # one running gang of each tainted pool finishes
            done = [g for gpu in ("volta", "pascal")
                    for g in [f"run-{gpu}-{cycle - 1}"]
                    if not (fill_pascal and gpu == "pascal")]
            delta = {"now": float(cycle), "pod_groups_delete": done,
                     "pods_delete": [f"{g}-pod-{t}" for g in done
                                     for t in range(TASKS)]}
            order = [kinds[i] for i in rng.permutation(len(kinds))]
            intake = _arrivals(cycle, order, 1000 * cycle)
            _post(base, "/cluster/delta", delta)
            accepted = _post(base, "/intake", intake)
            assert accepted["shed"] == 0
            assert accepted["accepted"] == accepted["total"]
            model.apply(delta)
            model.apply(intake)
            commit = _post(base, "/cycle/stored", {})
            verdict = model.verdicts()
            counts = model.judge(commit)
            health = json.load(urllib.request.urlopen(
                f"{base}/healthz"))["last_cycle"]
            out.append({"counts": counts, "commit": commit,
                        "verdict": verdict, "health": health})
    finally:
        server.stop()
    return out, pools


CASES = [(seed, split, 64) for seed in (0, 1, 2) for split in SPLITS]
LARGER = [(3, "3to1", 128), (4, "1to3", 256)]


@pytest.mark.parametrize("seed,split,nodes", CASES + LARGER)
def test_served_cycles_bind_where_the_oracle_allows(seed, split, nodes):
    cycles, _pools = _served(seed, split, nodes)
    for n, c in enumerate(cycles):
        assert c["counts"] == {"misplaced": 0, "split": 0, "unbound": 0,
                               "wrongly_bound": 0}, (n, c["verdict"])
        assert c["commit"]["evictions"] == []
        # four kinds fit; the gang that selects a GPU type and does not
        # tolerate its taint can go nowhere, in this cycle or a later one
        fits = [g for g, v in c["verdict"].items() if v == "fits"]
        assert len(fits) == 5, c["verdict"]
        assert all(v == "cannot" for g, v in c["verdict"].items()
                   if g.endswith("-nv")), c["verdict"]
        assert len(c["commit"]["bind_requests"]) == 5 * TASKS


@pytest.mark.parametrize("seed,split,nodes", CASES + LARGER)
def test_every_cycle_after_the_cold_one_patches(seed, split, nodes):
    """A toleration and a node selector ride the patch: the cold build
    numbers the pool's two filter specs and its selector key, every
    cycle after it patches against that vocabulary — arrivals, binds
    and finished gangs alike — and (verification on) never serves a
    patched state that differs from a fresh build.  The oracle's counts
    stay 0 on the patched states."""
    cycles, _pools = _served(seed, split, nodes)
    cold = cycles[0]["health"]
    assert cold["snapshot"]["mode"] == "full"
    assert cold["snapshot"]["fallback_reason"] == "cold"
    assert any(path.endswith("/encode.filters")
               for path in cold["span_self_seconds"])
    for c in cycles:
        snap = c["health"]["snapshot"]
        # the empty spec and the toleration's; the selector is a key
        assert snap["filter_classes"] == 2 and snap["selector_keys"] == 1
        # 6 gangs arrive, 4 of them with a toleration: a list of
        # structs, which the fast path for new pods leaves to the
        # generic parser (a node selector is a plain mapping and rides
        # the fast path)
        assert c["health"]["intake_parsed_pods"] == 4 * TASKS
        assert c["counts"] == {"misplaced": 0, "split": 0, "unbound": 0,
                               "wrongly_bound": 0}
    for c in cycles[1:]:
        snap = c["health"]["snapshot"]
        assert snap["mode"] == "patched", snap
        assert snap["nonplain_pods"] == 0 and snap["filtered_pods"] > 0
        # no spec is evaluated against the nodes again
        assert not any("encode." in path
                       for path in c["health"]["span_self_seconds"])


@pytest.mark.parametrize("seed,split,nodes", CASES)
def test_the_scan_gives_the_same_binds(seed, split, nodes):
    """The default wavefront against the ``B=1`` sequential scan over
    the same documents: the same pods bound in every cycle, and the scan
    held to the oracle too.  Lanes break score ties apart by design, so
    the node a pod takes inside its pool may differ."""
    wave, pools = _served(seed, split, nodes)
    scan, _ = _served(seed, split, nodes, scan=True)
    pool_of = {f"node-{i}": gpu for gpu, members in pools.items()
               for i in members}
    for w, s in zip(wave, scan):
        assert s["counts"] == {"misplaced": 0, "split": 0, "unbound": 0,
                               "wrongly_bound": 0}
        binds = [sorted((b["pod"], pool_of[b["node"]])
                        for b in c["commit"]["bind_requests"]
                        if "-tn" not in b["pod"]) for c in (w, s)]
        assert binds[0] == binds[1]
        assert ({b["pod"] for b in w["commit"]["bind_requests"]}
                == {b["pod"] for b in s["commit"]["bind_requests"]})


@pytest.mark.parametrize("scan", [False, True], ids=["wavefront", "scan"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_full_pool_leaves_its_gang_pending(seed, scan):
    """``pascal`` is full and ``volta`` half empty: the gang that selects
    ``pascal`` stays pending in every cycle, whole, while the others are
    bound beside it."""
    cycles, pools = _served(seed, "3to1", scan=scan, fill_pascal=True)
    for c in cycles:
        assert c["counts"] == {"misplaced": 0, "split": 0, "unbound": 0,
                               "wrongly_bound": 0}
        waiting = {g for g, v in c["verdict"].items() if v == "cannot"}
        assert any(g.endswith("-tp") for g in waiting)
        bound = {b["pod"].rsplit("-pod-", 1)[0]
                 for b in c["commit"]["bind_requests"]}
        assert not bound & waiting
        assert len(bound) == 4   # tv, tv, tn, nn


# -- the oracle's own semantics ------------------------------------------

@pytest.mark.parametrize("toleration,taint,expected", [
    ({"key": "k", "operator": "Exists"}, {"key": "k", "value": "v"}, True),
    ({"key": "k", "operator": "Equal", "value": "v"},
     {"key": "k", "value": "v"}, True),
    ({"key": "k", "value": "w"}, {"key": "k", "value": "v"}, False),
    ({"key": "other", "operator": "Exists"}, {"key": "k"}, False),
    ({"operator": "Exists"}, {"key": "k", "effect": "NoExecute"}, True),
    ({"key": "k", "operator": "Exists", "effect": "NoExecute"},
     {"key": "k", "effect": "NoSchedule"}, False),
    ({"key": "k", "operator": "Exists", "effect": "NoSchedule"},
     {"key": "k", "effect": "NoSchedule"}, True),
])
def test_oracle_and_program_agree_on_a_toleration(toleration, taint,
                                                  expected):
    assert oracle.tolerates(toleration, taint) is expected
    assert apis.Toleration(**toleration).tolerates(
        apis.Taint(**taint)) is expected


def test_oracle_prefer_no_schedule_forbids_nothing():
    node = {"name": "n", "labels": {"gpu.type": "volta"}, "taints": [
        {"key": "k", "value": "v", "effect": "PreferNoSchedule"}]}
    assert oracle.node_allows(node, {"node_selector": {"gpu.type": "volta"}})
    assert not oracle.node_allows(node,
                                  {"node_selector": {"gpu.type": "pascal"}})
    node["taints"].append(dict(TAINT))
    assert not oracle.node_allows(node, {})
    assert oracle.node_allows(node, {"tolerations": [dict(TOLERATION)]})


# -- the benchmark's generator through the program's parsers --------------

def _gpu_pools():
    path = os.path.join(ROOT, "benchmark", "generators", "gpu_pools.py")
    spec = importlib.util.spec_from_file_location("gpu_pools", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "pools-10k.json")) as fh:
        return mod, json.load(fh)["cluster"]


@pytest.mark.parametrize("created", range(8))
def test_generic_parser_agrees_with_a_hand_built_pod(created):
    """Every pod document of ``pools-10k`` carries a toleration, so the
    fast path for plain pods refuses it and the generic parser builds
    it: to the same ``apis.Pod`` as one written by hand."""
    gen, spec = _gpu_pools()
    _group, docs = gen.gang_docs("job", "queue-0-0", spec, float(created))
    pool = spec["selects"][created % len(spec["selects"])]
    cluster = Cluster()
    before = intake_apply.PARSED_PODS[0]
    intake_apply.apply_cluster_delta(cluster, {"pods_upsert": docs})
    assert intake_apply.PARSED_PODS[0] - before == len(docs) == 8
    for t, doc in enumerate(docs):
        assert intake_apply._fast_new_pod(doc) is None
        assert cluster.pods[doc["name"]] == apis.Pod(
            name=f"job-pod-{t}", group="job",
            resources=apis.ResourceVec(1.0, 1.0, 4.0),
            creation_timestamp=float(created),
            node_selector={"gpu.type": pool} if pool else {},
            tolerations=[apis.Toleration(
                key="nvidia.com/gpu", operator="Exists",
                effect="NoSchedule")])
