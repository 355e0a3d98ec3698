"""Served cycles against the plain topology oracle
(``tests/oracles/topology_oracle.py``).

Clusters of 64 to 256 nodes in a block / rack / host tree, racks filled
to a seeded permutation of levels, and training jobs of 8, 16 and 32
equal pods: 8 and 16 ask for the rack as their required level, 32 for
the block and prefer the rack.  Every gang has equal pods and none
declares a subgroup, so the session keeps the whole-gang kernel
(``uniform_tasks``) and compiles it with ``subgroup_topology`` and
``preferred_topology``.  The ``B=1`` sequential scan is held to the
oracle's sequential placer gang for gang — the same gangs bound, each in
the domain the placer chose, on the placer's nodes pod for pod; the
default wavefront, whose lanes take the k-th fullest fitting domain by
design, is held to the checker and to the set of gangs the placer binds.
After the cold build every refresh is a patch, and
``verify_incremental`` holds each one to a fresh build.

Departures from node-for-node equality, each beside its case: none for
the scan; the wavefront is never compared node for node (lane ``k``
takes the ``k``-th fullest fitting domain and breaks node ties by its
lane, ``ops/allocate.py`` ``domain_pick``).
"""
import functools
import json
import urllib.request

import numpy as np
import pytest

from oracles import topology_oracle as oracle

from kai_scheduler_tpu.framework.scheduler import Scheduler, SchedulerConfig
from kai_scheduler_tpu.framework.server import SchedulerServer
from kai_scheduler_tpu.framework.session import SessionConfig
from kai_scheduler_tpu.ops.allocate import AllocateConfig
from kai_scheduler_tpu.ops.victims import VictimConfig
from kai_scheduler_tpu.runtime.snapshot import load_cluster

BLOCK = "cloud.provider.com/topology-block"
RACK = "cloud.provider.com/topology-rack"
HOST = "kubernetes.io/hostname"
TREE = "cluster-topology"
POD = {"accel": 1.0, "cpu": 1.0, "memory": 4.0}
NODE = {"accel": 4.0, "cpu": 8.0, "memory": 64.0}
#: nodes -> (blocks, racks a block, nodes a rack)
TREES = {64: (2, 4, 8), 128: (2, 4, 16), 256: (4, 4, 16)}
#: a job's size -> the levels it asks for
RACK_REQUIRED = {"required_level": RACK}
BLOCK_REQUIRED = {"required_level": BLOCK, "preferred_level": RACK}
CONSTRAINT = {4: {"required_level": HOST}, 8: RACK_REQUIRED,
              16: RACK_REQUIRED, 32: BLOCK_REQUIRED}


def _job(name, size, created, node=None, constraint=None):
    """One job's pod group and its equal pods; on ``node`` when given."""
    tc = dict(CONSTRAINT[size] if constraint is None else constraint,
              topology=TREE)
    group = {"name": name, "queue": "team-0", "min_member": size,
             "priority": 0, "preemptibility": "Preemptible",
             "phase": "Pending", "creation_timestamp": created,
             "last_start_timestamp": 0.0 if node else None,
             "topology_constraint": tc}
    pods = [{"name": f"{name}-pod-{t}", "group": name, "status": 0,
             "resources": dict(POD), "creation_timestamp": created}
            for t in range(size)]
    if node:
        for pod in pods:
            pod["status"], pod["node"] = 2, node
    return group, pods


def _fills(seed, racks, per_rack, shape):
    """Whole nodes the running jobs hold in each rack.  ``levels``: a
    seeded permutation of evenly spread levels from empty to one node
    short of full, so no two racks are equally full; ``one_full``: the
    same with the fullest rack left one free accelerator-node short of
    the smallest job; ``tight``: every rack keeps one node free."""
    rng = np.random.default_rng(seed)
    if shape == "tight":
        return [per_rack - 1] * racks
    levels = [k * per_rack // racks for k in range(racks)]
    fills = [levels[i] for i in rng.permutation(racks)]
    if shape == "one_full":
        fills[fills.index(max(fills))] = per_rack - 1
    return fills


def _cluster(seed, nodes=64, shape="levels"):
    blocks, per_block, per_rack = TREES[nodes]
    node_docs = []
    for i in range(nodes):
        rack = i // per_rack
        node_docs.append({
            "name": f"node-{i}", "allocatable": dict(NODE),
            "labels": {BLOCK: f"block-{rack // per_block}",
                       RACK: f"rack-{rack // per_block}-{rack % per_block}",
                       HOST: f"node-{i}"}})
    res = {"quota": -1.0, "over_quota_weight": 1.0, "limit": -1.0}
    queues = [{"name": "dept", "parent": None, "accel": dict(res),
               "cpu": dict(res), "memory": dict(res),
               "creation_timestamp": 0.0},
              {"name": "team-0", "parent": "dept", "accel": dict(res),
               "cpu": dict(res), "memory": dict(res),
               "creation_timestamp": 0.0}]
    groups, pods = [], []
    fills = _fills(seed, blocks * per_block, per_rack, shape)
    for rack, full in enumerate(fills):
        for k in range(full):
            # a running job of 4 pods holds one node whole; it asks for
            # the host level, so every gang of the snapshot has a level
            grp, gp = _job(f"run-{rack}-{k}", 4, float(len(groups)),
                           f"node-{rack * per_rack + k}")
            if shape == "tight":
                # nothing to move or evict: the victim actions, which
                # would make room across racks, stay closed
                grp["preemptibility"] = "NonPreemptible"
            groups.append(grp)
            pods += gp
    return {"version": 1, "now": 0.0, "nodes": node_docs, "queues": queues,
            "pod_groups": groups, "pods": pods,
            "topology": {"name": TREE, "levels": [BLOCK, RACK, HOST]}}


def _arrivals(cycle, sizes):
    groups, pods = [], []
    for i, size in enumerate(sizes):
        grp, gp = _job(f"job-{cycle}-{i}-x{size}", size,
                       float(1000 * cycle + i))
        groups.append(grp)
        pods += gp
    return {"pod_groups_upsert": groups, "pods_upsert": pods}


#: the ``B=1`` sequential scan
SCAN = SessionConfig(
    allocate=AllocateConfig(batch_size=1),
    victims=VictimConfig(batch_size=1, batch_size_preempt=1,
                         placement=AllocateConfig(batch_size=1)))


def _post(base, path, doc):
    req = urllib.request.Request(base + path, data=json.dumps(doc).encode(),
                                 headers={"Content-Type": "application/json"})
    return json.load(urllib.request.urlopen(req))


@functools.lru_cache(maxsize=None)
def _served(seed, nodes=64, shape="levels", scan=False, sizes=(8, 8, 16, 32),
            cycles=2):
    """Served cycles over the seeded cluster: before each, the oldest
    running job finishes (none in a ``tight`` cluster) and ``sizes``
    arrive.  Returns per
    cycle what the placer would do, the checker's verdict on the commit,
    the commit and ``/healthz``'s ``last_cycle``."""
    doc = _cluster(seed, nodes, shape)
    model = oracle.Oracle(doc)
    config = SchedulerConfig(verify_incremental=True,
                             **({"session": SCAN} if scan else {}))
    server = SchedulerServer(load_cluster(doc), Scheduler(config),
                             port=0).start()
    base = f"http://127.0.0.1:{server.port}"
    running = [g["name"] for g in doc["pod_groups"]]
    out = []
    try:
        for cycle in range(1, cycles + 1):
            delta = {"now": float(cycle)}
            if running and shape != "tight":
                done = running.pop(0)
                delta.update(pod_groups_delete=[done], pods_delete=[
                    f"{done}-pod-{t}" for t in range(4)])
            intake = _arrivals(cycle, sizes)
            _post(base, "/cluster/delta", delta)
            accepted = _post(base, "/intake", intake)
            assert accepted["shed"] == 0
            assert accepted["accepted"] == accepted["total"]
            model.apply(delta)
            model.apply(intake)
            commit = _post(base, "/cycle/stored", {})
            placed = model.place()
            waiting = list(model.pending_gangs())
            verdict = model.judge(commit)
            health = json.load(urllib.request.urlopen(
                f"{base}/healthz"))["last_cycle"]
            out.append({"placed": placed, "waiting": waiting,
                        "verdict": verdict, "commit": commit,
                        "health": health})
    finally:
        server.stop()
    return out


def _binds(commit):
    """gang -> {pod: node} of a commit."""
    out = {}
    for b in commit["bind_requests"]:
        out.setdefault(b["pod"].rsplit("-pod-", 1)[0], {})[
            b["pod"]] = b["node"]
    return out


#: (seed, nodes, the sizes that arrive before every cycle).  Cases of
#: one size and one list of sizes share their compiled programs (the
#: racks' fills are a permutation: every seed has the same counts)
RACK_ONLY = [(0, 64, (8, 8, 16)), (1, 64, (8, 8, 16)), (2, 64, (8, 8, 16))]
BLOCK_PREF = [(3, 128, (32, 32, 32, 32)), (4, 128, (32, 32, 32, 32))]
MIXED = [(5, 128, (8, 8, 16, 32)), (6, 128, (8, 8, 16, 32)),
         (7, 256, (8, 8, 16, 32)), (8, 256, (8, 8, 16, 32))]
CASES = RACK_ONLY + BLOCK_PREF + MIXED


@pytest.mark.parametrize("seed,nodes,sizes", CASES)
def test_the_scan_lands_every_gang_where_the_sequential_placer_does(
        seed, nodes, sizes):
    """``B=1``: the same gangs bound, each inside the domain the placer
    chose — the fullest that holds all of it — and on the placer's
    nodes, pod for pod (no departure: the fullest node first, the lowest
    index among equals, the preferred rack of the fullest node first,
    pods onto the chosen nodes in ascending node order)."""
    for n, c in enumerate(_served(seed, nodes, scan=True, sizes=sizes)):
        assert c["verdict"]["counts"] == oracle.ZERO, n
        binds = _binds(c["commit"])
        assert sorted(binds) == sorted(c["placed"]), n
        assert c["verdict"]["domain"] == {
            g: p["domain"] for g, p in c["placed"].items()}, n
        for gang, want in c["placed"].items():
            assert binds[gang] == want["nodes"], (n, gang)


@pytest.mark.parametrize("seed,nodes,sizes", CASES)
def test_the_wavefront_splits_no_gang_and_binds_what_the_placer_binds(
        seed, nodes, sizes):
    """The default wavefront: no gang across two domains of its required
    level, none in part, no node over, none left pending that a domain
    holds, and every gang the placer binds is bound.  Which domain and
    which nodes is the lanes' choice (lane ``k`` takes the ``k``-th
    fullest fitting domain), so neither is compared."""
    for n, c in enumerate(_served(seed, nodes, sizes=sizes)):
        assert c["verdict"]["counts"] == oracle.ZERO, n
        assert set(c["placed"]) <= set(_binds(c["commit"])), n
        assert c["commit"]["evictions"] == []


@pytest.mark.parametrize("scan", [False, True], ids=["wavefront", "scan"])
@pytest.mark.parametrize("seed,nodes,sizes", RACK_ONLY[:2])
def test_a_rack_that_is_full_sends_the_gang_to_the_next(seed, nodes, sizes,
                                                        scan):
    """The fullest rack of these clusters keeps one free node, 4
    accelerators: too few for any gang here, so none lands in it and
    each goes, whole, to the fullest rack that does hold it."""
    fills = _fills(seed, 8, 8, "levels")
    crowded = _cluster(seed)["nodes"][fills.index(7) * 8]["labels"]
    for n, c in enumerate(_served(seed, nodes, scan=scan, sizes=sizes)):
        assert c["verdict"]["counts"] == oracle.ZERO, n
        assert len(c["verdict"]["domain"]) == len(sizes)
        assert (crowded[BLOCK], crowded[RACK]) not in \
            c["verdict"]["domain"].values()
        for gang, binds in _binds(c["commit"]).items():
            assert len(binds) == int(gang.rsplit("-x", 1)[1])


@pytest.mark.parametrize("scan", [False, True], ids=["wavefront", "scan"])
@pytest.mark.parametrize("seed", [12])   # a tight cluster has no seed
def test_no_rack_with_room_leaves_the_gang_pending_whole(seed, scan):
    """Every rack keeps one free node: 32 free accelerators in all and 4
    in any rack, 16 in any block.  A gang of 8 and a gang of 32 stay
    pending whole, cycle after cycle, and the domain gate counts its
    misses; the reference that knows no tree would have bound the 8."""
    for n, c in enumerate(_served(seed, shape="tight", scan=scan,
                                  sizes=(8, 32))):
        assert c["verdict"]["counts"] == oracle.ZERO, n
        assert c["placed"] == {}
        assert c["commit"]["bind_requests"] == []
        assert c["commit"]["evictions"] == []
        assert len(c["waiting"]) == 2 * (n + 1)
        topo = c["health"]["topology"]
        # the wavefront attempts every waiting gang in one chunk; the
        # scan attempts one of each kind and skips its equals (the
        # signature skip, ``fit_reason`` 2)
        assert topo["required_attempted"] == (2 if scan
                                              else len(c["waiting"]))
        assert topo["domain_misses"] == topo["required_attempted"]
        assert topo["required_bound"] == 0


@pytest.mark.parametrize("seed,nodes,sizes", MIXED[2:])
def test_served_cycles_keep_the_whole_gang_kernel_and_patch_after_the_cold_cycle(
        seed, nodes, sizes):
    """What the session chose is served: the whole-gang kernel with the
    domain lock and the preferred band compiled in, over a padded task
    axis that holds 32 pods beside gangs of 8; the device's counter
    agrees with the commit; after the cold build every refresh is a
    patch that equals a fresh build (``verify_incremental``).  At 256
    nodes a cycle's arrivals and last cycle's binds stay under the
    patch's dirty threshold; the smaller clusters rebuild for it."""
    blocks, per_block, _per_rack = TREES[nodes]
    for n, c in enumerate(_served(seed, nodes, sizes=sizes)):
        k = c["health"]["kernels"]
        assert k["uniform_tasks"] is True and k["track_devices"] is False
        assert k["subgroup_topology"] is True
        assert k["preferred_topology"] is True
        assert k["dense_feasibility"] is False
        assert k["topology_levels"] == 3
        assert k["topology_domains"] == blocks + blocks * per_block + nodes
        assert k["tasks"] >= 32 and k["pending_gangs"] == len(sizes)
        topo = c["health"]["topology"]
        bound = _binds(c["commit"])
        assert topo["required_attempted"] == len(sizes)
        assert topo["required_bound"] == len(bound) == len(sizes)
        assert topo["domain_misses"] == 0
        assert topo["preferred_together"] == \
            c["verdict"]["preferred_together"]
        assert c["verdict"]["preferred_bound"] == sizes.count(32)
        snap = c["health"]["snapshot"]
        if n == 0:
            assert snap["mode"] == "full"
            assert snap["fallback_reason"] == "cold"
        else:
            assert snap["mode"] == "patched"
            assert snap["fallback_reason"] == ""


# -- the oracle's own semantics ------------------------------------------

def _tiny():
    """Two racks of two nodes in one block, rack 0 half full; a pending
    gang of 8 that asks for the rack and one of 8 that prefers it."""
    doc = _cluster(0)
    doc["nodes"] = [n for n in doc["nodes"]
                    if n["name"] in ("node-0", "node-1", "node-8", "node-9")]
    doc["pod_groups"], doc["pods"] = [], []
    for name, node in (("run-a", "node-0"),):
        grp, gp = _job(name, 4, 0.0, node)
        doc["pod_groups"].append(grp)
        doc["pods"] += gp
    model = oracle.Oracle(doc)
    for i, tc in enumerate((RACK_REQUIRED, {"preferred_level": RACK})):
        grp, gp = _job(f"j{i}", 8, 1.0 + i, constraint=tc)
        model.apply({"pod_groups_upsert": [grp], "pods_upsert": gp})
    return model


def _commit(**nodes_of):
    return {"bind_requests": [
        {"pod": f"{gang}-pod-{t}", "node": node}
        for gang, nodes in nodes_of.items() for t, node in enumerate(nodes)]}


def test_the_placer_takes_the_rack_that_holds_all_of_it():
    placed = _tiny().place()
    assert list(placed) == ["j0"]          # j1 asks for no required level
    assert placed["j0"]["domain"] == ("block-0", "rack-0-1")
    assert sorted(placed["j0"]["nodes"].values()) == \
        ["node-8"] * 4 + ["node-9"] * 4


@pytest.mark.parametrize("nodes,expected", [
    (["node-8"] * 4 + ["node-9"] * 4, oracle.ZERO),
    (["node-1"] * 4 + ["node-8"] * 4, dict(oracle.ZERO, split=1)),
    (["node-8"] * 4 + ["node-9"] * 3,
     dict(oracle.ZERO, partial=1, left_pending=0)),
    (["node-8"] * 8, dict(oracle.ZERO, over_capacity=1)),
])
def test_the_checker_sees_a_split_a_part_and_a_node_over(nodes, expected):
    assert _tiny().judge(_commit(j0=nodes))["counts"] == expected


def test_the_checker_sees_a_gang_left_pending_and_tallies_the_preferred():
    verdict = _tiny().judge(_commit(j1=["node-1"] * 4 + ["node-8"] * 4))
    assert verdict["counts"] == dict(oracle.ZERO)
    assert (verdict["preferred_bound"], verdict["preferred_together"]) == \
        (1, 0)
    verdict = _tiny().judge(_commit(j1=["node-0"] * 0 + ["node-1"] * 4))
    assert verdict["counts"]["partial"] == 1
    # j0 is still pending and rack-0-1 holds all of it
    assert verdict["counts"]["left_pending"] == 1
    assert _tiny().judge(_commit(j1=["node-8"] * 4 + ["node-9"] * 4))[
        "preferred_together"] == 1
