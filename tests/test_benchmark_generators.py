"""The benchmark's ``kubeflow_jobs`` generator against the program's own
podgrouper and parsers: the pod group it writes for a job is the one
``podgrouper/hub.py:kubeflow_grouper`` makes of that job's replica
specs, every seed gives the same counts and shapes, and the document is
one ``load_cluster`` takes."""
import collections
import importlib.util
import json
import os

import pytest

from kai_scheduler_tpu.podgrouper.hub import Workload, kubeflow_grouper
from kai_scheduler_tpu.runtime.snapshot import load_cluster

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROLE_LABEL = "training.kubeflow.org/job-role"
#: generator kind -> (workload kind, the key of its replica specs)
KINDS = {"pytorch": ("PyTorchJob", "pytorchReplicaSpecs"),
         "mpi": ("MPIJob", "mpiReplicaSpecs")}


def _kubeflow_jobs():
    path = os.path.join(ROOT, "benchmark", "generators", "kubeflow_jobs.py")
    spec = importlib.util.spec_from_file_location("kubeflow_jobs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kubeflow-10k.json")) as fh:
        return mod, json.load(fh)["cluster"]


@pytest.mark.parametrize("created", range(4))
def test_pod_group_is_what_the_grouper_makes_of_the_job(created):
    """The operator's job, its replica types capitalised as the
    documentation writes them, through ``kubeflow_grouper``: the same
    ``min_member``, the same subgroups with the same quorums."""
    gen, spec = _kubeflow_jobs()
    kind = spec["kinds"][created % len(spec["kinds"])]
    workload_kind, specs_key = KINDS[kind]
    job = Workload(kind=workload_kind, name="job", spec={specs_key: {
        rt["role"].capitalize(): {"replicas": rt["replicas"]}
        for rt in spec["jobs"][kind]}})
    md = kubeflow_grouper(job, [])
    group, pods = gen.gang_docs("job", "queue-0-0", spec, float(created))
    assert group["min_member"] == md.min_member == len(pods)
    assert group["sub_groups"] == [
        {"name": s.name, "min_member": s.min_member} for s in md.sub_groups]
    # every pod names a declared subgroup and carries it as its job-role
    assert collections.Counter(p["subgroup"] for p in pods) == {
        s.name: s.min_member for s in md.sub_groups}
    assert all(p["labels"] == {ROLE_LABEL: p["subgroup"]} for p in pods)
    leader = pods[0]
    assert leader["subgroup"] in ("master", "launcher")
    assert (leader["resources"]["accel"] == 0.0) == (kind == "mpi")
    assert sum(p["resources"]["accel"] for p in pods) == 8.0


@pytest.mark.parametrize("nodes", [None, 64, 256, 1000])
def test_every_seed_gives_the_same_counts_and_shapes(nodes):
    gen, full = _kubeflow_jobs()
    spec = gen.scaled(full, nodes)
    rounds = spec["running_gangs"] // 4
    assert spec["running_gangs"] == 4 * rounds
    tallies = []
    for seed in (0, 7, 2**31 + 11):
        doc = gen.cluster_doc(spec, seed)
        by_gang = collections.Counter(p["group"] for p in doc["pods"])
        tallies.append((
            len(doc["nodes"]), len(doc["queues"]), len(doc["pod_groups"]),
            len(doc["pods"]), sorted(by_gang.values()),
            sum(p["resources"]["accel"] for p in doc["pods"]),
            collections.Counter(p["subgroup"] for p in doc["pods"])))
        # a running launcher sits beside its job's first worker
        node_of = {p["name"]: p["node"] for p in doc["pods"]}
        for p in doc["pods"]:
            if p["subgroup"] == "launcher":
                assert p["node"] == node_of[f"{p['group']}-pod-1"]
        per_node = collections.Counter(
            p["node"] for p in doc["pods"] if p["resources"]["accel"])
        assert max(per_node.values()) <= spec["node"]["accel"]
    assert tallies[0] == tallies[1] == tallies[2]
    pods = rounds * (3 * 8 + 9)
    assert tallies[0][2:4] == (4 * rounds, pods)
    assert tallies[0][5] == 8.0 * 4 * rounds
    shapes = gen.shapes(spec)
    assert shapes["placed_pods"] == pods
    assert shapes["tasks_per_gang"] == 9
    assert shapes["gangs"] == 4 * rounds
    if nodes is None:
        assert (shapes["gangs"], shapes["placed_pods"]) == (5000, 41250)


def test_arrivals_follow_the_creation_counter_not_the_seed():
    """48 jobs a cycle at the cell's size: 36 PyTorchJobs and 12
    MPIJobs, 396 pods and 384 accelerators, whatever the seed."""
    gen, spec = _kubeflow_jobs()
    for start in (5000.0, 5001.0, 7777.0):
        jobs = [gen.gang_docs(f"job-{i}", "queue-0-0", spec, start + i)
                for i in range(48)]
        sizes = collections.Counter(g["min_member"] for g, _pods in jobs)
        assert sizes == {8: 36, 9: 12}
        pods = [p for _g, gp in jobs for p in gp]
        assert len(pods) == 396
        assert sum(p["resources"]["accel"] for p in pods) == 384.0


def test_load_cluster_takes_the_document():
    gen, full = _kubeflow_jobs()
    doc = gen.cluster_doc(gen.scaled(full, 64), 3)
    cluster = load_cluster(doc)
    assert len(cluster.pods) == len(doc["pods"])
    group = cluster.pod_groups["gang-0"]
    assert [(s.name, s.min_member) for s in group.sub_groups] == [
        (s["name"], s["min_member"])
        for s in doc["pod_groups"][0]["sub_groups"]]
    pod = cluster.pods["gang-0-pod-0"]
    assert pod.subgroup == group.sub_groups[0].name
    assert pod.labels[ROLE_LABEL] == pod.subgroup


# -- ``topology_tree`` (PR 34) ---------------------------------------------

def _topology_tree():
    path = os.path.join(ROOT, "benchmark", "generators", "topology_tree.py")
    spec = importlib.util.spec_from_file_location("topology_tree", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "topology-10k.json")) as fh:
        return mod, json.load(fh)["cluster"]


@pytest.mark.parametrize("nodes", [None, 200, 256, 1000])
def test_topology_tree_gives_the_stated_counts_at_every_seed(nodes):
    """Every seed: the same nodes, queues, jobs and pods; three levels
    on every node; every running job of equal pods inside one rack, so
    inside one domain of its required level; every rack between a
    quarter and three quarters full; no node over its accelerators; no
    leaf above its quota."""
    gen, full = _topology_tree()
    spec = gen.scaled(full, nodes)
    blocks, per_block, per_rack = gen.tree_of(spec)
    assert len(spec["topology"]["levels"]) == 3 and blocks >= 2
    block_key, rack_key, host_key = spec["topology"]["levels"]
    rounds = spec["running_gangs"] // 4
    assert spec["running_gangs"] == 4 * rounds
    rack_accel = per_rack * spec["node"]["accel"]
    tallies = []
    for seed in (0, 7, 2**31 + 11):
        doc = gen.cluster_doc(spec, seed)
        assert doc["topology"] == spec["topology"]
        labels = {n["name"]: n["labels"] for n in doc["nodes"]}
        assert all(set(lb) == {block_key, rack_key, host_key}
                   for lb in labels.values())
        racks = collections.Counter(
            (lb[block_key], lb[rack_key]) for lb in labels.values())
        assert len(racks) == blocks * per_block
        assert set(racks.values()) == {per_rack}
        assert len({b for b, _r in racks}) == blocks
        size = {g["name"]: g["min_member"] for g in doc["pod_groups"]}
        held_by_rack = collections.Counter()
        racks_of = collections.defaultdict(set)
        for p in doc["pods"]:
            lb = labels[p["node"]]
            held_by_rack[lb[block_key], lb[rack_key]] += 1
            racks_of[p["group"]].add((lb[block_key], lb[rack_key]))
        assert all(len(r) == 1 for r in racks_of.values())
        assert set(held_by_rack) == set(racks)
        assert rack_accel / 4 <= min(held_by_rack.values())
        assert max(held_by_rack.values()) <= 3 * rack_accel / 4
        per_node = collections.Counter(p["node"] for p in doc["pods"])
        assert max(per_node.values()) <= spec["node"]["accel"]
        for g in doc["pod_groups"]:
            tc = g["topology_constraint"]
            assert tc == gen.constraint_of(spec, g["min_member"])
            assert tc["required_level"] == (
                block_key if g["min_member"] == 32 else rack_key)
            assert tc["preferred_level"] == (
                rack_key if g["min_member"] == 32 else None)
            assert g["min_member"] == gen.size_of(
                spec, g["creation_timestamp"])
        by_leaf = collections.Counter()
        for g in doc["pod_groups"]:
            by_leaf[g["queue"]] += g["min_member"]
        quota = next(q["accel"]["quota"] for q in doc["queues"]
                     if q["name"] == doc["pod_groups"][0]["queue"])
        assert max(by_leaf.values()) <= quota
        tallies.append((
            len(doc["nodes"]), len(doc["queues"]), len(doc["pod_groups"]),
            len(doc["pods"]), sorted(size.values()),
            sorted(held_by_rack.values()), sorted(by_leaf.values())))
    assert tallies[0] == tallies[1] == tallies[2]
    assert tallies[0][2:4] == (4 * rounds, 64 * rounds)
    shapes = gen.shapes(spec)
    assert shapes["placed_pods"] == 64 * rounds
    assert shapes["tasks_per_gang"] == 32
    assert shapes["gangs"] == 4 * rounds
    if nodes is None:
        assert (blocks, per_block, per_rack) == (25, 16, 25)
        assert (shapes["gangs"], shapes["placed_pods"]) == (2500, 40000)
        assert collections.Counter(tallies[0][4]) == {
            8: 1250, 16: 625, 32: 625}
        assert max(tallies[0][6]) <= 56
    if nodes == 200:
        assert (blocks, per_block, per_rack) == (2, 4, 25)


def test_topology_tree_arrivals_follow_the_creation_counter():
    """48 jobs a cycle at the cell's size: 24 of 8 pods, 12 of 16 and
    12 of 32, 768 pods, 36 gangs rack-required and 12 block-required
    and rack-preferred, wherever the counter starts."""
    gen, spec = _topology_tree()
    _block_key, rack_key, _host = spec["topology"]["levels"]
    for start in (2501.0, 2502.0, 7777.0):
        jobs = [gen.gang_docs(f"job-{i}", "queue-0-0", spec, start + i)
                for i in range(48)]
        assert collections.Counter(
            g["min_member"] for g, _p in jobs) == {8: 24, 16: 12, 32: 12}
        assert sum(len(gp) for _g, gp in jobs) == 768
        assert all(len(gp) == g["min_member"] for g, gp in jobs)
        required = collections.Counter(
            g["topology_constraint"]["required_level"] for g, _p in jobs)
        assert required[rack_key] == 36 and sum(required.values()) == 48
        assert sum(1 for g, _p in jobs
                   if g["topology_constraint"]["preferred_level"]) == 12


def test_load_cluster_takes_the_tree_document():
    gen, full = _topology_tree()
    doc = gen.cluster_doc(gen.scaled(full, 200), 3)
    cluster = load_cluster(doc)
    assert cluster.topology.levels == full["topology"]["levels"]
    assert len(cluster.pods) == len(doc["pods"])
    group = cluster.pod_groups["gang-0"]
    assert group.topology_constraint.topology == full["topology"]["name"]
    assert group.topology_constraint.required_level in \
        full["topology"]["levels"][:2]
    assert set(cluster.nodes["node-0"].labels) == set(
        full["topology"]["levels"])


# -- the reference that judges ``topology-10k`` (PR 34) --------------------

@pytest.fixture()
def tree_judge():
    """The generator loaded as the harness loads it (``lib.registry``,
    after ``lib.host_model``): ``(generator, spec at 200 nodes, the
    class the harness would judge with)``.  Leaves the process as it
    found it."""
    import sys
    bench = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, bench)
    try:
        from lib import host_model, registry
        plain = host_model.HostModel
        gen = registry.module("generators", "topology_tree")
        _b, _c, config, _m = registry.load_cell("topology-10k.churn")
        yield gen, gen.scaled(config["cluster"], 200), host_model.HostModel
        host_model.HostModel = plain
    finally:
        sys.path.remove(bench)
        for name in [m for m in sys.modules
                     if m == "lib" or m.startswith("lib.")]:
            del sys.modules[name]


def _rack_nodes(doc, spec, free_of):
    """Rack (block, rack label) -> its nodes with an accelerator free."""
    block_key, rack_key, _host = spec["topology"]["levels"]
    racks = collections.defaultdict(list)
    for n in doc["nodes"]:
        if free_of[n["name"]] > 0:
            racks[n["labels"][block_key], n["labels"][rack_key]].append(
                n["name"])
    return racks


@pytest.mark.parametrize("commit_kind, wrong", [
    ("one_rack", {}),
    ("last_pod_in_another_rack", {"gangs_split_across_domains": 1}),
    ("left_pending", {"domain_left_pending": 1, "gangs_bound_short": 1}),
])
def test_the_cells_reference_holds_a_required_level(tree_judge, commit_kind,
                                                    wrong):
    """``correct`` of ``topology-10k.churn`` sees ``topology_required``:
    loading the generator puts ``TreeHostModel`` where the harness takes
    its reference, and a rack-required gang bound inside one rack reads
    0 everywhere, one pod of it in another rack fails
    ``gangs_split_across_domains`` alone, and the gang left pending
    though a rack holds it fails ``domain_left_pending`` (and the plain
    reference's own ``gangs_bound_short``)."""
    gen, spec, judge = tree_judge
    assert judge.__name__ == "TreeHostModel"
    doc = gen.cluster_doc(spec, 11)
    model = judge(doc)
    # a job of 8 pods: created 0 -> sizes[0], rack-required
    group, pods = gen.gang_docs("job-a", doc["queues"][-1]["name"], spec,
                                float(len(spec["sizes"]) * 1000))
    assert len(pods) == 8 and group["topology_constraint"][
        "required_level"] == spec["topology"]["levels"][1]
    model.apply_doc({"pod_groups_upsert": [group], "pods_upsert": pods})
    free = {n["name"]: int(n["allocatable"]["accel"]) for n in doc["nodes"]}
    for p in doc["pods"]:
        free[p["node"]] -= 1
    racks = _rack_nodes(doc, spec, free)
    slots = {r: [n for n in names for _ in range(free[n])]
             for r, names in racks.items()}
    home, other = [r for r in slots if len(slots[r]) >= 8][:2]
    nodes = slots[home][:8]
    if commit_kind == "last_pod_in_another_rack":
        nodes[-1] = slots[other][0]
    binds = [] if commit_kind == "left_pending" else [
        {"pod": p["name"], "node": n} for p, n in zip(pods, nodes)]
    tally = model.check_commit({"bind_requests": binds, "evictions": []})
    assert tally["topology"]["required_pending"] == (
        commit_kind == "left_pending")
    got = {k: c["value"] for k, c in model.checks().items()
           if c["value"] > c["limit"]}
    assert got == wrong
    assert all(c["limit"] == 0 for c in model.checks().values())


def test_the_generator_loaded_by_path_replaces_no_reference():
    """Outside the harness (no ``lib.host_model`` imported) loading the
    generator touches nothing."""
    import sys
    before = sys.modules.get("lib.host_model")
    assert before is None, "only the fixture above imports it, and undoes it"
    _topology_tree()
    assert "lib.host_model" not in sys.modules
    assert "lib.topology_model" not in sys.modules
