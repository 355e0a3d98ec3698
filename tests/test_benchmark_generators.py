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
