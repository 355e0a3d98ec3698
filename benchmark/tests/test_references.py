"""The references a configuration names, and the verdict without them.

``data/judged/<cell>.json.gz`` holds a short run of each cell at 256
nodes on the CPU (the cluster document, every document posted and
commit returned, what the window read back) and, under ``parent``, what
``Run.judge`` said of it before configurations named references: for
the sound run and with each fault of the commit document planted into
every commit, the plain reference's numbers (``checks``) and the counts
of the three references that stood beside it then (``side``).

Run by hand (not part of tier-1): ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``.
"""
from __future__ import annotations

import gzip
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import control  # noqa: E402,F401  (puts benchmark/ on sys.path)
import faults  # noqa: E402
from lib import host_model, loop, registry  # noqa: E402

CELLS = ("reclaim-10k.steady", "alloc-10k.churn", "reclaim-10k.idle",
         "pools-10k.churn", "kubeflow-10k.churn", "topology-10k.churn")
VARIANTS = ("sound", "partial_gang", "state_unchanged", "half_left_out",
            "answer_altered")
#: the numbers each named reference adds, and the count that stood
#: beside ``correct`` for it before
NEW = {"binds_outside_pool": "placement",
       "subgroups_below_quorum": "below_quorum",
       "gangs_split_across_domains": "gangs_split_across_domains",
       "domain_left_pending": "domain_left_pending"}


def _recorded(cell: str) -> dict:
    with gzip.open(os.path.join(HERE, "data", "judged",
                                f"{cell}.json.gz"), "rt") as fh:
        return json.load(fh)


def _judge(doc: dict, variant: str, references: list) -> dict:
    """``Run.judge`` over the recording, ``variant`` planted into every
    commit: each number compared, by name."""
    records = []
    for delta, intake, commit in doc["records"]:
        commit = json.loads(commit)
        if variant != "sound":
            commit = getattr(faults, variant)(commit)
        records.append((delta.encode(), intake.encode(),
                        json.dumps(commit).encode()))
    run = types.SimpleNamespace(
        cluster_json=json.dumps(doc["cluster"]), records=records,
        references=references, failed=doc["failed"],
        cycles=[None] * doc["window_cycles"],
        window={"stored_nodes": doc["stored_nodes"],
                "intake": doc["intake"]})
    return {k: c["value"] for k, c in loop.Run.judge(run).items()}


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("cell", CELLS)
def test_without_references_the_verdict_is_unchanged(cell, variant):
    """A configuration that names no reference is judged exactly as
    before: the same numbers, key for key and value for value."""
    doc = _recorded(cell)
    assert _judge(doc, variant, []) == doc["parent"][variant]["checks"]


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("cell", CELLS[3:])
def test_a_named_reference_counts_what_stood_beside_correct(cell, variant):
    """The cell's own configuration: each named reference's numbers are
    the counts its module kept beside ``correct`` before, over the same
    commits; the plain numbers are as before but where ``_due`` now
    knows what the reference knows (``gangs_bound_short`` and the
    evictions it implies)."""
    _bench, _cell, config, _mix = registry.load_cell(cell)
    doc = _recorded(cell)
    got = _judge(doc, variant, config["references"])
    before = doc["parent"][variant]
    added = {k: v for k, v in got.items() if k not in before["checks"]}
    assert added == {k: before["side"][NEW[k]] for k in added}
    assert len(added) >= 1
    learned = ("gangs_bound_short", "evicted_accel_short",
               "evicted_accel_excess")
    assert ({k: v for k, v in got.items()
             if k in before["checks"] and k not in learned}
            == {k: v for k, v in before["checks"].items()
                if k not in learned})
    if cell == "kubeflow-10k.churn" and variant == "state_unchanged":
        # the MPIJobs left pending are owed now too
        assert got["gangs_bound_short"] > before["checks"][
            "gangs_bound_short"]


def _two_queues(held: float) -> dict:
    """Two nodes of 8 accelerators and two leaf queues with a quota of
    8 each; queue ``a`` runs one gang of ``held`` one-accelerator pods
    on node ``n0``."""
    node = {"allocatable": {"accel": 8.0, "cpu": 64.0, "memory": 256.0}}
    queue = {"accel": {"quota": 8.0, "over_quota_weight": 1.0}}
    pod = {"group": "g", "node": "n0",
           "resources": {"accel": 1.0, "cpu": 1.0, "memory": 1.0}}
    return {"nodes": [dict(node, name="n0"), dict(node, name="n1")],
            "queues": [dict(queue, name="a"), dict(queue, name="b")],
            "pod_groups": [{"name": "g", "queue": "a",
                            "min_member": int(held),
                            "creation_timestamp": 0.0}],
            "pods": [dict(pod, name=f"g-pod-{i}") for i in range(int(held))]}


@pytest.mark.parametrize("move_to, counted", [("n1", 0), (None, 1)])
def test_a_move_out_of_a_queue_within_its_share_is_no_victim(move_to,
                                                              counted):
    """Queue ``a`` holds 4 of its quota of 8: within its share.  Moving
    one of its pods to another node takes nothing from it; evicting it
    is an eviction within the fair share."""
    model = host_model.HostModel(_two_queues(4.0), [])
    model.check_commit({"bind_requests": [], "evictions": [
        {"pod": "g-pod-0", "group": "g", "move_to": move_to}]})
    checks = {k: c["value"] for k, c in model.checks().items()}
    assert checks["evictions_within_fair_share"] == counted
    assert checks["dangling_names"] == 0
    assert model.used[:, 0].tolist() == ([3.0, 1.0] if move_to else
                                         [4.0, 0.0])


def _pending_beside(labels: list, used: list, pending: list,
                    **group) -> dict:
    """Nodes of 8 accelerators with ``labels`` and ``used`` of them held
    by a running gang each, and one pending gang ``p`` of ``pending``
    (``{"accel": a, **pod fields}`` each), in one queue."""
    res = {"cpu": 64.0, "memory": 256.0}
    doc = {"nodes": [{"name": f"n{i}", "labels": lb,
                      "allocatable": dict(res, accel=8.0)}
                     for i, lb in enumerate(labels)],
           "queues": [{"name": "q", "accel": {"quota": 64.0,
                                              "over_quota_weight": 1.0}}],
           "pod_groups": [dict(group, name="p", queue="q",
                               min_member=len(pending),
                               creation_timestamp=9.0)],
           "pods": []}
    for i, n in enumerate(used):
        doc["pod_groups"].append({"name": f"r{i}", "queue": "q",
                                  "min_member": n,
                                  "creation_timestamp": float(i)})
        doc["pods"] += [{"name": f"r{i}-pod-{t}", "group": f"r{i}",
                         "node": f"n{i}",
                         "resources": dict(res, accel=1.0, cpu=1.0,
                                           memory=1.0)} for t in range(n)]
    for t, pod in enumerate(pending):
        extra = {k: v for k, v in pod.items() if k != "accel"}
        doc["pods"].append(dict(extra, name=f"p-pod-{t}", group="p",
                                resources={"accel": pod["accel"],
                                           "cpu": 1.0, "memory": 1.0}))
    return doc


VOLTA = {"node_selector": {"gpu.type": "volta"}, "accel": 1.0}
TREE = {"topology": {"name": "t", "levels": ["rack"]}}
DUE_CASES = {
    # the volta node is full, the pascal one has room: the gang fits
    # the cluster's free capacity, never its pool's
    "pool_full": ("placement_model", [{"gpu.type": "volta"},
                                      {"gpu.type": "pascal"}],
                  [8, 0], [VOLTA, VOLTA], {}, {}),
    # 2 free in each rack: 4 pods fit the cluster, no rack
    "no_rack_holds_it": ("topology_model", [{"rack": "a"}, {"rack": "b"}],
                         [6, 6], [{"accel": 1.0}] * 4,
                         {"topology_constraint": {"topology": "t",
                                                  "required_level": "rack"}},
                         TREE),
    # a launcher without accelerator and two workers: unequal pods
    "launcher_and_workers": ("subgroup_model", [{}, {}], [4, 4],
                             [{"accel": 0.0}, {"accel": 1.0},
                              {"accel": 1.0}], {}, {}),
}


@pytest.mark.parametrize("case", sorted(DUE_CASES))
def test_due_claims_what_the_named_reference_allows(case):
    """A sound cycle that leaves the pending gang pending: the plain
    reference owes a bind where the pool or the tree forbids one (and
    claims nothing for unequal pods); the named reference owes a bind
    only where the gang can be placed."""
    ref, labels, used, pending, group, extra = DUE_CASES[case]
    doc = dict(_pending_beside(labels, used, pending, **group), **extra)
    short = []
    for refs in ([], [ref]):
        model = host_model.HostModel(doc, refs)
        model.check_commit({"bind_requests": [], "evictions": []})
        short.append(model.counts["gangs_bound_short"])
    assert short == ([0, 1] if case == "launcher_and_workers" else [1, 0])


def test_every_reference_a_configuration_names_is_a_module_of_lib():
    """Every name under ``references`` builds, and what it adds to the
    numbers compared is the limits it states."""
    bench = registry.load_json(registry.ROOT, "BENCHMARK.json")
    named = set()
    for entry in bench["configs"]:
        config = registry.load_json(registry.ROOT, entry["file"])
        named.update(config.get("references", []))
        doc = _recorded(next(w["name"] for w in bench["workloads"]
                             if w["config"] == entry["name"]))
        model = host_model.HostModel(doc["cluster"],
                                     config.get("references", []))
        added = set(model.checks()) - set(host_model.HostModel.LIMITS)
        assert added == {k for r in model.references for k in r.LIMITS}
    assert named == {"placement_model", "subgroup_model", "topology_model"}


def test_first_fit_is_sufficient_and_honours_the_allowed_nodes():
    free = np.array([[4.0, 8.0, 8.0], [2.0, 8.0, 8.0]])
    big, small = np.array([3.0, 1.0, 1.0]), np.array([1.0, 1.0, 1.0])
    left = host_model.first_fit(free, [small, big, small])
    assert left[:, 0].tolist() == [0.0, 1.0]
    only_n1 = np.array([False, True])
    assert host_model.first_fit(free, [big], [only_n1]) is None
    assert host_model.first_fit(free, [small, small, small],
                                [only_n1] * 3) is None
