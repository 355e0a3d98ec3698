"""A Kubernetes accelerator pool as clusters deploy it: every node is
tainted, every pod tolerates the taint, and the nodes fall into pools by
a label that most gangs select with a ``nodeSelector``.

``uniform_gangs`` with exactly those two things added: its nodes,
queues, gang shape and the five functions it documents are taken from it
as they are.  The configuration gives, beside ``uniform_gangs``' sizes:

``taint``        the taint every node carries
``toleration``   the toleration every pod carries
``pool_label``   the node-label key that names a node's pool
``pools``        ``{value: nodes}`` in the order the pools are laid out
``selects``      the pool each of ``len(selects)`` consecutive gangs
                 selects (``null``: none), indexed by the gang's creation
                 counter modulo its length — never by the seed
``unselecting_run_in``  the pool on which running gangs with no selector
                 were placed

The seed chooses which nodes are in which pool, which nodes of its pool a
running gang holds and the gangs' creation order; never a count or a
shape.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "generators.uniform_gangs",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "uniform_gangs.py"))
_uniform = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_uniform)

arriving_leaves = _uniform.arriving_leaves
shapes = _uniform.shapes


def selected_pool(spec: dict, created: float) -> str | None:
    """The pool the gang with this creation counter selects."""
    return spec["selects"][int(created) % len(spec["selects"])]


def gang_docs(name: str, queue: str, spec: dict, created: float,
              node_names: list[str] | None = None) -> tuple[dict, list]:
    """``uniform_gangs``' gang; every pod tolerates the pool's taint and
    carries the gang's node selector, if it has one."""
    group, pods = _uniform.gang_docs(name, queue, spec, created, node_names)
    pool = selected_pool(spec, created)
    for pod in pods:
        pod["tolerations"] = [dict(spec["toleration"])]
        if pool is not None:
            pod["node_selector"] = {spec["pool_label"]: pool}
    return group, pods


def scaled(spec: dict, nodes: int | None) -> dict:
    """The configuration at a rehearsal's size: ``uniform_gangs``'
    scaling, the pools keeping their shares of the nodes and the running
    gangs a whole number of ``selects`` rounds, so that both splits
    stay."""
    if nodes is None or nodes == spec["nodes"]:
        return spec
    out = _uniform.scaled(spec, nodes)
    names = list(spec["pools"])
    sizes = [spec["pools"][p] * nodes // spec["nodes"] for p in names[1:]]
    out["pools"] = dict(zip(names, [nodes - sum(sizes), *sizes]))
    rounds = len(spec["selects"])
    out["running_gangs"] = max(rounds,
                               out["running_gangs"] // rounds * rounds)
    return out


def cluster_doc(spec: dict, seed: int) -> dict:
    """The cluster before the first cycle: the nodes dealt into pools by
    a seeded permutation, ``running_gangs`` gangs placed round-robin over
    a seeded order of the pool their selector names."""
    doc = _uniform.cluster_doc(dict(spec, running_gangs=0), seed)
    rng = np.random.default_rng([seed, 3])
    n, tasks = spec["nodes"], spec["tasks_per_gang"]
    dealt = rng.permutation(n)
    order: dict = {}   # pool -> its nodes, in the order gangs take them
    start = 0
    for pool, size in spec["pools"].items():
        order[pool] = dealt[start:start + size]
        start += size
        for i in order[pool]:
            doc["nodes"][i]["labels"][spec["pool_label"]] = pool
    assert start == n, "the pools are the cluster"
    for node in doc["nodes"]:
        node["taints"] = [dict(spec["taint"])]

    leaves = _uniform.leaves_of(spec, spec["running_leaves"])
    g_run = spec["running_gangs"]
    created = rng.permutation(g_run)
    taken = dict.fromkeys(order, 0)   # pods placed in each pool so far
    for g in range(g_run):
        pool = (selected_pool(spec, float(created[g]))
                or spec["unselecting_run_in"])
        slots = [f"node-{order[pool][(taken[pool] + t) % len(order[pool])]}"
                 for t in range(tasks)]
        taken[pool] += tasks
        grp, gp = gang_docs(f"gang-{g}", leaves[g % len(leaves)], spec,
                            float(created[g]), slots)
        doc["pod_groups"].append(grp)
        doc["pods"] += gp
    return doc
