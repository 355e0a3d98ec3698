"""``pools-10k``: the generator keeps its splits at any size, a
rehearsal of its cell is sound and patches in every cycle, and a gang
bound into the wrong pool fails ``correct`` by ``binds_outside_pool``.

Run by hand (not part of tier-1): ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``.
"""
from __future__ import annotations

import collections
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import control  # noqa: E402  (puts benchmark/ and the repo on sys.path)
import faults  # noqa: E402
from test_rehearsal import rehearse  # noqa: E402

CELL = "pools-10k.churn"


def _config():
    from lib import registry
    _bench, _cell, config, mix = registry.load_cell(CELL)
    gen = registry.module("generators", config["cluster"]["generator"])
    return registry, config, mix, gen


@pytest.mark.parametrize("nodes", [None, 64, 256, 1024])
def test_scaled_keeps_both_splits(nodes):
    """3 : 1 of the nodes and 5 : 2 : 1 of the gangs at every size; both
    pools half full, every running gang where its selector allows."""
    _registry, config, _mix, gen = _config()
    spec = gen.scaled(config["cluster"], nodes)
    doc = gen.cluster_doc(spec, 2**31 + 9)
    pool = {n["name"]: n["labels"]["gpu.type"] for n in doc["nodes"]}
    sizes = collections.Counter(pool.values())
    assert sizes["volta"] == 3 * sizes["pascal"]
    assert all(n["taints"] == [config["cluster"]["taint"]]
               for n in doc["nodes"])
    selects = collections.Counter(
        p.get("node_selector", {}).get("gpu.type") for p in doc["pods"])
    assert selects["volta"] * 2 == selects["pascal"] * 5
    assert selects["volta"] == selects[None] * 5
    assert all(p["tolerations"] == [config["cluster"]["toleration"]]
               for p in doc["pods"])
    held = collections.Counter(pool[p["node"]] for p in doc["pods"])
    accel = spec["node"]["accel"]
    assert held["volta"] * 2 == sizes["volta"] * accel
    assert held["pascal"] * 2 == sizes["pascal"] * accel
    assert all(pool[p["node"]] == p["node_selector"]["gpu.type"]
               for p in doc["pods"] if "node_selector" in p)


def test_arrivals_follow_the_creation_counter_not_the_seed():
    registry, config, mix, gen = _config()
    churn = registry.module("churn", mix["churn"])
    spec = config["cluster"]
    per_cycle = []
    for seed in (1, 2**31 + 3):
        cluster = gen.cluster_doc(spec, seed)
        ch = churn.Churn(gen, spec, mix, cluster, seed)
        for _ in range(2):
            _delta, intake = ch.documents()
            per_cycle.append(collections.Counter(
                p.get("node_selector", {}).get("gpu.type")
                for p in intake["pods_upsert"]))
    assert all(c == {"volta": 240, "pascal": 96, None: 48}
               for c in per_cycle), per_cycle


def test_rehearsal_is_sound_and_patches_every_cycle():
    proc = rehearse("--workload", CELL, "--seed", str(2**31 + 78),
                    "--seconds", "600", "--cycles", "12", "--nodes", "256",
                    "--trace", "1")
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == "", "a rehearsal prints no result"
    doc = json.loads(next(ln for ln in proc.stderr.splitlines()
                          if ln.startswith('{"rehearsal"')))
    res = doc["rehearsal"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 12
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert "binds_outside_pool" in res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["snapshot_fallbacks"] == 0
    assert m["window_compiles"] == 0
    assert m["filter_classes"] == 2 and m["selector_keys"] == 1
    # one gang of 8 arrives a cycle at this size, tolerating
    assert m["intake_parsed_pods"] == 8
    # no window cycle rebuilds
    for part in ("lists", "encode", "transfer", "ledgers"):
        assert m[f"snapshot_{part}_ms"] == 0


@pytest.fixture(scope="module")
def meter():
    from kai_scheduler_tpu.runtime import compile_cache
    compile_cache.enable()
    from lib import meters
    return meters.CompileMeter()


@pytest.mark.parametrize("fault", [None, "wrong_pool"],
                         ids=["sound", "wrong_pool"])
def test_a_gang_in_the_wrong_pool_is_counted(fault, meter):
    from lib import loop
    _registry, config, mix, _gen = _config()
    run = loop.Run(config, mix, 23, control.ROOT, nodes=256)
    with faults.planted(fault, run):
        try:
            run.start(meter)
            run.warm_up()
            run.measure(600.0, trace=False, max_cycles=12)
        finally:
            run.stop()
    wrong = {k: c["value"] for k, c in run.judge().items()
             if c["value"] > c["limit"]}
    if fault is None:
        assert not wrong, wrong
    else:
        # a whole gang of 8, in every cycle that bound a selecting gang.
        # The server's store keeps the pool it chose, so the read-back
        # sees the altered commit too
        assert wrong["binds_outside_pool"] % 8 == 0, wrong
        assert wrong.keys() <= {"binds_outside_pool", "readback_mismatch",
                                "nodes_over_allocatable",
                                "nodes_over_allocatable_recount"}, wrong
