"""``topology-10k``: a rehearsal of its cell is sound, patches after the
cold cycle and keeps the whole-gang kernel with the domain lock; a pod
of a rack-required gang bound into another rack makes the run not
``correct`` by ``gangs_split_across_domains`` alone, and a
rack-required gang dropped from the commit by ``domain_left_pending``
and by ``gangs_bound_short`` (``Run.judge``, the harness's own verdict,
through ``lib/topology_model.py``'s ``TreeHostModel``).

Run by hand (not part of tier-1): ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``.  The generator's own tests are tier-1
(``tests/test_benchmark_generators.py``).
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import control  # noqa: E402  (puts benchmark/ and the repo on sys.path)
import topology_faults  # noqa: E402
from test_rehearsal import rehearse  # noqa: E402

CELL = "topology-10k.churn"
CYCLES = 12


def test_rehearsal_is_sound_patches_and_keeps_the_whole_gang_kernel():
    proc = rehearse("--workload", CELL, "--seed", str(2**31 + 83),
                    "--seconds", "600", "--cycles", str(CYCLES),
                    "--nodes", "200", "--trace", "1")
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == "", "a rehearsal prints no result"
    doc = json.loads(next(ln for ln in proc.stderr.splitlines()
                          if ln.startswith('{"rehearsal"')))
    res = doc["rehearsal"]
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == CYCLES
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert {"gangs_split_across_domains", "domain_left_pending"} \
        <= res["checks"].keys()
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["topology_violations"] == 0
    assert m["snapshot_fallbacks"] == 0      # after the cold cycle
    assert m["window_compiles"] == 0
    # one job arrives a cycle at this size, every one under a required
    # level, and every one finds a rack or a block with room
    assert m["topology_gangs"] == 1.0
    assert m["domain_misses"] == 0.0
    assert 0.0 <= m["preferred_level_share"] <= 100.0
    assert m["victim_actions_skipped"] == 3.0
    assert res["window"]["cycles_with_binds"] == CYCLES
    assert res["window"]["pending_gangs"] == [1]


@pytest.fixture(scope="module")
def meter():
    from kai_scheduler_tpu.runtime import compile_cache
    compile_cache.enable()
    from lib import meters
    return meters.CompileMeter()


@pytest.mark.parametrize("fault", list(topology_faults.FAULTS))
def test_a_planted_fault_is_seen_by_the_number_that_holds_it(fault, meter):
    from lib import loop, registry
    _bench, _cell, config, mix = registry.load_cell(CELL)
    run = loop.Run(config, mix, 31, control.ROOT, nodes=200)
    with topology_faults.FAULTS[fault](run):
        try:
            run.start(meter)
            run.warm_up()
            run.measure(600.0, trace=False, max_cycles=CYCLES)
        finally:
            run.stop()
    checks = run.judge()
    wrong = {k: c["value"] for k, c in checks.items()
             if c["value"] > c["limit"]}
    count = registry.module("layer_metrics", "topology_violations").read(run)
    assert {"gangs_split_across_domains", "domain_left_pending"} \
        <= checks.keys()
    # of every 4 jobs 3 ask for the rack: 9 of the window's 12
    if fault == "sound":
        assert count == 0 and not wrong, wrong
    elif fault == "pod_in_another_rack":
        # the commit is sound but for the tree: one limit fails, alone
        assert wrong.keys() == {"gangs_split_across_domains"}, wrong
        assert wrong["gangs_split_across_domains"] >= count >= CYCLES // 2
    else:
        assert wrong.keys() == {"domain_left_pending",
                                "gangs_bound_short"}, wrong
        assert wrong["domain_left_pending"] >= count >= CYCLES // 2
        assert wrong["gangs_bound_short"] >= CYCLES // 2
