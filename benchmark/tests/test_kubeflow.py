"""``kubeflow-10k``: a rehearsal of its cell is sound, patches in every
cycle and places through the per-task kernel; a gang of unequal pods
left pending though it fits fails ``correct`` by ``gangs_bound_short``
alone, and a launcher left out of its job by ``subgroups_below_quorum``
and ``gangs_below_min_member``.

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
import faults  # noqa: E402
from test_rehearsal import rehearse  # noqa: E402

CELL = "kubeflow-10k.churn"
CYCLES = 12


def test_rehearsal_is_sound_patches_and_runs_the_per_task_kernel():
    proc = rehearse("--workload", CELL, "--seed", str(2**31 + 79),
                    "--seconds", "600", "--cycles", str(CYCLES),
                    "--nodes", "256", "--trace", "1")
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == "", "a rehearsal prints no result"
    doc = json.loads(next(ln for ln in proc.stderr.splitlines()
                          if ln.startswith('{"rehearsal"')))
    res = doc["rehearsal"]
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == CYCLES
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert "subgroups_below_quorum" in res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["snapshot_fallbacks"] == 0
    assert m["window_compiles"] == 0
    # one job arrives a cycle at this size, and the per-task kernel
    # places the MPIJobs alone, one job in four
    assert m["per_task_kernel_gangs"] == 0.25
    # a declared subgroup rides the patch: nothing is non-plain
    assert m["nonplain_gangs"] == 0 and m["nonplain_pods"] == 0
    assert m["victim_actions_skipped"] == 3.0
    # no window cycle rebuilds
    for part in ("lists", "encode", "transfer", "ledgers"):
        assert m[f"snapshot_{part}_ms"] == 0
    # every bind of the window: 9 of 12 jobs of 8 pods, 3 of 9
    assert res["window"]["cycles_with_binds"] == CYCLES


@pytest.fixture(scope="module")
def meter():
    from kai_scheduler_tpu.runtime import compile_cache
    compile_cache.enable()
    from lib import meters
    return meters.CompileMeter()


@pytest.mark.parametrize("fault", [None, "mixed_gangs_left_pending",
                                   "launcher_left_out"])
def test_a_planted_fault_is_seen_by_the_number_that_holds_it(fault, meter):
    from lib import loop, registry
    _bench, _cell, config, mix = registry.load_cell(CELL)
    run = loop.Run(config, mix, 29, control.ROOT, nodes=256)
    with faults.planted(fault, run):
        try:
            run.start(meter)
            run.warm_up()
            run.measure(600.0, trace=False, max_cycles=CYCLES)
        finally:
            run.stop()
    wrong = {k: c["value"] for k, c in run.judge().items()
             if c["value"] > c["limit"]}
    if fault is None:
        assert not wrong, wrong
    elif fault == "mixed_gangs_left_pending":
        # one job in four is an MPIJob: each stays pending from its
        # cycle on, and every later commit leaves it so though it fits;
        # the commit is sound in every other respect
        assert wrong.keys() == {"gangs_bound_short"}, wrong
        assert wrong["gangs_bound_short"] >= CYCLES // 4
    else:
        assert wrong["subgroups_below_quorum"] >= CYCLES // 4, wrong
        assert wrong["gangs_below_min_member"] >= CYCLES // 4, wrong
