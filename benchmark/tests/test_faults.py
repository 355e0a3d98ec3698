"""``correct`` has to come out false when the timed path misbehaves.

Run by hand (not part of tier-1): ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``.  Each test drives the rest of a run — serve, warm
up, a short window, the reference's replay — at 256 nodes on whatever
backend JAX finds, skipping only the harness's look for a chip, with one
fault planted under ``POST /cycle/stored``.  The faults against a
guarantee one configuration states are planted in that configuration's
own file, on its cell.
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import control  # noqa: E402  (puts benchmark/ and the repo on sys.path)
import faults  # noqa: E402


def _cell(name: str):
    from lib import registry
    return registry.load_cell(name)[2:]


@pytest.fixture(scope="module")
def meter():
    from kai_scheduler_tpu.runtime import compile_cache
    compile_cache.enable()
    from lib import meters
    return meters.CompileMeter()


CELLS = ("reclaim-10k.steady", "alloc-10k.churn")
GENERAL = ("answer_altered", "half_left_out", "partial_gang",
           "state_unchanged")
#: fault -> the file that plants it on its configuration's cell
BY_CONFIGURATION = {"wrong_pool": "test_pools.py",
                    "mixed_gangs_left_pending": "test_kubeflow.py",
                    "launcher_left_out": "test_kubeflow.py",
                    "pod_in_another_rack": "test_topology.py",
                    "rack_gang_dropped": "test_topology.py"}


def test_every_fault_is_planted_somewhere():
    assert set(faults.FAULTS) == set(GENERAL) | set(BY_CONFIGURATION)
    for fault, name in BY_CONFIGURATION.items():
        with open(os.path.join(HERE, name)) as fh:
            assert f'"{fault}"' in fh.read(), (fault, name)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, meter):
    config, mix = _cell(cell)
    out = control.one_run(config, mix, 21, 0.5, None, 256, meter)
    assert out["correct"], out
    assert out["compiles"] == 0, out


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", GENERAL)
def test_fault_is_not_correct(cell, fault, meter):
    config, mix = _cell(cell)
    out = control.one_run(config, mix, 22, 0.5, fault, 256, meter)
    assert not out["correct"], out
    broken = {k for k, v in out["checks"].items() if v}
    # a full cluster that sees no eviction never comes to owe a bind: there
    # the unchanged state shows as capacity not reclaimed
    expected = {
        "partial_gang": {"gangs_below_min_member"},
        "state_unchanged": {"gangs_bound_short", "evicted_accel_short"},
        "half_left_out": {"gangs_bound_short", "evicted_accel_short"},
        "answer_altered": {"nodes_over_allocatable"},
    }[fault]
    assert expected & broken, out
