"""The whole command on the CPU at 256 nodes: each traffic mix holds its
populations and one compiled program for 50 cycles, and a rehearsal
prints no result.  Run by hand (not part of tier-1)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def rehearse(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("cell", ["reclaim-10k.steady", "alloc-10k.churn"])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_fifty_cycles_one_program(cell, trace):
    seed = str(2**31 + 77)  # the driver's seeds are large
    proc = rehearse("--workload", cell, "--seed", seed, "--seconds", "600",
                    "--cycles", "50", "--nodes", "256", "--trace", trace)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == "", "a rehearsal prints no result"
    line = next(ln for ln in proc.stderr.splitlines()
                if ln.startswith('{"rehearsal"'))
    doc = json.loads(line)
    res, window = doc["rehearsal"], doc["rehearsal"]["window"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 50
    assert window["compiles"] == 0
    assert window["cycles_with_binds"] == 50
    assert len(window["pending_gangs"]) == 1, window
    assert len(window["placed_pods"]) == 1, window
    # one compile, in the first cycle.  (At this size one gang arrives a
    # cycle, and the step from one pending gang to two crosses a lane
    # bucket once, in cycle 2; the cells' own 8 -> 16 and 48 -> 48 do not:
    # PERF.md gives the cold runs' counts on the chip.)
    warm = doc["setup"]["warmup_cycles"]
    assert warm[0]["compile_requests"] > 0
    assert len(warm) <= 4, warm
    assert not warm[-1]["compile_requests"] and not warm[-1]["jit_misses"]
    if trace == "1":
        m = res["metrics"]
        # a pod-group delete is structural to the program's incremental
        # snapshot: with gangs finishing or evicted every cycle, every
        # cycle rebuilds in full
        assert m["snapshot_fallbacks"]["value"] == 50
        assert m["window_compiles"]["value"] == 0
        assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]


def test_no_result_without_a_chip():
    proc = rehearse("--workload", "reclaim-10k.steady", "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert proc.returncode not in (0, 3)
    assert proc.stdout.strip() == ""


def _small(config: str, mix: str, nodes: int = 64):
    """(generator, scaled spec, churn module, scaled mix) of a
    configuration and a mix, as ``lib/loop.py`` finds them."""
    sys.path[:0] = [os.path.join(ROOT, "benchmark")]
    from lib import registry
    cfg = registry.load_json(registry.BENCH, "configs", f"{config}.json")
    mix = registry.load_json(registry.BENCH, "traffic", f"{mix}.json")
    gen = registry.module("generators", cfg["cluster"]["generator"])
    churn = registry.module("churn", mix["churn"])
    spec = gen.scaled(cfg["cluster"], nodes)
    return gen, spec, churn, churn.scaled(
        mix, nodes / cfg["cluster"]["nodes"])


def test_same_seed_same_inputs():
    gen, spec, churn, mix = _small("alloc-10k", "churn")
    docs = []
    for seed in (2**31 + 5, 2**31 + 5, 7):
        cluster = gen.cluster_doc(spec, seed)
        docs.append(json.dumps(
            [cluster, churn.Churn(gen, spec, mix, cluster, seed).documents()]))
    assert docs[0] == docs[1] and docs[0] != docs[2]
    sizes = [{k: len(v) for k, v in json.loads(d)[0].items()
              if isinstance(v, list)} for d in docs]
    assert sizes[0] == sizes[2], "a seed changes which, never how many"


def test_finished_and_evicted_gangs_are_deleted_with_their_groups():
    """What a shim sends: a finished gang's pods and pod group go in the
    delta; an evicted gang's once the commit that evicted it is seen; new
    gangs bring pod groups of their own.  The reference follows."""
    sys.path[:0] = [os.path.join(ROOT, "benchmark")]
    from lib import host_model
    gen, spec, churn_mod, mix = _small("alloc-10k", "churn")
    cluster = gen.cluster_doc(spec, 9)
    churn = churn_mod.Churn(gen, spec, mix, cluster, 9)
    model = host_model.HostModel(cluster)
    delta, intake = churn.documents()
    gone = delta["pod_groups_delete"]
    assert len(gone) == mix["per_cycle"]["complete_gangs"]
    assert len(delta["pods_delete"]) == len(gone) * spec["tasks_per_gang"]
    model.apply_doc(delta)
    model.apply_doc(intake)
    assert not set(gone) & set(model.gangs)
    new = [g["name"] for g in intake["pod_groups_upsert"]]
    assert len(new) == mix["per_cycle"]["submit_gangs"]
    assert not set(new) & {g["name"] for g in cluster["pod_groups"]}
    # one whole gang and one pod of another are evicted
    whole, part = list(churn.placed)[:2]
    victims = sorted(churn.placed[whole]) + sorted(churn.placed[part])[:1]
    churn.observe({"bind_requests": [], "evictions": [
        {"pod": p, "group": churn.gang_of[p], "move_to": None}
        for p in victims]})
    churn.per = dict(churn.per, complete_gangs=0)
    delta, _ = churn.documents()
    assert sorted(victims) == sorted(delta["pods_delete"])
    assert whole in delta["pod_groups_delete"]
    assert part not in delta["pod_groups_delete"]
