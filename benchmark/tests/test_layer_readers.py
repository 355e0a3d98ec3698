"""The readers of the program's own account of a cycle
(``lib/spans.py`` and the metrics over it), on ``/healthz`` documents
recorded on the CPU at 256 nodes (``data/health_small.json``), and
``idle_unattributed_ms`` on the trace recorded on the chip
(``data/trace_small.json``).  Run by hand (not part of tier-1)."""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

from lib import registry, trace_reduce  # noqa: E402

NEW = ("snapshot_lists_ms", "snapshot_encode_ms", "snapshot_transfer_ms",
       "snapshot_ledgers_ms", "snapshot_patch_ms", "intake_coalesce_ms",
       "gc_pause_ms", "gc_full_collections", "retrace_s",
       "first_snapshot_s", "idle_unattributed_ms")


def recorded(key: str) -> dict:
    with open(os.path.join(HERE, "data", "health_small.json")) as fh:
        return json.load(fh)[key]


def run_of(*healths, trace=None):
    """As much of a ``lib.loop.Run`` as a reader looks at."""
    return types.SimpleNamespace(
        cycles=[{"health": h} for h in healths], trace=trace)


def read(metric: str, run):
    return registry.module("layer_metrics", metric).read(run)


def under(health: dict, name: str) -> float:
    return 1e3 * sum(s for p, s in health["span_self_seconds"].items()
                     if name in p.split("/"))


def test_every_new_metric_has_an_entry_and_a_reader():
    bench = registry.load_json(registry.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert name in entries, name
        assert callable(registry.module("layer_metrics", name).read)
    rebuilt = ["reclaim-10k.steady", "alloc-10k.churn"]
    for name in NEW[:4]:
        assert entries[name]["workloads"] == rebuilt
    assert entries["snapshot_patch_ms"]["workloads"] == ["reclaim-10k.idle"]
    assert all("workloads" not in entries[n] for n in NEW[5:])


def test_rebuilt_cycle_splits_four_ways():
    h = recorded("rebuilt")
    run = run_of(h, h)
    parts = {m: read(m, run) for m in NEW[:4]}
    assert all(v > 0 for v in parts.values())
    assert parts["snapshot_encode_ms"] == pytest.approx(
        under(h, "snapshot.encode"))
    # sections included: more than the encode span's own self time
    own = 1e3 * next(s for p, s in h["span_self_seconds"].items()
                     if p.endswith("/snapshot.encode"))
    assert parts["snapshot_encode_ms"] > own
    # the four are the rebuild, but for its own few lines
    assert sum(parts.values()) == pytest.approx(
        under(h, "snapshot.full_build"), rel=0.05)
    assert sum(parts.values()) <= 1e3 * h["phase_seconds"]["snapshot"]
    assert read("snapshot_patch_ms", run) == 0.0


def test_patched_cycle_reads_the_patch_and_no_rebuild():
    h = recorded("patched")
    run = run_of(h)
    patch = read("snapshot_patch_ms", run)
    assert 0 < patch <= 1e3 * h["phase_seconds"]["snapshot"]
    assert patch == pytest.approx(under(h, "snapshot.patch"))
    assert all(read(m, run) == 0.0 for m in NEW[:4])
    # an upload booked under the patch would be left out
    nested = json.loads(json.dumps(h))
    nested["span_self_seconds"][
        "cycle/snapshot/snapshot.patch/upload"] = 5.0
    assert read("snapshot_patch_ms", run_of(nested)) == pytest.approx(patch)


def test_counters_and_start_up():
    a, b = recorded("rebuilt"), recorded("patched")
    run = run_of(a, b)
    assert read("intake_coalesce_ms", run) == pytest.approx(
        1e3 * (a["entry_seconds"]["coalesce"]
               + b["entry_seconds"]["coalesce"]) / 2)
    assert read("gc_pause_ms", run) == pytest.approx(
        1e3 * (sum(a["gc"]["pause_seconds"])
               + sum(b["gc"]["pause_seconds"])) / 2)
    assert read("gc_full_collections", run) == (
        a["gc"]["collections"][2] + b["gc"]["collections"][2])
    # start-up is read as the first window cycle reports it
    assert read("retrace_s", run) == pytest.approx(
        a["startup"]["trace_s"] + a["startup"]["lower_s"])
    assert read("first_snapshot_s", run) == (
        a["startup"]["phase_seconds"]["snapshot"])


def test_a_program_without_the_spans_reads_nothing():
    """The parent commit serves none of the new keys: every reader
    returns ``None`` and raises nothing, traced or not."""
    old = {k: v for k, v in recorded("rebuilt").items()
           if k not in ("span_self_seconds", "snapshot", "gc",
                        "entry_seconds", "startup")}
    with open(os.path.join(HERE, "data", "trace_small.json")) as fh:
        trace = trace_reduce.reduce(json.load(fh)["raw"])
    for run in (run_of(old, trace=trace), run_of(old),
                types.SimpleNamespace(cycles=[{}], trace=None)):
        for name in NEW:
            assert read(name, run) is None, name


def test_idle_unattributed_on_the_recorded_trace():
    with open(os.path.join(HERE, "data", "trace_small.json")) as fh:
        trace = trace_reduce.reduce(json.load(fh)["raw"])
    assert trace["cycles"] == 1
    idle = trace["idle_by_host_span"]["cycle_post"]
    h = recorded("rebuilt")
    own = (sum(s for p, s in h["phase_seconds"].items()
               if p != "device_wait")
           + sum(h["entry_seconds"].values()))
    # only the traced cycles count, however long the window ran
    run = run_of(h, recorded("patched"), trace=trace)
    assert read("idle_unattributed_ms", run) == pytest.approx(
        1e3 * abs(idle - own))
    # a program that accounts for every idle instant reads 0
    exact = json.loads(json.dumps(h))
    exact["phase_seconds"] = dict.fromkeys(h["phase_seconds"], 0.0)
    exact["phase_seconds"]["snapshot"] = idle
    exact["entry_seconds"] = {"lock_wait": 0.0, "coalesce": 0.0}
    assert read("idle_unattributed_ms",
                run_of(exact, trace=trace)) == pytest.approx(0.0, abs=1e-9)
    assert read("idle_unattributed_ms", run_of(h)) is None
