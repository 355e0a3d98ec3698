"""The reduction from trace to numbers, on a trace small enough to work
out by hand and on a small one recorded on the chip.  Run by hand (not
part of tier-1)."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

from lib import solve_bytes, trace_reduce  # noqa: E402


def test_by_hand():
    s = 1e9  # ns
    raw = {
        "host": [["churn_post", 0.0, 1 * s], ["cycle_post", 1 * s, 4 * s],
                 ["churn_post", 6 * s, 1 * s], ["cycle_post", 7 * s, 3 * s]],
        "devices": {"/device:TPU:0": [
            ["while.1", 2 * s, 2 * s],        # 2..4, holds the next two
            ["fusion.7", 2 * s, 0.5 * s],
            ["fusion.7", 3 * s, 0.5 * s],
            ["copy.2", 4.5 * s, 0.25 * s],    # 4.5..4.75
            ["fusion.9", 8 * s, 1 * s],       # 8..9
            ["late.0", 9.5 * s, 2 * s],       # clipped at 10
        ]},
    }
    out = trace_reduce.reduce(raw)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["busy_s"] == pytest.approx(2 + 0.25 + 1 + 0.5)
    assert out["cycles"] == 2
    ops = dict(out["device_ops"])
    assert ops["while.1"] == pytest.approx(1.0)   # self time: 2 - 2 * 0.5
    assert ops["fusion.7"] == pytest.approx(1.0)
    assert ops["late.0"] == pytest.approx(0.5)
    # idle 0..2, 4..4.5, 4.75..8 and 9..9.5, cut at 1, 5, 6 and 7
    gaps = out["idle_gaps"]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [0.25, 0.5, 0.5, 1.0, 1.0, 1.0, 1.0, 1.0])
    assert sum(g[1] for g in gaps) == pytest.approx(10 - 3.75)
    by_span = out["idle_by_host_span"]
    assert by_span["between_posts"] == pytest.approx(1.0)   # 5..6
    assert by_span["churn_post"] == pytest.approx(2.0)      # 0..1, 6..7
    assert by_span["cycle_post"] == pytest.approx(3.25)


def test_nothing_to_read():
    assert trace_reduce.reduce({"host": [], "devices": {}}) is None
    assert trace_reduce.reduce(
        {"host": [["cycle_post", 0.0, 5.0]],
         "devices": {"/device:TPU:0": [["x", 9.0, 1.0]]}}) is None


def test_recorded_on_the_chip():
    path = os.path.join(HERE, "data", "trace_small.json")
    with open(path) as fh:
        doc = json.load(fh)
    out = trace_reduce.reduce(doc["raw"])
    for key, want in doc["reduced"].items():
        if isinstance(want, float):
            assert out[key] == pytest.approx(want, rel=1e-9), key
        else:
            assert json.loads(json.dumps(out[key])) == want, key
    assert 0 < out["busy_s"] <= out["window_s"]


def test_solve_bytes_counts_shapes_only():
    shapes = {"nodes": 10000, "gangs": 5016, "tasks_per_gang": 8,
              "placed_pods": 40000, "queues": 6, "resources": 3}
    one = solve_bytes.solve_min_bytes(shapes, ["allocate"])
    five = solve_bytes.solve_min_bytes(shapes, list("abcde"))
    assert five == 5 * one
    assert 1_500_000 < one < 3_000_000
