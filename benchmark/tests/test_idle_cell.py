"""``reclaim-10k.idle`` on the CPU at 256 nodes: the warm-up rule ends by
itself, every cycle of the window patches, nothing compiles, the chip's
``upload`` phase gets a reading, and every check of the reference reads
0.  Run by hand (not part of tier-1)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "benchmark")]

from lib import registry  # noqa: E402


def test_quiet_rule():
    done = registry.module("warmup", "quiet").done
    rule = {"until": "quiet", "min_cycles": 3, "max_cycles": 12}
    calm = {"jit_misses": 0, "compile_requests": 0, "binds": 0,
            "evictions": 0}
    assert not done([calm, calm], rule)              # min_cycles
    assert done([calm, calm, calm], rule)
    assert not done([calm, calm, dict(calm, jit_misses=1)], rule)
    assert not done([calm, dict(calm, evictions=8), calm], rule)
    assert done([dict(calm, binds=8), calm, calm], rule)
    assert not done([calm, calm, dict(calm, compile_requests=2)], rule)


def test_six_cycles_all_patched():
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"),
         "--workload", "reclaim-10k.idle", "--seed", str(2**31 + 7),
         "--seconds", "600", "--cycles", "6", "--nodes", "256",
         "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == "", "a rehearsal prints no result"
    doc = json.loads(next(ln for ln in proc.stderr.splitlines()
                          if ln.startswith('{"rehearsal"')))
    res, warm = doc["rehearsal"], doc["setup"]["warmup_cycles"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 6
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert len(warm) == 3 and not warm[-1]["compile_requests"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["snapshot_fallbacks"] == 0 and m["window_compiles"] == 0
    assert m["upload_ms"] > 0 and m["snapshot_patch_ms"] > 0
    assert m["snapshot_patch_ms"] <= m["snapshot_ms"]
    for rebuilt_only in ("snapshot_lists_ms", "snapshot_encode_ms",
                         "snapshot_transfer_ms", "snapshot_ledgers_ms",
                         "churn_post_ms"):
        assert rebuilt_only not in m
    for name in ("intake_coalesce_ms", "gc_pause_ms",
                 "gc_full_collections", "retrace_s", "first_snapshot_s",
                 "idle_unattributed_ms", "cycle_wall_max_ms"):
        assert name in m, name
    window = res["window"]
    assert window["cycles_with_binds"] == 0
    assert window["cycles_with_evictions"] == 0
    assert window["pending_gangs"] == [0]
