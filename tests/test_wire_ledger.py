"""kai-wire tests — transfer ledger, compile watcher, and the
``/debug/wire`` surface (ISSUE 7 tentpole).

The acceptance properties directly:

* every ``jax.device_put`` in the package flows through the
  TransferLedger (the KAI071 cleanliness half lives in
  ``tests/test_analysis.py``, which lints the package with the rest of
  the rules — here we pin the runtime side: cycles report their wire
  summary and the full build lands on the ledger);
* the redundancy invariant: a ≥20-cycle soak at 1% journaled churn
  reports re-uploaded-identical bytes == 0 on the patch path, with the
  patched leaves shipped in ONE batched dispatch;
* CompileWatcher attributes an induced shape-churn recompile to the
  right (entry, signature) pair, and a storm of misses raises the
  alarm;
* ``GET /debug/wire`` returns a valid document under a concurrent
  cycles-vs-scrapes hammer (ring entries are immutable once rolled).
"""
import json
import urllib.request

import numpy as np
import pytest

from bench import _churn_cluster
from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.framework.scheduler import Scheduler
from kai_scheduler_tpu.framework.server import SchedulerServer
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.runtime.compile_watch import WATCHER, CompileWatcher
from kai_scheduler_tpu.runtime.wire_ledger import (
    LEDGER, REASON_FULL_BUILD, REASON_JOURNAL_PATCH, TransferLedger)
from kai_scheduler_tpu.state import make_cluster

WIRE_SUMMARY_KEYS = {"cycle", "by_reason", "bytes", "leaves",
                     "dispatches", "redundant_bytes", "redundant_leaves",
                     "resident_bytes", "resident_buffers",
                     "peak_resident_bytes", "dropped",
                     "unfingerprinted_bytes"}


# ---------------------------------------------------------------------------
# ledger unit behaviour (private instances — the global LEDGER carries
# whatever other tests shipped)
# ---------------------------------------------------------------------------


def _tree(seed: int = 0):
    rng = np.random.default_rng(seed)
    return {"x": rng.standard_normal(16).astype(np.float32),
            "y": np.arange(12, dtype=np.int32).reshape(3, 4)}


def test_ledger_records_batched_dispatch_and_leaf_events():
    led = TransferLedger(retain_cycles=4)
    tree = _tree()
    out = led.device_put(tree, reason=REASON_FULL_BUILD, site="t",
                         replace_site=True, leaf_names=["x", "y"])
    assert set(out) == {"x", "y"}  # same pytree back, on device
    s = led.roll_cycle(0)
    assert WIRE_SUMMARY_KEYS <= set(s)
    assert s["leaves"] == 2 and s["dispatches"] == 1
    assert s["bytes"] == 16 * 4 + 12 * 4
    assert s["redundant_bytes"] == 0 and s["unfingerprinted_bytes"] == 0
    assert s["resident_buffers"] == 2 and s["resident_bytes"] == s["bytes"]
    [doc] = led.last(1)
    assert [e["leaf"] for e in doc["events"]] == ["x", "y"]
    ev = doc["events"][0]
    assert (ev["nbytes"], ev["dtype"], ev["shape"],
            ev["reason"], ev["redundant"]) == (
        64, "float32", [16], REASON_FULL_BUILD, False)


def test_ledger_redundancy_detector_counts_identical_reuploads():
    led = TransferLedger()
    tree = _tree()
    led.device_put(tree, reason=REASON_FULL_BUILD, site="t",
                   replace_site=True, leaf_names=["x", "y"])
    led.roll_cycle(0)
    # identical re-upload: every byte is redundant
    led.device_put(_tree(), reason=REASON_JOURNAL_PATCH, site="t",
                   leaf_names=["x", "y"])
    s = led.roll_cycle(1)
    assert s["redundant_leaves"] == 2
    assert s["redundant_bytes"] == s["bytes"]
    assert s["by_reason"][REASON_JOURNAL_PATCH]["redundant_bytes"] \
        == s["bytes"]
    # changed content is NOT redundant; unchanged sibling still is
    changed = _tree()
    changed["x"] = changed["x"] + 1.0
    led.device_put(changed, reason=REASON_JOURNAL_PATCH, site="t",
                   leaf_names=["x", "y"])
    s = led.roll_cycle(2)
    assert s["redundant_leaves"] == 1  # only y
    assert s["redundant_bytes"] == 48
    # a full rebuild that re-ships identical bytes is caught even with
    # replace_site=True (the compare happens before supersession)
    led.device_put(changed, reason=REASON_FULL_BUILD, site="t",
                   replace_site=True, leaf_names=["x", "y"])
    s = led.roll_cycle(3)
    assert s["redundant_leaves"] == 2


def test_ledger_residency_replace_site_and_shape_change():
    led = TransferLedger()
    led.device_put({"a": np.zeros(8, np.float32),
                    "b": np.zeros(4, np.float32)},
                   reason=REASON_FULL_BUILD, site="t", replace_site=True,
                   leaf_names=["a", "b"])
    assert led.residency() == {"buffers": 2, "bytes": 48,
                               "peak_bytes": 48}
    # a patch replaces one leaf with a BIGGER buffer: bytes track the
    # latest upload per key
    led.device_put({"a": np.zeros(16, np.float32)},
                   reason=REASON_JOURNAL_PATCH, site="t",
                   leaf_names=["a"])
    assert led.residency()["bytes"] == 64 + 16
    # a full rebuild with a different leaf set supersedes the site:
    # "b" leaves the resident set
    led.device_put({"a": np.zeros(16, np.float32)},
                   reason=REASON_FULL_BUILD, site="t", replace_site=True,
                   leaf_names=["a"])
    r = led.residency()
    assert r["buffers"] == 1 and r["bytes"] == 64
    assert r["peak_bytes"] >= 80  # the pre-supersession watermark held
    led.roll_cycle(0)
    # same content bytes, different shape geometry is NOT redundant
    # (the fingerprint qualifies the crc with nbytes/dtype/shape)
    led.device_put({"a": np.zeros((4, 4), np.float32)},
                   reason=REASON_JOURNAL_PATCH, site="t",
                   leaf_names=["a"])
    assert led.roll_cycle(1)["redundant_leaves"] == 0


def test_ledger_ring_and_event_bounds():
    led = TransferLedger(retain_cycles=2, max_events_per_cycle=3)
    for cid in range(4):
        led.device_put({f"l{i}": np.full(2, cid, np.float32)
                        for i in range(5)},
                       reason=REASON_FULL_BUILD, site="t",
                       leaf_names=[f"l{i}" for i in range(5)])
        s = led.roll_cycle(cid)
        # aggregates count ALL leaves even though the event list is
        # bounded — dropped bytes never vanish from the totals
        assert s["leaves"] == 5 and s["dropped"] == 2
    doc = led.wire_doc()
    assert [c["cycle"] for c in doc["cycles"]] == [2, 3]  # bounded ring
    assert all(len(c["events"]) == 3 for c in doc["cycles"])
    json.dumps(doc)  # fully serializable
    one = led.wire_doc(cycles=1)
    assert [c["cycle"] for c in one["cycles"]] == [3]


def test_ledger_leaf_names_pair_with_flatten_order():
    """jax flattens dict keys SORTED, not in insertion order — leaf
    names must pair with the flattened leaves, or every multi-leaf
    batch records bytes/fingerprints under the wrong keys (regression:
    the patch path passed insertion-ordered names)."""
    led = TransferLedger()
    tree = {}
    tree["z_small"] = np.zeros(2, np.float32)   # insertion order...
    tree["a_big"] = np.zeros(100, np.float32)   # ...inverts sort order
    led.device_put(tree, reason=REASON_JOURNAL_PATCH, site="t",
                   leaf_names=sorted(tree))
    s = led.roll_cycle(0)
    assert s["leaves"] == 2
    [doc] = led.last(1)
    by = {e["leaf"]: e["nbytes"] for e in doc["events"]}
    assert by == {"a_big": 400, "z_small": 8}
    with pytest.raises(ValueError):
        led.device_put(tree, reason=REASON_JOURNAL_PATCH, site="t",
                       leaf_names=["only-one"])


def test_patch_events_name_real_leaves_across_sections():
    """End-to-end ordering regression: a churned cycle patches leaves
    in several ClusterState sections (nodes occupancy + gang state +
    running table); every journal-patch event's (name -> dtype/shape/
    nbytes) must match the snapshotter's actual host leaf of that
    name."""
    import jax

    cluster = _steady_cluster(num_nodes=16, num_gangs=16)
    sched = Scheduler()
    sched.run_once(cluster)
    rng = np.random.default_rng(1)
    checked_sections = set()
    for _ in range(6):
        _churn_cluster(cluster, rng, 0.05, num_nodes=16)
        res = sched.run_once(cluster)
        if sched._snapshotter.stats.last["mode"] != "patched":
            continue
        host = {jax.tree_util.keystr(p): leaf for p, leaf in
                jax.tree_util.tree_flatten_with_path(
                    sched._snapshotter._host)[0]}
        [doc] = LEDGER.last(1)
        assert doc["cycle"] == res.wire["cycle"]
        for ev in doc["events"]:
            if ev["reason"] != REASON_JOURNAL_PATCH:
                continue
            leaf = host[ev["leaf"]]
            assert ev["nbytes"] == int(leaf.nbytes), ev
            assert ev["dtype"] == str(leaf.dtype), ev
            assert ev["shape"] == list(leaf.shape), ev
            checked_sections.add(ev["leaf"].split(".")[1])
    # the churn must actually have exercised a multi-section patch,
    # else the ordering property was never at stake
    assert len(checked_sections) >= 2, checked_sections


def test_ledger_reason_override_and_non_numpy_leaves():
    import jax.numpy as jnp
    led = TransferLedger()
    with led.override_reason("fallback"):
        led.device_put({"x": np.zeros(4, np.float32)},
                       reason=REASON_FULL_BUILD, site="t",
                       leaf_names=["x"])
    # a device-resident leaf is size-counted but not fingerprinted —
    # hashing it would itself force a transfer
    led.device_put({"d": jnp.zeros(4, jnp.float32)}, reason="mesh-shard",
                   site="t", leaf_names=["d"])
    s = led.roll_cycle(0)
    assert set(s["by_reason"]) == {"fallback", "mesh-shard"}
    assert s["by_reason"]["mesh-shard"]["unfingerprinted_bytes"] == 16


# ---------------------------------------------------------------------------
# the instrumented cycle + the redundancy soak
# ---------------------------------------------------------------------------


def _steady_cluster(num_nodes=48, num_gangs=48):
    """Post-binder steady state at a small shape (mirrors bench_churn:
    running pods carry concrete devices so churned rebinds patch)."""
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=num_nodes, node_accel=8.0, num_gangs=num_gangs,
        tasks_per_gang=2, running_fraction=0.5)
    cursor: dict = {}
    for p in pods:
        if p.status == apis.PodStatus.RUNNING:
            c = cursor.get(p.node, 0)
            p.accel_devices = [c]
            cursor[p.node] = c + 1
    return Cluster.from_objects(nodes, queues, groups, pods, topo)


def test_cycle_result_carries_wire_summary():
    cluster = _steady_cluster(num_nodes=8, num_gangs=8)
    sched = Scheduler()
    res = sched.run_once(cluster)
    assert WIRE_SUMMARY_KEYS <= set(res.wire)
    # the cold cycle's snapshot build landed on the ledger as the
    # incremental engine's full rebuild
    assert res.wire["by_reason"]["fallback"]["bytes"] > 0
    assert res.wire["by_reason"]["fallback"]["dispatches"] == 1
    assert res.wire["resident_bytes"] > 0
    # the wire counters ride the cycle trace as Chrome "C" lanes
    doc = sched.tracer.export_chrome(cycles=1)
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert {e["name"] for e in counters} == {"wire bytes/cycle",
                                             "device resident bytes"}
    up = [e for e in counters if e["name"] == "wire bytes/cycle"]
    assert up[0]["args"]["uploaded"] == res.wire["bytes"]
    json.dumps(doc)


def test_soak_patch_path_never_reuploads_identical_bytes():
    """THE redundancy invariant (ROADMAP-1 acceptance substrate): ≥20
    cycles at 1% journaled churn — every patched cycle ships changed
    bytes only (redundant-identical == 0) in ONE batched dispatch."""
    cluster = _steady_cluster()
    sched = Scheduler()
    sched.run_once(cluster)  # cold full build
    rng = np.random.default_rng(0)
    patched = 0
    for _ in range(22):
        _churn_cluster(cluster, rng, 0.01, num_nodes=48)
        res = sched.run_once(cluster)
        last = sched._snapshotter.stats.last
        if last["mode"] != "patched":
            continue
        patched += 1
        pr = res.wire["by_reason"].get(REASON_JOURNAL_PATCH)
        assert pr is not None and pr["bytes"] > 0, res.wire
        # the invariant: zero re-uploaded-identical bytes on the patch
        # path — _ship compares against the cached host leaves, the
        # ledger's content fingerprints independently agree
        assert pr["redundant_bytes"] == 0, res.wire
        # satellite: all patched leaves ride ONE batched device_put
        assert pr["dispatches"] == 1, res.wire
        assert last["ship_dispatches"] == 1
        assert pr["leaves"] == last["leaves_shipped"]
        assert pr["bytes"] == last["bytes_shipped"]
    # the soak is only meaningful if the patch path actually ran
    assert patched >= 15, sched._snapshotter.stats.fallbacks


# ---------------------------------------------------------------------------
# compile watcher
# ---------------------------------------------------------------------------


def test_compile_watcher_attributes_shape_churn_to_entry():
    """Deliberate shape churn: the same entry called at two padded
    shapes records two distinct (entry, signature) misses; a repeat
    call at a seen shape records none."""
    import jax.numpy as jnp

    from kai_scheduler_tpu.framework.session import _set_fair_share_jit

    def snap(n_queues):
        nodes, queues, groups, pods, topo = make_cluster(
            num_nodes=4, node_accel=8.0, num_gangs=4, tasks_per_gang=1,
            num_departments=1, queues_per_department=n_queues)
        from kai_scheduler_tpu.state.cluster_state import build_snapshot
        state, _ = build_snapshot(nodes, queues, groups, pods, topo,
                                  now=1.0)
        return state

    # num_levels=5 is unique to this test, so the signatures are fresh
    # no matter what the rest of the suite compiled before us
    st_small, st_big = snap(2), snap(40)  # queue axis pads 32 vs 64
    before = WATCHER.report()["entries"]["set_fair_share"]
    sigs_before = {e["signature"] for e in WATCHER.events()}
    _set_fair_share_jit(st_small, num_levels=5,
                        k_value=jnp.float32(0.0))
    _set_fair_share_jit(st_big, num_levels=5, k_value=jnp.float32(0.0))
    _set_fair_share_jit(st_small, num_levels=5,
                        k_value=jnp.float32(0.0))  # seen: no new miss
    after = WATCHER.report()["entries"]["set_fair_share"]
    assert after["misses"] - before["misses"] == 2
    assert after["calls"] - before["calls"] == 3
    assert after["seconds"] > before["seconds"]
    new = [e for e in WATCHER.events()
           if e["entry"] == "set_fair_share"
           and e["signature"] not in sigs_before]
    assert len(new) == 2
    # the two induced misses carry DISTINCT abstract signatures
    assert len({e["signature"] for e in new}) == 2


def test_compile_watcher_storm_alarm_and_cache_probe_forwarding():
    import jax

    w = CompileWatcher(storm_threshold=2, storm_window_s=3600.0)
    base = jax.jit(lambda x: x + 1)
    f = w.wrap("toy", base)
    # the jit cache probe and raw function survive the wrapper (the
    # trace probe's compile-once assertion depends on both)
    assert hasattr(f, "_cache_size")
    assert f.__wrapped__ is getattr(base, "__wrapped__", base)
    f(np.zeros(1, np.float32))   # miss 1
    rep = w.report()
    assert rep["alarms"] == 0
    f(np.zeros(2, np.float32))   # miss 2 -> storm threshold reached
    f(np.zeros(1, np.float32))   # seen signature: no new miss
    rep = w.report()
    assert rep["entries"]["toy"] == {
        "signatures": 2, "misses": 2, "calls": 3,
        "seconds": rep["entries"]["toy"]["seconds"]}
    assert rep["alarms"] == 1
    assert [e["storm"] for e in rep["events"]] == [False, True]


def test_compile_watcher_covers_callgraph_jit_entries():
    """Every jit entry the analysis call graph discovers is hooked into
    the watcher — add a new jitted kernel and this fails until it is
    wrapped (mirrors the probe-coverage meta-test)."""
    import os

    from kai_scheduler_tpu.analysis.callgraph import PackageGraph
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    entry_to_watch = {
        "_fused_pipeline": "fused_pipeline",
        "_pack_commit": "pack_commit",
        "allocate_jit": "allocate",
        "set_fair_share": "set_fair_share",
        "stale_gang_eviction": "stale_gang_eviction",
        "run_victim_action_jit": "run_victim_action",
        # kai-pulse cluster-health kernel (ops/analytics.py)
        "cluster_analytics": "analytics",
        # kai-repack defragmentation solver (ops/repack.py)
        "plan_repack": "repack",
        # analysis-only probe helper, never on the production cycle
        "cumsum_ds": None,
    }
    graph = PackageGraph(root)
    entries = {q for _m, q in graph._entries()}
    assert entries == set(entry_to_watch), (
        f"jit entry set changed: {sorted(entries)} — hook new entries "
        f"into runtime/compile_watch (and this map)")
    watched = set(WATCHER.entries())
    expected = {w for w in entry_to_watch.values() if w is not None}
    assert expected <= watched, expected - watched


# ---------------------------------------------------------------------------
# server endpoints
# ---------------------------------------------------------------------------


def _get_json(base, path):
    return json.load(urllib.request.urlopen(f"{base}{path}", timeout=10))


def _small_cluster():
    nodes = [apis.Node("n0", apis.ResourceVec(8, 64, 256))]
    queues = [apis.Queue("q", accel=apis.QueueResource(quota=8))]
    groups = [apis.PodGroup("g", queue="q", min_member=1)]
    pods = [apis.Pod("p", "g", apis.ResourceVec(1, 1, 1))]
    return Cluster.from_objects(nodes, queues, groups, pods)


def test_debug_wire_endpoint_and_healthz_wire_summary():
    server = SchedulerServer(_small_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        # before any cycle: a valid document (possibly with cycles from
        # earlier tests — the ledger is process-global, like /metrics)
        doc = _get_json(base, "/debug/wire")
        assert {"cycles", "window", "residency", "totals",
                "compile"} <= set(doc)
        req = urllib.request.Request(
            f"{base}/cycle/stored", data=b"{}",
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=60)
        doc = _get_json(base, "/debug/wire?cycles=1")
        assert len(doc["cycles"]) == 1
        cyc = doc["cycles"][0]
        assert cyc["bytes"] > 0 and cyc["events"]
        assert all({"leaf", "nbytes", "dtype", "shape", "reason",
                    "redundant"} <= set(e) for e in cyc["events"])
        assert doc["residency"]["bytes"] > 0
        assert doc["compile"]["entries"]  # per-entry miss attribution
        bad = urllib.request.Request(f"{base}/debug/wire?cycles=zap")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(bad, timeout=10)
        health = _get_json(base, "/healthz")
        wire = health["last_cycle"]["wire"]
        assert WIRE_SUMMARY_KEYS <= set(wire)
    finally:
        server.stop()


def test_debug_wire_hammer_no_torn_documents():
    """Cycles run while /debug/wire and /healthz are scraped
    concurrently: every response is a complete, valid document (ring
    entries are immutable once rolled; the summary doc is swapped)."""
    import concurrent.futures

    server = SchedulerServer(_small_cluster()).start()
    base = f"http://127.0.0.1:{server.port}"

    def post_cycle(_i):
        req = urllib.request.Request(
            f"{base}/cycle/stored", data=b"{}",
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=60).status

    def get_wire(_i):
        doc = _get_json(base, "/debug/wire")
        assert {"cycles", "window", "residency", "compile"} <= set(doc)
        for cyc in doc["cycles"]:
            assert WIRE_SUMMARY_KEYS <= set(cyc)
            # a rolled cycle's bounded event list is consistent with
            # its aggregates: retained events + dropped == leaves
            assert len(cyc["events"]) + cyc["dropped"] == cyc["leaves"]
        return 200

    def get_health(_i):
        _get_json(base, "/healthz")
        return 200

    try:
        post_cycle(0)  # compile before the storm
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futures = []
            for i in range(8):
                futures.append(pool.submit(post_cycle, i))
                futures.append(pool.submit(get_wire, i))
                futures.append(pool.submit(get_health, i))
            statuses = [f.result() for f in futures]
        assert all(s == 200 for s in statuses)
    finally:
        server.stop()


def test_wire_and_compile_metrics_registered_and_populated():
    from kai_scheduler_tpu.framework import metrics
    Scheduler().run_once(_small_cluster())
    text = metrics.registry.render()
    for name in ("kai_wire_uploaded_bytes_total",
                 "kai_wire_uploaded_leaves_total",
                 "kai_wire_dispatches_total",
                 "kai_wire_redundant_bytes_total",
                 "kai_wire_resident_bytes",
                 "kai_wire_resident_buffers",
                 "kai_wire_cycle_uploaded_bytes",
                 "kai_compile_cache_misses_total",
                 "kai_compile_seconds_total",
                 "kai_compile_storm_alarms_total"):
        assert name in text, name
    assert metrics.wire_uploaded_bytes.value("fallback") > 0
    assert metrics.wire_resident_bytes.value() > 0
    assert metrics.compile_cache_misses.value("fused_pipeline") >= 1
