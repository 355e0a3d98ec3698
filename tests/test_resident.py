"""kai-resident — device-resident cluster state (ops/resident.py).

Tier-1 coverage for ROADMAP item 1's endgame:

* packed-delta unit properties: pack/apply round-trip bit-exactness on
  randomized mirror mutations, identity reuse for unchanged leaves,
  NaN stability, shape-change rejection, fixed pytree structure;
* THE soak: 20+ churn cycles where the resident scheduler's bind
  requests, evictions, DecisionLog events, and analytics docs are
  bit-identical to a full-rebuild twin — including a mid-soak
  structural-change fallback and recovery back to resident mode —
  while every steady resident cycle performs exactly ONE watched jit
  dispatch and ONE ``device_put`` whose bytes equal the packed
  journal-delta size (asserted via the TransferLedger), with zero
  redundant-identical bytes and the full snapshot counted as reused
  device-resident bytes;
* the desync guard (a staged-but-never-adopted delta forces a full
  rebuild instead of serving a mirror the device never saw) and the
  verify gather (``verify_device_residency`` catches a device/mirror
  divergence).
"""
import copy

import jax
import numpy as np
import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.framework.scheduler import (Scheduler,
                                                   SchedulerConfig)
from kai_scheduler_tpu.intake import apply as intake_apply
from kai_scheduler_tpu.ops import resident as resident_ops
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.runtime.compile_watch import WATCHER
from kai_scheduler_tpu.runtime.wire_ledger import (LEDGER,
                                                   REASON_DELTA_APPLY)
from kai_scheduler_tpu.state.cluster_state import build_snapshot
from kai_scheduler_tpu.state.incremental import (IncrementalSnapshotter,
                                                 IncrementalVerifyError)
from kai_scheduler_tpu.state.synthetic import make_cluster


# ---------------------------------------------------------------------------
# delta pack/apply units
# ---------------------------------------------------------------------------


def _host_mirror(now=100.0):
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=8, node_accel=8.0, num_gangs=8, tasks_per_gang=2,
        running_fraction=0.5)
    _state, _index, host = build_snapshot(
        nodes, queues, groups, pods, topo, now=now, _return_host=True)
    return host


def _mutate(host, rng, leaf_fraction=0.5, elem_fraction=0.05):
    """A randomized same-shape mirror mutation: copy the pytree and
    perturb a few elements in a random subset of leaves."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(host)
    out = []
    for _path, leaf in paths:
        if rng.random() > leaf_fraction or leaf.size == 0:
            out.append(leaf)
            continue
        new = leaf.copy()
        k = max(1, int(leaf.size * elem_fraction))
        idx = rng.choice(leaf.size, size=min(k, leaf.size),
                         replace=False)
        flat = new.reshape(-1)
        if new.dtype.kind == "f":
            flat[idx] += 1.5
        elif new.dtype.kind == "b":
            flat[idx] = ~flat[idx]
        else:
            flat[idx] = flat[idx] + 1
        out.append(new)
    return jax.tree_util.tree_unflatten(treedef, out)


def test_pack_apply_roundtrip_bit_exact():
    rng = np.random.default_rng(0)
    old = _host_mirror()
    apply_jit = jax.jit(resident_ops.apply_delta)
    dev = jax.device_put(old)
    for trial in range(4):
        new = _mutate(old, rng)
        delta, merged, stats = resident_ops.pack_delta(old, new)
        assert stats["bytes"] == resident_ops.delta_nbytes(delta)
        dev = apply_jit(dev, jax.device_put(delta))
        for (p, want), got, kept in zip(
                jax.tree_util.tree_flatten_with_path(new)[0],
                jax.tree_util.tree_leaves(dev),
                jax.tree_util.tree_leaves(merged)):
            name = jax.tree_util.keystr(p)
            assert np.array_equal(np.asarray(got), want,
                                  equal_nan=want.dtype.kind == "f"), name
            assert np.array_equal(kept, want,
                                  equal_nan=want.dtype.kind == "f"), name
        old = merged


def test_pack_reuses_unchanged_leaf_objects_and_empty_delta():
    old = _host_mirror()
    # identical mirrors: every class ships zero-size segments and the
    # merged mirror is the OLD leaf objects (identity short-circuit)
    same = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(old),
        [leaf.copy() for leaf in jax.tree_util.tree_leaves(old)])
    delta, merged, stats = resident_ops.pack_delta(old, same)
    assert (stats["leaves"], stats["elements"], stats["bytes"]) \
        == (0, 0, 0)
    assert all(k == 0 for k in stats["buckets"].values())
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(old)):
        assert a is b
    # fixed structure: the no-op delta and a real one flatten alike
    real = resident_ops.pack_delta(old, _mutate(
        old, np.random.default_rng(1)))[0]
    assert (jax.tree_util.tree_structure(delta)
            == jax.tree_util.tree_structure(real))
    assert (jax.tree_util.tree_structure(delta)
            == jax.tree_util.tree_structure(
                resident_ops.empty_delta(old)))


def test_pack_bucket_hysteresis_pins_the_signature():
    """Fed back as ``min_buckets``, chosen segment lengths never
    shrink — a smaller later delta reuses the same padded shapes, so
    the fused entry's abstract signature cannot flip cycle-to-cycle
    (every flip would be a full XLA recompile)."""
    rng = np.random.default_rng(5)
    old = _host_mirror()
    big = _mutate(old, rng, leaf_fraction=0.9, elem_fraction=0.2)
    delta1, merged, stats1 = resident_ops.pack_delta(old, big)
    small = _mutate(merged, rng, leaf_fraction=0.2,
                    elem_fraction=0.01)
    delta2, _m, stats2 = resident_ops.pack_delta(
        merged, small, min_buckets=stats1["buckets"])
    for part in ("idx", "val"):
        assert {k: v.shape for k, v in delta2[part].items()} \
            == {k: v.shape for k, v in delta1[part].items()}
    assert all(stats2["buckets"][k] >= v
               for k, v in stats1["buckets"].items())


def test_pack_is_nan_stable():
    old = _host_mirror()
    leaves = jax.tree_util.tree_leaves(old)
    f32 = next(l for l in leaves if l.dtype == np.float32 and l.size > 4)
    f32.reshape(-1)[1] = np.nan
    new = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(old),
        [l.copy() for l in jax.tree_util.tree_leaves(old)])
    _delta, _merged, stats = resident_ops.pack_delta(old, new)
    # the NaN cell matches its NaN twin: nothing to ship
    assert stats["elements"] == 0 and stats["bytes"] == 0


def test_pack_rejects_shape_change():
    old = _host_mirror()
    paths, treedef = jax.tree_util.tree_flatten_with_path(old)
    bad = [leaf for _p, leaf in paths]
    bad[0] = np.zeros(np.asarray(bad[0]).shape + (2,), bad[0].dtype)
    with pytest.raises(resident_ops.DeltaShapeError):
        resident_ops.pack_delta(
            old, jax.tree_util.tree_unflatten(treedef, bad))


# ---------------------------------------------------------------------------
# THE soak: resident vs full-rebuild twin, bit-exact, one dispatch
# ---------------------------------------------------------------------------


def _steady_cluster(num_nodes=24, num_gangs=24):
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


def _churn(cluster, rng, frac, num_nodes):
    k = max(1, int(len(cluster.pods) * frac / 2))
    running = [p.name for p in cluster.pods.values()
               if p.status == apis.PodStatus.RUNNING][:k]
    # restart=True: the controller recreates the evicted pods, so they
    # re-enter PENDING and the next (resident) cycle actually has to
    # PLACE them — the bit-exact compare sees real bind decisions, not
    # an idle equilibrium
    for nm in running:
        cluster.evict_pod(nm, restart=True)
    pending = [p for p in cluster.pods.values()
               if p.status == apis.PodStatus.PENDING][:k]
    for p in pending:
        try:
            cluster.bind_pod(p.name, f"node-{rng.integers(0, num_nodes)}")
        except RuntimeError:
            pass
    cluster.tick()


def _submit_extra_gang(cluster, cyc):
    """A fresh 2-pod gang through the journal's gangs_added/pods_added
    path — exercised ON resident cycles (appends are patchable)."""
    queue = next(iter(cluster.pod_groups.values())).queue
    name = f"soak-extra-{cyc}"
    group = apis.PodGroup(name, queue=queue, min_member=2)
    pods = [apis.Pod(f"{name}-{t}", name, apis.ResourceVec(1, 1, 4))
            for t in range(2)]
    cluster.submit(group, pods)


def _last_cycle_events(sched):
    evs = sched.decisions.events(limit=100000)
    if not evs:
        return []
    last = max(e["cycle"] for e in evs)
    return sorted((e["gang"], e["queue"], e["outcome"], e["detail"])
                  for e in evs if e["cycle"] == last)


def test_soak_resident_bit_exact_vs_rebuild_twin_one_dispatch():
    """ROADMAP-1 acceptance: ≥20 churn cycles where the resident path
    is bit-exact against a full-rebuild twin, every steady resident
    cycle is ONE watched dispatch + ONE device_put whose bytes equal
    the packed delta size, and a forced mid-soak structural change
    falls back to the full build and recovers to resident mode."""
    num_nodes = 24
    c_res = _steady_cluster(num_nodes=num_nodes)
    c_twin = copy.deepcopy(c_res)
    s_res = Scheduler(SchedulerConfig(resident=True))
    s_twin = Scheduler(SchedulerConfig(incremental=False))
    rng_a = np.random.default_rng(7)
    rng_b = np.random.default_rng(7)
    resident_cycles = 0
    resident_cycles_with_binds = 0
    structural_at = 11
    recovered_after_structural = False
    late_misses = 0
    for cyc in range(24):
        rep = WATCHER.report()["entries"]
        calls_before = {k: v["calls"] for k, v in rep.items()}
        misses_before = rep.get("resident_cycle", {}).get("misses", 0)
        r1 = s_res.run_once(c_res)
        rep = WATCHER.report()["entries"]
        calls_after = {k: v["calls"] for k, v in rep.items()}
        if cyc >= 18:
            late_misses += (rep.get("resident_cycle", {})
                            .get("misses", 0) - misses_before)
        r2 = s_twin.run_once(c_twin)
        # --- bit-exactness: the whole commit surface -----------------
        assert r1.bind_requests == r2.bind_requests, cyc
        assert r1.evictions == r2.evictions, cyc
        assert r1.analytics == r2.analytics, cyc
        assert _last_cycle_events(s_res) == _last_cycle_events(s_twin), cyc
        last = s_res._snapshotter.stats.last
        if last["mode"] == "resident":
            resident_cycles += 1
            resident_cycles_with_binds += bool(r1.bind_requests)
            if cyc > structural_at:
                recovered_after_structural = True
            # --- exactly one watched jit dispatch --------------------
            dcalls = {k: calls_after.get(k, 0) - calls_before.get(k, 0)
                      for k in calls_after}
            dcalls = {k: v for k, v in dcalls.items() if v}
            assert dcalls == {"resident_cycle": 1}, (cyc, dcalls)
            # --- exactly one upload, bytes == packed delta size ------
            wire = r1.wire
            assert sorted(wire["by_reason"]) == [REASON_DELTA_APPLY], cyc
            da = wire["by_reason"][REASON_DELTA_APPLY]
            assert da["dispatches"] == 1, cyc
            assert da["bytes"] == last["bytes_shipped"] > 0, cyc
            assert wire["redundant_bytes"] == 0, cyc
            # --- the kai-resident payoff gauge pair ------------------
            # (reused == full resident snapshot: no snapshot leaf
            # touched the wire.  At toy scale the per-group bucket
            # floors dominate the delta, so delta ≪ snapshot is a
            # bench-scale property, not asserted here.)
            assert wire["resident_uploaded_bytes"] == da["bytes"], cyc
            assert (wire["resident_reused_bytes"]
                    == wire["resident_bytes"] > 0), cyc
        if cyc % 3 == 0:
            # fresh gangs through the journal append path — placed by
            # RESIDENT cycles (gang/pod adds are patchable)
            _submit_extra_gang(c_res, cyc)
            _submit_extra_gang(c_twin, cyc)
        if cyc == structural_at:
            # structural change on BOTH clusters: a new node appears —
            # unpatchable, the resident path must fall back whole
            for cl in (c_res, c_twin):
                node = apis.Node(f"node-{num_nodes}",
                                 apis.ResourceVec(8.0, 64.0, 256.0))
                cl.nodes[node.name] = node
                cl.journal.mark_structural("test-node-added")
        _churn(c_res, rng_a, 0.05, num_nodes)
        _churn(c_twin, rng_b, 0.05, num_nodes)
    assert resident_cycles >= 15, s_res._snapshotter.stats.fallbacks
    # the compare is about REAL decisions: resident cycles must have
    # actually placed work (restarted churn pods + appended gangs), not
    # matched an idle twin on empty lists
    assert resident_cycles_with_binds >= 8, resident_cycles_with_binds
    # the structural fallback actually fired and resident mode resumed
    assert "structural" in s_res._snapshotter.stats.fallbacks
    assert recovered_after_structural
    # bucket hysteresis holds: once settled, steady churn never flips
    # the fused entry's signature (a flip = full XLA recompile)
    assert late_misses == 0


def test_repack_fires_with_real_ages_on_nonanalytics_resident_cycle():
    """Regression: the frag streak completes at the end of an analytics
    cycle, so with ``analytics_every > 1`` the repack trigger typically
    fires on the NEXT (analytics-skipped) cycle.  On the resident path
    that cycle feeds the fused entry a zeros ages placeholder — the
    repack solve must still compute REAL pending ages (an all-zero
    vector fails ``plan_repack``'s target gate and burns the cooldown
    on an infeasible plan)."""
    from tests.test_repack import _frag_cluster, _repack_cfg
    import dataclasses

    from kai_scheduler_tpu.binder import Binder
    cluster = _frag_cluster()
    cfg = dataclasses.replace(_repack_cfg(), resident=True,
                              analytics_every=2)
    sched, binder = Scheduler(cfg), Binder()
    fired = placed = None
    fired_mode = None
    for cyc in range(1, 12):
        res = sched.run_once(cluster)
        if res.repack and fired is None:
            fired = cyc
            fired_mode = sched._snapshotter.stats.last["mode"]
            assert res.repack["feasible"], res.repack
            assert res.repack["target_gang"] == "big-gang"
            assert res.repack["migrations_executed"] > 0
        if sum(b.pod_name.startswith("big-")
               for b in res.bind_requests) >= 8:
            placed = cyc
            break
        binder.reconcile(cluster)
        cluster.tick()
    assert fired is not None, "repack never fired"
    # the scenario's point: the firing landed on a RESIDENT cycle (the
    # fused entry ran with the zeros placeholder) and the solve still
    # saw real ages
    assert fired_mode == "resident", fired_mode
    assert placed is not None and placed >= fired


def test_resident_verify_mode_passes_and_catches_divergence():
    cluster = _steady_cluster(num_nodes=8, num_gangs=8)
    sched = Scheduler(SchedulerConfig(resident=True,
                                      verify_incremental=True))
    rng = np.random.default_rng(3)
    sched.run_once(cluster)
    for _ in range(3):
        _churn(cluster, rng, 0.1, 8)
        sched.run_once(cluster)  # verify gathers + compares each cycle
    snap = sched._snapshotter
    assert snap.stats.patched >= 1
    # corrupt ONE mirror element: the gather-and-compare must catch it
    snap._host.nodes.free.reshape(-1)[0] += 1.0
    with pytest.raises(IncrementalVerifyError, match="resident leaf"):
        snap.verify_device_residency()


def test_group_delete_rides_the_packed_delta():
    """Finished gangs' groups go with their pods: the mirror closes up
    the gang rows and the cycle stays RESIDENT — every gang row behind
    a removed one moves, and all of that rides the one packed delta
    (no fallback, no re-upload).  Device state equal to the mirror,
    decisions equal to a full-rebuild twin's."""
    c_res = _steady_cluster(num_nodes=8, num_gangs=12)
    c_twin = copy.deepcopy(c_res)
    s_res = Scheduler(SchedulerConfig(resident=True,
                                      verify_incremental=True,
                                      incremental_dirty_threshold=1.0))
    s_twin = Scheduler(SchedulerConfig(incremental=False))
    assert s_res.run_once(c_res).bind_requests \
        == s_twin.run_once(c_twin).bind_requests
    for cyc in range(3):
        running = {p.group for p in c_res.pods.values()
                   if p.status == apis.PodStatus.RUNNING}
        gone = [g for g in c_res.pod_groups if g in running][:2]
        assert len(gone) == 2
        pods = [p.name for p in c_res.pods.values() if p.group in gone]
        for cl in (c_res, c_twin):
            intake_apply.apply_cluster_delta(cl, {
                "pod_groups_delete": gone, "pods_delete": pods,
                "now": cl.now + 1.0})
            _submit_extra_gang(cl, cyc)
        r1, r2 = s_res.run_once(c_res), s_twin.run_once(c_twin)
        assert r1.bind_requests == r2.bind_requests, cyc
        assert r1.evictions == r2.evictions, cyc
        last = s_res._snapshotter.stats.last
        assert last["mode"] == "resident", (cyc, last)
        assert last["gangs_removed"] == 2
        assert last["pods_removed"] == len(pods)
    snap = s_res._snapshotter
    assert set(snap.stats.fallbacks) == {"cold"}
    snap.verify_device_residency()  # device == the closed-up mirror


def test_desync_guard_forces_full_rebuild():
    """A staged delta that was never adopted (aborted cycle) must not
    leave the mirror ahead of the device: the next resident refresh
    rebuilds in full instead of diffing against a future the device
    never saw."""
    cluster = _steady_cluster(num_nodes=8, num_gangs=8)
    snap = IncrementalSnapshotter()
    rr = snap.refresh_resident(cluster, now=cluster.now)
    assert rr.mode == "full"
    cluster.tick()
    rr = snap.refresh_resident(cluster, now=cluster.now)
    assert rr.mode == "resident"
    # abort: no adopt_device_state — the guard is armed
    cluster.tick()
    rr = snap.refresh_resident(cluster, now=cluster.now)
    assert rr.mode == "full"
    assert "resident-desync" in snap.stats.fallbacks
    # a clean staged+adopted cycle resumes resident mode
    cluster.tick()
    rr = snap.refresh_resident(cluster, now=cluster.now)
    assert rr.mode == "resident"
    from kai_scheduler_tpu.ops.resident import apply_delta
    snap.adopt_device_state(
        jax.jit(apply_delta)(snap.device_state, rr.delta))
    snap.verify_device_residency()  # device == mirror after adopt


def test_delta_upload_is_transient_on_the_ledger():
    """Delta uploads ride the wire books (bytes/dispatches) but never
    join the device-residency watermark — donated consumable buffers
    must not double-count against the resident snapshot."""
    before = LEDGER.residency()["bytes"]
    out = LEDGER.device_put(
        {"idx": np.zeros((64,), np.int32),
         "val": np.zeros((64,), np.float32)},
        reason=REASON_DELTA_APPLY, site="delta-test", transient=True)
    assert int(np.asarray(out["idx"]).sum()) == 0
    after = LEDGER.residency()["bytes"]
    assert after == before
    totals = LEDGER.totals()["by_reason"][REASON_DELTA_APPLY]
    assert totals["bytes"] >= 64 * 8
