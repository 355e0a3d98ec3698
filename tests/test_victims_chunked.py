"""Chunked-victim-wavefront equivalence properties (sparse + dense).

The PR-5 sparse-lane rework gives preempt two compiled paths — the
sparse/optimistic queue-disjoint wavefront and the dense composed
fallback — on top of the sequential B=1 scan (reference-exact).  These
properties pin, on randomized many-queue snapshots, that every path at
every lane width produces IDENTICAL placements and victim sets to the
sequential scan, and that the runtime dense fallback engages exactly
when a queue's unit count overflows the compact tables.
"""
import dataclasses

import numpy as np
import pytest

from kai_scheduler_tpu.framework.session import Session
from kai_scheduler_tpu.ops.allocate import init_result
from kai_scheduler_tpu.ops.victims import (_sparse_preempt_ok,
                                           run_victim_action_jit)
from kai_scheduler_tpu.state import make_cluster

WIDTHS = (1, 64, 256)


def _many_queue_session(seed, *, boost=100, tasks=2):
    """Randomized many-queue snapshot: 16 leaf queues, each with a
    boosted pending preemptor over a saturated share of running gangs —
    the production steady state the sparse path is built for."""
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=48, node_accel=2.0, num_gangs=64, tasks_per_gang=tasks,
        running_fraction=48 / 64, num_departments=2,
        queues_per_department=8, pending_priority_boost=boost, seed=seed)
    return Session.open(nodes, queues, groups, pods, topo)


def _run(ses, mode, cfg):
    import jax
    return jax.block_until_ready(run_victim_action_jit(
        ses.state, ses.state.queues.fair_share, init_result(ses.state),
        num_levels=2, mode=mode, config=cfg))


def _outs(res):
    return (np.asarray(res.allocated), np.asarray(res.victim),
            np.asarray(res.placements), np.asarray(res.pipelined))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("path", ["sparse", "dense"])
def test_chunked_preempt_identical_to_sequential(seed, path):
    """Chunked preempt at every lane width — sparse/optimistic AND the
    forced dense composed path — must reproduce the sequential scan's
    placements and victim set bit-for-bit on the many-queue family."""
    ses = _many_queue_session(seed)
    # the Session auto-tune must have enabled the sparse protocol for
    # this shape (uniform, no devices/extended/subgroup topology)
    assert _sparse_preempt_ok(ses.config.victims)
    base = None
    for b in WIDTHS:
        cfg = dataclasses.replace(
            ses.config.victims, batch_size=b, batch_size_preempt=b,
            optimistic_preempt=(None if path == "sparse" else False))
        out = _outs(_run(ses, "preempt", cfg))
        if base is None:
            base = out          # B=1: the sequential reference scan
            assert base[0].any(), "family must exercise preemption"
            assert base[1].any()
        else:
            for got, want, name in zip(out, base,
                                       ("allocated", "victim",
                                        "placements", "pipelined")):
                np.testing.assert_array_equal(got, want, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1])
def test_chunked_reclaim_identical_to_sequential(seed):
    """Chunked reclaim at every lane width vs the sequential scan on a
    partitioned over-quota snapshot: the same reclaimers admitted and
    the IDENTICAL victim set.  Node choice may drift among equal-scoring
    nodes (lanes place against chunk-start state — the documented
    composed-wavefront drift), so placements are compared as per-gang
    counts, not cells; the preempt test above pins full bit-equality
    for the sparse path."""
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=48, node_accel=4.0, num_gangs=24, tasks_per_gang=4,
        running_fraction=0.5, num_departments=2, queues_per_department=4,
        queue_accel_quota=8.0, partition_queues_by_running=True,
        seed=seed)
    ses = Session.open(nodes, queues, groups, pods, topo)
    base = None
    for b in WIDTHS:
        cfg = dataclasses.replace(ses.config.victims, batch_size=b,
                                  chunk_reclaim=True)
        out = _outs(_run(ses, "reclaim", cfg))
        if base is None:
            base = out
            assert base[0].any(), "family must exercise reclaim"
        else:
            np.testing.assert_array_equal(out[0], base[0],
                                          err_msg="allocated")
            np.testing.assert_array_equal(out[1], base[1],
                                          err_msg="victim")
            np.testing.assert_array_equal(
                (out[2] >= 0).sum(-1), (base[2] >= 0).sum(-1),
                err_msg="placement counts")


def test_wide_gang_family_identical_to_sequential():
    """8-task gangs over 8-accel nodes: each victim gang spreads across
    several nodes, so earlier placements' claims shift later lanes'
    density/availability score ties.  Before the canonical (node-
    ascending) replica assignment this family produced within-gang
    task→node PERMUTATIONS between the wavefront and the sequential
    scan (same node multiset, different cells) — pinned here
    bit-exact."""
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=256, node_accel=8.0, num_gangs=320, tasks_per_gang=8,
        running_fraction=256 / 320, num_departments=2,
        queues_per_department=32, pending_priority_boost=100, seed=3)
    ses = Session.open(nodes, queues, groups, pods, topo)
    assert _sparse_preempt_ok(ses.config.victims)
    base = None
    for b in (1, 64):
        cfg = dataclasses.replace(ses.config.victims, batch_size=b,
                                  batch_size_preempt=b)
        res = _run(ses, "preempt", cfg)
        out = _outs(res)
        if base is None:
            base = out
            assert base[0].any() and base[1].any()
        else:
            for got, want, name in zip(out, base,
                                       ("allocated", "victim",
                                        "placements", "pipelined")):
                np.testing.assert_array_equal(got, want, err_msg=name)
            # the steady-state family must stay demotion-free (the
            # exactness machinery must not serialize the wavefront)
            assert np.asarray(res.wavefront_stats)[1, 4] == 0


def _leftover_session():
    """Hand-built snapshot where an earlier lane's NET leftover freed
    capacity decides a later lane's placement: evicting queue A's 2-pod
    quorum gang on node-0 frees 2 accel but preemptor A consumes only 1,
    and the sequential scan then binpacks preemptor B onto that leftover
    (node-0) instead of its own victim's node-1."""
    from kai_scheduler_tpu.apis import types as apis
    Vec, QR = apis.ResourceVec, apis.QueueResource
    nodes = [apis.Node("node-0", Vec(2.0, 16.0, 64.0)),
             apis.Node("node-1", Vec(2.0, 16.0, 64.0))]
    queues = [apis.Queue("qa", accel=QR(quota=2.0), creation_timestamp=0.0),
              apis.Queue("qb", accel=QR(quota=2.0), creation_timestamp=1.0)]
    groups = [
        apis.PodGroup("victim-a", queue="qa", min_member=2, priority=0,
                      creation_timestamp=0.0, last_start_timestamp=0.0),
        apis.PodGroup("victim-b", queue="qb", min_member=1, priority=0,
                      creation_timestamp=1.0, last_start_timestamp=0.0),
        apis.PodGroup("filler-b", queue="qb", min_member=1, priority=200,
                      creation_timestamp=2.0, last_start_timestamp=0.0),
        apis.PodGroup("preemptor-a", queue="qa", min_member=1,
                      priority=100, creation_timestamp=10.0),
        apis.PodGroup("preemptor-b", queue="qb", min_member=1,
                      priority=100, creation_timestamp=11.0),
    ]
    pods = [apis.Pod(f"va-{i}", "victim-a", resources=Vec(1.0, 1.0, 4.0),
                     status=apis.PodStatus.RUNNING, node="node-0",
                     creation_timestamp=0.0) for i in range(2)]
    pods += [
        apis.Pod("vb-0", "victim-b", resources=Vec(1.0, 1.0, 4.0),
                 status=apis.PodStatus.RUNNING, node="node-1",
                 creation_timestamp=1.0),
        apis.Pod("fb-0", "filler-b", resources=Vec(1.0, 1.0, 4.0),
                 status=apis.PodStatus.RUNNING, node="node-1",
                 creation_timestamp=2.0),
        apis.Pod("ga-0", "preemptor-a", resources=Vec(1.0, 1.0, 4.0),
                 creation_timestamp=10.0),
        apis.Pod("gb-0", "preemptor-b", resources=Vec(1.0, 1.0, 4.0),
                 creation_timestamp=11.0),
    ]
    return Session.open(nodes, queues, groups, pods)


@pytest.mark.parametrize("path", ["sparse", "dense"])
def test_leftover_freed_capacity_stays_sequential(path):
    """Net-leftover regression: a lane whose victims free MORE than its
    claims consume demotes later same-chunk lanes to conflict-retry, so
    the retried lane re-solves with exact composed inputs (and no
    own-freed bias) and lands where the sequential scan does.  Without
    the demotion both wavefront paths silently placed preemptor B on
    node-1 while the sequential scan binpacks it onto node-0's leftover."""
    ses = _leftover_session()
    assert _sparse_preempt_ok(ses.config.victims)
    base = None
    for b in WIDTHS[:2] + (4,):
        cfg = dataclasses.replace(
            ses.config.victims, batch_size=b, batch_size_preempt=b,
            optimistic_preempt=(None if path == "sparse" else False))
        res = _run(ses, "preempt", cfg)
        out = _outs(res)
        if base is None:
            base = out
            assert base[0].any() and base[1].any()
        else:
            for got, want, name in zip(out, base,
                                       ("allocated", "victim",
                                        "placements", "pipelined")):
                np.testing.assert_array_equal(got, want, err_msg=name)
            # the wide chunk must have exercised the demotion
            assert np.asarray(res.wavefront_stats)[1, 4] >= 1


def test_sparse_overflow_falls_back_dense():
    """A queue whose candidate-unit count overflows the compact tables
    must take the dense composed path (identical result, fallback
    counted in wavefront_stats)."""
    # 2 leaf queues × 10 running gangs each: >8 candidate units per
    # queue, so a sparse_unit_k=8 table overflows at run time while the
    # padded pod axis (>8) keeps the overflow cond live
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=24, node_accel=2.0, num_gangs=24, tasks_per_gang=2,
        running_fraction=20 / 24, num_departments=1,
        queues_per_department=2, pending_priority_boost=100, seed=0)
    ses = Session.open(nodes, queues, groups, pods, topo)
    assert _sparse_preempt_ok(ses.config.victims)
    cfg_lo = dataclasses.replace(ses.config.victims, batch_size=64,
                                 batch_size_preempt=64, sparse_unit_k=8)
    cfg_hi = dataclasses.replace(ses.config.victims, batch_size=64,
                                 batch_size_preempt=64)
    res_lo = _run(ses, "preempt", cfg_lo)
    res_hi = _run(ses, "preempt", cfg_hi)
    stats_lo = np.asarray(res_lo.wavefront_stats)
    stats_hi = np.asarray(res_hi.wavefront_stats)
    assert stats_lo[1, 3] == 1, stats_lo     # fell back to dense
    assert stats_hi[1, 3] == 0, stats_hi     # sparse path held
    assert stats_hi[1, 0] >= 1               # chunks counted
    assert 0 < stats_hi[1, 1] <= stats_hi[1, 2]  # occupancy sane
    for got, want in zip(_outs(res_lo), _outs(res_hi)):
        np.testing.assert_array_equal(got, want)


def test_auto_tune_clamps_lane_width_to_pending_spread():
    """Session auto-tuning v2: the preempt lane width follows the
    snapshot's live preemptor count (pow2-bucketed), not a fixed
    constant — junk lanes past the pending spread stop paying the
    per-lane freed-pool cost."""
    ses = _many_queue_session(0)
    bsp = ses.config.victims.batch_size_preempt
    pending = ses.index.num_pending_gangs
    assert pending == 16
    assert bsp == 16                         # pow4ceil(16)
    assert ses.config.victims.sparse_unit_k >= 8


def _saturated_session(seed):
    """chip_smoke.py's saturated shape at test size: running gangs fill
    every accelerator, pending gangs sit in under-served queues."""
    return Session.open(*make_cluster(
        num_nodes=32, node_accel=4.0, num_gangs=20, tasks_per_gang=8,
        running_fraction=0.8, queue_accel_quota=3.2,
        partition_queues_by_running=True, seed=seed))


@pytest.mark.parametrize("case", ["reclaim", "preempt", "consolidate",
                                  "allocate-repush"])
def test_uniform_kernel_prior_node_flag_matches_sequential(case):
    """The uniform whole-gang kernel's per-node "holds a replica from a
    prior attempt" flag (ops/allocate.py) is a [T, N] compare-and-any —
    the scatter-add it replaced made the TPU compiler abort on every
    victim action.  Each action that reaches the kernel must still
    agree with the ``batch_size=1`` sequential scan: the victim solver
    (where the flag is constant-false) on a seeded saturated cluster,
    and allocate's elastic re-push (where it is live: a one-per-node
    gang's remainder must avoid the nodes its quorum took)."""
    if case == "allocate-repush":
        from kai_scheduler_tpu.ops.allocate import allocate_jit
        nodes, queues, groups, pods, topo = make_cluster(
            num_nodes=12, node_accel=4.0, num_gangs=6, tasks_per_gang=6,
            seed=3)
        for i, g in enumerate(groups):
            g.min_member = 2          # quorum first, remainder re-pushed
            for p in pods:
                if p.group == g.name:
                    p.host_ports = [8000 + i]   # one replica per node
        ses = Session.open(nodes, queues, groups, pods, topo)
        assert ses.config.allocate.uniform_tasks
        assert (np.asarray(ses.state.gangs.anti_self_level)[:6] >= 0).all()
        outs = []
        for b in (ses.config.allocate.batch_size, 1):
            res = allocate_jit(
                ses.state, ses.state.queues.fair_share, num_levels=2,
                config=dataclasses.replace(ses.config.allocate,
                                           batch_size=b))
            outs.append((np.asarray(res.allocated),
                         np.asarray(res.placements)))
        # lanes break score ties apart, so the wavefront's node choice
        # drifts from the scan's by design; what the flag decides is
        # that every gang is whole and no node holds two of its replicas
        np.testing.assert_array_equal(outs[0][0], outs[1][0])
        for allocated, placements in outs:
            assert allocated[:6].all()
            for row in placements[:6]:
                placed = row[row >= 0]
                assert len(placed) == 6
                assert len(set(placed.tolist())) == 6, row
        return
    # preempt is intra-queue: the partitioned shape gives it nothing
    ses = (_many_queue_session(0) if case == "preempt"
           else _saturated_session(seed=0))
    assert ses.config.victims.placement.uniform_tasks
    tuned = _outs(_run(ses, case, ses.config.victims))
    seq = _outs(_run(ses, case, dataclasses.replace(
        ses.config.victims, batch_size=1, batch_size_preempt=1,
        placement=dataclasses.replace(ses.config.victims.placement,
                                      batch_size=1))))
    if case != "consolidate":   # nothing to defragment on this shape
        assert seq[0].any() and seq[1].any(), "must exercise the action"
    np.testing.assert_array_equal(tuned[0], seq[0], err_msg="allocated")
    np.testing.assert_array_equal(tuned[1], seq[1], err_msg="victim")
    np.testing.assert_array_equal((tuned[2] >= 0).sum(-1),
                                  (seq[2] >= 0).sum(-1),
                                  err_msg="placement counts")


def _fragmented_session(seed):
    """Consolidation's shape, seeded: 4-accel nodes each half full with
    one 2-accel runner, and pending gangs that need a whole node — they
    fit only once a runner has moved."""
    from kai_scheduler_tpu.apis import types as apis
    rng = np.random.default_rng(seed)
    n_nodes = int(rng.integers(6, 10))
    nodes = [apis.Node(f"node-{i}", apis.ResourceVec(4.0, 64.0, 256.0))
             for i in range(n_nodes)]
    queues = [apis.Queue("q0", accel=apis.QueueResource(
        quota=4.0 * n_nodes))]
    groups, pods = [], []
    for i in rng.permutation(n_nodes):
        groups.append(apis.PodGroup(
            f"frag{i}", queue="q0", min_member=1,
            creation_timestamp=float(i), last_start_timestamp=float(i)))
        pods.append(apis.Pod(
            f"f{i}", f"frag{i}", resources=apis.ResourceVec(2.0, 1.0, 4.0),
            status=apis.PodStatus.RUNNING, node=f"node-{i}"))
    for j in range(2):
        groups.append(apis.PodGroup(f"big{j}", queue="q0", min_member=1,
                                    creation_timestamp=100.0 + j))
        pods.append(apis.Pod(
            f"big{j}-0", f"big{j}",
            resources=apis.ResourceVec(4.0, 1.0, 4.0),
            creation_timestamp=100.0 + j))
    return Session.open(nodes, queues, groups, pods, None)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("mode,path", [
    ("reclaim", "chunked"), ("reclaim", "sequential"),
    ("preempt", "sparse"), ("preempt", "dense"),
    ("preempt", "sequential"), ("consolidate", "sequential")])
def test_gated_action_equals_its_ungated_body(mode, path, seed):
    """The action-level gate changes nothing when it is open: with
    viable preemptors, ``run_victim_action`` (prefilter, then
    ``lax.cond`` over the search) returns what the search body called
    directly returns, leaf for leaf — on the chunked wavefront (dense
    tables, and preempt's compact ones) and on the sequential scan."""
    import jax
    from tests.test_victims import assert_same_result, ungated_action
    if mode == "reclaim":
        ses = Session.open(*make_cluster(
            num_nodes=48, node_accel=4.0, num_gangs=24, tasks_per_gang=4,
            running_fraction=0.5, num_departments=2,
            queues_per_department=4, queue_accel_quota=8.0,
            partition_queues_by_running=True, seed=seed))
    elif mode == "preempt":
        ses = _many_queue_session(seed)
    else:
        ses = _fragmented_session(seed)
    b = 1 if path == "sequential" else 64
    cfg = dataclasses.replace(
        ses.config.victims, batch_size=b, batch_size_preempt=b,
        chunk_reclaim=True,
        optimistic_preempt=(False if path == "dense" else None))
    nl = ses.config.num_levels
    state, fs = ses.state, ses.state.queues.fair_share
    want = jax.jit(ungated_action(mode, nl, cfg))(
        state, fs, init_result(state))
    assert np.asarray(want.allocated).any(), "the family must open the gate"
    assert np.asarray(want.victim).any()
    got = run_victim_action_jit(state, fs, init_result(state),
                                num_levels=nl, mode=mode, config=cfg)
    assert_same_result(got, want, but=())
    assert not np.asarray(got.victim_skipped).any()
