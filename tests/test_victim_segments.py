"""The victim search's per-queue unit tables as sorted segments
(``ops/unit_segments.py``): every probe against a NumPy model of the
dense ``[U, Q, R]`` definition it replaced, a structural guard that no
``U·Q``-sized array is traced any more, and chunked reclaim against the
sequential solver at a many-tenant shape.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.analysis.trace_probe import eqn_sub_jaxprs
from kai_scheduler_tpu.framework.session import Session
from kai_scheduler_tpu.ops import unit_segments as us
from kai_scheduler_tpu.ops.allocate import init_result
from kai_scheduler_tpu.ops.victims import (run_victim_action,
                                           run_victim_action_jit)
from kai_scheduler_tpu.state import make_cluster
from kai_scheduler_tpu.utils.numerics import segmented_cumsum_ds

U, R = 96, 3


def _tree(levels, fanout=3):
    """parent [Q] of a complete tree, roots first, leaves last."""
    parent, level = [], [-1] * (fanout if levels > 1 else 4)
    first = 0
    for _ in range(levels):
        parent += level
        idx = range(first, first + len(level))
        first += len(level)
        level = [i for i in idx for _ in range(fanout)]
    return np.asarray(parent, np.int32)


def _case(shape, seed):
    """(parent, num_levels, unit_leaf [U], unit_req [U, R], prio [U]):
    ``num_units`` ranks hold a unit, the rest of the axis is padding.
    Requests are multiples of 1/8, so every sum is exact in f32 and the
    model's float64 answers must be met to the bit."""
    rng = np.random.default_rng(seed)
    levels = {"one_level": 1, "two_levels": 2, "three_levels": 3,
              "empty_queue": 2, "one_queue_holds_all": 2,
              "single_queue": 1}[shape]
    parent = (np.asarray([-1], np.int32) if shape == "single_queue"
              else _tree(levels))
    Q = parent.shape[0]
    is_parent = np.zeros(Q, bool)
    is_parent[parent[parent >= 0]] = True
    leaves = np.flatnonzero(~is_parent)
    num_units = U if shape == "one_queue_holds_all" else int(
        rng.integers(U // 2, U - 4))
    if shape == "one_queue_holds_all":
        pool = leaves[-1:]
    elif shape == "empty_queue":
        pool = leaves[1:]
    else:
        pool = leaves
    unit_leaf = np.full(U, -1, np.int32)
    unit_leaf[:num_units] = rng.choice(pool, num_units)
    unit_req = np.zeros((U, R), np.float32)
    unit_req[:num_units] = rng.integers(0, 64, (num_units, R)) / 8.0
    # a unit that frees nothing of one resource: a tie inside a column
    unit_req[:num_units:7, 1] = 0.0
    # priorities ascend inside a queue, as the frozen order ranks them
    prio = np.zeros(U, np.float32)
    for ql in np.unique(unit_leaf[:num_units]):
        rows = np.flatnonzero(unit_leaf == ql)
        prio[rows] = np.sort(rng.integers(0, 5, rows.size))
    return parent, levels, unit_leaf, unit_req, prio


class Dense:
    """The dense per-queue-column tables, as ``ops/victims.py`` defined
    them before the segments: written from the definitions, in float64."""

    def __init__(self, parent, unit_leaf, unit_req, prio):
        Q = parent.shape[0]
        self.Q = Q
        has = unit_leaf >= 0
        onehot = (unit_leaf[:, None] == np.arange(Q)[None, :]) & has[:, None]
        req = unit_req.astype(np.float64)
        self.C_all = np.cumsum(req, axis=0)                    # [U, R]
        self.C_leaf = np.cumsum(onehot[:, :, None] * req[:, None, :],
                                axis=0)                        # [U, Q, R]
        self.cl = np.concatenate(
            [np.zeros((1, Q), int), np.cumsum(onehot, axis=0)])  # [U+1, Q]
        # pos_q[q, j]: global rank of q's j-th unit, U past its last
        self.pos_q = np.full((Q, U + 1), U, int)
        self.prio_by_q = np.full((Q, U), 1e30)
        for ql in range(Q):
            rows = np.flatnonzero(onehot[:, ql])
            self.pos_q[ql, :rows.size] = rows
            self.prio_by_q[ql, :rows.size] = prio[rows]
        chain = np.zeros((Q, Q), bool)
        for ql in range(Q):
            a = ql
            while a >= 0:
                chain[ql, a] = True
                a = parent[a]
        inc = (chain[np.maximum(unit_leaf, 0)] & has[:, None])[:, :, None] \
            * req[:, None, :]
        self.S_excl = np.cumsum(inc, axis=0) - inc             # [U, Q, R]
        self.S_total = inc.sum(axis=0)                         # [Q, R]
        self.onehot = onehot


SHAPES = ["one_level", "two_levels", "three_levels", "empty_queue",
          "one_queue_holds_all", "single_queue"]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", SHAPES)
def test_segment_probes_match_dense_tables(shape, seed):
    """Each of the chunk's nine reads of the dense tables, answered from
    the segments, equals the dense answer: the own-queue sum at the
    consumed pointer (``Cv_at_c``), the lanes' own-queue columns
    (reclaim's ``C_all - C_leaf[:, q_b]``, preempt's ``C_leaf[:, q_b]``),
    the lanes' available-unit counts (both modes), the two strategy
    bounds on the subtree columns, the count-before / first-bad pair, and
    preempt's priority bound."""
    parent, levels, unit_leaf, unit_req, prio = _case(shape, seed)
    d = Dense(parent, unit_leaf, unit_req, prio)
    Q = d.Q
    rng = np.random.default_rng(100 + seed)
    leaf = us.leaf_segments(jnp.asarray(unit_leaf), jnp.asarray(unit_req),
                            Q)
    sub = us.subtree_segments(jnp.asarray(unit_leaf), jnp.asarray(unit_req),
                              jnp.asarray(parent), levels)
    qidx = np.arange(Q)

    # the segments themselves: every queue's rows, in rank order
    off = np.asarray(leaf.off)
    for ql in range(Q):
        np.testing.assert_array_equal(
            np.asarray(leaf.pos)[off[ql]:off[ql + 1]],
            np.flatnonzero(d.onehot[:, ql]))
    assert np.asarray(sub.pos).shape[0] == U * levels

    # (1) Cv_at_c = C_leaf[c[q], q], 0 for c < 0
    for c in (np.full(Q, -1), np.full(Q, U - 1),
              rng.integers(-1, U, Q), rng.integers(-1, U, Q)):
        want = np.where((c >= 0)[:, None],
                        d.C_leaf[np.clip(c, 0, U - 1), qidx], 0.0)
        got = us.sum_through(leaf, jnp.asarray(c, jnp.int32))
        np.testing.assert_array_equal(np.asarray(got), want)

    # (2, 3) the lanes' columns, for reclaim and for preempt
    q_b = rng.integers(0, Q, 5)
    mine = jnp.asarray(unit_leaf)[None, :] == jnp.asarray(q_b)[:, None]
    col = np.asarray(us.lane_columns(us.rank_order_cum(leaf), mine))
    want = d.C_leaf[:, q_b].transpose(1, 2, 0)                 # [B, R, U]
    np.testing.assert_array_equal(col, want)
    np.testing.assert_array_equal(
        np.asarray(jnp.asarray(d.C_all.T, jnp.float32)[None] - col),
        d.C_all.T[None] - want)

    # (4) cum_av_b, both modes
    c = rng.integers(-1, U, Q)
    num_units = int((unit_leaf >= 0).sum())
    avail = ((unit_leaf >= 0) & (np.arange(U) < num_units)
             & (np.arange(U) > c[np.clip(unit_leaf, 0, Q - 1)]))
    cum_av_leaf = np.cumsum(avail[:, None] & d.onehot, axis=0)  # [U, Q]
    np.testing.assert_array_equal(
        np.asarray(us.lane_available(jnp.asarray(avail), mine)),
        cum_av_leaf[:, q_b].T)
    np.testing.assert_array_equal(
        np.asarray(us.lane_available(jnp.asarray(avail), ~mine)),
        np.cumsum(avail)[None, :] - cum_av_leaf[:, q_b].T)

    # (5, 6) searchsorted on the subtree's exclusive column: thresholds
    # under zero, at zero, at every step of the column (a tie), between
    # steps, at and above the segment's total, and -inf
    totals = d.S_total                                         # [Q, R]
    steps = d.S_excl[rng.integers(0, U, 6)]                    # [6, Q, R]
    thr = np.concatenate([
        np.full((1, Q, R), -1.0), np.zeros((1, Q, R)),
        np.full((1, Q, R), -np.inf), steps, steps + 0.0625,
        steps - 0.0625, totals[None], totals[None] + 0.125,
        np.full((1, Q, R), 1e9)]).astype(np.float32)
    got = np.asarray(us.subtree_bound(sub, jnp.asarray(thr), U))
    want = np.empty(thr.shape, int)
    for i in range(thr.shape[0]):
        for a in range(Q):
            for r in range(R):
                want[i, a, r] = np.searchsorted(
                    d.S_excl[:, a, r], thr[i, a, r])
    np.testing.assert_array_equal(got, want)

    # (7, 8) cnt_before = cl[x, q]; first_bad = pos_q[q, cnt_before]
    x = rng.integers(0, U + 1, (Q, 7))
    x[:, 0], x[:, 1] = 0, U
    want = d.pos_q[qidx[:, None], d.cl[x, qidx[:, None]]]
    got = us.first_at_or_after(leaf, jnp.asarray(x, jnp.int32), U)
    np.testing.assert_array_equal(np.asarray(got), want)

    # (9) allowed = searchsorted(prio_by_q[q_b], p); pos_q[q_b, allowed]
    for p in (-1.0, 0.0, 2.0, 2.5, 9.0):
        pv = np.full(q_b.shape, p, np.float32)
        allowed = np.asarray([np.searchsorted(d.prio_by_q[ql], p)
                              for ql in q_b])
        got_n, got_rank = us.first_not_below(
            leaf, jnp.asarray(prio)[leaf.pos], jnp.asarray(q_b),
            jnp.asarray(pv), U)
        np.testing.assert_array_equal(np.asarray(got_n), allowed)
        np.testing.assert_array_equal(np.asarray(got_rank),
                                      d.pos_q[q_b, allowed])


@pytest.mark.parametrize("seed", [0, 1])
def test_segmented_sum_is_compensated_per_segment(seed):
    """GiB-scale requests with fractions f32 cannot add up plainly: each
    segment's running sums equal the float64 sums of that segment's own
    values, rounded once — a long segment before it leaves no trace."""
    rng = np.random.default_rng(seed)
    n = 5000
    x = (rng.integers(1, 64, (n, 2)) * 2.0 ** 30
         + rng.random((n, 2)) * 1e3).astype(np.float32)
    first = np.zeros(n, bool)
    first[[0, 1, 2, 4000, 4001]] = True
    got = np.asarray(segmented_cumsum_ds(jnp.asarray(x),
                                         jnp.asarray(first)[:, None]))
    want = np.empty_like(x, dtype=np.float64)
    starts = np.flatnonzero(first).tolist() + [n]
    for a, b in zip(starts[:-1], starts[1:]):
        want[a:b] = np.cumsum(x[a:b].astype(np.float64), axis=0)
    np.testing.assert_array_equal(got, want.astype(np.float32))


def _trace_largest(ses, mode, cfg, num_levels=2):
    jaxpr = jax.make_jaxpr(functools.partial(
        run_victim_action, num_levels=num_levels, mode=mode, config=cfg))(
        ses.state, ses.state.queues.fair_share, init_result(ses.state))
    worst = (0, None)

    def walk(jpr):
        nonlocal worst
        jpr = getattr(jpr, "jaxpr", jpr)
        for eqn in jpr.eqns:
            for v in eqn.outvars:
                size = int(np.prod(v.aval.shape))
                if size > worst[0]:
                    worst = (size, f"{eqn.primitive.name} {v.aval.shape}")
            for sub in eqn_sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    return worst


@pytest.mark.parametrize("mode", ["reclaim", "preempt"])
def test_no_unit_by_queue_array_is_traced(mode):
    """Structure: in the traced chunked reclaim and dense-path preempt
    no equation, at any depth, has an output of ``U·Q`` elements — the
    per-queue tables are segments of the unit axis, and nobody put a
    column per queue back."""
    ses = Session.open(*make_cluster(
        num_nodes=1024, node_accel=8.0, num_gangs=2048 + 32,
        tasks_per_gang=4, running_fraction=2048 / 2080, num_departments=3,
        queues_per_department=15, pending_priority_boost=100, seed=0))
    M, Q = ses.state.running.m, ses.state.queues.q
    # (the leveled-queue table's [Q, Q, Q] intermediate is not a unit
    # table: the shape keeps Q * Q under U so that it cannot hide one)
    assert M >= 8192 and 48 <= Q and Q * Q < M, (M, Q)
    cfg = dataclasses.replace(
        ses.config.victims, chunk_reclaim=True, batch_size=8,
        batch_size_preempt=None, optimistic_preempt=False)
    size, what = _trace_largest(ses, mode, cfg)
    assert size < M * Q, (what, M, Q)
    # and the bound means something: the unit axis is in there
    assert size >= M, (what, M)


def test_many_tenant_reclaim_identical_to_sequential():
    """1 000 tenants under 4 departments over 2 048 running pods, six
    reclaimers — the many-tenant shape the dense tables ([U, Q, R] three
    times over, and a [U, Q] count per chunk) kept out of tier-1: the
    chunked wavefront admits the sequential scan's reclaimers and picks
    its victims.  (ISSUE 37 asked for 2 000 tenants; there the
    sequential oracle's own per-preemptor [U, Q, R] and the
    leveled-queue table's Q^3 compares take 30 s on the CPU, 5 s
    here.)"""
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=256, node_accel=8.0, num_gangs=1024 + 6,
        tasks_per_gang=2, running_fraction=1024 / 1030,
        num_departments=4, queues_per_department=250,
        queue_accel_quota=1.0, partition_queues_by_running=True, seed=0)
    ses = Session.open(nodes, queues, groups, pods, topo)
    assert ses.state.queues.q >= 1004
    outs = []
    for b in (1, 64):
        cfg = dataclasses.replace(ses.config.victims, batch_size=b,
                                  chunk_reclaim=True)
        res = jax.block_until_ready(run_victim_action_jit(
            ses.state, ses.state.queues.fair_share, init_result(ses.state),
            num_levels=2, mode="reclaim", config=cfg))
        outs.append((np.asarray(res.allocated), np.asarray(res.victim),
                     (np.asarray(res.placements) >= 0).sum(-1)))
    assert outs[0][0].any() and outs[0][1].any(), \
        "the family must exercise reclaim"
    for got, want, name in zip(outs[1], outs[0],
                               ("allocated", "victim", "placement counts")):
        np.testing.assert_array_equal(got, want, err_msg=name)
