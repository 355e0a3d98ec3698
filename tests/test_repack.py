"""kai-repack tests — the proactive defragmentation solver
(``ops/repack.py``) and its trigger/execution surfaces (ISSUE 10
tentpole).

Layers:

1. **NumPy-oracle bit-exactness** on randomized small snapshots: the
   kernel's vectorized min-migration solve (fixed marginal unit gains +
   per-rack prefix sums) must match a SEQUENTIAL host reference that
   literally simulates canonical-order evictions one at a time and
   first-fit ascending-node re-placement — pod indices, destination
   nodes, counts and feasibility all exactly equal.
2. **ROADMAP-5 end-to-end scenario**: a fragmented two-rack cluster
   where a rack-required gang is cluster-feasible but rack-stranded —
   the trigger fires after ``repack_trigger_cycles`` high-frag cycles,
   the plan migrates the minimum pods, the gang places within
   ``repack_cooldown + 1`` cycles of the firing, and ``frag_score``
   drops THE SAME cycle the gang places.
3. **No-op guarantees**: repack disabled leaves the stranded gang
   permanently unplaced (seed behavior), and an enabled-but-untriggered
   scheduler produces byte-identical commits and wire bytes to a
   disabled twin on every cycle.
4. **Single rack-domain knob**: ``RepackConfig`` has NO rack_level of
   its own (it embeds the AnalyticsConfig), and the ``rackLevel``
   config-document key steers both gauges and solver at once.
5. **Pipelined-rebind unification**: consolidation moves and repack
   migrations commit through ONE ``Session.pipelined_rebind`` helper
   with identical bind shapes and parallel DecisionLog event shapes.
6. **Coverage meta + endpoint**: the kernel is registered in the jaxpr
   probe and CompileWatcher; ``GET /debug/repack`` serves the trigger
   state.
"""
import dataclasses
import json
import urllib.request

import numpy as np
import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.ops import analytics as pulse
from kai_scheduler_tpu.ops import repack
from kai_scheduler_tpu.ops.allocate import EPS

# ---------------------------------------------------------------------------
# oracle — the sequential reference spec of the repack solve
# ---------------------------------------------------------------------------


def _units_row(avail, valid, unit):
    """f32 — canonical unit pods for one node row (the analytics
    ``_unit_pods_per_node`` formula, sequentially)."""
    f32 = np.float32
    if not valid:
        return f32(0.0)
    if not all(avail[r] + f32(EPS) >= unit[r] for r in range(len(unit))):
        return f32(0.0)
    u = np.inf
    for r in range(len(unit)):
        if unit[r] > 0:
            u = min(u, np.floor(f32(avail[r] / max(unit[r], f32(EPS)))))
    return f32(0.0) if not np.isfinite(u) else f32(max(u, 0.0))


def _oracle_plan(state, ages, cfg):
    """Sequential reference: simulate canonical-order evictions per
    rack one at a time (recomputing unit counts from scratch after
    every eviction) and first-fit ascending-node re-placement."""
    f32 = np.float32
    n, g, r = state.nodes, state.gangs, state.running
    topo = np.asarray(n.topology)
    nvalid = np.asarray(n.valid)
    free = np.maximum(np.asarray(n.free), f32(0.0))
    N, L = topo.shape
    rl = min(max(cfg.analytics.rack_level, 0), L - 1)
    P = cfg.max_migrations
    junk = N * L + N
    empty = dict(move_pod=[], move_node=[], num_moves=0, feasible=False,
                 target_gang=-1, target_rack=-1)

    # target gang: oldest starving rack-required pending gang
    gvalid = np.asarray(g.valid)
    req_level = np.asarray(g.required_level)
    cand = gvalid & (req_level == rl)
    keys = np.where(cand, ages, f32(-1.0))
    target = int(np.argmax(keys))
    if keys[target] <= 0:
        return empty
    unit = np.asarray(g.task_req)[target, 0]
    needed = f32(max(int(np.asarray(g.min_needed)[target]), 0))
    if needed <= 0:
        return empty

    seg = np.full((N,), junk, np.int64)
    for i in range(N):
        if nvalid[i]:
            seg[i] = topo[i, rl] if topo[i, rl] >= 0 else N * L + i
    units0 = np.array([_units_row(free[i], nvalid[i], unit)
                       for i in range(N)], f32)
    have = {}
    for i in range(N):
        if seg[i] != junk:
            have[seg[i]] = f32(have.get(seg[i], f32(0.0)) + units0[i])
    total = f32(units0.sum())
    max_rack = max(have.values(), default=f32(0.0))
    if not (total >= needed and max_rack < needed):
        return empty

    rvalid = np.asarray(r.valid)
    rgang = np.asarray(r.gang)
    # consolidation-mode minruntime protection (victim_candidates):
    # gang runtime = max pod runtime, -1 when never started
    G = gvalid.shape[0]
    grt = np.full((G,), f32(-1.0))
    runt_all = np.asarray(r.runtime_s)
    for m in range(rgang.shape[0]):
        if rvalid[m] and rgang[m] >= 0:
            grt[rgang[m]] = max(grt[rgang[m]], runt_all[m])
    mrt = np.asarray(state.queues.preempt_min_runtime_eff)[
        np.maximum(np.asarray(g.queue), 0)]
    prot_g = (grt >= 0) & (grt < mrt)
    movable = (rvalid & ~np.asarray(r.releasing)
               & np.asarray(r.preemptible) & (np.asarray(r.node) >= 0)
               & (rgang >= 0) & (rgang != target)
               & ~prot_g[np.clip(rgang, 0, G - 1)])
    node_m = np.asarray(r.node)
    prio = np.asarray(r.priority)
    runt = np.asarray(r.runtime_s)
    reqs = np.asarray(r.req)
    order = [m for m in np.lexsort((runt, prio)).tolist() if movable[m]]

    # per-rack sequential simulation: evict in canonical order,
    # recomputing the rack's unit count from scratch each step
    k_of = {}
    victims_of = {}
    for d in sorted({int(seg[node_m[m]]) for m in order}):
        pods_d = [m for m in order if int(seg[node_m[m]]) == d]
        free_d = free.copy()
        taken = []
        found = None
        for k, m in enumerate(pods_d[:P], start=1):
            free_d[node_m[m]] = free_d[node_m[m]] + reqs[m]
            taken.append(m)
            rack_units = f32(sum(
                _units_row(free_d[i], nvalid[i], unit)
                for i in range(N) if seg[i] == d))
            if rack_units >= needed:
                found = k
                break
        if found is not None:
            k_of[d] = found
            victims_of[d] = taken
    if not k_of:
        return empty
    best = min(k_of, key=lambda d: (k_of[d], d))
    victims = victims_of[best]

    # destination: first-fit ascending node id outside the target rack
    fmask = np.asarray(n.filter_masks)
    free_dest = np.where((nvalid & (seg != best))[:, None], free,
                         f32(0.0))
    moves = []
    for m in victims:
        fc = min(max(int(np.asarray(r.filter_class)[m]), 0),
                 fmask.shape[0] - 1)
        dest = -1
        for i in range(N):
            if (nvalid[i] and seg[i] != best and fmask[fc, i]
                    and all(free_dest[i, x] + f32(EPS) >= reqs[m, x]
                            for x in range(reqs.shape[1]))):
                dest = i
                break
        if dest < 0:
            return empty
        free_dest[dest] = free_dest[dest] - reqs[m]
        moves.append((m, dest))
    return dict(move_pod=[m for m, _ in moves],
                move_node=[d for _, d in moves],
                num_moves=len(moves), feasible=True,
                target_gang=target, target_rack=int(best))


def _random_snapshot(seed, **kw):
    from kai_scheduler_tpu.state.cluster_state import build_snapshot
    from kai_scheduler_tpu.state.synthetic import make_cluster
    kw.setdefault("num_nodes", 12)
    kw.setdefault("node_accel", 4.0)
    kw.setdefault("num_gangs", 10)
    kw.setdefault("tasks_per_gang", 3)
    kw.setdefault("running_fraction", 0.6)
    kw.setdefault("priority_spread", 3)
    kw.setdefault("topology_levels", (3,))
    kw.setdefault("required_level", "topo/level0")
    kw.setdefault("seed", seed)
    nodes, queues, groups, pods, topo = make_cluster(**kw)
    return build_snapshot(nodes, queues, groups, pods, topo, now=100.0)


def _stranded_snapshot(seed):
    """A randomized rack-stranded instance: 3 racks x 3 nodes x 4
    accel, each node holding 1-3 single-accel fillers with random
    priorities (a random minority non-preemptible — the movable filter
    must prune them), and a rack-required 8-pod pending gang.  Depending
    on the draw the instance is feasible, infeasible-by-candidacy (some
    rack already hosts the gang / cluster-infeasible), or
    infeasible-by-budget — the oracle must agree everywhere."""
    from kai_scheduler_tpu.state.cluster_state import build_snapshot
    rng = np.random.default_rng(seed)
    topo = apis.Topology(name="default",
                         levels=["topo/rack", "kubernetes.io/hostname"])
    nodes, pods, groups = [], [], []
    for i in range(9):
        name = f"node-{i}"
        nodes.append(apis.Node(
            name, apis.ResourceVec(4, 64, 256),
            labels={"topo/rack": f"rack-{i // 3}",
                    "kubernetes.io/hostname": name}))
    # a random minority of draws protects the fillers via queue
    # preempt-minruntime (fillers start at t<=50, snapshot now=100, so
    # mrt=200 protects everything and mrt=75 a random subset)
    mrt = float(rng.choice([0.0, 0.0, 75.0, 200.0]))
    queues = [apis.Queue("fill", accel=apis.QueueResource(quota=36),
                         preempt_min_runtime=mrt),
              apis.Queue("big", accel=apis.QueueResource(quota=8))]
    gi = 0
    for i in range(9):
        for t in range(int(rng.integers(1, 4))):
            kind = (apis.Preemptibility.NON_PREEMPTIBLE
                    if rng.random() < 0.2
                    else apis.Preemptibility.PREEMPTIBLE)
            grp = apis.PodGroup(
                f"fill-{gi}", queue="fill", min_member=1,
                priority=int(rng.integers(0, 3)), preemptibility=kind,
                last_start_timestamp=float(rng.integers(0, 50)))
            groups.append(grp)
            pods.append(apis.Pod(
                f"fill-{gi}-0", grp.name, apis.ResourceVec(1, 1, 4),
                status=apis.PodStatus.RUNNING, node=f"node-{i}"))
            gi += 1
    gang = apis.PodGroup(
        "big-gang", queue="big", min_member=8,
        topology_constraint=apis.TopologyConstraint(
            topology="default", required_level="topo/rack"))
    groups.append(gang)
    for t in range(8):
        pods.append(apis.Pod(f"big-{t}", "big-gang",
                             apis.ResourceVec(1, 1, 4)))
    return build_snapshot(nodes, queues, groups, pods, topo, now=100.0)


def _randomized_case(family, seed):
    """(state, ages) for one oracle-equivalence draw."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed + 1000)
    if family == "random":
        state, _ = _random_snapshot(seed)
        # perturb the free pool so unit counts vary per node
        state = state.replace(nodes=state.nodes.replace(
            free=jnp.maximum(
                state.nodes.free
                - jnp.asarray(rng.integers(0, 3, state.nodes.free.shape)
                              .astype(np.float32)), 0.0)))
    else:
        state, _ = _stranded_snapshot(seed)
    ages = np.zeros((state.gangs.g,), np.float32)
    idx = np.nonzero(np.asarray(state.gangs.valid))[0]
    ages[idx] = rng.integers(0, 6, idx.size).astype(np.float32)
    return state, ages


@pytest.mark.parametrize("family,seed", [
    ("random", 0), ("random", 1), ("random", 2),
    ("stranded", 0), ("stranded", 1), ("stranded", 2), ("stranded", 3),
])
def test_numpy_oracle_bit_exactness(family, seed):
    """The vectorized min-migration solve == the sequential eviction
    simulation, bit for bit (integer-valued resources keep f32 exact)."""
    state, ages = _randomized_case(family, seed)
    cfg = repack.RepackConfig(max_migrations=8)
    # destinations drawn from the snapshot pool (the oracle's view;
    # production passes the cycle's post-decision AllocationResult.free)
    plan = repack.plan_repack_jit(state, ages, state.nodes.free,
                                  config=cfg)
    want = _oracle_plan(state, ages, cfg)
    assert bool(plan.feasible) == want["feasible"]
    if not want["feasible"]:
        assert int(plan.num_moves) == 0
        assert np.all(np.asarray(plan.move_pod) == -1)
        return
    assert int(plan.target_gang) == want["target_gang"]
    assert int(plan.target_rack) == want["target_rack"]
    assert int(plan.num_moves) == want["num_moves"]
    mp = np.asarray(plan.move_pod)
    mn = np.asarray(plan.move_node)
    live = mp >= 0
    np.testing.assert_array_equal(mp[live], np.asarray(want["move_pod"]))
    np.testing.assert_array_equal(mn[live],
                                  np.asarray(want["move_node"]))


def test_oracle_exercises_both_outcomes():
    """The randomized families must cover feasible AND infeasible plans
    — otherwise the bit-exactness parametrization proves less than it
    claims."""
    cfg = repack.RepackConfig(max_migrations=8)
    outcomes = {
        _oracle_plan(*_randomized_case(family, seed), cfg)["feasible"]
        for family, seed in (("random", 0), ("stranded", 0),
                             ("stranded", 1), ("stranded", 2),
                             ("stranded", 3))}
    assert outcomes == {True, False}


# ---------------------------------------------------------------------------
# the ROADMAP-5 end-to-end scenario
# ---------------------------------------------------------------------------

RACK = "topo/rack"


def _frag_cluster(preemptible_fillers=True):
    """Two racks x 4 nodes x 4 accel, every node 3/4 full with fillers:
    each rack strands 4 free devices, so a rack-required 8-pod gang is
    cluster-feasible (8 free) but unplaceable in any single rack.  With
    PREEMPTIBLE fillers the repack solver can free a rack by migrating
    4 of them across; the PR-9 analytics scenario used non-preemptible
    fillers precisely so nothing could."""
    from kai_scheduler_tpu.runtime.cluster import Cluster
    topo = apis.Topology(name="default",
                         levels=[RACK, "kubernetes.io/hostname"])
    nodes, pods, groups = [], [], []
    for i in range(8):
        name = f"node-{i}"
        nodes.append(apis.Node(
            name, apis.ResourceVec(4, 64, 256),
            labels={RACK: f"rack-{i // 4}",
                    "kubernetes.io/hostname": name}))
    queues = [apis.Queue("fill", accel=apis.QueueResource(quota=24)),
              apis.Queue("big", accel=apis.QueueResource(quota=8))]
    kind = (apis.Preemptibility.PREEMPTIBLE if preemptible_fillers
            else apis.Preemptibility.NON_PREEMPTIBLE)
    for i in range(8):
        g = apis.PodGroup(f"fill-{i}", queue="fill", min_member=3,
                          preemptibility=kind, last_start_timestamp=0.0)
        groups.append(g)
        for t in range(3):
            pods.append(apis.Pod(
                f"fill-{i}-{t}", g.name, apis.ResourceVec(1, 1, 4),
                status=apis.PodStatus.RUNNING, node=f"node-{i}"))
    gang = apis.PodGroup(
        "big-gang", queue="big", min_member=8,
        topology_constraint=apis.TopologyConstraint(
            topology="default", required_level=RACK))
    groups.append(gang)
    for t in range(8):
        pods.append(apis.Pod(f"big-{t}", "big-gang",
                             apis.ResourceVec(1, 1, 4)))
    return Cluster.from_objects(nodes, queues, groups, pods, topo)


def _repack_cfg(**kw):
    from kai_scheduler_tpu.framework.scheduler import SchedulerConfig
    # consolidation excluded: it is the REACTIVE mover and would race
    # the proactive solver for the same fillers — this scenario isolates
    # the repack path (the production default keeps both; first mover
    # wins and the other finds nothing left to move)
    kw.setdefault("actions",
                  ("allocate", "reclaim", "preempt", "stalegangeviction"))
    kw.setdefault("repack_frag_threshold", 0.2)
    kw.setdefault("repack_trigger_cycles", 2)
    kw.setdefault("repack_cooldown", 3)
    return SchedulerConfig(**kw)


def test_repack_unblocks_rack_required_gang():
    """The acceptance scenario: trigger fires after the streak, the
    plan migrates the minimum 4 fillers within budget, the gang places
    within ``repack_cooldown + 1`` cycles of the firing, and the
    fragmentation score drops the SAME cycle it places."""
    from kai_scheduler_tpu.binder import Binder
    from kai_scheduler_tpu.framework import metrics
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    cluster = _frag_cluster()
    cfg = _repack_cfg()
    sched, binder = Scheduler(cfg), Binder()
    unblocked0 = metrics.repack_gangs_unblocked.value()
    fired_cycle = placed_cycle = None
    stranded_score = None
    for cyc in range(1, 10):
        res = sched.run_once(cluster)
        if stranded_score is None:
            stranded_score = res.analytics["fragmentation"]["score"]
        if res.repack:
            assert fired_cycle is None, "repack fired twice (no cooldown)"
            fired_cycle = cyc
            assert res.repack["feasible"]
            assert res.repack["target_gang"] == "big-gang"
            # min-migration: exactly one filler per target-rack node,
            # within the configured budget
            assert res.repack["migrations_executed"] == 4
            assert (res.repack["migrations_executed"]
                    <= cfg.repack_max_migrations)
            assert res.repack["rack_units_after"] >= 8.0
            moved = [ev for ev in res.evictions if ev.reason == "repack"]
            assert len(moved) == 4
            assert all(ev.move_to is not None for ev in moved)
            assert len(res.move_bind_requests) == 4
        if any(b.pod_name.startswith("big-")
               for b in res.bind_requests):
            placed_cycle = cyc
            # frag_score drops the unblocking cycle (the predictive
            # property: fragmentation reads the pre-decision pool the
            # repacked capacity now consolidates)
            assert (res.analytics["fragmentation"]["score"]
                    < stranded_score)
            assert len([b for b in res.bind_requests
                        if b.pod_name.startswith("big-")]) == 8
            break
        binder.reconcile(cluster)
        cluster.tick()
    assert stranded_score > 0.2          # the trigger's signal was real
    assert fired_cycle is not None, "repack trigger never fired"
    assert fired_cycle == cfg.repack_trigger_cycles + 1
    assert placed_cycle is not None, "gang never placed"
    assert placed_cycle - fired_cycle <= cfg.repack_cooldown + 1
    # the payoff metric observed the unblock
    assert metrics.repack_gangs_unblocked.value() == unblocked0 + 1
    # repacked-for decision events name the beneficiary
    evs = [e for e in sched.decisions.events()
           if e["outcome"] == "repacked-for"]
    assert evs and all("big-gang" in e["detail"] for e in evs)
    # /debug/repack status doc reflects the firing
    status = sched.repack_status()
    assert status["ok"] and status["last"]["target_gang"] == "big-gang"
    assert status["last"]["migrations_executed"] == 4


def test_repack_fires_with_real_ages_on_a_cadence_skipped_cycle():
    """The frag streak completes at the end of an analytics cycle, so
    with ``analytics_every > 1`` the trigger fires on the NEXT cycle,
    which the cadence skips: no analytics pass has computed the pending
    ages (``ages is None`` at the repack dispatch).  The solve must then
    compute REAL ages itself — an all-zero vector fails
    ``plan_repack``'s target gate and burns the cooldown on an
    infeasible plan."""
    from kai_scheduler_tpu.binder import Binder
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    cluster = _frag_cluster()
    sched, binder = Scheduler(_repack_cfg(analytics_every=2)), Binder()
    fired = placed = None
    for cyc in range(1, 12):
        res = sched.run_once(cluster)
        if res.repack and fired is None:
            fired = cyc
            # the scenario's point: the firing landed on a cycle with
            # no analytics pass (nothing else had computed the ages)
            assert res.analytics == {} and res.analytics_seconds == 0.0
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
    assert placed is not None and placed >= fired


def test_minruntime_protected_fillers_are_not_movable():
    """The consolidation-mode victim protection applies to repack too:
    fillers inside their queue's preempt-minruntime window expose no
    movable pods, so the plan is infeasible until they age out."""
    from kai_scheduler_tpu.state.cluster_state import build_snapshot

    def snap(mrt):
        cluster = _frag_cluster()
        cluster.queues["fill"] = dataclasses.replace(
            cluster.queues["fill"], preempt_min_runtime=mrt)
        cluster.now = 100.0
        return build_snapshot(*cluster.snapshot_lists(), now=cluster.now)

    cfg = repack.RepackConfig()
    for mrt, want in ((1000.0, False), (50.0, True)):
        state, index = snap(mrt)
        ages = np.zeros((state.gangs.g,), np.float32)
        ages[index.gang_names.index("big-gang")] = 3.0
        plan = repack.plan_repack_jit(state, ages, state.nodes.free,
                                      config=cfg)
        assert bool(plan.feasible) is want, mrt
        assert _oracle_plan(state, ages, cfg)["feasible"] is want


def test_unblock_metric_with_zero_cooldown():
    """Regression for the watch window arithmetic: with
    ``repack_cooldown=0`` the same-cycle decrement must not expire the
    observation window before the gang's next-cycle placement."""
    from kai_scheduler_tpu.binder import Binder
    from kai_scheduler_tpu.framework import metrics
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    cluster = _frag_cluster()
    sched, binder = Scheduler(_repack_cfg(repack_cooldown=0)), Binder()
    base = metrics.repack_gangs_unblocked.value()
    for _ in range(8):
        res = sched.run_once(cluster)
        if any(b.pod_name.startswith("big-") for b in res.bind_requests):
            break
        binder.reconcile(cluster)
        cluster.tick()
    else:
        raise AssertionError("gang never placed")
    assert metrics.repack_gangs_unblocked.value() == base + 1


def test_repack_disabled_leaves_gang_stranded():
    """Seed behavior with the knob off: the rack-required gang stays
    permanently unplaceable and no migration ever happens."""
    from kai_scheduler_tpu.binder import Binder
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    cluster = _frag_cluster()
    sched = Scheduler(_repack_cfg(repack_enable=False))
    binder = Binder()
    for _ in range(6):
        res = sched.run_once(cluster)
        assert res.repack == {}
        assert res.evictions == []
        assert not any(b.pod_name.startswith("big-")
                       for b in res.bind_requests)
        binder.reconcile(cluster)
        cluster.tick()
    assert sched.repack_status()["ok"] is False


def test_untriggered_repack_is_byte_identical_to_disabled():
    """Zero overhead below threshold: an enabled scheduler whose
    trigger never fires commits byte-identically to a disabled twin —
    same bind/eviction documents, same wire bytes, every cycle."""
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.framework.server import _commit_doc
    from kai_scheduler_tpu.runtime.cluster import Cluster
    from kai_scheduler_tpu.state.synthetic import make_cluster

    def run(enable: bool):
        nodes, queues, groups, pods, topo = make_cluster(
            num_nodes=16, num_gangs=12, tasks_per_gang=2,
            running_fraction=0.5, seed=7)
        cluster = Cluster.from_objects(nodes, queues, groups, pods, topo)
        sched = Scheduler(_repack_cfg(repack_enable=enable))
        rows = []
        for step in range(6):
            res = sched.run_once(cluster)
            assert res.repack == {} and res.repack_seconds == 0.0
            doc = _commit_doc(res)
            doc.pop("action_seconds")         # wall time, not a commit
            rows.append((json.dumps(doc, sort_keys=True),
                         res.wire["bytes"]))
            running = sorted(p.name for p in cluster.pods.values()
                             if p.status == apis.PodStatus.RUNNING)
            if running:
                cluster.evict_pod(running[step % len(running)])
            cluster.tick()
        return rows

    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# single rack-domain knob
# ---------------------------------------------------------------------------


def test_rack_level_has_one_source_of_truth():
    """``RepackConfig`` carries NO rack level of its own — it embeds the
    AnalyticsConfig, so the fragmentation trigger and the solver derive
    the rack partition from the same knob by construction."""
    fields = {f.name for f in dataclasses.fields(repack.RepackConfig)}
    assert "rack_level" not in fields
    assert fields == {"analytics", "max_migrations"}
    # the embedded config IS the analytics one (same dataclass, which
    # carries the one rack_level the gauges use)
    assert (type(repack.RepackConfig().analytics)
            is pulse.AnalyticsConfig)


def test_conf_rack_level_knob_plumbs_both_consumers():
    from kai_scheduler_tpu.conf import effective_config_doc, load_config
    cfg = load_config({"rackLevel": 1,
                       "repack": {"fragThreshold": 0.7,
                                  "triggerCycles": 3,
                                  "cooldownCycles": 5,
                                  "maxMigrations": 16,
                                  "enabled": True}})
    assert cfg.session.analytics.rack_level == 1
    assert cfg.repack_frag_threshold == 0.7
    assert cfg.repack_trigger_cycles == 3
    assert cfg.repack_cooldown == 5
    assert cfg.repack_max_migrations == 16
    # the solver config built the way the scheduler builds it sees the
    # SAME level — there is no second field to diverge
    rcfg = repack.RepackConfig(analytics=cfg.session.analytics)
    assert rcfg.analytics.rack_level == 1
    doc = effective_config_doc(cfg)
    assert doc["rackLevel"] == 1
    assert doc["repack"]["maxMigrations"] == 16
    # round-trip: feeding the effective repack/rack keys back keeps them
    cfg2 = load_config({"rackLevel": doc["rackLevel"],
                        "repack": doc["repack"]})
    assert cfg2.session.analytics.rack_level == 1
    assert cfg2.repack_cooldown == 5


# ---------------------------------------------------------------------------
# pipelined-rebind unification (consolidation move == repack move path)
# ---------------------------------------------------------------------------


def _consolidation_cluster():
    from kai_scheduler_tpu.runtime.cluster import Cluster
    nodes = [apis.Node(f"node-{i}", apis.ResourceVec(4.0, 64.0, 256.0))
             for i in range(2)]
    queues = [apis.Queue("q0", accel=apis.QueueResource(quota=8.0))]
    frag0 = apis.PodGroup("frag0", queue="q0", min_member=1,
                          last_start_timestamp=0.0)
    frag1 = apis.PodGroup("frag1", queue="q0", min_member=1,
                          creation_timestamp=0.5,
                          last_start_timestamp=0.5)
    pending = apis.PodGroup("big", queue="q0", min_member=1,
                            creation_timestamp=1.0)
    pods = [
        apis.Pod("f0", "frag0", resources=apis.ResourceVec(2.0, 1.0, 4.0),
                 status=apis.PodStatus.RUNNING, node="node-0",
                 accel_devices=[0, 1]),
        apis.Pod("f1", "frag1", resources=apis.ResourceVec(2.0, 1.0, 4.0),
                 status=apis.PodStatus.RUNNING, node="node-1",
                 accel_devices=[0, 1]),
        apis.Pod("big-0", "big", resources=apis.ResourceVec(4.0, 1.0, 4.0),
                 creation_timestamp=1.0),
    ]
    c = Cluster.from_objects(nodes, queues, [frag0, frag1, pending], pods)
    c.now = 100.0
    return c


def test_consolidation_and_repack_share_one_rebind_path(monkeypatch):
    """Both movers flow through ``Session.pipelined_rebind`` (counted),
    emit BindRequests of identical shape, and log DecisionLog events of
    identical shape — the satellite's regression bar."""
    from kai_scheduler_tpu.framework.scheduler import (Scheduler,
                                                       SchedulerConfig)
    from kai_scheduler_tpu.framework.session import Session
    calls = []
    orig = Session.pipelined_rebind

    def spy(self, cluster, ev):
        out = orig(self, cluster, ev)
        calls.append((ev.reason, ev.pod_name, out))
        return out

    monkeypatch.setattr(Session, "pipelined_rebind", spy)

    # consolidation move
    sched_c = Scheduler(SchedulerConfig())
    res_c = sched_c.run_once(_consolidation_cluster())
    consol = [c for c in calls if c[0] != "repack"]
    assert len(consol) == len(res_c.move_bind_requests) == 1

    # repack move
    calls.clear()
    sched_r = Scheduler(_repack_cfg())
    cluster = _frag_cluster()
    res_r = None
    for _ in range(4):
        res_r = sched_r.run_once(cluster)
        if res_r.repack:
            break
        cluster.tick()
    assert res_r is not None and res_r.repack
    rep = [c for c in calls if c[0] == "repack"]
    assert len(rep) == len(res_r.move_bind_requests) == 4

    # identical bind SHAPE: same dataclass fields populated the same way
    bc, br = res_c.move_bind_requests[0], res_r.move_bind_requests[0]
    assert dataclasses.asdict(bc).keys() == dataclasses.asdict(br).keys()
    for b in (bc, br):
        assert b.received_resource_type == apis.ReceivedResourceType.REGULAR
        assert b.phase == "Pending"
        assert b.backoff_limit == 3
    # identical EVENT shape: same doc keys, the shared rebind phrasing,
    # outcomes split only by mover
    ev_c = [e for e in sched_c.decisions.events()
            if e["outcome"] == "preempted-for"
            and "pipelined rebind" in e["detail"]][0]
    ev_r = [e for e in sched_r.decisions.events()
            if e["outcome"] == "repacked-for"][0]
    assert ev_c.keys() == ev_r.keys()
    assert "(pipelined rebind)" in ev_c["detail"]
    assert "(pipelined rebind)" in ev_r["detail"]


def test_gang_with_repack_and_plain_evictions_reports_both():
    """A gang can lose pods to a repack migration AND a plain
    preemption in one cycle — the DecisionLog must report BOTH
    outcomes (counts and events), not collapse them into one."""
    from kai_scheduler_tpu.framework.session import Session, SessionConfig
    from kai_scheduler_tpu.ops.allocate import init_result
    from kai_scheduler_tpu.runtime import events as gang_events
    state, index = _stranded_snapshot(0)
    session = Session.from_state(state, index, SessionConfig())
    res = init_result(state)
    host = session.gather_host(res)
    group = index.gang_names[0]
    evictions = [
        apis.Eviction(pod_name="p0", group=group,
                      reason=Session.REPACK_REASON, move_to="node-1"),
        apis.Eviction(pod_name="p1", group=group),
    ]
    events, _dropped, counts = session.decision_events(
        res, host=host, evictions=evictions, repack_for="big-gang")
    assert counts[gang_events.OUTCOME_REPACKED_FOR] == 1
    assert counts[gang_events.OUTCOME_PREEMPTED_FOR] == 1
    got = {e.outcome for e in events if e.gang == group}
    assert {gang_events.OUTCOME_REPACKED_FOR,
            gang_events.OUTCOME_PREEMPTED_FOR} <= got


# ---------------------------------------------------------------------------
# coverage meta + endpoint
# ---------------------------------------------------------------------------


def test_repack_registered_in_probe_and_watcher():
    from kai_scheduler_tpu.analysis.trace_probe import registered_ops
    from kai_scheduler_tpu.runtime.compile_watch import WATCHER
    assert "repack" in registered_ops()
    assert "repack" in WATCHER.entries()
    assert hasattr(repack.plan_repack_jit, "_cache_size")


def test_debug_repack_endpoint():
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.framework.server import SchedulerServer
    srv = SchedulerServer(_frag_cluster(), Scheduler(_repack_cfg()))
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        doc = json.load(urllib.request.urlopen(
            f"{base}/debug/repack", timeout=10))
        assert doc["ok"] is False and doc["enabled"] is True
        assert doc["frag_threshold"] == 0.2
        assert doc["last"] == {}
        # drive stored cycles until the trigger fires; the endpoint
        # then serves the firing's immutable plan doc
        for _ in range(3):
            req = urllib.request.Request(f"{base}/cycle/stored",
                                         data=b"", method="POST")
            urllib.request.urlopen(req, timeout=60).read()
        doc = json.load(urllib.request.urlopen(
            f"{base}/debug/repack", timeout=10))
        assert doc["ok"] is True
        assert doc["last"]["target_gang"] == "big-gang"
        assert doc["cooldown_remaining"] > 0
    finally:
        srv.stop()
