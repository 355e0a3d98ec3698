"""Reclaim / preempt action tests — mirroring the reference suites
``actions/reclaim/reclaim_test.go`` and ``actions/preempt/preempt_test.go``
(fake-cluster scenario style, SURVEY.md §4 tier 2)."""
import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.apis import types as apis
from kai_scheduler_tpu.ops import drf
from kai_scheduler_tpu.ops.allocate import init_result
from kai_scheduler_tpu.ops.victims import VictimConfig, run_victim_action
from kai_scheduler_tpu.state import build_snapshot

Vec = apis.ResourceVec
QR = apis.QueueResource


def two_queue_cluster(*, victim_gpus=8, q0_quota=4.0, q1_quota=4.0,
                      victim_preemptible=True, reclaim_mrt=0.0,
                      victim_runtime=100.0):
    """One 8-GPU node; queue-1's running gang holds `victim_gpus` GPUs;
    queue-0 has a pending gang wanting 4 GPUs."""
    nodes = [apis.Node("node-0", Vec(8.0, 64.0, 256.0))]
    queues = [
        apis.Queue("q0", accel=QR(quota=q0_quota)),
        apis.Queue("q1", accel=QR(quota=q1_quota),
                   reclaim_min_runtime=reclaim_mrt),
    ]
    running = apis.PodGroup(
        "running-gang", queue="q1", min_member=1,
        preemptibility=(apis.Preemptibility.PREEMPTIBLE if victim_preemptible
                        else apis.Preemptibility.NON_PREEMPTIBLE),
        creation_timestamp=0.0, last_start_timestamp=0.0)
    pending = apis.PodGroup("pending-gang", queue="q0", min_member=2,
                            creation_timestamp=1.0)
    pods = []
    for i in range(int(victim_gpus)):
        pods.append(apis.Pod(
            f"victim-{i}", "running-gang", resources=Vec(1.0, 1.0, 4.0),
            status=apis.PodStatus.RUNNING, node="node-0",
            creation_timestamp=0.0))
    for i in range(2):
        pods.append(apis.Pod(
            f"pending-{i}", "pending-gang", resources=Vec(2.0, 1.0, 4.0),
            creation_timestamp=1.0))
    groups = [running, pending]
    state, index = build_snapshot(
        nodes, queues, groups, pods, now=victim_runtime)
    return state, index


def run_reclaim(state, num_levels=1, **cfg):
    fair_share = drf.set_fair_share(state, num_levels=num_levels)
    res = run_victim_action(
        state, fair_share, init_result(state), num_levels=num_levels,
        mode="reclaim", config=VictimConfig(**cfg))
    return res, fair_share


class TestReclaim:
    def test_reclaims_over_quota_queue(self):
        # q1 uses all 8 GPUs (quota 4); q0 (quota 4) pending 4 GPUs ->
        # reclaim should evict enough victims and place the pending gang.
        state, index = two_queue_cluster()
        res, fs = run_reclaim(state)
        pending_gi = index.gang_names.index("pending-gang")
        assert bool(res.allocated[pending_gi])
        # both tasks placed, pipelined (await victim termination)
        assert int((np.asarray(res.placements[pending_gi]) >= 0).sum()) == 2
        assert bool(res.pipelined[pending_gi, 0])
        n_victims = int(np.asarray(res.victim).sum())
        assert n_victims >= 4  # at least the 4 GPUs worth of pods
        # q1 must keep its deserved quota: can't evict below 4 GPUs
        assert n_victims <= 4

    def test_no_reclaim_when_victim_queue_within_fair_share(self):
        # q1 only uses 4 GPUs = its fair share; nothing to reclaim.
        state, index = two_queue_cluster(victim_gpus=4)
        res, _ = run_reclaim(state)
        pending_gi = index.gang_names.index("pending-gang")
        assert not bool(res.allocated[pending_gi])
        assert int(np.asarray(res.victim).sum()) == 0

    def test_no_reclaim_of_nonpreemptible_victims(self):
        state, index = two_queue_cluster(victim_preemptible=False)
        res, _ = run_reclaim(state)
        assert int(np.asarray(res.victim).sum()) == 0

    def test_reclaimer_over_fair_share_gated(self):
        # q0 quota 0 => fair share gives q0 only surplus; with q1 over its
        # 4-GPU quota... make q0 fair share tiny by quota 0 + weight 0.
        nodes = [apis.Node("node-0", Vec(8.0, 64.0, 256.0))]
        queues = [
            apis.Queue("q0", accel=QR(quota=0.0, over_quota_weight=0.0)),
            apis.Queue("q1", accel=QR(quota=8.0)),
        ]
        running = apis.PodGroup("rg", queue="q1", min_member=1,
                                last_start_timestamp=0.0)
        pending = apis.PodGroup("pg", queue="q0", min_member=1)
        pods = [apis.Pod(f"v{i}", "rg", resources=Vec(1.0, 1.0, 4.0),
                         status=apis.PodStatus.RUNNING, node="node-0")
                for i in range(8)]
        pods.append(apis.Pod("p0", "pg", resources=Vec(1.0, 1.0, 4.0)))
        state, index = build_snapshot(nodes, queues, [running, pending],
                                      pods, now=100.0)
        res, _ = run_reclaim(state)
        assert not bool(res.allocated[index.gang_names.index("pg")])
        assert int(np.asarray(res.victim).sum()) == 0

    def test_minruntime_protects_quorum_not_surplus(self):
        # victims have run 10s < reclaimMinRuntime 60s -> protected.  The
        # running gang is ELASTIC (minMember 1, 8 pods): protection keeps
        # its quorum but surplus pods remain reclaimable (ref
        # minruntime reclaimFilterFn passing elastic jobs through to the
        # below-minAvailable scenario validator).
        state, index = two_queue_cluster(reclaim_mrt=60.0,
                                         victim_runtime=10.0)
        res, _ = run_reclaim(state)
        n_vic = int(np.asarray(res.victim).sum())
        assert 0 < n_vic <= 7  # at least minMember=1 pod survives
        # once they've run long enough, reclaim proceeds
        state2, index2 = two_queue_cluster(reclaim_mrt=60.0,
                                           victim_runtime=120.0)
        res2, _ = run_reclaim(state2)
        assert bool(res2.allocated[index2.gang_names.index("pending-gang")])

    def test_minruntime_fully_protects_nonelastic_gang(self):
        # minMember == pod count: no surplus, the whole gang is its
        # quorum — a protected gang yields zero victims.
        nodes = [apis.Node("node-0", Vec(8.0, 64.0, 256.0))]
        queues = [apis.Queue("q0", accel=QR(quota=4.0)),
                  apis.Queue("q1", accel=QR(quota=4.0),
                             reclaim_min_runtime=60.0)]
        running = apis.PodGroup("rg", queue="q1", min_member=8,
                                creation_timestamp=0.0,
                                last_start_timestamp=0.0)
        pending = apis.PodGroup("pg", queue="q0", min_member=2,
                                creation_timestamp=1.0)
        pods = [apis.Pod(f"v{i}", "rg", resources=Vec(1.0, 1.0, 4.0),
                         status=apis.PodStatus.RUNNING, node="node-0")
                for i in range(8)]
        pods += [apis.Pod(f"p{i}", "pg", resources=Vec(2.0, 1.0, 4.0),
                          creation_timestamp=1.0) for i in range(2)]
        state, _ = build_snapshot(nodes, queues, [running, pending], pods,
                                  now=10.0)
        res, _ = run_reclaim(state)
        assert int(np.asarray(res.victim).sum()) == 0

    def test_minruntime_inherited_from_parent_queue(self):
        """A leaf without reclaimMinRuntime inherits its department's —
        ref plugins/minruntime/resolver.go inheritance walk."""
        nodes = [apis.Node("node-0", Vec(8.0, 64.0, 256.0))]
        queues = [
            apis.Queue("dept-a", accel=QR(quota=4.0)),
            apis.Queue("dept-b", accel=QR(quota=4.0),
                       reclaim_min_runtime=60.0),
            apis.Queue("qa", parent="dept-a", accel=QR(quota=4.0)),
            apis.Queue("qb", parent="dept-b", accel=QR(quota=4.0)),
        ]
        running = apis.PodGroup("rg", queue="qb", min_member=8,
                                creation_timestamp=0.0,
                                last_start_timestamp=0.0)
        pending = apis.PodGroup("pg", queue="qa", min_member=2,
                                creation_timestamp=1.0)
        pods = [apis.Pod(f"v{i}", "rg", resources=Vec(1.0, 1.0, 4.0),
                         status=apis.PodStatus.RUNNING, node="node-0")
                for i in range(8)]
        pods += [apis.Pod(f"p{i}", "pg", resources=Vec(2.0, 1.0, 4.0),
                          creation_timestamp=1.0) for i in range(2)]
        state, _ = build_snapshot(nodes, queues, [running, pending], pods,
                                  now=10.0)
        res, _ = run_reclaim(state, num_levels=2)
        assert int(np.asarray(res.victim).sum()) == 0  # qb inherits 60s


def preempt_cluster(*, preemptor_priority=100, victim_priority=50,
                    victim_preemptible=True, nonpreempt_preemptor=False):
    """Single queue, full node: high-priority pending gang vs low-priority
    running gang in the same queue."""
    nodes = [apis.Node("node-0", Vec(8.0, 64.0, 256.0))]
    queues = [apis.Queue("q0", accel=QR(quota=8.0))]
    running = apis.PodGroup(
        "low-gang", queue="q0", min_member=1, priority=victim_priority,
        preemptibility=(apis.Preemptibility.PREEMPTIBLE if victim_preemptible
                        else apis.Preemptibility.NON_PREEMPTIBLE),
        last_start_timestamp=0.0)
    pending = apis.PodGroup(
        "high-gang", queue="q0", min_member=2, priority=preemptor_priority,
        preemptibility=(apis.Preemptibility.NON_PREEMPTIBLE
                        if nonpreempt_preemptor
                        else apis.Preemptibility.PREEMPTIBLE),
        creation_timestamp=1.0)
    pods = [apis.Pod(f"victim-{i}", "low-gang", resources=Vec(1.0, 1.0, 4.0),
                     status=apis.PodStatus.RUNNING, node="node-0")
            for i in range(8)]
    pods += [apis.Pod(f"high-{i}", "high-gang", resources=Vec(2.0, 1.0, 4.0),
                      creation_timestamp=1.0) for i in range(2)]
    return build_snapshot(nodes, queues, [running, pending], pods, now=100.0)


def run_preempt(state, num_levels=1, **cfg):
    fair_share = drf.set_fair_share(state, num_levels=num_levels)
    return run_victim_action(
        state, fair_share, init_result(state), num_levels=num_levels,
        mode="preempt", config=VictimConfig(**cfg))


class TestPreempt:
    def test_higher_priority_preempts(self):
        state, index = preempt_cluster()
        res = run_preempt(state)
        hi = index.gang_names.index("high-gang")
        assert bool(res.allocated[hi])
        assert int(np.asarray(res.victim).sum()) >= 4

    def test_equal_priority_does_not_preempt(self):
        state, index = preempt_cluster(preemptor_priority=50)
        res = run_preempt(state)
        assert not bool(res.allocated[index.gang_names.index("high-gang")])
        assert int(np.asarray(res.victim).sum()) == 0

    def test_nonpreemptible_victims_protected(self):
        state, index = preempt_cluster(victim_preemptible=False)
        res = run_preempt(state)
        assert int(np.asarray(res.victim).sum()) == 0

    def test_nonpreemptible_preemptor_over_quota_gated(self):
        # queue quota 0: a non-preemptible preemptor would put the queue's
        # non-preemptible allocation over deserved -> gate refuses.
        nodes = [apis.Node("node-0", Vec(8.0, 64.0, 256.0))]
        queues = [apis.Queue("q0", accel=QR(quota=0.0))]
        running = apis.PodGroup("low", queue="q0", min_member=1, priority=1,
                                last_start_timestamp=0.0)
        pending = apis.PodGroup(
            "high", queue="q0", min_member=1, priority=9,
            preemptibility=apis.Preemptibility.NON_PREEMPTIBLE)
        pods = [apis.Pod(f"v{i}", "low", resources=Vec(1.0, 1.0, 4.0),
                         status=apis.PodStatus.RUNNING, node="node-0")
                for i in range(8)]
        pods.append(apis.Pod("h0", "high", resources=Vec(1.0, 1.0, 4.0)))
        state, index = build_snapshot(nodes, queues, [running, pending],
                                      pods, now=100.0)
        res = run_preempt(state)
        assert not bool(res.allocated[index.gang_names.index("high")])


class TestElasticScaleUp:
    def test_running_pods_count_toward_min_member(self):
        """A gang with min_member=4 and 2 pods already running needs only
        2 more placements (min_needed) — regression for the pipelined-
        remainder deadlock."""
        from kai_scheduler_tpu.ops import drf
        from kai_scheduler_tpu.ops.allocate import allocate

        nodes = [apis.Node("node-0", Vec(4.0, 64.0, 256.0))]
        queues = [apis.Queue("q0", accel=QR(quota=4.0))]
        group = apis.PodGroup("g0", queue="q0", min_member=4,
                              last_start_timestamp=0.0)
        pods = [apis.Pod(f"r{i}", "g0", resources=Vec(1.0, 1.0, 4.0),
                         status=apis.PodStatus.RUNNING, node="node-0")
                for i in range(2)]
        pods += [apis.Pod(f"p{i}", "g0", resources=Vec(1.0, 1.0, 4.0))
                 for i in range(2)]
        state, index = build_snapshot(nodes, queues, [group], pods)
        gi = index.gang_names.index("g0")
        assert int(state.gangs.min_needed[gi]) == 2
        fair_share = drf.set_fair_share(state, num_levels=1)
        res = allocate(state, fair_share, num_levels=1)
        assert bool(res.allocated[gi])
        assert int((np.asarray(res.placements[gi]) >= 0).sum()) == 2


class TestCycleWithVictims:
    def test_full_cycle_reclaim_then_rebind(self):
        """allocate fails -> reclaim evicts -> next cycle binds preemptor."""
        from kai_scheduler_tpu.binder import Binder
        from kai_scheduler_tpu.framework import Scheduler, SchedulerConfig
        from kai_scheduler_tpu.runtime.cluster import Cluster

        nodes = [apis.Node("node-0", Vec(8.0, 64.0, 256.0))]
        queues = [apis.Queue("q0", accel=QR(quota=4.0)),
                  apis.Queue("q1", accel=QR(quota=4.0))]
        running = apis.PodGroup("rg", queue="q1", min_member=1,
                                last_start_timestamp=0.0)
        pending = apis.PodGroup("pg", queue="q0", min_member=2,
                                creation_timestamp=1.0)
        pods = [apis.Pod(f"v{i}", "rg", resources=Vec(1.0, 1.0, 4.0),
                         status=apis.PodStatus.RUNNING, node="node-0",
                         creation_timestamp=0.0)
                for i in range(8)]
        pods += [apis.Pod(f"p{i}", "pg", resources=Vec(2.0, 1.0, 4.0),
                          creation_timestamp=1.0) for i in range(2)]
        cluster = Cluster.from_objects(nodes, queues, [running, pending], pods)
        cluster.now = 100.0

        from kai_scheduler_tpu.framework.session import SessionConfig
        sched = Scheduler(SchedulerConfig(
            actions=("allocate", "reclaim", "preempt"),
            session=SessionConfig(num_levels=1)))
        binder = Binder()

        r1 = sched.run_once(cluster)
        assert len(r1.evictions) == 4          # 4 GPUs reclaimed from q1
        assert len(r1.bind_requests) == 0      # preemptor pipelined
        binder.reconcile(cluster)
        cluster.tick()                          # releasing pods vanish

        r2 = sched.run_once(cluster)
        assert {br.pod_name for br in r2.bind_requests} == {"p0", "p1"}
        binder.reconcile(cluster)
        assert cluster.pods["p0"].status == apis.PodStatus.BOUND


class TestEvictionUnitAccounting:
    """ADVICE r1 (medium): surplus must be sized from the *effective*
    active count — running pods minus victims already taken this cycle —
    so successive actions cannot shrink a gang below minMember without
    evicting the whole remainder as one unit (ref Statement.Evict
    updating the counts GetTasksToEvict reads)."""

    def _state(self):
        nodes = [apis.Node("node-0", Vec(16.0, 64.0, 256.0))]
        queues = [apis.Queue("q0", accel=QR(quota=16.0))]
        gang = apis.PodGroup("elastic", queue="q0", min_member=8,
                             last_start_timestamp=0.0)
        pods = [apis.Pod(f"p{i}", "elastic", resources=Vec(1.0, 1.0, 1.0),
                         status=apis.PodStatus.RUNNING, node="node-0",
                         creation_timestamp=float(i))
                for i in range(10)]
        # a pending gang so G > 1 (not used by the unit ranking directly)
        pending = apis.PodGroup("pend", queue="q0", min_member=1,
                                creation_timestamp=20.0)
        pods.append(apis.Pod("pend-0", "pend", resources=Vec(1.0, 1.0, 1.0),
                             creation_timestamp=20.0))
        return build_snapshot(nodes, queues, [gang, pending], pods,
                              now=100.0)

    def test_surplus_shrinks_with_accumulated_victims(self):
        from kai_scheduler_tpu.ops.victims import _rank_eviction_units

        state, index = self._state()
        M = state.running.m
        fair_share = drf.set_fair_share(state, num_levels=1)
        gang_row = np.asarray(state.running.gang)
        gi = index.gang_names.index("elastic")
        cand_np = (np.asarray(state.running.valid)
                   & (gang_row == gi))

        # fresh cycle: 10 running, minMember 8 -> 2 single-pod units + 1
        # whole-gang unit
        no_victims = jnp.zeros((M,), bool)
        _, num_units = _rank_eviction_units(
            state, jnp.asarray(cand_np), state.queues.allocated,
            fair_share, no_victims)
        assert int(num_units) == 3

        # 2 pods already victimised this cycle: gang sits AT minMember —
        # the only remaining unit is the whole remaining gang
        prior = np.zeros((M,), bool)
        prior[np.nonzero(cand_np)[0][:2]] = True
        cand2 = jnp.asarray(cand_np & ~prior)
        _, num_units2 = _rank_eviction_units(
            state, cand2, state.queues.allocated, fair_share,
            jnp.asarray(prior))
        assert int(num_units2) == 1


# ---------------------------------------------------------------------------
# the action-level gate: a victim action with no viable preemptor builds
# nothing (ref reclaim/preempt/consolidation.Execute return when no job
# is pending)
# ---------------------------------------------------------------------------

from kai_scheduler_tpu.ops import victims  # noqa: E402

SLOT = victims._SKIP_SLOT
MODES = tuple(SLOT)


def _leaves(res):
    import jax
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(res)}


def assert_same_result(got, want, *, but=("victim_skipped",)):
    """Leaf for leaf, bit for bit; ``but`` names the leaves left out."""
    got, want = _leaves(got), _leaves(want)
    assert got.keys() == want.keys()
    for name in want:
        if any(b in name for b in but):
            continue
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def ungated_action(mode, num_levels, config):
    """The action's prefilter and its search called one after the other,
    no gate in between: what the program did before it had one."""
    def action(state, fair_share, res):
        chain = victims._chain_membership(state.queues.parent, num_levels)
        remaining0, cnt_q, task_req_g = victims._viable_preemptors(
            state, fair_share, res, num_levels=num_levels, mode=mode,
            chain=chain)
        return victims._victim_search(
            state, fair_share, res, num_levels=num_levels, mode=mode,
            config=config, remaining0=remaining0, chain=chain, cnt_q=cnt_q,
            task_req_g=task_req_g)
    return action


def _gate_session(scenario, seed):
    """Seeded clusters on which no gang is a viable preemptor."""
    from kai_scheduler_tpu.framework.session import Session
    from kai_scheduler_tpu.state import make_cluster
    if scenario == "nobody-pending":
        # everything runs: after allocate nobody is left to serve
        kw = dict(num_nodes=16, node_accel=4.0, num_gangs=12,
                  tasks_per_gang=4, running_fraction=1.0)
    elif scenario == "all-placed-by-allocate":
        # half full: allocate places every pending gang before the
        # victim actions look (the churn cells' cycle)
        kw = dict(num_nodes=16, node_accel=4.0, num_gangs=12,
                  tasks_per_gang=4, running_fraction=0.5)
    else:
        # pending gangs that do not fit and nothing running to evict
        assert scenario == "no-victims"
        kw = dict(num_nodes=2, node_accel=2.0, num_gangs=6,
                  tasks_per_gang=4, running_fraction=0.0)
    return Session.open(*make_cluster(
        num_departments=2, queues_per_department=3, seed=seed, **kw))


class TestActionGate:
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("scenario", ["nobody-pending",
                                          "all-placed-by-allocate",
                                          "no-victims"])
    def test_closed_gate_returns_its_input(self, mode, scenario):
        """(a) no viable preemptor: the action returns the commit set it
        was given, leaf for leaf, and raises its skip flag — what a
        search loop of zero iterations returned before the gate."""
        from kai_scheduler_tpu.ops.allocate import allocate_jit
        from kai_scheduler_tpu.ops.victims import run_victim_action_jit
        ses = _gate_session(scenario, seed=7)
        fs = ses.state.queues.fair_share
        before = allocate_jit(ses.state, fs, num_levels=2,
                              config=ses.config.allocate)
        pending = np.asarray(ses.state.gangs.valid)
        placed = np.asarray(before.allocated)[pending]
        if scenario == "all-placed-by-allocate":
            assert placed.size and placed.all()
        elif scenario == "no-victims":
            assert not placed.all(), "somebody must still be waiting"
        after = run_victim_action_jit(
            ses.state, fs, before, num_levels=2, mode=mode,
            config=ses.config.victims)
        assert_same_result(after, before)
        want = np.zeros(3, np.int32)
        want[SLOT[mode]] = 1
        np.testing.assert_array_equal(
            np.asarray(after.victim_skipped), want)

    def test_open_gate_leaves_its_flag_down(self):
        state, index = two_queue_cluster()
        res, _ = run_reclaim(state)
        assert bool(res.allocated[index.gang_names.index("pending-gang")])
        np.testing.assert_array_equal(
            np.asarray(res.victim_skipped), [0, 0, 0])

    def test_cycle_with_reclaim_open_and_preempt_closed(self, monkeypatch):
        """(c) one fused five-action cycle on a saturated, partitioned
        cluster (reclaim serves the under-quota leaves; nothing of lower
        priority sits in them for preempt): the gated program commits
        exactly what the composition of the ungated action bodies does,
        and the served counters say which gates stayed closed."""
        import jax
        from kai_scheduler_tpu.framework import Scheduler, SchedulerConfig
        from kai_scheduler_tpu.framework import metrics
        from kai_scheduler_tpu.framework import scheduler as sched_mod
        from kai_scheduler_tpu.runtime.cluster import Cluster
        from kai_scheduler_tpu.state import make_cluster

        nodes, queues, groups, pods, topo = make_cluster(
            num_nodes=32, node_accel=4.0, num_gangs=20, tasks_per_gang=8,
            running_fraction=0.8, queue_accel_quota=3.2,
            partition_queues_by_running=True, seed=0)
        cluster = Cluster.from_objects(nodes, queues, groups, pods,
                                       topology=topo)
        # what the cycle hands its one program
        calls = []
        fused = sched_mod._fused_pipeline
        monkeypatch.setattr(
            sched_mod, "_fused_pipeline",
            lambda *a, **kw: calls.append((a, kw)) or fused(*a, **kw))
        sched = Scheduler(SchedulerConfig())
        result = sched.run_once(cluster)
        (state, fair_share), kw = calls[0]
        assert kw["actions"] == ("allocate", "consolidation", "reclaim",
                                 "preempt", "stalegangeviction")
        assert result.victim_actions_skipped["reclaim"] == 0
        assert result.victim_actions_skipped["preempt"] == 1
        assert metrics.victim_action_skipped.value("reclaim") == 0.0
        assert metrics.victim_action_skipped.value("preempt") == 1.0
        assert result.evictions, "reclaim must have worked"
        assert np.asarray(result.tensors.victim).sum() == len(
            result.evictions)

        # the same pipeline with every victim action's body called
        # directly, no gate: what the program was before the gate
        nl = kw["num_levels"]

        def ungated(mode):
            action = ungated_action(mode, nl, kw["vcfg"])
            return lambda st, fs, res, *_: action(st, fs, res)

        plain = dict(sched_mod._PURE_ACTIONS,
                     consolidation=ungated("consolidate"),
                     reclaim=ungated("reclaim"), preempt=ungated("preempt"))

        @jax.jit
        def reference(st, fs):
            res = init_result(st)
            for name in kw["actions"]:
                res = plain[name](st, fs, res, nl, kw["acfg"], kw["vcfg"],
                                  kw["grace_s"])
            return res

        assert_same_result(result.tensors, reference(state, fair_share))

    @pytest.mark.parametrize("mode,chunked", [
        ("reclaim", True), ("reclaim", False), ("preempt", True),
        ("preempt", False), ("consolidate", False)])
    def test_nothing_large_is_built_before_the_gate(self, mode, chunked):
        """(d) structure: in the action's jaxpr no equation outside the
        one ``cond`` has an output of [M, Q] elements or more — the
        frozen orders, rankings and per-queue tables all lie under it.
        An edit that hoists a table back out fails here, not in a
        benchmark."""
        import dataclasses
        import functools
        import jax
        from kai_scheduler_tpu.framework.session import Session
        from kai_scheduler_tpu.ops.victims import run_victim_action
        from kai_scheduler_tpu.state import make_cluster

        ses = Session.open(*make_cluster(
            num_nodes=48, node_accel=2.0, num_gangs=64, tasks_per_gang=2,
            running_fraction=48 / 64, num_departments=2,
            queues_per_department=8, pending_priority_boost=100, seed=0))
        cfg = dataclasses.replace(
            ses.config.victims, chunk_reclaim=chunked,
            batch_size=ses.config.victims.batch_size if chunked else 1,
            batch_size_preempt=None,
            # the composed path over the full unit segments
            optimistic_preempt=False)
        M, Q = ses.state.running.m, ses.state.queues.q
        jaxpr = jax.make_jaxpr(functools.partial(
            run_victim_action, num_levels=2, mode=mode, config=cfg))(
            ses.state, ses.state.queues.fair_share,
            init_result(ses.state)).jaxpr

        def largest(jpr, into_cond):
            worst = 0
            for eqn in jpr.eqns:
                worst = max([worst] + [int(np.prod(v.aval.shape))
                                       for v in eqn.outvars])
                if eqn.primitive.name == "cond" and not into_cond:
                    continue
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    worst = max(worst, largest(sub, True))
            return worst

        gates = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
        assert len(gates) == 1
        assert largest(jaxpr, into_cond=False) < M * Q, (M, Q)
        if chunked:
            # and the bound means something: the tables are in there
            assert largest(jaxpr, into_cond=True) >= M * Q
