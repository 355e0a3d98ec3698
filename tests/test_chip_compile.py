"""The main path's device programs compile for a described v5e chip.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached (on-chip-measurement guide, section 2).
Before PR 23 it aborted the process — a fatal check in its scatter
emitter, not a Python exception — on every victim action of the default
cycle, at any size; these compiles guard that at the saturated 64-node
shape where the abort reproduced, the fifth on the non-dense placement
path (selectors, a filter class, feasible-rank tie-break) that a cluster
of tainted pools takes and the CPU alone had run before PR 28, the sixth
on the per-task placement kernel that declared subgroups and gangs of
unequal pods take (Kubeflow's jobs; PR 32), the seventh on the
whole-gang kernel under a topology tree (required and preferred levels,
the hoisted domain tables; PR 34).  Nothing runs: a compile that passes
says what the compiler accepts, never what the chip does.

Only one process at a time may load the TPU library, and it keeps it
until it exits: the topology is described inside a module-scoped
fixture (never at import, never in ``conftest.py``), the compiles run in
the test's own process, and every such test lives in THIS file so that
xdist hands them all to one worker.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # conftest compiles the suite at -O0; that would skip the very
    # passes under test.  And a described-chip executable is written to
    # the persistent cache but cannot be read back without a chip.
    saved = (jax.config.read("jax_disable_most_optimizations"),
             jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_disable_most_optimizations", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_disable_most_optimizations", saved[0])
    jax.config.update("jax_enable_compilation_cache", saved[1])
    compilation_cache.reset_cache()


def _saturated_objects():
    from kai_scheduler_tpu.state import make_cluster
    return make_cluster(
        num_nodes=64, node_accel=4.0, num_gangs=40, tasks_per_gang=8,
        running_fraction=0.8, queue_accel_quota=6.4,
        partition_queues_by_running=True, seed=0)


@pytest.fixture(scope="module")
def saturated():
    """64 nodes x 4 accelerators filled exactly by 32 running gangs of
    8; 8 pending gangs sit in under-served queues, so every victim
    action has work.  The session's auto-tuned config is the one
    production would compile."""
    from kai_scheduler_tpu.framework.session import Session
    return Session.open(*_saturated_objects())


@pytest.fixture(scope="module")
def saturated_pools():
    """``saturated`` as accelerator pools are deployed: every node
    tainted and of one of two GPU types, every pod tolerating, the
    pending gangs selecting one type, the other, or none."""
    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.framework.session import Session
    nodes, queues, groups, pods, topology = _saturated_objects()
    for i, node in enumerate(nodes):
        node.labels["gpu.type"] = "volta" if i % 4 else "pascal"
        node.taints = [apis.Taint("nvidia.com/gpu", "present")]
    selects = {g.name: ("volta", "pascal", None)[i % 3]
               for i, g in enumerate(groups)}
    for pod in pods:
        pod.tolerations = [apis.Toleration(
            "nvidia.com/gpu", "Exists", effect="NoSchedule")]
        if pod.node is None and selects[pod.group]:
            pod.node_selector = {"gpu.type": selects[pod.group]}
    return Session.open(nodes, queues, groups, pods, topology)


@pytest.fixture(scope="module")
def saturated_kubeflow():
    """``saturated`` with its pending gangs as Kubeflow's operators make
    them: a subgroup per replica type, the leader labelled with its
    job-role, and in every second one an MPI launcher that asks for no
    accelerator, so that the gang's pods differ."""
    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.framework.session import Session
    nodes, queues, groups, pods, topology = _saturated_objects()
    by_group: dict = {}
    for pod in pods:
        if pod.node is None:
            by_group.setdefault(pod.group, []).append(pod)
    for i, group in enumerate(g for g in groups if g.name in by_group):
        leader = "launcher" if i % 2 else "master"
        mine = by_group[group.name]
        group.sub_groups = [apis.SubGroup(leader, 1),
                            apis.SubGroup("worker", len(mine) - 1)]
        for t, pod in enumerate(mine):
            role = "worker" if t else leader
            pod.subgroup = role
            pod.labels["training.kubeflow.org/job-role"] = role
        if leader == "launcher":
            mine[0].resources = apis.ResourceVec(0.0, 1.0, 2.0)
    return Session.open(nodes, queues, groups, pods, topology)


@pytest.fixture(scope="module")
def saturated_tree():
    """``saturated`` under a block / rack / host tree (2 blocks x 4
    racks x 8 nodes): the running gangs ask for the rack; the pending
    ones have 8 pods and ask for the rack, or lose half their pods and
    ask for the block and prefer the rack.  Gangs of unequal size, each
    of equal pods, none with subgroups: the whole-gang kernel with the
    domain lock and the preferred band."""
    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.framework.session import Session
    from kai_scheduler_tpu.state import make_cluster
    nodes, queues, groups, pods, topology = make_cluster(
        num_nodes=64, node_accel=4.0, num_gangs=40, tasks_per_gang=8,
        running_fraction=0.8, queue_accel_quota=6.4,
        partition_queues_by_running=True, topology_levels=(2, 4),
        required_level="topo/level1", seed=0)
    pending = [g for g in groups if g.last_start_timestamp is None]
    for group in pending[::2]:
        group.min_member = 4
        group.topology_constraint = apis.TopologyConstraint(
            topology="default", required_level="topo/level0",
            preferred_level="topo/level1")
    small = {g.name for g in pending[::2]}
    pods = [p for p in pods if not (
        p.group in small and int(p.name.rsplit("-", 1)[1]) >= 4)]
    return Session.open(nodes, queues, groups, pods, topology)


def _shapes(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` — one
    sharding for every leaf, or a matching pytree of them."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _pipeline_kwargs(cfg):
    from kai_scheduler_tpu.framework.scheduler import SchedulerConfig
    return dict(actions=SchedulerConfig().actions,
                num_levels=cfg.num_levels, acfg=cfg.allocate,
                vcfg=cfg.victims, grace_s=cfg.stale_grace_s)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes + m.generated_code_size_in_bytes)
    assert 0 < total < 16e9


def test_fused_five_actions_compile(topo, saturated):
    """The classic entry: ``_fused_pipeline`` as ``run_once`` calls it."""
    from kai_scheduler_tpu.framework import scheduler as S
    one = SingleDeviceSharding(topo.devices[0])
    st = _shapes(saturated.state, one)
    compiled = S._fused_pipeline.__kai_jit__.lower(
        st, st.queues.fair_share,
        **_pipeline_kwargs(saturated.config)).compile()
    _fits_one_chip(compiled)


def test_reclaim_temporaries_do_not_grow_with_queues_times_units(topo):
    """Chunked reclaim's per-queue unit tables are segments of the unit
    axis (``ops/unit_segments.py``), not a column per queue: the
    described-v5e executable's temporaries at 64 and at 256 queues over
    the same 8 192 running pods differ by what ``[B, Q, R]`` lanes and
    ``[Q, Q]`` leveled-queue tables add (+2.7 MB when written; a
    different width of chunk moved it by 4 MB either way, which is the
    buffer assignment's own) — nowhere near one ``[U, dQ, R]`` f32
    table (18.9 MB), of which the dense form held five (+92.4 MB on the
    parent of PR 37).  A tenth of one table is what the issue asked
    for; at the 32 768 pods where that bound clears the noise (+2.5 MB
    of 75.5) one compile takes four minutes, so this one holds half."""
    import dataclasses
    import functools
    from kai_scheduler_tpu.framework.session import Session
    from kai_scheduler_tpu.ops.allocate import init_result
    from kai_scheduler_tpu.ops.victims import run_victim_action
    from kai_scheduler_tpu.state import make_cluster
    one = SingleDeviceSharding(topo.devices[0])
    temps, shape = [], []
    for per_department in (30, 126):
        ses = Session.open(*make_cluster(
            num_nodes=1024, node_accel=8.0, num_gangs=2048 + 32,
            tasks_per_gang=4, running_fraction=2048 / 2080,
            num_departments=2, queues_per_department=per_department,
            queue_accel_quota=1.0, partition_queues_by_running=True,
            seed=0))
        cfg = dataclasses.replace(ses.config.victims, chunk_reclaim=True)
        compiled = jax.jit(functools.partial(
            run_victim_action, num_levels=ses.config.num_levels,
            mode="reclaim", config=cfg)).lower(
            _shapes(ses.state, one), _shapes(ses.state.queues.fair_share,
                                             one),
            _shapes(init_result(ses.state), one)).compile()
        _fits_one_chip(compiled)
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
        shape.append((ses.state.running.m, ses.state.queues.q,
                      ses.state.nodes.free.shape[1]))
    (u, q0, r), (u1, q1, _) = shape
    assert u == u1 >= 8192 and q1 - q0 >= 192, shape
    assert temps[1] - temps[0] < u * (q1 - q0) * r * 4 / 2, (temps, shape)


def test_fused_five_actions_compile_non_dense(topo, saturated_pools):
    """The same entry over tainted pools: two selector values, the
    toleration's filter class, ``dense_feasibility`` false in allocate
    and in the victim actions' placements."""
    from kai_scheduler_tpu.framework import scheduler as S
    ses = saturated_pools
    assert ses.index.selector_keys == ["gpu.type"]
    assert ses.state.nodes.filter_masks.shape[0] == 2
    assert not ses.config.allocate.dense_feasibility
    assert not ses.config.victims.placement.dense_feasibility
    assert ses.config.allocate.uniform_tasks
    one = SingleDeviceSharding(topo.devices[0])
    st = _shapes(ses.state, one)
    compiled = S._fused_pipeline.__kai_jit__.lower(
        st, st.queues.fair_share, **_pipeline_kwargs(ses.config)).compile()
    _fits_one_chip(compiled)


def test_fused_five_actions_compile_per_task(topo, saturated_kubeflow):
    """The same entry over Kubeflow jobs: declared subgroups and a
    launcher without accelerator, so allocate and every victim
    placement compile the per-task kernel (and preempt its dense
    composed path), which no other compile here reaches."""
    from kai_scheduler_tpu.framework import scheduler as S
    ses = saturated_kubeflow
    assert not ses.index.uniform_gangs
    assert not ses.config.allocate.uniform_tasks
    assert not ses.config.victims.placement.uniform_tasks
    assert ses.state.gangs.s >= 3
    one = SingleDeviceSharding(topo.devices[0])
    st = _shapes(ses.state, one)
    compiled = S._fused_pipeline.__kai_jit__.lower(
        st, st.queues.fair_share, **_pipeline_kwargs(ses.config)).compile()
    _fits_one_chip(compiled)


def test_fused_five_actions_compile_topology(topo, saturated_tree):
    """The same entry over a three-level tree: rack-required gangs of 8
    and block-required, rack-preferred gangs of 4, so allocate and every
    victim placement compile the whole-gang kernel with the hoisted
    domain tables (``topology_tables``), the lane-spread domain pick
    (``domain_pick``) and the preferred band — the CPU alone had run
    them before PR 34."""
    from kai_scheduler_tpu.framework import scheduler as S
    ses = saturated_tree
    assert ses.index.uniform_gangs and ses.state.nodes.topology.shape[1] == 3
    for cfg in (ses.config.allocate, ses.config.victims.placement):
        assert cfg.uniform_tasks and not cfg.dense_feasibility
        assert cfg.subgroup_topology and cfg.preferred_topology
    sizes = set(ses.state.gangs.min_needed[
        ses.state.gangs.valid].tolist())
    assert sizes == {4, 8}
    assert ses.kernels()["topology_domains"] == 2 + 8 + 64
    one = SingleDeviceSharding(topo.devices[0])
    st = _shapes(ses.state, one)
    compiled = S._fused_pipeline.__kai_jit__.lower(
        st, st.queues.fair_share, **_pipeline_kwargs(ses.config)).compile()
    _fits_one_chip(compiled)


def test_headline_allocate_compiles(topo):
    """``bench.py``'s headline step — fair share + allocate on an empty
    cluster under the session's tuned config."""
    from kai_scheduler_tpu.framework.session import Session
    from kai_scheduler_tpu.ops import drf
    from kai_scheduler_tpu.ops.allocate import allocate
    from kai_scheduler_tpu.state import make_cluster
    ses = Session.open(*make_cluster(
        num_nodes=64, node_accel=8.0, num_gangs=40, tasks_per_gang=8))
    cfg = ses.config

    def cycle(state):
        fair_share = drf.set_fair_share(state, num_levels=cfg.num_levels)
        state = state.replace(
            queues=state.queues.replace(fair_share=fair_share))
        res = allocate(state, fair_share, num_levels=cfg.num_levels,
                       config=cfg.allocate)
        return res.placements, res.allocated, res.free

    one = SingleDeviceSharding(topo.devices[0])
    _fits_one_chip(jax.jit(cycle).lower(_shapes(ses.state, one)).compile())


def test_fused_five_actions_compile_node_sharded(topo, saturated):
    """The one multi-device program (``__graft_entry__.sharded_cycle``,
    what ``chip_smoke.py --chips 4`` runs): the full cycle with the node
    axis sharded over the four described chips."""
    import __graft_entry__ as ge
    from kai_scheduler_tpu.parallel import make_mesh, state_shardings
    mesh = make_mesh(list(topo.devices))
    shardings = state_shardings(saturated.state, mesh)
    compiled = jax.jit(
        ge._full_cycle_fn(saturated.config),
        in_shardings=(shardings,)).lower(
            _shapes(saturated.state, shardings)).compile()
    _fits_one_chip(compiled)
