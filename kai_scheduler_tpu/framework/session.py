"""Session — the per-cycle unit of work.

Reference: ``framework/framework.go:33-79`` OpenSession builds a snapshot
and lets every plugin register callbacks on it; actions then drive the
cycle through those callbacks and a Statement transaction log, and
CloseSession flushes status.  Here the Session is a *value*: the
tensorized snapshot plus the solver outputs, and "commit" is a pure
translation from placement tensors back to BindRequest/Eviction objects
via the SnapshotIndex (the reverse of ``build_snapshot``).

The Statement's checkpoint/rollback machinery lives *inside* the
compiled kernels (functional state selection, see ``ops/allocate.py``);
by the time tensors reach the Session they are already committed in the
transactional sense — this mirrors how the reference only materializes
BindRequests at ``Statement.Commit`` (``framework/statement.go``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..apis import types as apis
from ..ops import analytics as pulse
from ..ops import drf
from ..runtime import compile_watch
from ..runtime import events as gang_events
from ..ops.allocate import (TOPOLOGY_STATS, AllocateConfig,
                            AllocationResult,
                            lane_width as allocate_lane_width)
from ..ops.victims import VICTIM_ACTIONS, VictimConfig
from ..state.cluster_state import (ClusterState, SnapshotIndex,
                                   _pow2_ceil, build_snapshot)

#: ``set_fair_share`` must run compiled: eagerly, the vmapped waterfill
#: while_loop re-traces (and recompiles) every cycle — measured ~2.5 s per
#: Session.open at 10k nodes vs ~ms jitted.  ``k_value`` rides as a traced
#: array so sweeping it never recompiles.
_set_fair_share_jit = compile_watch.watch(
    "set_fair_share",
    functools.partial(
        jax.jit, static_argnames=("num_levels",))(drf.set_fair_share))

#: The commit-path host bundle.  Two principles keep it small — every
#: device→host transfer is a sync, and its cost grows with the bytes:
#: 1. snapshot-side arrays (task portions/requests, running-pod gangs,
#:    usage) came FROM the host at build time — the SnapshotIndex keeps
#:    the numpy originals, so only RESULT tensors transfer back;
#: 2. results pack into ONE i16 array (indices are < 32k; bools ride 8
#:    per lane; the small f32 queue tables bitcast to i16 pairs).


def _bitpack(b: jax.Array) -> jax.Array:
    """bool [K] → i16 [ceil(K/8)], bit k = element 8i+k (zero-padded —
    snapshot padding is caller-settable, so K need not divide 8)."""
    pad = (-b.shape[0]) % 8
    if pad:
        b = jnp.pad(b, (0, pad))
    pb = b.reshape(-1, 8).astype(jnp.int16)
    return jnp.sum(pb * (2 ** jnp.arange(8, dtype=jnp.int16)), axis=-1
                   ).astype(jnp.int16)


def _bitunpack(p: "np.ndarray", k: int) -> "np.ndarray":
    return (((p.astype(np.int32)[:, None] >> np.arange(8)) & 1)
            .astype(bool).reshape(-1)[:k])


@functools.partial(jax.jit, static_argnames=("track_devices",
                                              "track_analytics",
                                              "track_repack"))
def _pack_commit(result: AllocationResult, state: ClusterState,
                 *, track_devices: bool, track_analytics: bool = False,
                 analytics=None, track_repack: bool = False,
                 repack_plan=None) -> jax.Array:
    q = state.queues
    parts = [
        (result.placements + 1).ravel().astype(jnp.int16),
        _bitpack(result.pipelined.ravel()),
        _bitpack(result.allocated),
        _bitpack(result.attempted),
        result.fit_reason.astype(jnp.int16),
        _bitpack(result.victim),
        (result.victim_move + 1).astype(jnp.int16),
        jax.lax.bitcast_convert_type(
            result.queue_allocated, jnp.int16).ravel(),
        jax.lax.bitcast_convert_type(q.fair_share, jnp.int16).ravel(),
        jax.lax.bitcast_convert_type(
            result.wavefront_stats, jnp.int16).ravel(),
        jax.lax.bitcast_convert_type(
            result.victim_skipped, jnp.int16).ravel(),
        jax.lax.bitcast_convert_type(
            result.topology_stats, jnp.int16).ravel(),
    ]
    if track_devices:
        parts.append(
            (result.placement_device + 1).ravel().astype(jnp.int16))
    if track_analytics:
        # kai-pulse: the cluster-health bundle rides the SAME packed
        # transfer (ops/analytics.py) — zero extra dispatches or bytes
        # beyond its own payload
        a32, ai = pulse.flatten(analytics)
        parts.append(
            jax.lax.bitcast_convert_type(a32, jnp.int16).ravel())
        parts.append(
            jax.lax.bitcast_convert_type(ai, jnp.int16).ravel())
    if track_repack:
        # kai-repack: a fired cycle's migration plan rides the packed
        # commit too (pod indices can exceed i16, so i32/f32 fields
        # bitcast to i16 pairs) — the plan never costs its own
        # device→host readback
        parts.append(jax.lax.bitcast_convert_type(
            repack_plan.move_pod, jnp.int16).ravel())
        parts.append(jax.lax.bitcast_convert_type(
            repack_plan.move_node, jnp.int16).ravel())
        ints = jnp.stack([
            repack_plan.num_moves, repack_plan.target_gang,
            repack_plan.target_rack,
            repack_plan.feasible.astype(jnp.int32)])
        parts.append(
            jax.lax.bitcast_convert_type(ints, jnp.int16).ravel())
        fls = jnp.stack([
            repack_plan.needed, repack_plan.rack_units_before,
            repack_plan.rack_units_after, repack_plan.total_units])
        parts.append(
            jax.lax.bitcast_convert_type(fls, jnp.int16).ravel())
    return jnp.concatenate(parts)


# kai-wire compile watcher: per-(entry, signature) cache-miss
# attribution (runtime/compile_watch.py)
_pack_commit = compile_watch.watch("pack_commit", _pack_commit)


def _pow4_ceil(x: int) -> int:
    b = 1
    while b < int(x):
        b <<= 2
    return b


def _preempt_lane_width(batch_size: int, num_pending: int,
                        num_leaf_queues: int, padded_nodes: int) -> int:
    """Victim-wavefront lane width for preempt (auto-tuning v2).

    The chunk wants one lane per live preemptor up to a memory bound:
    every lane carries [N, R]-sized freed/score tensors through the
    placement vmap, so width is capped where B·N crosses ~4M elements
    (≈50 MB of f32 per per-lane tensor at R=3).  The final width is
    clamped to the snapshot's pending-gang count — junk lanes past the
    live preemptor spread pay full freed-pool cost for nothing.

    The width is a STATIC jit arg, so every distinct value compiles
    the victim kernels once: the spread buckets to powers of FOUR
    ({1, 4, 16, 64, 256} before the cap) so a cluster whose pending
    count wanders across cycles settles into a handful of compiled
    configs, at the price of ≤4x junk lanes at the narrow end where
    lanes are cheapest.  The memory cap itself halves in powers of TWO
    (512→256→128→64), so a node count crossing the B·N bound can add
    one off-bucket width (e.g. 128) to the compiled set."""
    cap = 512
    while cap > 64 and cap * max(padded_nodes, 1) > (1 << 22):
        cap //= 2
    if num_pending < 0:
        # hint unavailable (hand-built index): leaf-queue heuristic
        spread = num_leaf_queues if num_leaf_queues > 64 else batch_size
    else:
        spread = max(num_pending, 1)
    return max(1, min(cap, _pow4_ceil(spread)))


def _sparse_unit_width(padded_pods: int, num_leaf_queues: int) -> int:
    """Compact victim-table width when ``VictimConfig.sparse_unit_k``
    is None (auto): a few multiples of the mean running-pod count per
    leaf queue, pow2-bucketed, floored at 256 so sparsely-populated
    snapshots never shrink below a useful table.  An explicitly-set
    ``sparse_unit_k`` bypasses this entirely."""
    per_leaf = padded_pods // max(num_leaf_queues, 1)
    return max(256, min(1024, _pow2_ceil(4 * max(per_leaf, 1))))


#: fit_reason code → message (ref ``api/unschedule_info.go`` fit errors).
#: Module-level: a class attribute dict is shared across instances and
#: every thread touching any of them (KAI104)
FIT_REASONS = {
    1: ("no node satisfies the pod requirements "
        "(resources / selector / taints / affinity)"),
    2: "an equivalent pod group already failed this cycle",
    3: "placement attempt failed (capacity or queue gates)",
}


@dataclasses.dataclass
class SessionConfig:
    """Cycle-level knobs (ref ``conf/scheduler_conf.go`` SchedulerConfiguration)."""

    allocate: AllocateConfig = dataclasses.field(default_factory=AllocateConfig)
    victims: VictimConfig = dataclasses.field(default_factory=VictimConfig)
    #: kai-pulse cluster-health kernel knobs (ops/analytics.py); the
    #: cadence itself is a Scheduler-level knob (analytics_every)
    analytics: pulse.AnalyticsConfig = dataclasses.field(
        default_factory=pulse.AnalyticsConfig)
    #: derive kernel fast-path flags (track_devices / uniform_tasks) from
    #: the snapshot shape at session open — a snapshot with no fractional
    #: requests skips the per-device bookkeeping, and one whose gangs are
    #: all identical replicas uses the whole-gang placement kernel
    auto_tune: bool = True
    #: queue-hierarchy depth for fair-share recursion / capacity walks
    num_levels: int = 2
    #: proportion plugin kValue (time-based fairshare coupling)
    k_value: float = 0.0
    default_bind_backoff_limit: int = 3
    #: stalegangeviction grace period (ref options.go:34, default 60s)
    stale_grace_s: float = 60.0


def _auto_tune(config: SessionConfig, index: SnapshotIndex,
               padded_nodes: int, padded_running: int) -> SessionConfig:
    """Derive the kernel fast-path flags + wavefront widths from the
    snapshot's index hints and padded shapes."""
    # a hierarchy deeper than the configured recursion would
    # leave leaf levels undivided — widen to the snapshot depth
    if index.max_queue_depth + 1 > config.num_levels:
        config = dataclasses.replace(
            config, num_levels=index.max_queue_depth + 1)
    devices = index.needs_device_table
    # the whole-gang kernel is exactly the sequential greedy
    # under BINPACK scoring only (a filling node's score rises,
    # so the greedy keeps hitting it — the capacity-count fill);
    # under spread the per-task loop re-ranks after every task,
    # so spread-configured shards keep the per-task kernel
    uniform = (index.uniform_gangs and not devices
               and config.allocate.placement.binpack_accel
               and config.allocate.placement.binpack_cpu)
    sub_topo = (index.has_subgroup_topology
                or index.has_required_topology)
    ext = index.has_extended_resources
    dense = index.dense_feasibility
    return dataclasses.replace(
        config,
        allocate=dataclasses.replace(
            config.allocate, track_devices=devices,
            uniform_tasks=uniform, subgroup_topology=sub_topo,
            extended=ext, dense_feasibility=dense,
            preferred_topology=index.has_preferred_topology,
            anti_groups=index.has_anti_groups,
            attract_groups=index.has_attract_groups),
        victims=dataclasses.replace(
            config.victims,
            chunk_reclaim=not index.has_reclaim_minruntime,
            # auto-tuning v2: lane width follows the snapshot's
            # live preemptor spread (clamped so junk lanes past
            # the pending-gang count stop paying freed-pool
            # cost) under a padded-node-count memory bound; the
            # compact victim-table width follows running-pod
            # density per leaf queue (see VictimConfig)
            batch_size_preempt=(
                _preempt_lane_width(
                    config.victims.batch_size,
                    index.num_pending_gangs,
                    index.num_leaf_queues, padded_nodes)
                if config.victims.batch_size_preempt is None
                else config.victims.batch_size_preempt),
            sparse_unit_k=(
                _sparse_unit_width(
                    padded_running, index.num_leaf_queues)
                if config.victims.sparse_unit_k is None
                else config.victims.sparse_unit_k),
            placement=dataclasses.replace(
                config.victims.placement, track_devices=devices,
                uniform_tasks=uniform, subgroup_topology=sub_topo,
                extended=ext, dense_feasibility=dense,
                preferred_topology=index.has_preferred_topology,
                anti_groups=index.has_anti_groups,
                attract_groups=index.has_attract_groups)))


@dataclasses.dataclass
class Session:
    """One cycle's snapshot + derived tensors."""

    state: ClusterState
    index: SnapshotIndex
    config: SessionConfig

    @classmethod
    def open(
        cls,
        nodes: list[apis.Node],
        queues: list[apis.Queue],
        pod_groups: list[apis.PodGroup],
        pods: list[apis.Pod],
        topology: apis.Topology | None = None,
        config: SessionConfig | None = None,
        **snapshot_kwargs,
    ) -> "Session":
        """OpenSession: snapshot + proportion plugin share division."""
        config = config or SessionConfig()
        state, index = build_snapshot(
            nodes, queues, pod_groups, pods, topology, **snapshot_kwargs)
        return cls.from_state(state, index, config)

    @classmethod
    def from_state(cls, state: ClusterState, index: SnapshotIndex,
                   config: SessionConfig | None = None) -> "Session":
        """Open a session over an already-built snapshot — the entry the
        incremental snapshotter uses (``state/incremental.py``): auto-tune
        the kernel config from the index hints, then run the proportion
        plugin's share division exactly as :meth:`open` would."""
        config = config or SessionConfig()
        if config.auto_tune:
            config = _auto_tune(config, index, state.nodes.n,
                                state.running.m)
        fair_share = _set_fair_share_jit(
            state, num_levels=config.num_levels,
            k_value=jnp.float32(config.k_value))
        state = state.replace(queues=state.queues.replace(fair_share=fair_share))
        return cls(state=state, index=index, config=config)

    def kernels(self) -> dict:
        """Which placement kernels this cycle compiles and runs, as
        ``_auto_tune`` chose them from the snapshot, with the shapes
        they unroll over (``/healthz`` ``last_cycle.kernels``)."""
        acfg, g = self.config.allocate, self.state.gangs
        return {"uniform_tasks": acfg.uniform_tasks,
                "track_devices": acfg.track_devices,
                "dense_feasibility": acfg.dense_feasibility,
                "subgroup_topology": acfg.subgroup_topology,
                "preferred_topology": acfg.preferred_topology,
                "topology_levels": len(self.index.topology_levels),
                "topology_domains": self.index.topology_domains,
                "allocate_lanes": allocate_lane_width(acfg, g.g),
                "tasks": g.t,
                "subgroups": g.s,
                "pending_gangs": self.index.num_pending_gangs}

    def _gangs_queue_host(self) -> "np.ndarray":
        """The gang→queue column as host numpy."""
        return np.asarray(self.state.gangs.queue)

    # -- commit path ------------------------------------------------------

    def gather_host(self, result: AllocationResult,
                    analytics=None, *, repack_plan=None) -> dict:
        """ONE compact device→host transfer of the cycle's results,
        merged with the snapshot-side numpy tables the host never let go
        of (see ``_pack_commit``).  ``analytics`` (an
        ``ops.analytics.AnalyticsBundle``, optional) rides the same
        packed array — the kai-pulse bundle never costs a second
        transfer — and so does a fired cycle's kai-repack plan
        (``repack_plan``), decoded into ``host["repack_plan"]``.
        """
        g, q, r = self.state.gangs, self.state.queues, self.state.running
        G, T, M, Q = g.g, g.t, r.m, q.q
        R_ = self.state.nodes.free.shape[1]
        if self.state.nodes.n + 1 >= 2**15:
            # survives `python -O`: silently wrapped i16 node indices
            # would bind pods to the wrong nodes
            raise ValueError("i16 commit packing needs < 32k nodes")
        devices = self.index.needs_device_table
        has_analytics = analytics is not None
        has_plan = repack_plan is not None
        flat = np.asarray(_pack_commit(
            result, self.state, track_devices=devices,
            track_analytics=has_analytics, analytics=analytics,
            track_repack=has_plan, repack_plan=repack_plan))

        def take(n):
            nonlocal off
            part = flat[off:off + n]
            off += n
            return part

        def bits(k):
            return (k + 7) // 8

        off = 0
        out = dict(self.index.host_tables)
        out["placements"] = (take(G * T).astype(np.int32) - 1
                             ).reshape(G, T)
        out["pipelined"] = _bitunpack(take(bits(G * T)),
                                      G * T).reshape(G, T)
        out["allocated"] = _bitunpack(take(bits(G)), G)
        out["attempted"] = _bitunpack(take(bits(G)), G)
        out["fit_reason"] = take(G).astype(np.int32)
        out["victim"] = _bitunpack(take(bits(M)), M)
        out["victim_move"] = take(M).astype(np.int32) - 1
        out["queue_allocated"] = np.frombuffer(
            take(Q * R_ * 2).tobytes(), np.float32).reshape(Q, R_)
        out["fair_share"] = np.frombuffer(
            take(Q * R_ * 2).tobytes(), np.float32).reshape(Q, R_)
        out["wavefront_stats"] = np.frombuffer(
            take(2 * 5 * 2).tobytes(), np.int32).reshape(2, 5)
        out["victim_skipped"] = np.frombuffer(
            take(len(VICTIM_ACTIONS) * 2).tobytes(), np.int32)
        out["topology_stats"] = np.frombuffer(
            take(len(TOPOLOGY_STATS) * 2).tobytes(), np.int32)
        if devices:
            out["placement_device"] = (take(G * T).astype(np.int32) - 1
                                       ).reshape(G, T)
        else:
            out["placement_device"] = np.full((G, T), -1, np.int32)
        if has_analytics:
            acfg = self.config.analytics
            nf = pulse.f32_len(acfg, q=Q, r=R_, g=G)
            ni = pulse.i32_len(acfg, q=Q, r=R_, g=G)
            a32 = np.frombuffer(take(nf * 2).tobytes(), np.float32)
            ai = np.frombuffer(take(ni * 2).tobytes(), np.int32)
            out["analytics"] = pulse.host_unpack(
                a32, ai, config=acfg, q=Q, r=R_, g=G)
        if has_plan:
            P = repack_plan.move_pod.shape[0]
            mp = np.frombuffer(take(2 * P).tobytes(), np.int32)
            mn = np.frombuffer(take(2 * P).tobytes(), np.int32)
            ints = np.frombuffer(take(8).tobytes(), np.int32)
            fls = np.frombuffer(take(8).tobytes(), np.float32)
            out["repack_plan"] = {
                "move_pod": mp, "move_node": mn,
                "num_moves": ints[0], "target_gang": ints[1],
                "target_rack": ints[2], "feasible": bool(ints[3]),
                "needed": fls[0], "rack_units_before": fls[1],
                "rack_units_after": fls[2], "total_units": fls[3]}
        return out

    def bind_requests_from(self, result: AllocationResult,
                           host: dict | None = None) -> list[apis.BindRequest]:
        """Placement tensors → BindRequest objects (``cache.Bind`` analogue).

        Only gangs with ``allocated=True`` produce requests — the kernels
        guarantee those rows are internally consistent (all-or-nothing).
        Pipelined placements (tasks waiting on releasing/victim resources)
        do NOT bind yet: the reference queues them in the Statement and
        binds on a later cycle once capacity actually frees
        (``stmt.Pipeline`` vs ``stmt.Allocate``).
        """
        if host is None:
            host = self.gather_host(result)
        placements = host["placements"]
        devices = host["placement_device"]
        allocated = host["allocated"]
        pipelined = host["pipelined"]
        # columnar translation: vectorized selection + per-column gathers,
        # then ONE tight zip constructing the objects — never per-row
        # numpy scalar indexing (that was ~0.5 s at 50k placements)
        sel = allocated[:, None] & (placements >= 0) & ~pipelined
        sel[len(self.index.gang_names):] = False
        gi, ti = np.nonzero(sel)
        names = self.index.task_names_arr[gi, ti]
        keep = names != None  # noqa: E711  (object-array elementwise)
        if not keep.all():
            gi, ti, names = gi[keep], ti[keep], names[keep]
        node_names = self.index.node_names_arr[placements[gi, ti]]
        portion = host["task_portion"][gi, ti]
        mem = host["task_accel_mem"][gi, ti]
        is_frac = (portion > 0) | (mem > 0)
        count = np.where(
            is_frac, 0,
            np.rint(host["task_req0"][gi, ti]).astype(np.int64))
        dev = devices[gi, ti]
        dra = host["task_dra"][gi, ti]
        # DRA claim allocations: pods with real ResourceClaims record the
        # claim NAMES (the binder allocates concrete devices onto the
        # claim objects); bare dra_accel_count pods keep legacy integer
        # placeholders (ref ResourceClaimAllocations)
        claims = self.index.claims_by_pod
        frac_t = apis.ReceivedResourceType.FRACTION
        reg_t = apis.ReceivedResourceType.REGULAR
        backoff = self.config.default_bind_backoff_limit
        return [
            apis.BindRequest(
                pod_name=nm,
                selected_node=nn,
                received_resource_type=frac_t if fr else reg_t,
                received_accel_portion=po,
                received_accel_memory_gib=me,
                received_accel_count=ct,
                selected_accel_groups=[dv] if dv >= 0 else [],
                resource_claim_allocations=(
                    claims.get(nm) or list(range(dr))),
                backoff_limit=backoff,
            )
            for nm, nn, fr, po, me, ct, dv, dr in zip(
                names.tolist(), node_names.tolist(), is_frac.tolist(),
                portion.tolist(), mem.tolist(), count.tolist(),
                dev.tolist(), dra.tolist())
        ]

    def evictions_from(self, victim_mask, victim_move=None,
                       host: dict | None = None) -> list[apis.Eviction]:
        """Victim tensor [M] → Eviction objects (``cache.Evict`` analogue).

        ``victim_move`` ([M] node index, -1 = none) attaches the
        consolidation move target so the commit path can emit the
        pipelined rebind for the relocated pod.
        """
        if host is not None:
            mask = host["victim"].copy()
            moves_all = host["victim_move"]
            gang_all = host["running_gang"]
        else:
            mask = np.asarray(victim_mask).copy()
            moves_all = (None if victim_move is None
                         else np.asarray(victim_move))
            gang_all = np.asarray(self.state.running.gang)
        mask[len(self.index.running_pod_names_arr):] = False
        mi = np.nonzero(mask)[0]
        names = self.index.running_pod_names_arr[mi]
        keep = names != ""
        if not keep.all():
            mi, names = mi[keep], names[keep]
        gangs = gang_all[mi]
        ok_g = (gangs >= 0) & (gangs < len(self.index.gang_names))
        if len(self.index.gang_names):
            groups = np.where(ok_g, self.index.gang_names_arr[
                np.clip(gangs, 0, len(self.index.gang_names) - 1)], "")
        else:
            groups = np.full(len(mi), "", object)
        if moves_all is None:
            targets = [None] * len(mi)
        else:
            moves = moves_all[mi]
            targets = [
                self.index.node_names[m] if m >= 0 else None
                for m in moves.tolist()]
        return [apis.Eviction(pod_name=nm, group=gr, move_to=mv)
                for nm, gr, mv in zip(names.tolist(), groups.tolist(),
                                      targets)]

    def unschedulable_explanations(
            self, result: AllocationResult,
            host: dict | None = None) -> dict[str, str]:
        """Per-gang fit-failure messages for gangs that ended the cycle
        unplaced — the UnschedulableExplanation surface."""
        if host is not None:
            reasons, allocated = host["fit_reason"], host["allocated"]
        else:
            reasons = np.asarray(result.fit_reason)
            allocated = np.asarray(result.allocated)
        out: dict[str, str] = {}
        # touch only failing gangs (O(failed), not O(G) int conversions)
        ng = len(self.index.gang_names)
        for gi in np.nonzero((reasons[:ng] != 0) & ~allocated[:ng])[0]:
            out[self.index.gang_names[gi]] = FIT_REASONS.get(
                int(reasons[gi]), f"code {int(reasons[gi])}")
        return out

    def analytics_doc(self, host: dict, *,
                      alarm_cycles: int = 0) -> dict:
        """The kai-pulse bundle as a JSON-able cluster-health document —
        the ``GET /debug/cluster`` payload and ``CycleResult.analytics``.
        Names come from the SnapshotIndex; array data from the bundle
        that rode this cycle's packed commit transfer (``host``)."""
        a = host.get("analytics")
        if a is None:
            return {}
        from ..apis.types import RESOURCE_NAMES
        acfg = self.config.analytics
        qnames = self.index.queue_names
        gnames = self.index.gang_names
        reasons = host["fit_reason"]
        queues_of = self._gangs_queue_host()
        drift = a["queue_drift"][:len(qnames)]
        top_q = np.argsort(-drift)[:5]
        oldest = []
        for age, gi in zip(a["starv_age"].tolist(),
                           a["starv_gang"].tolist()):
            if age <= 0 or not 0 <= gi < len(gnames):
                continue
            qi = int(queues_of[gi])
            code = int(reasons[gi])
            oldest.append({
                "gang": gnames[gi],
                "queue": qnames[qi] if 0 <= qi < len(qnames) else "",
                "age_cycles": int(age),
                "blocker": FIT_REASONS.get(code, f"code {code}")
                if code else "",
            })
        return {
            "fragmentation": {
                "score": round(float(a["frag_score"]), 4),
                "total_unit_pods": float(a["total_units"]),
                "largest_rack_unit_pods": float(a["max_rack_units"]),
                "unit_req": list(acfg.unit_req),
                "stranded_free_frac": {
                    RESOURCE_NAMES[r]: round(float(v), 4)
                    for r, v in enumerate(a["stranded_frac"].tolist())},
                "free_hist": {
                    RESOURCE_NAMES[r]: [int(c) for c in row]
                    for r, row in enumerate(a["free_hist"].tolist())},
                "gang_ladder": [
                    {"pods": int(p), "cluster_feasible": bool(c > 0),
                     "rack_placeable": bool(k > 0)}
                    for p, c, k in zip(acfg.gang_ladder,
                                       a["ladder_cluster_ok"].tolist(),
                                       a["ladder_rack_ok"].tolist())],
            },
            "utilization": {
                RESOURCE_NAMES[r]: round(float(v), 4)
                for r, v in enumerate(a["util"].tolist())},
            "goodput": round(float(a["goodput"]), 4),
            "fairness": {
                "drift_max": round(float(a["drift_max"]), 4),
                "drift_mean": round(float(a["drift_mean"]), 4),
                "drift_gini": round(float(a["drift_gini"]), 4),
                "top_drift": [
                    {"queue": qnames[int(qi)],
                     "drift": round(float(drift[int(qi)]), 4)}
                    for qi in top_q if drift[int(qi)] > 0],
            },
            "starvation": {
                "pending_gangs": int(a["pending_gangs"]),
                "alarm_cycles": int(alarm_cycles),
                "oldest": oldest,
            },
        }

    #: per-cycle caps on decision-event CONSTRUCTION (the commit path
    #: must not spend milliseconds building event objects; exact
    #: outcome COUNTS are always recorded regardless).  Failures keep
    #: the larger budget — they are the diagnostic payload — and
    #: ``allocated`` success events the smallest.
    MAX_FAILURE_EVENTS = 1024
    MAX_ALLOCATED_EVENTS = 512

    def decision_events(self, result: AllocationResult,
                        host: dict | None = None, evictions=None,
                        limit: int = 4096, repack_for: str = ""):
        """Per-gang outcome events for the cycle — the "why is my job
        not running" surface (``runtime/events.py``).  Returns
        ``(events, dropped, counts)``: a bounded list of
        :class:`~..runtime.events.GangDecision`, how many candidate
        events the bounds cut, and the EXACT per-outcome counts
        (computed vectorized, unaffected by truncation).

        Ordering is by diagnostic value: fit failures first (the answer
        an operator is actually looking for), then preemption victims,
        then allocations (bounded hardest — see
        ``MAX_ALLOCATED_EVENTS``).
        """
        if host is None:
            host = self.gather_host(result)
        names = self.index.gang_names
        ng = len(names)
        allocated = host["allocated"][:ng]
        reasons = host["fit_reason"][:ng]
        pipelined = host["pipelined"][:ng]
        queues_of = self._gangs_queue_host()[:ng]
        qnames = self.index.queue_names
        nq = len(qnames)

        def queue_name(gi: int) -> str:
            qi = int(queues_of[gi])
            return qnames[qi] if 0 <= qi < nq else ""

        out: list = []
        dropped = 0
        # beneficiaries of freed capacity: gangs whose placements
        # pipelined onto releasing/victim resources this cycle
        pipe_g = np.nonzero(pipelined.any(axis=1))[0]
        beneficiaries = ", ".join(names[int(g)] for g in pipe_g[:3])
        if len(pipe_g) > 3:
            beneficiaries += f", +{len(pipe_g) - 3} more"
        # exact outcome counts, vectorized — truncation below never
        # skews the /healthz summary
        failed = (reasons != 0) & ~allocated
        # victim GANGS split by eviction reason: kai-repack migrations
        # surface as `repacked-for`, everything else as `preempted-for`
        # — the commit path for both is the ONE pipelined-rebind
        # helper.  A gang can legitimately appear in BOTH sets in one
        # cycle (some pods migrated, others plainly preempted) and then
        # counts — and events below — report both outcomes.
        repack_groups = {ev.group for ev in evictions or ()
                         if ev.group and ev.reason == self.REPACK_REASON}
        plain_groups = {ev.group for ev in evictions or ()
                        if ev.group and ev.reason != self.REPACK_REASON}
        counts = {
            gang_events.OUTCOME_ALLOCATED: int(allocated.sum()),
            gang_events.OUTCOME_QUOTA_GATE: int(
                (failed & (reasons == 3)).sum()),
            gang_events.OUTCOME_FIT_FAILURE: int(
                (failed & (reasons != 3)).sum()),
            gang_events.OUTCOME_PREEMPTED_FOR: len(plain_groups),
            gang_events.OUTCOME_REPACKED_FOR: len(repack_groups),
        }
        counts = {k: v for k, v in counts.items() if v}
        # 1. fit failures (reason code -> outcome + FIT_REASONS detail).
        # Every section SLICES to its remaining room and counts the
        # overflow arithmetically — the loops never iterate past the
        # bound (this runs on the commit path of every cycle)
        fail_g = np.nonzero(failed)[0]
        take = fail_g[:min(limit, self.MAX_FAILURE_EVENTS)].tolist()
        dropped += len(fail_g) - len(take)
        for gi in take:
            code = int(reasons[gi])
            outcome = (gang_events.OUTCOME_QUOTA_GATE if code == 3
                       else gang_events.OUTCOME_FIT_FAILURE)
            out.append(gang_events.GangDecision(
                gang=names[gi], queue=queue_name(gi), outcome=outcome,
                detail=FIT_REASONS.get(code, f"code {code}")))
        # 2. preemption/reclaim/consolidation victims, one event per
        # victim GANG (bounded like everything else)
        if evictions:
            # first NON-repack eviction decides a group's plain "moved"
            # reading (the consolidation-move detail)
            moved: dict[str, bool] = {}
            entries: list[tuple[str, str]] = []
            seen: set[tuple[str, str]] = set()
            for ev in evictions:
                if not ev.group:
                    continue
                kind = ("repack" if ev.reason == self.REPACK_REASON
                        else "plain")
                if kind == "plain" and ev.group not in moved:
                    moved[ev.group] = ev.move_to is not None
                if (ev.group, kind) not in seen:
                    seen.add((ev.group, kind))
                    entries.append((ev.group, kind))
            room = max(0, limit - len(out))
            dropped += max(0, len(entries) - room)
            for group, kind in entries[:room]:
                if kind == "repack":
                    out.append(gang_events.GangDecision(
                        gang=group, queue="",
                        outcome=gang_events.OUTCOME_REPACKED_FOR,
                        detail=("repack move (pipelined rebind); "
                                f"frees a rack for: {repack_for}")))
                    continue
                detail = ("consolidation move (pipelined rebind)"
                          if moved.get(group)
                          else (f"freed capacity for: {beneficiaries}"
                                if beneficiaries else "over fair share"))
                out.append(gang_events.GangDecision(
                    gang=group, queue="",
                    outcome=gang_events.OUTCOME_PREEMPTED_FOR,
                    detail=detail))
        # 3. allocations (bounded hardest; the exact counts above keep
        # the summary honest about the rest)
        alloc_g = np.nonzero(allocated)[0]
        room = max(0, min(limit - len(out), self.MAX_ALLOCATED_EVENTS))
        take = alloc_g[:room].tolist()
        dropped += len(alloc_g) - len(take)
        pipe_set = set(pipe_g.tolist())
        for gi in take:
            out.append(gang_events.GangDecision(
                gang=names[gi], queue=queue_name(gi),
                outcome=gang_events.OUTCOME_ALLOCATED,
                detail=("pipelined onto releasing capacity"
                        if gi in pipe_set else "")))
        return out, dropped, counts

    def pipelined_rebind(self, cluster,
                         ev: apis.Eviction) -> apis.BindRequest | None:
        """THE pipelined-rebind path for a moved victim — consolidation
        moves and kai-repack migrations both commit through this one
        helper (the scheduler's commit loop calls it for every eviction
        carrying a ``move_to`` target), so the two can never drift in
        bind shape.  Returns None when the pod vanished between solve
        and commit."""
        pod = cluster.pods.get(ev.pod_name)
        if pod is None or ev.move_to is None:
            return None
        return self.move_bind_request(pod, ev.move_to)

    #: Eviction.reason marking a kai-repack migration (vs a plain
    #: consolidation move) — selects the ``repacked-for`` decision
    #: outcome; the bind/commit path is IDENTICAL for both
    REPACK_REASON = "repack"

    def repack_evictions(self, plan: dict, host: dict,
                         target_gang: str) -> list[apis.Eviction]:
        """A feasible repack plan (host copies of ``RepackPlan`` fields)
        → evictions with move targets, committed through the SAME
        pipelined-rebind path as consolidation moves.

        Cross-dispatch guards: pods the cycle's own victim actions
        already evicted are dropped (their capacity frees anyway), and
        a plan whose target gang placed this cycle is discarded whole
        (``[]``) — repack must never migrate for a gang that no longer
        needs it.
        """
        gi = int(plan["target_gang"])
        if (not bool(plan["feasible"]) or int(plan["num_moves"]) <= 0
                or not 0 <= gi < len(self.index.gang_names)
                or self.index.gang_names[gi] != target_gang):
            return []
        if host["allocated"][gi]:
            return []
        victim = host["victim"]
        out: list[apis.Eviction] = []
        names = self.index.running_pod_names_arr
        gang_all = host["running_gang"]
        ng = len(self.index.gang_names)
        for pi, ni in zip(plan["move_pod"].tolist(),
                          plan["move_node"].tolist()):
            if pi < 0 or ni < 0 or pi >= len(names) or victim[pi]:
                continue
            name = names[pi]
            if not name:
                continue
            gidx = int(gang_all[pi])
            out.append(apis.Eviction(
                pod_name=name,
                group=(self.index.gang_names[gidx]
                       if 0 <= gidx < ng else ""),
                reason=self.REPACK_REASON,
                move_to=self.index.node_names[ni]))
        return out

    def move_bind_request(self, pod: apis.Pod,
                          target_node: str) -> apis.BindRequest:
        """The pipelined rebind for a consolidation-moved victim: binds
        once the old pod has vacated and its replacement is pending —
        the persistent equivalent of the reference's pipelined victim
        re-allocation inside the committed Statement."""
        is_frac = pod.accel_portion > 0 or pod.accel_memory_gib > 0
        return apis.BindRequest(
            pod_name=pod.name,
            selected_node=target_node,
            received_resource_type=(
                apis.ReceivedResourceType.FRACTION if is_frac
                else apis.ReceivedResourceType.REGULAR),
            received_accel_portion=pod.accel_portion,
            received_accel_memory_gib=pod.accel_memory_gib,
            received_accel_count=(
                0 if is_frac else int(round(pod.resources.accel))),
            backoff_limit=self.config.default_bind_backoff_limit,
        )
