"""Cycle driver — ``scheduler.go`` ``Scheduler.Run``/``runOnce`` rebuilt.

The reference loop (``pkg/scheduler/scheduler.go:109-170``): every
``schedulePeriod`` open a session (snapshot + plugin init), execute the
configured action pipeline (default ``allocate, consolidation, reclaim,
preempt, stalegangeviction``), close the session (flush status).  The
TPU rebuild keeps that exact shape; the configured actions run as ONE
compiled program over the snapshot (``_fused_pipeline``), each merging
into the cycle's commit set.

Actions are selected and ordered by name (ref ``actions/factory.go:31-37``)
the same way ``SchedulerConfiguration.Actions`` does.
"""
from __future__ import annotations

import dataclasses
import time
import weakref

import functools

import jax
import numpy as np

from ..apis import types as apis
from ..ops.allocate import (TOPOLOGY_STATS, AllocationResult, allocate,
                            init_result)
from ..ops.analytics import cluster_analytics_jit
from ..ops.repack import RepackConfig, plan_repack_jit
from ..ops.stale import stale_gang_eviction
from ..ops.victims import VICTIM_ACTIONS, run_victim_action
from ..runtime import compile_watch
from ..runtime import wire_ledger as _wire
from ..runtime.cluster import Cluster
from ..runtime import events as gang_events
from ..runtime.events import DecisionLog
from ..runtime.tracing import CycleTracer
from .session import FIT_REASONS, Session, SessionConfig

stale_eviction_jit = compile_watch.watch(
    "stale_gang_eviction",
    functools.partial(jax.jit, static_argnames=(
        "grace_s", "num_levels"))(stale_gang_eviction))

#: pure (unjitted) action bodies — composed into ONE jitted program per
#: cycle.  Separate per-action jit calls would cost a dispatch each and
#: hide cross-action fusion from XLA.
_PURE_ACTIONS = {
    "allocate": lambda st, fs, res, nl, acfg, vcfg, grace: allocate(
        st, fs, num_levels=nl, config=acfg, init=res),
    "consolidation": lambda st, fs, res, nl, acfg, vcfg, grace:
        run_victim_action(st, fs, res, num_levels=nl, mode="consolidate",
                          config=vcfg),
    "reclaim": lambda st, fs, res, nl, acfg, vcfg, grace:
        run_victim_action(st, fs, res, num_levels=nl, mode="reclaim",
                          config=vcfg),
    "preempt": lambda st, fs, res, nl, acfg, vcfg, grace:
        run_victim_action(st, fs, res, num_levels=nl, mode="preempt",
                          config=vcfg),
    "stalegangeviction": lambda st, fs, res, nl, acfg, vcfg, grace:
        stale_gang_eviction(st, res, grace_s=grace, num_levels=nl),
}


def run_actions(state, fair_share, *, actions, num_levels, acfg, vcfg,
                grace_s):
    """Pure composition of the action pipeline over a fresh commit set —
    shared by the jitted production pipeline below and by harnesses
    (e.g. the multichip dryrun) that must compile EXACTLY what
    production compiles."""
    res = init_result(state)
    for name in actions:
        # a scope per action: every device operation's op_name in the
        # profiler's trace says which action it belongs to
        with jax.named_scope(name):
            res = _PURE_ACTIONS[name](state, fair_share, res, num_levels,
                                      acfg, vcfg, grace_s)
    return res


@functools.partial(jax.jit, static_argnames=(
    "actions", "num_levels", "acfg", "vcfg", "grace_s"))
def _fused_pipeline(state, fair_share, *, actions, num_levels, acfg,
                    vcfg, grace_s):
    return run_actions(state, fair_share, actions=actions,
                       num_levels=num_levels, acfg=acfg, vcfg=vcfg,
                       grace_s=grace_s)


# kai-wire compile watcher: per-(entry, signature) cache-miss
# attribution (runtime/compile_watch.py)
_fused_pipeline = compile_watch.watch("fused_pipeline", _fused_pipeline)

@dataclasses.dataclass
class CycleResult:
    """Everything one ``runOnce`` decided (the Statement commit set)."""

    bind_requests: list[apis.BindRequest] = dataclasses.field(default_factory=list)
    evictions: list[apis.Eviction] = dataclasses.field(default_factory=list)
    #: pipelined rebinds for consolidation-moved victims
    move_bind_requests: list[apis.BindRequest] = dataclasses.field(
        default_factory=list)
    #: the on-device commit set threaded through the action pipeline
    tensors: AllocationResult | None = None
    #: action name -> wall seconds (ref per-action latency metrics).
    #: NOTE: kernels dispatch async — an action's time is dispatch cost;
    #: device execution overlaps and is absorbed by the ``device_wait``
    #: phase (the first host transfer syncs).
    action_seconds: dict[str, float] = dataclasses.field(default_factory=dict)
    session_seconds: float = 0.0
    #: Session.open wall seconds (host snapshot build + DRF dispatch)
    open_seconds: float = 0.0
    #: tensors→BindRequests/evictions + API writes wall seconds
    #: (= device_wait + host_decode + the commit phase's write section)
    commit_seconds: float = 0.0
    #: kai-trace phase attribution: contiguous checkpoints on ONE clock
    #: partition the cycle into snapshot / upload / solve_dispatch /
    #: device_wait / host_decode / commit, so the phases sum to the
    #: cycle wall time by construction (see runtime/tracing.py)
    phase_seconds: dict[str, float] = dataclasses.field(default_factory=dict)
    #: kai-wire per-cycle transfer summary (runtime/wire_ledger.py):
    #: bytes/leaves/dispatches/redundant-bytes by reason plus the
    #: device-residency gauge — the ledger window rolled at cycle end
    wire: dict = dataclasses.field(default_factory=dict)
    #: kai-pulse cluster-health document (ops/analytics.py) — empty on
    #: cycles the analytics cadence skipped (``analytics_every``)
    analytics: dict = dataclasses.field(default_factory=dict)
    #: host-side dispatch cost of the analytics pass (the device work
    #: itself overlaps the solve and lands in ``device_wait``)
    analytics_seconds: float = 0.0
    #: kai-repack migration-plan document (ops/repack.py) — empty on
    #: every cycle the trigger did not fire (the overwhelming majority:
    #: non-fired cycles dispatch nothing and ship zero extra bytes)
    repack: dict = dataclasses.field(default_factory=dict)
    #: host-side dispatch cost of the repack solve (0.0 when not fired)
    repack_seconds: float = 0.0
    #: victim action -> 1 when its gate stayed closed this cycle (no
    #: viable preemptor, so no order frozen and no table built;
    #: ``AllocationResult.victim_skipped``), else 0
    victim_actions_skipped: dict[str, int] = dataclasses.field(
        default_factory=dict)
    #: the placement kernels the session chose for this cycle and the
    #: shapes they unroll over (``Session.kernels``)
    kernels: dict = dataclasses.field(default_factory=dict)
    #: what allocate did under the topology tree, by the names of
    #: ``ops.allocate.TOPOLOGY_STATS`` (the device counter
    #: ``AllocationResult.topology_stats``); all 0 in a cycle whose
    #: program was compiled without a required or preferred level
    topology: dict[str, int] = dataclasses.field(default_factory=dict)
    #: kai-twin determinism anchors: the cycle's logical index and the
    #: per-cycle seed derived from ``SchedulerConfig.seed`` — pure
    #: functions of (config seed, cycle index), never of wall clock or
    #: process RNG, so two replays of the same stream observe identical
    #: pairs by construction (twin/replay.py digests them)
    cycle_index: int = 0
    cycle_seed: int = 0
    #: the cycle's span tree (``runtime.tracing.CycleTrace``), complete
    #: once ``run_once`` has returned; None for a follower's empty result
    trace: object | None = dataclasses.field(
        default=None, repr=False, compare=False)


def cycle_seed_for(seed: int, cycle_index: int) -> int:
    """Deterministic per-cycle seed: a splitmix64-style mix of the
    configured stream seed and the logical cycle index.  Stateless and
    wall-clock-free on purpose — this is the ONLY randomness anchor the
    decision path may consume, and it makes replay determinism a
    construction rather than an audit finding (kai-twin's oracle pins
    it per digest)."""
    mask = 0xFFFFFFFFFFFFFFFF
    x = (seed * 0x9E3779B97F4A7C15 + cycle_index + 1) & mask
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & mask
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & mask
    x ^= x >> 31
    return x & 0x7FFFFFFF


def action_names() -> list[str]:
    """The actions a configuration may name, in the reference's default
    order (ref ``actions/factory.go:31-37``)."""
    return list(_PURE_ACTIONS)


@dataclasses.dataclass
class SchedulerConfig:
    """ref ``conf/scheduler_conf.go:49-62`` SchedulerConfiguration.

    Default action pipeline matches the reference default order
    (``conf_util/scheduler_conf_util.go:37``).
    """

    actions: tuple[str, ...] = ("allocate", "consolidation", "reclaim",
                                "preempt", "stalegangeviction")
    session: SessionConfig = dataclasses.field(default_factory=SessionConfig)
    schedule_period_s: float = 1.0
    #: the shard this instance serves: filters the snapshot to the
    #: shard's node-pool partition and applies the shard's args
    #: (placement strategy, k_value, queue depth) — ref SchedulingShard
    shard: apis.SchedulingShard | None = None
    node_pool_label_key: str = apis.NODE_POOL_LABEL_KEY
    #: HA: a shared runtime.leader.Lease gating the cycle — only the
    #: elected instance schedules (ref cmd/scheduler/app/server.go:60-63
    #: leader election); None = single instance, always leads
    leader_lease: object | None = None
    #: this instance's election identity (pod name in the reference)
    identity: str = "scheduler-0"
    #: continuous-profiling push target (ref ``pyroscope-address``
    #: flag, ``cmd/scheduler/app/options/options.go:110-113``); "" with
    #: profiler_sample_hz=0 leaves the sampler off, "" with a rate
    #: retains windows locally for ``/debug/pprof/continuous``
    pyroscope_address: str = ""
    #: wall-stack samples per second (the mutex/block-rate analogue for
    #: a Python runtime); None = unset (an address alone implies
    #: 100 Hz), an explicit 0 disables even with an address
    profiler_sample_hz: float | None = None
    #: journaled incremental snapshot refresh (state/incremental.py):
    #: re-derive only dirty rows each cycle instead of the full host
    #: rebuild, falling back to the full builder on structural change,
    #: feature pods, or churn above the threshold.  Disabled
    #: automatically for sharded instances (the shard filter re-shapes
    #: the object set per cycle).
    incremental: bool = True
    #: after every patched refresh, rebuild from scratch and assert the
    #: patched ClusterState is element-wise identical (debug/CI flag).
    verify_incremental: bool = False
    #: dirty fraction above which patching falls back to a full rebuild
    incremental_dirty_threshold: float = 0.35
    #: kai-pulse cadence: run the cluster-health analytics kernel every
    #: K cycles (1 = every cycle, 0 = off).  Skipped cycles pay nothing
    #: — no dispatch, no extra bytes on the packed commit transfer.
    analytics_every: int = 1
    #: pending age (in cycles) at which a gang fires a ``starved``
    #: DecisionLog event + the starvation alarm gauges; 0 disables
    starvation_alarm_cycles: int = 32
    #: kai-repack (ops/repack.py): proactively migrate movable running
    #: pods to defragment rack-level capacity for a stranded gang.
    #: The trigger is host-side and cheap — it fires ONLY when the
    #: kai-pulse fragmentation score exceeded ``repack_frag_threshold``
    #: for ``repack_trigger_cycles`` CONSECUTIVE analytics cycles AND
    #: the last analytics doc shows a starving gang plus a
    #: cluster-feasible-but-rack-stranded ladder rung AND the snapshot
    #: carries required topology at all; every other cycle pays zero
    #: dispatches and zero wire bytes.  Disabled = byte-identical
    #: commits to the repack-free scheduler.
    repack_enable: bool = True
    #: kai-pulse ``frag_score`` above which a cycle counts toward the
    #: trigger streak
    repack_frag_threshold: float = 0.5
    #: consecutive high-fragmentation analytics cycles required to fire
    repack_trigger_cycles: int = 2
    #: cycles to wait after a firing (feasible or not) before the next
    #: — repack must never storm migrations
    repack_cooldown: int = 8
    #: per-firing migration cap and plan width; the effective budget is
    #: ``min(repack_max_migrations, VictimConfig.max_victim_pods)`` so
    #: repack can never out-migrate the victim machinery.  0 disables.
    repack_max_migrations: int = 64
    #: kai-intake (intake/router.py): the server's async multi-lane
    #: mutation front end — ``POST /intake`` hash-shards delta events
    #: into this many bounded lanes (one drain worker each), admission
    #: runs in vectorized batches, and the staged stream coalesces into
    #: the hub journal at cycle boundaries under the commit lock
    intake_lanes: int = 4
    #: per-lane bound on queued + staged events; overflow sheds (429)
    #: or degrades to sync per ``intake_policy``
    intake_lane_capacity: int = 65536
    #: lane-overflow policy: "shed" refuses the offered group atomically
    #: (HTTP 429, nothing journaled), "sync" drains inline + flushes a
    #: coalesce through the commit lock and retries (the classic
    #: single-writer behavior as the pressure valve, never the steady
    #: state)
    intake_policy: str = "shed"
    #: max events per worker drain round (the vectorized admission batch)
    intake_batch: int = 512
    #: kai-twin (twin/): the explicit determinism seed threaded through
    #: ``run_once`` — each cycle derives ``cycle_seed_for(seed, index)``
    #: onto its ``CycleResult``/trace, the only sanctioned randomness
    #: anchor on the decision path (wall clock feeds timings ONLY).
    #: Replays pin this from the stream header so same seed → same
    #: stream → bit-identical decisions twice.
    seed: int = 0
    #: attach a kai-twin stream recorder to the server's stored cluster
    #: at startup (``twin/stream.StreamRecorder`` via the shared intake
    #: applier's choke point); recording is ring-bounded and costs one
    #: list append per applied event
    twin_record: bool = True


def apply_shard_args(session: SessionConfig,
                     shard: apis.SchedulingShard) -> SessionConfig:
    """Render a shard's args over the base session config — the operator's
    per-shard config rendering (ref ``schedulingshard_types.go:34-64``)."""
    from ..ops.scoring import PlacementConfig
    placement = PlacementConfig(
        binpack_accel=(shard.placement_strategy_accel
                       == apis.PlacementStrategy.BINPACK),
        binpack_cpu=(shard.placement_strategy_cpu
                     == apis.PlacementStrategy.BINPACK))
    return dataclasses.replace(
        session,
        k_value=shard.k_value,
        allocate=dataclasses.replace(
            session.allocate, placement=placement,
            queue_depth=shard.queue_depth_per_action.get(
                "allocate", session.allocate.queue_depth)),
        victims=dataclasses.replace(
            session.victims,
            queue_depth=shard.queue_depth_per_action.get(
                "reclaim", session.victims.queue_depth)))


class Scheduler:
    """The cycle driver.  One instance per SchedulingShard.

    ``usage_lister`` (optional, a ``runtime.usagedb.UsageLister``) feeds
    time-based fairshare: each cycle polls it and threads the normalized
    per-queue usage into the snapshot, where the proportion kernel's
    ``k_value`` term consumes it (ref ``cache/usagedb``).
    """

    def __init__(self, config: SchedulerConfig | None = None,
                 usage_lister=None, status_updater=None, tracer=None):
        self.config = config or SchedulerConfig()
        #: kai-trace flight recorder: every cycle records its
        #: phase-attributed span tree into the tracer's bounded ring
        #: (served as Chrome-trace JSON by GET /debug/trace)
        self.tracer = tracer or CycleTracer()
        #: per-gang decision event log (GET /debug/events?gang=)
        self.decisions = DecisionLog()
        if self.config.shard is not None:
            self.config = dataclasses.replace(
                self.config,
                session=apply_shard_args(self.config.session,
                                         self.config.shard))
        self.usage_lister = usage_lister
        #: optional runtime.status_updater.AsyncStatusUpdater — fit
        #: failure / condition writes go through its worker pool instead
        #: of the cycle thread (ref cache/status_updater)
        self.status_updater = status_updater
        self._elector = None
        if self.config.leader_lease is not None:
            from ..runtime.leader import LeaderElector
            self._elector = LeaderElector(self.config.leader_lease,
                                          self.config.identity)
        #: cycle-side view of fit-failure counts whose status writes may
        #: still be queued (see _record_fit_status).  Scoped to ONE
        #: cluster document: the HTTP server reuses a Scheduler across
        #: POST /cycle requests, and a stale entry for a same-named gang
        #: of an unrelated document would inflate its failure count —
        #: ``_fit_shadow_cluster`` (a weakref) detects the switch and
        #: clears the shadow.
        self._fit_shadow: dict[str, int] = {}
        self._fit_shadow_cluster = None
        #: per-cluster incremental snapshotter (weakref-scoped like the
        #: fit shadow: the HTTP server reuses a Scheduler across
        #: documents, and a snapshotter only understands ONE journal)
        self._snapshotter = None
        self._snapshotter_cluster = None
        #: kai-pulse: gang name → pending age in cycles (host-owned so
        #: the counters survive snapshot reindexing; weakref-scoped to
        #: one cluster document like the fit shadow)
        self._pending_age: dict[str, int] = {}
        self._age_cluster = None
        #: cycles this Scheduler has run — drives the analytics cadence
        self._cycle_index = 0
        #: gang labels currently carrying a nonzero starvation-age
        #: gauge series — zeroed when they leave the top-K table, so a
        #: placed gang never keeps reporting its last starving age
        self._starv_gauge_gangs: set[str] = set()
        #: last kai-pulse document, served by GET /debug/cluster.
        #: Swapped whole (never mutated after publication) so handler
        #: threads read it without the server's state lock.
        #: (atomic-swap discipline: handler threads read the current
        #: binding; the cycle thread swaps in a fresh immutable dict)
        self._last_analytics: dict = {}
        #: kai-repack trigger state (host-owned, cycle-thread only):
        #: consecutive analytics cycles with frag_score above the
        #: threshold, cycles left in the post-firing cooldown, gangs a
        #: firing migrated for (name -> cycles left to observe the
        #: unblock), and the last firing's immutable plan document
        #: (atomic-swap, served by GET /debug/repack)
        self._frag_streak: int = 0
        self._repack_cooldown: int = 0
        self._repack_watch: dict[str, int] = {}
        self._last_repack: dict = {}
        unknown = [a for a in self.config.actions if a not in _PURE_ACTIONS]
        if unknown:
            raise KeyError(f"unknown action(s): {unknown}")

    def _shard_filter(self, nodes, queues, groups, pods, topology):
        """Restrict the snapshot to this shard's partition (ref
        ``SchedulingNodePoolParams.GetLabelSelector``): label == value,
        or label-absent for the default (value-less) shard."""
        shard = self.config.shard
        key = self.config.node_pool_label_key
        if shard is None:
            return nodes, queues, groups, pods, topology
        val = shard.partition_label_value

        def selects(labels: dict) -> bool:
            # empty-string label values are legal: only None means "the
            # default shard" (label-absent selector)
            if val is None:
                return key not in labels
            return labels.get(key) == val

        nodes = [n for n in nodes if selects(n.labels)]
        groups = [g for g in groups if selects(g.labels)]
        keep = {g.name for g in groups}
        pods = [p for p in pods if p.group in keep]
        return nodes, queues, groups, pods, topology

    def run_once(self, cluster: Cluster) -> CycleResult:
        """One scheduling cycle: snapshot → actions → commit set.

        Under leader election, a non-leader instance performs NO work
        and commits nothing (the reference's followers block inside
        ``leaderelection`` until elected)."""
        if self._elector is not None and not self._elector.is_leader(
                cluster.now):
            return CycleResult()
        t0 = time.perf_counter()
        with self.tracer.cycle() as trace:
            result = self._run_traced(cluster, trace, t0)
            trace.root.attrs.update(
                binds=len(result.bind_requests),
                evictions=len(result.evictions),
                cycle_index=result.cycle_index,
                cycle_seed=result.cycle_seed)
        result.trace = trace
        return result

    def _run_traced(self, cluster: Cluster, trace, t0: float) -> CycleResult:
        """The cycle body, recorded under an open kai-trace cycle.
        Phase timings are CONTIGUOUS checkpoints on one clock, so
        ``phase_seconds`` partitions the wall time exactly (the
        acceptance property BENCH phase attribution relies on)."""
        from . import metrics
        with self.tracer.span("snapshot") as snap_sp:
            queue_usage = None
            if self.usage_lister is not None:
                self.usage_lister.maybe_fetch(cluster.now)
                queue_usage = self.usage_lister.queue_usage(cluster.now)
            # NOTE on concurrent status writes: the cycle NEVER blocks on
            # the async status pool (a slow store must not stall
            # scheduling — test-pinned), so a snapshot can race an
            # in-flight apply.  Each attribute store is GIL-atomic,
            # applies are serialized under the updater's apply_lock, and
            # the apply closures order their writes so every observable
            # prefix is a conservative state (see _record_fit_status) —
            # a racing snapshot at worst treats a gang as schedulable for
            # one extra cycle, never spuriously unschedulable with a
            # stale reason.
            upload_s = 0.0
            if self.config.incremental and self.config.shard is None:
                # journaled incremental refresh: the snapshotter patches
                # the previous cycle's snapshot from the cluster's
                # mutation journal (dirty rows only, changed leaves only
                # to device), falling back to build_snapshot whenever the
                # patch cannot be proven identical — see
                # state/incremental.py
                if (self._snapshotter_cluster is None
                        or self._snapshotter_cluster() is not cluster):
                    from ..state.incremental import IncrementalSnapshotter
                    self._snapshotter = IncrementalSnapshotter(
                        verify=self.config.verify_incremental,
                        dirty_threshold=self.config
                        .incremental_dirty_threshold,
                        tracer=self.tracer)
                    self._snapshotter_cluster = weakref.ref(cluster)
                state, index = self._snapshotter.refresh(
                    cluster, now=cluster.now, queue_usage=queue_usage)
                # snapshot.session: the Session over the refreshed state
                # (the fair-share dispatch)
                with self.tracer.span("snapshot.session"):
                    session = Session.from_state(
                        state, index, config=self.config.session)
                # journal-delta stats of THIS refresh onto the span:
                # mode (patched/full), fallback reason, dirty rows,
                # changed leaves and bytes actually uploaded
                snap_sp.attrs.update(self._snapshotter.stats.last)
                upload_s = float(
                    self._snapshotter.stats.last.get("ship_seconds", 0.0))
            else:
                session = Session.open(
                    *self._shard_filter(*cluster.snapshot_lists()),
                    config=self.config.session,
                    now=cluster.now, queue_usage=queue_usage,
                    resource_claims=cluster.resource_claims,
                    device_classes=cluster.device_classes,
                    volume_claims=cluster.volume_claims,
                    storage_classes=cluster.storage_classes)
                snap_sp.attrs["mode"] = "open"
        t_open = time.perf_counter()
        open_s = t_open - t0
        metrics.open_session_latency.observe(value=open_s)
        result = CycleResult()
        # kai-twin determinism anchor: logical index + derived seed,
        # fixed before any action runs (pure function of config seed
        # and index — never of wall clock)
        result.cycle_index = self._cycle_index
        result.cycle_seed = cycle_seed_for(self.config.seed,
                                           self._cycle_index)
        result.open_seconds = open_s
        result.kernels = session.kernels()
        with self.tracer.span("solve_dispatch"):
            # a dozen small dispatches: inside the span, as the phase's
            # checkpoints already count them
            with self.tracer.span("dispatch.init_result"):
                result.tensors = init_result(session.state)
            every = self.config.analytics_every
            run_analytics = every > 0 and self._cycle_index % every == 0
            self._cycle_index += 1
            bundle = None
            ages = None
            # the whole action pipeline as one compiled program
            cfg = session.config
            ta = time.perf_counter()
            with self.tracer.span("action:pipeline"):
                result.tensors = _fused_pipeline(
                    session.state, session.state.queues.fair_share,
                    actions=tuple(self.config.actions),
                    num_levels=cfg.num_levels, acfg=cfg.allocate,
                    vcfg=cfg.victims, grace_s=cfg.stale_grace_s)
            result.action_seconds["pipeline"] = time.perf_counter() - ta
            metrics.action_latency.observe(
                "pipeline", value=result.action_seconds["pipeline"])
            # kai-pulse: dispatch the cluster-health kernel over the
            # final commit set (ops/analytics.py) — async like the
            # pipeline above, so its device time overlaps and lands in
            # device_wait; the bundle rides the packed commit transfer.
            if run_analytics:
                ta = time.perf_counter()
                with self.tracer.span("analytics"):
                    ages = self._pending_age_vector(cluster, session)
                    bundle = cluster_analytics_jit(
                        session.state, result.tensors, ages,
                        config=session.config.analytics)
                result.analytics_seconds = time.perf_counter() - ta
            # kai-repack: dispatch the defragmentation solve ONLY when
            # the host trigger fires (ops/repack.py) — every other
            # cycle pays a few attribute reads and nothing else (the
            # zero-overhead-below-threshold acceptance bar)
            repack_plan = None
            if self._repack_trigger(cluster, session):
                ta = time.perf_counter()
                with self.tracer.span("repack"):
                    if ages is None:
                        ages = self._pending_age_vector(cluster, session)
                    # destinations draw on the POST-decision idle pool
                    # (result.tensors.free) so the plan never races the
                    # cycle's own placements for the same capacity
                    repack_plan = plan_repack_jit(
                        session.state, ages, result.tensors.free,
                        config=RepackConfig(
                            analytics=session.config.analytics,
                            max_migrations=min(
                                self.config.repack_max_migrations,
                                session.config.victims.max_victim_pods)))
                result.repack_seconds = time.perf_counter() - ta
                metrics.repack_trigger_firings.inc()
                metrics.repack_solve_seconds.observe(
                    value=result.repack_seconds)
        t_solve = time.perf_counter()
        # commit: translate the final tensors into BindRequests/evictions
        # and write them back through the API hub (Statement.Commit).
        # ONE batched device→host transfer feeds every host-side step —
        # the device_wait span brackets it as the cycle's explicit
        # device-sync marker (dispatches above were async, so this wait
        # is link + device time, not host work).
        with self.tracer.span("device_wait", device_sync=True):
            # ONE batched transfer: the packed commit (the analytics
            # bundle and a fired cycle's repack plan ride it; see
            # Session.gather_host)
            host = session.gather_host(
                result.tensors, analytics=bundle, repack_plan=repack_plan)
            plan_host = host.get("repack_plan")
        t_gather = time.perf_counter()
        repack_target = ""
        with self.tracer.span("host_decode"):
            result.bind_requests = session.bind_requests_from(
                result.tensors, host=host)
            result.evictions = session.evictions_from(
                result.tensors.victim, result.tensors.victim_move,
                host=host)
            if plan_host is not None:
                tg = int(plan_host["target_gang"])
                names = session.index.gang_names
                repack_target = names[tg] if 0 <= tg < len(names) else ""
                repack_evs = session.repack_evictions(
                    plan_host, host, repack_target)
                # repack migrations join the ONE eviction list: the
                # commit loop below moves them through the same
                # pipelined-rebind path as consolidation victims
                result.evictions = result.evictions + repack_evs
                self._record_repack(plan_host, repack_evs, repack_target,
                                    result)
        t_decode = time.perf_counter()
        with self.tracer.span("commit"):
            with self.tracer.span("writes"):
                for br in result.bind_requests:
                    cluster.create_bind_request(br)
                for ev in result.evictions:
                    # moved victims (consolidation moves AND kai-repack
                    # migrations) restart and get a pipelined rebind on
                    # their verified target node — evicted, not lost
                    # (ref consolidation.go allPodsReallocated + stmt
                    # pipelining); both flavors commit through the ONE
                    # Session.pipelined_rebind helper
                    cluster.evict_pod(ev.pod_name,
                                      restart=ev.move_to is not None)
                    if ev.move_to is not None:
                        rebind = session.pipelined_rebind(cluster, ev)
                        if rebind is not None:
                            result.move_bind_requests.append(rebind)
                            cluster.create_bind_request(rebind)
            result.commit_seconds = time.perf_counter() - t_solve
            with self.tracer.span("status_updates") as st_sp:
                self._record_fit_status(cluster, session, result, host)
                if self.status_updater is not None:
                    st_sp.attrs.update(
                        pending=self.status_updater.pending,
                        applied=self.status_updater.applied,
                        errors=self.status_updater.errors)
            with self.tracer.span("commit.decisions"):
                events, dropped, counts = session.decision_events(
                    result.tensors, host=host, evictions=result.evictions,
                    limit=self.decisions.max_events_per_cycle,
                    repack_for=repack_target)
                # kai-pulse starvation: advance the per-gang pending-age
                # counters and fire `starved` events for gangs crossing
                # the alarm threshold this cycle (crossings counted
                # EXACTLY; only event construction is bounded)
                starved, crossings = self._advance_starvation(
                    cluster, session, host)
                if crossings:
                    counts[gang_events.OUTCOME_STARVED] = crossings
                    room = max(0, self.decisions.max_events_per_cycle
                               - len(events))
                    events = events + starved[:room]
                self.decisions.record_cycle(trace.cycle_id, events,
                                            dropped=dropped, counts=counts)
            with self.tracer.span("commit.metrics"):
                self._record_metrics(session, result, host)
                if host.get("analytics") is not None:
                    result.analytics = session.analytics_doc(
                        host,
                        alarm_cycles=self.config.starvation_alarm_cycles)
                    self._record_analytics(session, host)
                    # atomic swap: published doc is never mutated, so
                    # /debug/cluster reads it without the server state
                    # lock
                    self._last_analytics = result.analytics
                    # kai-repack trigger streak: consecutive analytics
                    # cycles with the fragmentation gauge above threshold
                    score = float(host["analytics"]["frag_score"])
                    self._frag_streak = (
                        self._frag_streak + 1
                        if score > self.config.repack_frag_threshold
                        else 0)
                # kai-repack unblock accounting: a gang a firing
                # migrated for that places within the observation window
                # counts as unblocked (the
                # kai_repack_gangs_unblocked_total payoff metric).  The
                # dict is empty on every non-repack cycle.
                if self._repack_watch:
                    self._watch_repack_unblocks(session, host)
                # kai-wire: close this cycle's transfer window.  The
                # summary rides the result (healthz/bench) and the trace
                # as Chrome counter lanes — bytes-on-wire and live-bytes
                # step charts aligned with the phase spans above.
                result.wire = _wire.LEDGER.roll_cycle(trace.cycle_id)
                trace.counters.append(("wire bytes/cycle", {
                    "uploaded": result.wire["bytes"],
                    "redundant": result.wire["redundant_bytes"]}))
                trace.counters.append(("device resident bytes", {
                    "live": result.wire["resident_bytes"]}))
        t_end = time.perf_counter()
        result.phase_seconds = {
            "snapshot": max(0.0, open_s - upload_s),
            "upload": upload_s,
            "solve_dispatch": t_solve - t_open,
            "device_wait": t_gather - t_solve,
            "host_decode": t_decode - t_gather,
            "commit": t_end - t_decode,
        }
        for phase, secs in result.phase_seconds.items():
            metrics.cycle_phase_seconds.observe(phase, value=secs)
        result.session_seconds = time.perf_counter() - t0
        metrics.e2e_latency.observe(value=result.session_seconds)
        return result

    def _record_metrics(self, session: Session, result: CycleResult,
                        host: dict) -> None:
        """Per-cycle metric updates (ref metrics.go counters/gauges)."""
        from . import metrics
        from ..apis.types import RESOURCE_NAMES
        metrics.podgroups_considered.inc(
            by=float(host["attempted"].sum()))
        metrics.podgroups_scheduled.inc(
            "all", by=float(host["allocated"].sum()))
        # victim-wavefront counters ride the packed commit transfer
        # (AllocationResult.wavefront_stats): per action, chunk count,
        # lane occupancy, and sparse→dense fallbacks of this cycle
        ws = host.get("wavefront_stats")
        if ws is not None:
            for row, action in ((0, "reclaim"), (1, "preempt")):
                chunks, live, slots, fb, demo = (int(x) for x in ws[row])
                metrics.victim_wavefront_chunks.set(
                    action, value=float(chunks))
                metrics.victim_wavefront_lane_occupancy.set(
                    action, value=(live / slots) if slots else 0.0)
                if action == "preempt":
                    # reclaim has no sparse path or leftover demotion,
                    # so no fallback/demotion series
                    metrics.victim_wavefront_sparse_fallbacks.set(
                        action, value=float(fb))
                    metrics.victim_wavefront_leftover_demotions.set(
                        action, value=float(demo))
        # ... and so does one flag per victim action whose gate stayed
        # closed: nobody was a viable preemptor, nothing was built
        skipped = host.get("victim_skipped")
        if skipped is not None:
            result.victim_actions_skipped = {
                action: int(n) for action, n in zip(VICTIM_ACTIONS, skipped)}
            for action, n in result.victim_actions_skipped.items():
                metrics.victim_action_skipped.set(action, value=float(n))
        # ... and the four counts of allocate's work under the tree
        topo = host.get("topology_stats")
        if topo is not None:
            result.topology = {
                name: int(n) for name, n in zip(TOPOLOGY_STATS, topo)}
        # arrays come from the cycle's single batched transfer; change
        # detection is VECTORIZED against the previous cycle's tables so
        # the Python loop touches only cells that moved — O(changed)
        # rather than 3·Q·R dict probes per cycle (round-3 advisor)
        fs = host["fair_share"]
        alloc = host["queue_allocated"]
        usage = host["queue_usage"]
        prev = getattr(self, "_gauge_prev", None)
        if prev is None:
            prev = self._gauge_prev = {}
        qnames = tuple(session.index.queue_names)
        nq = len(qnames)
        for key, gauge, table in (("fs", metrics.queue_fair_share, fs),
                                  ("alloc", metrics.queue_allocated, alloc),
                                  ("usage", metrics.queue_usage, usage)):
            old = prev.get(key)
            # the diff is positional, so it is only valid while index →
            # queue-name is unchanged; any queue churn/reorder falls
            # back to a full update (a swapped queue with a coinciding
            # value would otherwise keep a stale series)
            if (old is not None and old[0] == qnames
                    and old[1].shape == table.shape):
                rows, cols = np.nonzero(old[1] != table)
            else:
                rows, cols = np.nonzero(np.ones_like(table, bool))
            for qi, ri in zip(rows.tolist(), cols.tolist()):
                if qi < nq:
                    gauge.set(qnames[qi], RESOURCE_NAMES[ri],
                              value=float(table[qi, ri]))
            prev[key] = (qnames, table.copy())

    @property
    def last_analytics(self) -> dict:
        """The most recent kai-pulse cluster-health document (empty
        before the first analytics cycle) — the ``GET /debug/cluster``
        payload.  Atomic-swap discipline: published docs are immutable."""
        return self._last_analytics

    def _scope_ages(self, cluster: Cluster) -> None:
        """Reset the pending-age counters — and the kai-repack trigger
        state derived from them — when the Scheduler is pointed at a
        different cluster document (the HTTP server reuses one
        Scheduler across documents — same discipline as the fit
        shadow)."""
        if (self._age_cluster is None
                or self._age_cluster() is not cluster):
            self._pending_age.clear()
            self._frag_streak = 0
            self._repack_cooldown = 0
            self._repack_watch.clear()
            # the trigger reads this doc — a new cluster must not
            # inherit the previous document's stranded/starving signal
            self._last_analytics = {}
            self._age_cluster = weakref.ref(cluster)

    # -- kai-repack (ops/repack.py) ---------------------------------------

    def _repack_trigger(self, cluster: Cluster,
                        session: Session) -> bool:
        """The host-side repack gate — a handful of attribute reads per
        cycle, no device work.  Fires when the fragmentation gauge has
        been high for ``repack_trigger_cycles`` consecutive analytics
        cycles AND the last kai-pulse doc shows a starving gang plus a
        cluster-feasible-but-rack-stranded ladder rung AND the snapshot
        carries required topology (no rack-required gang can exist
        without it), outside the post-firing cooldown."""
        cfg = self.config
        # scope BEFORE reading trigger state: a re-pointed Scheduler
        # must not fire off the previous cluster's streak/doc
        self._scope_ages(cluster)
        if (not cfg.repack_enable or cfg.repack_max_migrations <= 0
                or cfg.analytics_every <= 0):
            return False
        if self._repack_cooldown > 0:
            self._repack_cooldown -= 1
            return False
        if self._frag_streak < max(cfg.repack_trigger_cycles, 1):
            return False
        if not session.index.has_required_topology:
            return False
        doc = self._last_analytics
        if not doc:
            return False
        ladder = doc.get("fragmentation", {}).get("gang_ladder", ())
        stranded = any(r["cluster_feasible"] and not r["rack_placeable"]
                       for r in ladder)
        starving = bool(doc.get("starvation", {}).get("oldest"))
        return stranded and starving

    def _record_repack(self, plan: dict, executed: list,
                       target: str, result: CycleResult) -> None:
        """Account one repack firing: metrics, the cooldown that keeps
        repack from storming, the unblock watch, and the immutable
        ``GET /debug/repack`` plan document (atomic-swap)."""
        from . import metrics
        cfg = self.config
        planned = int(plan["num_moves"])
        metrics.repack_migrations_planned.inc(by=float(planned))
        metrics.repack_migrations_executed.inc(by=float(len(executed)))
        # cooldown applies whether or not the solve found a feasible
        # plan — an infeasible instance will stay infeasible until the
        # cluster changes, and re-solving it every cycle IS the storm
        self._repack_cooldown = max(cfg.repack_cooldown, 0)
        if executed and target:
            # +2, not +1: _watch_repack_unblocks already decrements this
            # entry later in the SAME cycle (the firing cycle, where the
            # target is pending by construction), so the window must
            # survive cooldown + 1 further cycles of observation
            self._repack_watch[target] = max(cfg.repack_cooldown, 0) + 2
        doc = {
            "feasible": bool(plan["feasible"]),
            "target_gang": target,
            "target_rack": int(plan["target_rack"]),
            "needed_unit_pods": float(plan["needed"]),
            "rack_units_before": float(plan["rack_units_before"]),
            "rack_units_after": float(plan["rack_units_after"]),
            "total_unit_pods": float(plan["total_units"]),
            "migrations_planned": planned,
            "migrations_executed": len(executed),
            "solve_seconds": result.repack_seconds,
            # complete by construction: executed is already bounded by
            # min(repack_max_migrations, VictimConfig.max_victim_pods)
            "moves": [{"pod": ev.pod_name, "to": ev.move_to}
                      for ev in executed],
        }
        result.repack = doc
        self._last_repack = doc

    def _watch_repack_unblocks(self, session: Session,
                               host: dict) -> None:
        from . import metrics
        names = session.index.gang_names
        allocated = host["allocated"]
        for nm in list(self._repack_watch):
            try:
                gi = names.index(nm)
            except ValueError:
                gi = -1
            if 0 <= gi < len(allocated) and allocated[gi]:
                metrics.repack_gangs_unblocked.inc()
                del self._repack_watch[nm]
                continue
            self._repack_watch[nm] -= 1
            if self._repack_watch[nm] <= 0:
                del self._repack_watch[nm]

    @property
    def last_repack(self) -> dict:
        """The most recent kai-repack firing's plan document (empty
        before the first firing) — atomic-swap discipline like
        ``last_analytics``."""
        return self._last_repack

    def repack_status(self) -> dict:
        """The ``GET /debug/repack`` payload: trigger knobs + live
        trigger state + the last firing's plan document."""
        cfg = self.config
        return {
            "ok": bool(self._last_repack),
            "enabled": cfg.repack_enable,
            "frag_threshold": cfg.repack_frag_threshold,
            "trigger_cycles": cfg.repack_trigger_cycles,
            "cooldown_cycles": cfg.repack_cooldown,
            "max_migrations": cfg.repack_max_migrations,
            "frag_high_streak": self._frag_streak,
            "cooldown_remaining": self._repack_cooldown,
            "last": self._last_repack,
        }

    def _pending_age_vector(self, cluster: Cluster,
                            session: Session) -> "np.ndarray":
        """f32 [G] — each gang slot's pending age BEFORE this cycle,
        aligned to the current snapshot (the host owns the name-keyed
        counters; the analytics kernel advances them on device for the
        top-K table, and ``_advance_starvation`` advances the host copy
        identically after decode)."""
        self._scope_ages(cluster)
        ages = np.zeros((session.state.gangs.g,), np.float32)
        if self._pending_age:
            names = session.index.gang_names
            valid = session.index.host_tables["gang_valid"]
            for gi in np.nonzero(valid[:len(names)])[0].tolist():
                a = self._pending_age.get(names[gi])
                if a:
                    ages[gi] = a
        return ages

    #: per-cycle bound on starved-event construction (the alarm fires
    #: once per gang at the crossing, so bursts only happen when many
    #: gangs starve in lockstep)
    MAX_STARVED_EVENTS = 64

    def _advance_starvation(self, cluster: Cluster, session: Session,
                            host: dict) -> tuple[list, int]:
        """Advance the per-gang pending-age counters from this cycle's
        outcome (+1 for still-pending gangs, reset on placement/exit)
        and return ``(events, crossings)``: bounded ``starved``
        GangDecision events for gangs whose age crossed
        ``starvation_alarm_cycles`` exactly this cycle, plus the EXACT
        crossing count (event construction is capped, the count never
        is — the DecisionLog summary invariant)."""
        alarm = self.config.starvation_alarm_cycles
        if alarm <= 0 and self.config.analytics_every <= 0:
            # feature fully off: no alarm to fire and no analytics
            # kernel consuming the ages — skip the O(pending) walk
            return [], 0
        self._scope_ages(cluster)
        names = session.index.gang_names
        valid = host["gang_valid"][:len(names)]
        alloc = host["allocated"][:len(names)]
        reasons = host["fit_reason"]
        old = self._pending_age
        new: dict[str, int] = {}
        starved: list = []
        crossings = 0
        qnames = session.index.queue_names
        queues_of = None
        for gi in np.nonzero(valid & ~alloc)[0].tolist():
            name = names[gi]
            age = old.get(name, 0) + 1
            new[name] = age
            if alarm > 0 and age == alarm:
                crossings += 1
                if len(starved) < self.MAX_STARVED_EVENTS:
                    code = int(reasons[gi])
                    if queues_of is None:
                        queues_of = session._gangs_queue_host()
                    qi = int(queues_of[gi])
                    starved.append(gang_events.GangDecision(
                        gang=name,
                        queue=(qnames[qi]
                               if 0 <= qi < len(qnames) else ""),
                        outcome=gang_events.OUTCOME_STARVED,
                        detail=(f"pending {age} cycles; blocker: "
                                + FIT_REASONS.get(code,
                                                  f"code {code}"))))
        # rebuilt each cycle: placed/vanished gangs fall out (the reset
        # path) and the dict never outgrows the live pending set
        self._pending_age = new
        return starved, crossings

    def _record_analytics(self, session: Session, host: dict) -> None:
        """kai_cluster_* / kai_gang_* gauge updates from the analytics
        bundle that rode this cycle's packed commit."""
        from . import metrics
        from ..apis.types import RESOURCE_NAMES
        a = host["analytics"]
        metrics.cluster_fragmentation_score.set(
            value=float(a["frag_score"]))
        metrics.cluster_largest_rack_gang.set(
            value=float(a["max_rack_units"]))
        metrics.cluster_free_unit_pods.set(value=float(a["total_units"]))
        metrics.cluster_goodput.set(value=float(a["goodput"]))
        metrics.cluster_fairness_drift_max.set(
            value=float(a["drift_max"]))
        metrics.cluster_fairness_drift_gini.set(
            value=float(a["drift_gini"]))
        metrics.cluster_pending_gangs.set(
            value=float(a["pending_gangs"]))
        for r, rn in enumerate(RESOURCE_NAMES):
            metrics.cluster_stranded_free_frac.set(
                rn, value=float(a["stranded_frac"][r]))
            metrics.cluster_utilization.set(rn, value=float(a["util"][r]))
        drift = a["queue_drift"]
        for qi, qn in enumerate(session.index.queue_names):
            metrics.cluster_fairness_drift.set(
                qn, value=float(drift[qi]))
        gnames = session.index.gang_names
        current: set[str] = set()
        for age, gi in zip(a["starv_age"].tolist(),
                           a["starv_gang"].tolist()):
            if age > 0 and 0 <= gi < len(gnames):
                metrics.gang_starvation_age.set(
                    gnames[gi], value=float(age))
                current.add(gnames[gi])
        # a gang that placed (or fell out of the top-K) must stop
        # reporting its last starving age — zero its stale series
        for name in self._starv_gauge_gangs - current:
            metrics.gang_starvation_age.set(name, value=0.0)
        self._starv_gauge_gangs = current

    def _record_fit_status(self, cluster: Cluster, session: Session,
                           result: CycleResult, host: dict) -> None:
        """Write fit failures back to PodGroup status — the
        status_updater's UnschedulableOnNodePool marking (ref
        ``cache/status_updater``, ``utils/pod_group_utils.go``): after
        ``scheduling_backoff`` consecutive failed cycles the group is
        marked unschedulable and the snapshot skips it until pod churn
        clears the condition (podgroup controller)."""
        allocated = host["allocated"]
        explanations = session.unschedulable_explanations(
            result.tensors, host=host)
        names = session.index.gang_names
        # touch only gangs whose status actually changed: successes reset,
        # failures (the explanations keys) accumulate — O(changed), not
        # O(G) Python work on the cycle path
        # Writes go through the async worker pool when configured, so a
        # slow status store never stalls the cycle (ref
        # cache/status_updater/concurrency.go); inline otherwise.  The
        # pool coalesces per key (latest wins), so every queued write is
        # an ABSOLUTE status computed on the cycle thread — the shadow
        # dict is the cycle's authoritative failure count while writes
        # are in flight (the reference's in-flight pod-group records).
        def write(key, fn):
            if self.status_updater is None:
                fn()
            else:
                self.status_updater.enqueue(key, fn)

        if (self._fit_shadow_cluster is None
                or self._fit_shadow_cluster() is not cluster):
            self._fit_shadow.clear()
            self._fit_shadow_cluster = weakref.ref(cluster)
        shadow = self._fit_shadow

        # Write ORDER inside the apply closures matters: a racing
        # snapshot (the cycle never blocks on the status pool) observes
        # some GIL-atomic prefix of these stores, so each prefix must be
        # a conservative state.  reset() clears the skip flag FIRST (a
        # partially-reset gang is at worst re-attempted with a stale
        # count); fail() sets the flag/phase LAST (a partially-failed
        # gang is at worst attempted one more cycle — never skipped with
        # a stale reason).
        def reset(group):
            def apply():
                group.unschedulable = False
                group.unschedulable_reason = ""
                group.fit_failures = 0
            return apply

        def fail(group, failures, reason):
            unsched = (group.scheduling_backoff >= 1
                       and failures >= group.scheduling_backoff)

            def apply():
                group.fit_failures = failures
                group.unschedulable_reason = reason
                if unsched:
                    group.phase = apis.PodGroupPhase.UNSCHEDULABLE
                    group.unschedulable = True
            return apply

        for gi in np.nonzero(allocated[:len(names)])[0]:
            group = cluster.pod_groups.get(names[gi])
            if group is None:
                continue
            had = shadow.get(names[gi])
            if had or group.fit_failures or group.unschedulable:
                # record the reset IN the shadow (0), don't drop the
                # entry: per-key coalescing means a later fail write can
                # supersede this queued reset, and reading the stale
                # pre-reset group.fit_failures then would prematurely
                # trip the unschedulable backoff
                shadow[names[gi]] = 0
                write(names[gi], reset(group))
        for name, reason in explanations.items():
            group = cluster.pod_groups.get(name)
            if group is None:
                continue
            failures = shadow.get(name, group.fit_failures) + 1
            shadow[name] = failures
            write(name, fail(group, failures, reason))
