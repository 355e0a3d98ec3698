"""The scheduler's metric catalog — ref ``pkg/scheduler/metrics/metrics.go:39-58``
and this repo's generated ``docs/metrics/METRICS.md``, same metric
names (kai_ prefix).

Every metric registers HERE (one module, one registry) so the catalog
doc can be generated — and drift-checked — from a single source:

    python -m kai_scheduler_tpu.framework.metrics > docs/metrics/METRICS.md

``tests/test_metrics_catalog.py`` asserts the committed doc equals the
registry exactly (name, type, labels, help); ``scripts/lint.py`` runs
the same check jax-free by AST-parsing this module's registrations.
"""
from __future__ import annotations

from ..utils.metrics import Registry, render_catalog

registry = Registry()

e2e_latency = registry.histogram(
    "kai_e2e_scheduling_latency_seconds",
    "End-to-end scheduling cycle latency")
open_session_latency = registry.histogram(
    "kai_open_session_latency_seconds",
    "Snapshot + plugin-init (session open) latency")
action_latency = registry.histogram(
    "kai_action_scheduling_latency_seconds",
    "Per-action latency", label_names=("action",))
podgroups_scheduled = registry.counter(
    "kai_podgroups_scheduled_total", "Pod groups scheduled by action",
    label_names=("action",))
podgroups_considered = registry.counter(
    "kai_podgroups_considered_total", "Pod groups considered per cycle")
queue_fair_share = registry.gauge(
    "kai_queue_fair_share", "Per-queue fair share",
    label_names=("queue", "resource"))
queue_allocated = registry.gauge(
    "kai_queue_allocated", "Per-queue allocated amount",
    label_names=("queue", "resource"))
queue_usage = registry.gauge(
    "kai_queue_usage", "Per-queue normalized historical usage",
    label_names=("queue", "resource"))
# victim-wavefront observability (ops/victims.py chunked engine): chunk
# count and lane occupancy per action per cycle, plus how often the
# sparse preempt path fell back to the dense composed path (compact
# unit-table overflow)
victim_wavefront_chunks = registry.gauge(
    "kai_victim_wavefront_chunks",
    "Victim-wavefront chunks run last cycle", label_names=("action",))
victim_wavefront_lane_occupancy = registry.gauge(
    "kai_victim_wavefront_lane_occupancy",
    "Live lanes / lane slots across last cycle's victim chunks",
    label_names=("action",))
victim_wavefront_sparse_fallbacks = registry.gauge(
    "kai_victim_wavefront_sparse_fallbacks",
    "Sparse-path actions that fell back to the dense composed path "
    "last cycle", label_names=("action",))
victim_wavefront_leftover_demotions = registry.gauge(
    "kai_victim_wavefront_leftover_demotions",
    "Lane-chunk demotion events last cycle (a lane demoted to "
    "conflict-retry because an earlier lane's victims freed more than "
    "its claims consumed; the same lane re-demoted in a later chunk "
    "counts again — the gauge measures serialization pressure, not "
    "distinct lanes)", label_names=("action",))
victim_action_skipped = registry.gauge(
    "kai_victim_action_skipped",
    "1 when the victim action found no viable preemptor last cycle and "
    "built no order or table (its gate stayed closed)",
    label_names=("action",))
# kai-trace phase attribution (runtime/tracing.py): the cycle timeline
# partitioned into contiguous phases — snapshot (host build/patch),
# upload (changed-leaves transfer DISPATCH; device_put is async, so the
# transfer itself overlaps the solve), solve_dispatch (async kernel
# dispatch), device_wait (first blocking sync: link + device + any
# still-inflight transfer time), host_decode (tensors ->
# BindRequests/evictions), commit (API writes, status, bookkeeping).
# The phases sum to the cycle wall time.
cycle_phase_seconds = registry.histogram(
    "kai_cycle_phase_seconds",
    "Per-phase scheduling cycle latency (phases partition the cycle "
    "wall time; device_wait brackets the first blocking transfer)",
    label_names=("phase",))
# continuous profiler push counters (runtime/profiling.py) — were bare
# instance attributes invisible to /metrics
profiler_pushed_windows = registry.counter(
    "kai_profiler_pushed_windows_total",
    "Continuous-profiler windows pushed to the ingest server")
profiler_push_errors = registry.counter(
    "kai_profiler_push_errors_total",
    "Continuous-profiler window pushes that failed (swallowed after "
    "counting — a profiling sink never affects scheduling)")
# kai-wire transfer ledger (runtime/wire_ledger.py): every host→device
# upload in the package flows through the TransferLedger choke point
# (KAI071), labeled with WHY it shipped — full-build (build_snapshot's
# one-shot transfer), journal-patch (incremental changed-leaves ship),
# fallback (incremental engine rebuilt in full), verify (patched==fresh
# reference rebuild), mesh-shard (mesh placement).
wire_uploaded_bytes = registry.counter(
    "kai_wire_uploaded_bytes_total",
    "Bytes shipped host→device through the transfer ledger",
    label_names=("reason",))
wire_uploaded_leaves = registry.counter(
    "kai_wire_uploaded_leaves_total",
    "Pytree leaves shipped host→device through the transfer ledger",
    label_names=("reason",))
wire_dispatches = registry.counter(
    "kai_wire_dispatches_total",
    "device_put dispatch calls (one batched dispatch may carry many "
    "leaves — leaves/dispatches exposes unbatched transfer loops)",
    label_names=("reason",))
wire_redundant_bytes = registry.counter(
    "kai_wire_redundant_bytes_total",
    "Re-uploaded-IDENTICAL bytes: the uploaded leaf's content "
    "fingerprint matched the last upload of the same leaf — zero on "
    "the patch path, which ships changed leaves only",
    label_names=("reason",))
wire_dispatch_seconds = registry.counter(
    "kai_wire_dispatch_seconds_total",
    "Wall seconds spent in device_put dispatch calls (async enqueue, "
    "not transfer completion — that is the cycle's device_wait phase)",
    label_names=("reason",))
wire_resident_bytes = registry.gauge(
    "kai_wire_resident_bytes",
    "Ledger-known device-resident bytes (last upload per leaf key)")
wire_resident_buffers = registry.gauge(
    "kai_wire_resident_buffers",
    "Ledger-known device-resident buffer count")
# per cycle, snapshot bytes REUSED on device without touching the wire
# vs bytes actually uploaded (a patched cycle's changed leaves)
wire_resident_reused_bytes = registry.gauge(
    "kai_wire_resident_reused_bytes",
    "Device-resident bytes reused last cycle without re-upload "
    "(resident snapshot leaves not touched by the wire)")
wire_resident_uploaded_bytes = registry.gauge(
    "kai_wire_resident_uploaded_bytes",
    "Bytes uploaded last cycle (a patched cycle: its changed leaves)")
wire_cycle_uploaded_bytes = registry.histogram(
    "kai_wire_cycle_uploaded_bytes",
    "Per-cycle bytes on the wire (all reasons; observed at cycle roll)",
    buckets=(4096.0, 65536.0, 1048576.0, 4194304.0, 16777216.0,
             67108864.0, 268435456.0, 1073741824.0))
# kai-wire compile watcher (runtime/compile_watch.py): every jit entry
# point of the package is wrapped, and each first-seen abstract shape
# signature is attributed as that entry's compile
compile_cache_misses = registry.counter(
    "kai_compile_cache_misses_total",
    "Jit cache misses attributed per entry point (first call with an "
    "unseen abstract shape signature)", label_names=("entry",))
compile_seconds = registry.counter(
    "kai_compile_seconds_total",
    "Wall seconds spent in cache-miss dispatches (trace + XLA compile "
    "dominated)", label_names=("entry",))
compile_storm_alarms = registry.counter(
    "kai_compile_storm_alarms_total",
    "Recompile-storm alarms: misses on one entry reached the storm "
    "threshold inside the sliding window (padded-capacity oscillation "
    "or unstable static config)", label_names=("entry",))
# kai-pulse cluster-health analytics (ops/analytics.py): the on-device
# gauge kernel that rides the packed commit every K cycles —
# fragmentation, goodput/utilization, fairness drift, starvation
cluster_fragmentation_score = registry.gauge(
    "kai_cluster_fragmentation_score",
    "Rack-stranded fraction of the canonical gang ladder: rungs the "
    "cluster could serve by raw free unit pods but NO single rack "
    "domain can host (0 = consolidated, 1 = fully stranded) — the "
    "gauge the repack solver is gated behind")
cluster_stranded_free_frac = registry.gauge(
    "kai_cluster_stranded_free_frac",
    "Fraction of free capacity sitting on nodes that cannot fit even "
    "one canonical unit pod", label_names=("resource",))
cluster_largest_rack_gang = registry.gauge(
    "kai_cluster_largest_rack_gang_units",
    "Canonical unit pods placeable inside the single best rack domain "
    "(the largest-placeable-gang probe)")
cluster_free_unit_pods = registry.gauge(
    "kai_cluster_free_unit_pods",
    "Canonical unit pods placeable cluster-wide (allocate fit "
    "predicate over the post-cycle free pool)")
cluster_utilization = registry.gauge(
    "kai_cluster_utilization",
    "Allocated / capacity per resource axis (post-cycle, releasing "
    "counted as idle)", label_names=("resource",))
cluster_goodput = registry.gauge(
    "kai_cluster_goodput",
    "Cluster goodput in Gavel's effective-throughput sense: running + "
    "newly-bound accel throughput over accel capacity (unit throughput "
    "per device until the per-(job, accel-type) tensors land)")
cluster_fairness_drift = registry.gauge(
    "kai_cluster_fairness_drift",
    "Per-queue max_r |allocated - DRF fair share| / cluster capacity",
    label_names=("queue",))
cluster_fairness_drift_max = registry.gauge(
    "kai_cluster_fairness_drift_max",
    "Largest per-queue fairness drift this analytics cycle")
cluster_fairness_drift_gini = registry.gauge(
    "kai_cluster_fairness_drift_gini",
    "Gini coefficient of the dominant allocated shares across valid "
    "queues (0 = equal, 1 = maximally concentrated)")
cluster_pending_gangs = registry.gauge(
    "kai_cluster_pending_gangs",
    "Gangs still pending after the cycle (kai-pulse starvation family)")
gang_starvation_age = registry.gauge(
    "kai_gang_starvation_age_cycles",
    "Pending age in cycles for the top-K oldest starving gangs (the "
    "kai-pulse on-device top-K table; series update on analytics "
    "cycles)", label_names=("gang",))
# kai-repack proactive defragmentation (ops/repack.py): the
# constraint-based migration solver the fragmentation gauge gates —
# fired when frag_score stays above SchedulerConfig.repack_frag_threshold
# for repack_trigger_cycles consecutive analytics cycles while a
# rack-required gang starves cluster-feasible-but-rack-stranded
repack_trigger_firings = registry.counter(
    "kai_repack_trigger_firings_total",
    "Repack solver dispatches (the fragmentation trigger fired; "
    "feasible or not, each firing starts the cooldown)")
repack_migrations_planned = registry.counter(
    "kai_repack_migrations_planned_total",
    "Migrations in feasible repack plans (bounded per firing by "
    "min(repack_max_migrations, VictimConfig.max_victim_pods))")
repack_migrations_executed = registry.counter(
    "kai_repack_migrations_executed_total",
    "Repack migrations committed as evictions with pipelined rebinds "
    "(planned moves dropped by cross-dispatch guards are not executed)")
repack_solve_seconds = registry.histogram(
    "kai_repack_solve_seconds",
    "Host-side repack solve dispatch latency per firing (device time "
    "overlaps the cycle's device_wait phase)")
repack_gangs_unblocked = registry.counter(
    "kai_repack_gangs_unblocked_total",
    "Target gangs that placed within the post-firing observation "
    "window after their repack migrations committed")
# kai-intake multi-lane mutation front end (intake/router.py): cluster
# deltas hash-shard by entity key into bounded lanes, drain workers
# admission-check them in vectorized batches, and a cycle-boundary
# coalesce merges the staged events into the hub journal — replacing
# the per-mutation single-writer wall with explicit, metered
# backpressure
intake_accepted = registry.counter(
    "kai_intake_accepted_total",
    "Events accepted into an intake lane (queued for admission + "
    "coalesce)")
intake_shed = registry.counter(
    "kai_intake_shed_total",
    "Events shed by lane backpressure (the offered group exceeded the "
    "lane bound; the whole group is refused atomically — HTTP 429, "
    "nothing journaled)", label_names=("lane",))
intake_rejected = registry.counter(
    "kai_intake_rejected_total",
    "Events rejected by the batched admission sweep (unknown "
    "collection, malformed document, resource scalar non-finite / "
    "negative / absurd)", label_names=("lane",))
intake_coalesced = registry.counter(
    "kai_intake_coalesced_total",
    "Staged events merged into the hub journal at cycle-boundary "
    "coalesce (global sequence order, bit-identical to the sequential "
    "classic path)")
intake_apply_errors = registry.counter(
    "kai_intake_apply_errors_total",
    "Admitted events the coalesce applier had to skip (doc passed the "
    "door check but failed object construction) — skipped, not fatal: "
    "one poisoned doc must never destroy other clients' accepted "
    "events or fail the cycle")
intake_sync_degrades = registry.counter(
    "kai_intake_sync_degrades_total",
    "Overflow requests that degraded to the synchronous path "
    "(policy=sync: drain inline + flush a coalesce through the commit "
    "lock, then retry)")
intake_lane_depth = registry.gauge(
    "kai_intake_lane_depth",
    "Queued + staged events per lane (observed at coalesce)",
    label_names=("lane",))
intake_coalesce_seconds = registry.histogram(
    "kai_intake_coalesce_seconds",
    "Cycle-boundary coalesce latency (take staged + seq sort + "
    "sequential apply + bulk journal merge)")
# kai-twin digital twin (twin/): recorded-stream replay, differential
# oracle, scenario fuzzer, and the closed-loop policy tuner
twin_recorded_events = registry.counter(
    "kai_twin_recorded_events_total",
    "Mutation events mirrored into the twin stream recorder at the "
    "shared intake apply choke point")
twin_replayed_events = registry.counter(
    "kai_twin_replayed_events_total",
    "Mutation events applied by the twin replayer (fresh scheduler + "
    "cluster driven through a recorded or generated stream)")
twin_replay_cycles = registry.counter(
    "kai_twin_replay_cycles_total",
    "Scheduling cycles executed by the twin replayer")
twin_oracle_checks = registry.counter(
    "kai_twin_oracle_checks_total",
    "Digest fields compared by the differential oracle (binds, "
    "evictions, decisions, journal cursor/generation, analytics, "
    "clock, determinism anchors)")
twin_oracle_divergences = registry.counter(
    "kai_twin_oracle_divergences_total",
    "Digest divergences the differential oracle found — any nonzero "
    "value is a determinism bug")
twin_fuzz_violations = registry.counter(
    "kai_twin_fuzz_violations_total",
    "Invariant violations found by the scenario fuzzer",
    label_names=("family",))
twin_fuzz_minimized = registry.counter(
    "kai_twin_fuzz_minimized_total",
    "Events dropped by the greedy event-drop delta-debugging minimizer")
twin_tuner_rollouts = registry.counter(
    "kai_twin_tuner_rollouts_total",
    "Candidate-config rollouts replayed by the closed-loop policy "
    "tuner")
twin_tuner_best_score = registry.gauge(
    "kai_twin_tuner_best_score",
    "Best composite objective the policy tuner has found (weighted "
    "goodput minus fairness drift, starvation age, and cycle p99)")


def catalog() -> list[dict]:
    """Every registered metric as ``{name, type, labels, help}`` — the
    source of truth for ``docs/metrics/METRICS.md``."""
    return sorted(({"name": m.name, "type": m.kind,
                    "labels": list(m.label_names), "help": m.help}
                   for m in registry.metrics()),
                  key=lambda r: r["name"])


if __name__ == "__main__":
    print(render_catalog(catalog()), end="")
