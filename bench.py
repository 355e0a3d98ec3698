"""Benchmark harness — prints ONE JSON line for the driver.

Headline (default): the BASELINE.json north star — full compiled
scheduling step (DRF division + gang allocate) at **10k nodes × 50k
pending pods**, p99 cycle latency against the driver's 50 ms bar
(``vs_baseline = 50 ms / p99`` — 1.0 means the bar is met).

``BENCH_CONFIG`` selects the other BASELINE configs:

  1 fairshare   100 nodes / 500 pods, 2-level DRF division
  2 scoring     1k nodes × 5k single-accel pods (dense score path)
  3 gang        2k nodes, 1k gangs × 8 pods (all-or-nothing)
  4 topology    5k nodes, 3-level tree, rack-constrained gangs
  5 reclaim     10k nodes × 50k pods, over-quota victim search
  preempt       512 queues × 1 boosted preemptor @ 10k nodes (the
                sparse victim-wavefront hot path; quick alias of
                preempt_many_queues)
  phases        kai-trace per-phase cycle attribution (snapshot/upload/
                solve-dispatch/device-wait/host-decode/commit) @ 10k
                nodes × 50k pods, 1% journaled churn
  frag          kai-pulse fragmentation scenario: 10k nodes, 70k
                running fillers strand 10k single devices across 40
                racks; a rack-required 256-pod gang is unplaceable
                until a rack frees — measures analytics overhead and
                the gauge's predictive drop
  storm         kai-intake traffic storm: a 1M-event pod create/delete
                burst (BENCH_STORM_EVENTS overrides) through the async
                multi-lane router while cycles keep running — sustained
                ingest events/s, cycle p99 under storm vs quiescent,
                coalesce p99, and the deliberate-overload shed fraction
  headline      10k nodes × 50k pods allocate
  e2e/e2e_alloc full cycle (snapshot→actions→commit), saturated /
                allocate-heavy shapes
  full          (default) headline to stdout with every other BASELINE
                config and the unpipelined per-cycle p99 folded into the
                same JSON line's "extra" field — the driver artifact
  all           run everything; extra lines to stderr, headline to stdout

``--compare PREV.json`` folds benchstat-style per-config deltas vs a
previous artifact into ``extra.vs_prev`` (and prints them to stderr).

Measured through the *default* semantic path: Session.open's auto-tuned
config (dynamic ordering, prefilter + signature skip on), kernels jitted
once and timed over BENCH_ITERS repetitions.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _p99(times: list[float]) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(times), 99) * 1e3)


#: dispatches per timed batch: a scheduler runs cycles back to back, so
#: per-cycle latency is measured as pipelined batches (dispatch K, sync
#: once, divide) and p99 is taken over batches; ``pipeline=1`` is the
#: plain dispatch-and-sync cycle
PIPELINE = int(os.environ.get("BENCH_PIPELINE", "5"))


def _device_stamp() -> dict:
    """The device every row of this run was measured on, as JAX
    reports it."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _time(fn, iters: int, pipeline: int | None = None) -> float:
    import jax
    pipeline = PIPELINE if pipeline is None else pipeline
    jax.block_until_ready(fn())  # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready([fn() for _ in range(pipeline)])
        times.append((time.perf_counter() - t0) / pipeline)
    return _p99(times)


def _time_double_buffered(fn, iters: int) -> float:
    """Per-cycle p99 with ONE cycle in flight: dispatch cycle N+1, then
    gather cycle N — the deployable double-buffered cycle loop (the host
    prepares/commits cycle N while the device already solves N+1)."""
    import jax
    prev = fn()
    jax.block_until_ready(prev)  # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        nxt = fn()               # dispatch N+1 (async)
        jax.block_until_ready(prev)   # gather N
        prev = nxt
        times.append(time.perf_counter() - t0)
    jax.block_until_ready(prev)
    return _p99(times)


def _session(**kw):
    from kai_scheduler_tpu.framework.session import Session
    from kai_scheduler_tpu.state import make_cluster
    nodes, queues, groups, pods, topo = make_cluster(**kw)
    return Session.open(nodes, queues, groups, pods, topo)


def bench_fairshare(iters: int) -> dict:
    import jax

    from kai_scheduler_tpu.ops import drf
    ses = _session(num_nodes=100, node_accel=8.0, num_gangs=250,
                   tasks_per_gang=2, num_departments=2,
                   queues_per_department=4)

    @jax.jit
    def run(state):
        return drf.set_fair_share(state, num_levels=2)

    p99 = _time(lambda: run(ses.state), iters)
    return {"metric": "DRF fair-share division p99 (100 nodes, 500 pods)",
            "value": round(p99, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(p99, 1e-9), 3)}


def _allocate_bench(name: str, iters: int, pipeline: int | None = None,
                    _reuse=None, double_buffer: bool = False, **kw) -> dict:
    import jax
    import numpy as np

    from kai_scheduler_tpu.ops import drf
    from kai_scheduler_tpu.ops.allocate import allocate
    ses = _reuse if _reuse is not None else _session(**kw)
    num_levels = ses.config.num_levels
    config = ses.config.allocate

    @jax.jit
    def cycle(state):
        fair_share = drf.set_fair_share(state, num_levels=num_levels)
        st = state.replace(
            queues=state.queues.replace(fair_share=fair_share))
        res = allocate(st, fair_share, num_levels=num_levels, config=config)
        return res.placements, res.allocated

    placements, _ = jax.block_until_ready(cycle(ses.state))
    placed = int((np.asarray(placements) >= 0).sum())
    if double_buffer:
        p99 = _time_double_buffered(lambda: cycle(ses.state),
                                    max(iters * 3, 8))
    else:
        p99 = _time(lambda: cycle(ses.state), iters, pipeline=pipeline)
    total = int(np.asarray(ses.state.gangs.task_valid).sum())
    return {"metric": f"{name} ({placed}/{total} pods placed)",
            "value": round(p99, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(p99, 1e-9), 3)}


def bench_scoring(iters: int) -> dict:
    return _allocate_bench(
        "sched-cycle p99, scoring: 1k nodes x 5k single-accel pods", iters,
        num_nodes=1000, node_accel=8.0, num_gangs=5000, tasks_per_gang=1)


def bench_gang(iters: int) -> dict:
    return _allocate_bench(
        "sched-cycle p99, gang: 2k nodes x 1k gangs x 8 pods", iters,
        num_nodes=2000, node_accel=8.0, num_gangs=1000, tasks_per_gang=8)


def bench_topology(iters: int) -> dict:
    return _allocate_bench(
        "sched-cycle p99, topology: 5k nodes, 3-level tree, "
        "rack-required gangs", iters,
        num_nodes=5000, node_accel=8.0, num_gangs=2500, tasks_per_gang=8,
        topology_levels=(8, 16), required_level="topo/level1")


def bench_headline(iters: int) -> dict:
    return _allocate_bench(
        "sched-cycle p99 @ 10k nodes x 50k pending pods", iters,
        num_nodes=10_000, node_accel=8.0, num_gangs=6250, tasks_per_gang=8)


def bench_headline_full(iters: int) -> dict:
    """The driver's default: the headline number, with every other
    BASELINE config AND the honest unpipelined per-cycle p99 folded
    into the same JSON line (VERDICT r2 items 3 + 10: all five configs
    in one artifact, tail latency without batch averaging)."""
    ses = _session(num_nodes=10_000, node_accel=8.0, num_gangs=6250,
                   tasks_per_gang=8)
    out = _allocate_bench(
        "sched-cycle p99 @ 10k nodes x 50k pending pods", iters,
        _reuse=ses)
    extra = {}
    for name, fn in (("fairshare", bench_fairshare),
                     ("scoring", bench_scoring),
                     ("gang", bench_gang),
                     ("topology", bench_topology),
                     ("reclaim", bench_reclaim),
                     ("preempt_many_queues", bench_preempt_many_queues),
                     ("churn", bench_churn),
                     ("phases", bench_phases),
                     ("frag", bench_frag),
                     # bounded storm in the artifact row; the
                     # standalone BENCH_CONFIG=storm run does the full
                     # 1M-event burst
                     ("storm", lambda it: bench_storm(
                         it, events=250_000))):
        r = fn(max(3, iters // 2))
        unit = r.get("unit", "ms")
        extra[name] = {"value": r["value"], "unit": unit,
                       "vs_baseline": r["vs_baseline"],
                       "metric": r["metric"]}
        if unit == "ms":
            # legacy column name — cross-artifact p99 comparisons
            # (and --compare) read this; non-latency configs (storm
            # events/s) must NOT masquerade as a latency
            extra[name]["p99_ms"] = r["value"]
        if r.get("extra"):
            extra[name]["extra"] = r["extra"]
    # unbatched tails, same session and compiled cycle as the headline:
    # - sync_p99_ms: dispatch + sync per cycle, nothing in flight
    # - p99_ms: ONE cycle in flight (dispatch N+1, then gather N) — the
    #   deployable double-buffered loop
    r1 = _allocate_bench("per-cycle", max(3, iters // 2),
                         pipeline=1, _reuse=ses)
    rdb = _allocate_bench("per-cycle-db", max(3, iters // 2),
                          _reuse=ses, double_buffer=True)
    extra["headline_per_cycle"] = {"p99_ms": rdb["value"],
                                   "sync_p99_ms": r1["value"]}
    out["extra"] = extra
    return out


def bench_reclaim(iters: int) -> dict:
    import jax
    import numpy as np

    from kai_scheduler_tpu.ops.allocate import init_result
    from kai_scheduler_tpu.ops.victims import run_victim_action
    ses = _session(
        num_nodes=10_000, node_accel=8.0, num_gangs=6250, tasks_per_gang=8,
        running_fraction=0.5, queue_accel_quota=1000.0,
        partition_queues_by_running=True)
    num_levels = ses.config.num_levels
    config = ses.config.victims

    @jax.jit
    def cycle(state):
        res = run_victim_action(
            state, state.queues.fair_share, init_result(state),
            num_levels=num_levels, mode="reclaim", config=config)
        return res.victim, res.allocated

    victims, _ = jax.block_until_ready(cycle(ses.state))
    n_vic = int(np.asarray(victims).sum())
    p99 = _time(lambda: cycle(ses.state), iters)
    return {"metric": ("reclaim victim-search p99 @ 10k nodes x 50k pods "
                       f"({n_vic} victims)"),
            "value": round(p99, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(p99, 1e-9), 3)}


def bench_preempt_many_queues(iters: int) -> dict:
    """Preempt with ~512 queues each holding ONE boosted preemptor over
    a saturated cluster — the adversarial shape for the wavefront's
    single-queue-per-chunk batching (round-4 VERDICT weak 7): every
    chunk can serve at most one queue's preemptor, so per-chunk
    overheads dominate if the action degrades toward sequential."""
    import jax
    import numpy as np

    from kai_scheduler_tpu.ops.allocate import init_result
    from kai_scheduler_tpu.ops.victims import run_victim_action
    ses = _session(
        num_nodes=10_000, node_accel=8.0, num_gangs=10_512,
        tasks_per_gang=8, running_fraction=10_000 / 10_512,
        num_departments=2, queues_per_department=256,
        pending_priority_boost=100)
    num_levels = ses.config.num_levels
    config = ses.config.victims

    @jax.jit
    def cycle(state):
        res = run_victim_action(
            state, state.queues.fair_share, init_result(state),
            num_levels=num_levels, mode="preempt", config=config)
        return res.victim, res.allocated

    victims, alloc = jax.block_until_ready(cycle(ses.state))
    n_vic = int(np.asarray(victims).sum())
    n_alloc = int(np.asarray(alloc).sum())
    p99 = _time(lambda: cycle(ses.state), iters)
    return {"metric": ("preempt p99, 512 queues x 1 preemptor each @ "
                       f"10k nodes ({n_alloc} preemptors placed, "
                       f"{n_vic} victims)"),
            "value": round(p99, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(p99, 1e-9), 3)}


def _cost_model_peak_mb(sched) -> float | None:
    """kai-cost's peak-live-bytes model for the fused entry, traced at
    the scheduler's CURRENT snapshot shapes (analysis/costmodel.py) —
    a pure re-trace, no compile/dispatch; None when no snapshot has
    been built yet."""
    from kai_scheduler_tpu.analysis import costmodel
    snap = getattr(sched, "_snapshotter", None)
    state = getattr(snap, "_dev", None) if snap is not None else None
    if state is None:
        return None
    return costmodel.peak_mb_for_state(state).get("fused_pipeline")


def _comm_model_bytes_per_cycle(sched) -> int | None:
    """kai-comms' modeled cross-device collective bytes for the fused
    entry, traced at the scheduler's CURRENT snapshot shapes
    (analysis/comms.py) — a pure re-trace over ShapeDtypeStructs, no
    compile/dispatch; None when no snapshot has been built yet."""
    from kai_scheduler_tpu.analysis import comms
    snap = getattr(sched, "_snapshotter", None)
    state = getattr(snap, "_dev", None) if snap is not None else None
    if state is None:
        return None
    return comms.comm_bytes_for_state(state).get("fused_pipeline")


def _churn_cluster(cluster, rng, frac: float,
                   num_nodes: int = 10_000) -> None:
    """Journaled churn (evict half / rebind half / tick) through the
    mutation paths the cluster hub marks, so the incremental refresh
    can patch — shared by the churn and phases benches."""
    from kai_scheduler_tpu.apis import types as apis
    k = max(1, int(len(cluster.pods) * frac / 2))
    running = [p.name for p in cluster.pods.values()
               if p.status == apis.PodStatus.RUNNING][:k]
    for nm in running:
        cluster.evict_pod(nm)
    pending = [p for p in cluster.pods.values()
               if p.status == apis.PodStatus.PENDING][:k]
    for p in pending:
        try:
            cluster.bind_pod(p.name, f"node-{rng.integers(0, num_nodes)}")
        except RuntimeError:
            pass  # node full — the churn mix, not the refresh, varies
    cluster.tick()


def _wire_totals() -> dict:
    """Cumulative per-reason transfer-ledger aggregates (kai-wire)."""
    from kai_scheduler_tpu.runtime.wire_ledger import LEDGER
    return LEDGER.totals()["by_reason"]


def _wire_delta(before: dict, after: dict, cycles: int) -> dict:
    """Per-cycle (total, patch, redundant) bytes-on-the-wire between
    two ledger totals snapshots — the BENCH_r06+ wire columns."""
    def diff(field, reason=None):
        tot = 0
        for r, t in after.items():
            if reason is not None and r != reason:
                continue
            tot += t[field] - before.get(r, {}).get(field, 0)
        return tot

    n = max(1, cycles)
    return {
        "total": round(diff("bytes") / n),
        "patch": round(diff("bytes", "journal-patch") / n),
        "redundant": round(diff("redundant_bytes") / n),
        "redundant_patch": round(
            diff("redundant_bytes", "journal-patch") / n),
        "dispatches": round(diff("dispatches") / n, 2),
    }


def bench_churn(iters: int) -> dict:
    """Snapshot-refresh latency vs churn — the incremental snapshot
    engine (state/incremental.py) against the full ``build_snapshot``
    host pass at 10k nodes × 50k pods.  Cycle-to-cycle churn at
    production scale is a tiny fraction of the cluster, so the refresh
    should cost O(change): measured at 0.1% / 1% / 10% dirty pods per
    cycle (evictions + new binds + reap ticks) in the post-binder
    steady state (running pods carry concrete devices).  Headline value
    is the 1%-churn p99; ``vs_full`` > 1 means the patch path beats the
    full rebuild (the acceptance bar is ≥ 5x at ≤ 1%)."""
    import numpy as np

    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.runtime.cluster import Cluster
    from kai_scheduler_tpu.state import make_cluster
    from kai_scheduler_tpu.state.cluster_state import build_snapshot
    from kai_scheduler_tpu.state.incremental import IncrementalSnapshotter

    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=10_000, node_accel=8.0, num_gangs=6250,
        tasks_per_gang=8, running_fraction=0.5)
    cursor: dict = {}
    for p in pods:
        if p.status == apis.PodStatus.RUNNING:
            c = cursor.get(p.node, 0)
            p.accel_devices = [c]
            cursor[p.node] = c + 1
    cluster = Cluster.from_objects(nodes, queues, groups, pods, topo)
    snap = IncrementalSnapshotter()
    snap.refresh(cluster, now=cluster.now)

    lists = cluster.snapshot_lists()
    full_times = []
    for _ in range(max(3, iters // 2)):
        t0 = time.perf_counter()
        build_snapshot(*lists, now=cluster.now)
        full_times.append(time.perf_counter() - t0)
    full_p99 = _p99(full_times)

    rng = np.random.default_rng(0)
    extra: dict = {"full_rebuild_p99_ms": round(full_p99, 1)}
    p99_1pct = None
    for frac, label in ((0.001, "0.1pct"), (0.01, "1pct"),
                        (0.10, "10pct")):
        times = []
        before = snap.stats.patched
        wire_before = _wire_totals()
        for _ in range(max(5, iters)):
            _churn_cluster(cluster, rng, frac)
            t0 = time.perf_counter()
            snap.refresh(cluster, now=cluster.now)
            times.append(time.perf_counter() - t0)
        p99 = _p99(times)
        extra[f"refresh_p99_ms_{label}"] = round(p99, 1)
        extra[f"speedup_vs_full_{label}"] = round(full_p99 / p99, 1)
        extra[f"patched_cycles_{label}"] = snap.stats.patched - before
        # kai-wire: measured bytes-on-the-wire per refresh (total /
        # patch-path / redundant re-uploaded-identical — the ROADMAP-1
        # invariant, 0 on the patch path), from the transfer-ledger
        # per-reason deltas over this label's cycles
        extra[f"wire_bytes_per_cycle_{label}"] = _wire_delta(
            wire_before, _wire_totals(), len(times))
        if label == "1pct":
            p99_1pct = p99
            extra["wire_bytes_per_cycle"] = \
                extra["wire_bytes_per_cycle_1pct"]
    extra["fallbacks"] = dict(snap.stats.fallbacks)
    return {"metric": ("incremental snapshot refresh p99 @ 1% churn, "
                       "10k nodes x 50k pods (vs "
                       f"{extra['full_rebuild_p99_ms']} ms full rebuild)"),
            "value": round(p99_1pct, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(p99_1pct, 1e-9), 3),
            "extra": extra}


def bench_phases(iters: int, *, num_nodes: int = 10_000,
                 num_gangs: int = 6250, tasks_per_gang: int = 8) -> dict:
    """Measured per-cycle phase attribution at the headline shape —
    the kai-trace breakdown (snapshot / upload / solve-dispatch /
    device-wait / host-decode / commit) of a full production cycle at
    10k nodes × 50k pods with 1% journaled churn per cycle, so the
    incremental snapshotter stays on the patch path and "upload" is the
    real changed-leaves transfer.  Phases are contiguous checkpoints on
    one clock (framework/scheduler.py), so they sum to the cycle wall
    time by construction; ``coverage`` reports that sum / measured wall
    (the acceptance bar is within 10%)."""
    import numpy as np

    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.runtime.cluster import Cluster
    from kai_scheduler_tpu.state import make_cluster

    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=num_nodes, node_accel=8.0, num_gangs=num_gangs,
        tasks_per_gang=tasks_per_gang, running_fraction=0.5)
    cluster = Cluster.from_objects(nodes, queues, groups, pods, topo)
    sched = Scheduler()
    sched.run_once(cluster)  # compile + warm the incremental cache
    rng = np.random.default_rng(0)

    walls: list[float] = []
    acc: dict[str, list[float]] = {}
    wires: list[tuple[int, int, int, int]] = []
    an_dispatch: list[float] = []
    for _ in range(max(5, iters)):
        _churn_cluster(cluster, rng, 0.01, num_nodes)
        t0 = time.perf_counter()
        res = sched.run_once(cluster)
        walls.append(time.perf_counter() - t0)
        an_dispatch.append(res.analytics_seconds)
        for k, v in res.phase_seconds.items():
            acc.setdefault(k, []).append(v)
        # kai-wire per-cycle summary rides CycleResult.wire
        patch = res.wire["by_reason"].get("journal-patch", {})
        wires.append((res.wire["bytes"], patch.get("bytes", 0),
                      res.wire["redundant_bytes"],
                      patch.get("redundant_bytes", 0)))
    wall_mean = float(np.mean(walls))
    phases_ms = {k: round(float(np.mean(v)) * 1e3, 2)
                 for k, v in acc.items()}
    phase_sum = sum(float(np.mean(v)) for v in acc.values())
    wall_p99 = _p99(walls)
    snap = sched._snapshotter
    extra = {
        "phases_ms": phases_ms,
        "wall_mean_ms": round(wall_mean * 1e3, 2),
        "phase_sum_ms": round(phase_sum * 1e3, 2),
        # phases are contiguous checkpoints, so this is ~1.0 by
        # construction — reported so the artifact PROVES the 10% bar
        "coverage": round(phase_sum / max(wall_mean, 1e-12), 4),
        "snapshot_mode": (dict(snap.stats.last)
                          if snap is not None else {}),
        "patched_cycles": (snap.stats.patched
                           if snap is not None else 0),
        "fallbacks": (dict(snap.stats.fallbacks)
                      if snap is not None else {}),
        # measured bytes-on-the-wire per cycle next to the phase
        # attribution (total / patch-path / redundant) — redundant must
        # read 0 while cycles stay on the patch path (ROADMAP-1's soak
        # invariant, now measured in every BENCH_r06+ artifact)
        "wire_bytes_per_cycle": {
            "total": round(float(np.mean([w[0] for w in wires]))),
            "patch": round(float(np.mean([w[1] for w in wires]))),
            "redundant": round(float(np.mean([w[2] for w in wires]))),
            "redundant_patch": round(
                float(np.mean([w[3] for w in wires]))),
        },
        # kai-cost (analysis/costmodel.py): the fused entry's
        # liveness-model peak-live-bytes traced AT this bench shape —
        # the model-side HBM watermark printed beside the measured
        # wire/phase columns (BENCH_r08+; the tier-1 cross-validation
        # test pins the model's traffic ranking against measured
        # dispatch ordering at canonical shapes)
        "cost_model_peak_mb": _cost_model_peak_mb(sched),
        # kai-comms (analysis/comms.py): the fused entry's modeled
        # collective bytes per cycle at this bench shape, priced for
        # the 8-way virtual mesh — the next MULTICHIP artifact records
        # this column beside the measured per-device wall time so the
        # model's scaling fit can be checked against hardware
        "comm_model_bytes_per_cycle": _comm_model_bytes_per_cycle(
            sched),
        # kai-pulse rides every cycle here (analytics_every=1 default):
        # host dispatch cost of the analytics pass + the BENCH_r06+
        # cluster-health tracking columns from the last cycle
        "analytics_dispatch_ms": round(
            float(np.mean(an_dispatch)) * 1e3, 2),
        "analytics_pct_of_wall": round(
            float(np.mean(an_dispatch)) / max(wall_mean, 1e-12) * 100,
            2),
        "fragmentation": res.analytics.get(
            "fragmentation", {}).get("score"),
        "goodput": res.analytics.get("goodput"),
        "fairness_drift": res.analytics.get(
            "fairness", {}).get("drift_max"),
    }
    return {"metric": (f"cycle phase attribution p99 @ {num_nodes} "
                       f"nodes x {num_gangs * tasks_per_gang} pods, "
                       "1% churn (snapshot/upload/solve-dispatch/"
                       "device-wait/host-decode/commit)"),
            "value": round(wall_p99, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(wall_p99, 1e-9), 3),
            "extra": extra}


def bench_storm(iters: int, *, num_nodes: int = 2000,
                num_gangs: int = 500, tasks_per_gang: int = 4,
                events: int | None = None) -> dict:
    """kai-intake traffic storm (ROADMAP item 3): a burst of pod
    create/delete mutations (default 1M events, ``BENCH_STORM_EVENTS``
    overrides) rides the async multi-lane router — hash-sharded
    bounded lanes, per-lane drain workers running the vectorized
    admission sweep, cycle-boundary coalesce into the hub journal —
    while scheduling cycles keep running against the same cluster.

    Columns: sustained ingest events/s (submit → drain → coalesce, the
    honest end-to-end clock including the final coalesce), cycle p99
    under storm vs quiescent, coalesce p99, and a deliberate-overload
    phase (tiny lanes, no drain headroom) proving the shed valve is
    nonzero and metered while memory stays bounded by the lane caps.

    Environment note: CPU container, GIL-shared producers/workers/cycle
    thread — the ingest figure is a floor, not a ceiling; the
    differential (storm == sequential classic path, bit-identical) is
    pinned by tests/test_intake_router.py, not re-proven here."""
    import threading

    from kai_scheduler_tpu.framework import metrics as _metrics
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.intake.router import IntakeConfig, IntakeRouter
    from kai_scheduler_tpu.runtime.cluster import Cluster
    from kai_scheduler_tpu.state import make_cluster

    events = int(events if events is not None
                 else os.environ.get("BENCH_STORM_EVENTS", 1_000_000))
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=num_nodes, node_accel=8.0, num_gangs=num_gangs,
        tasks_per_gang=tasks_per_gang, running_fraction=0.5)
    cluster = Cluster.from_objects(nodes, queues, groups, pods, topo)
    sched = Scheduler()
    for _ in range(3):  # compile every late-arriving entry (victim
        sched.run_once(cluster)  # paths, analytics, repack probes)
    # -- quiescent cycle p99 (no storm, same cluster/scheduler) ------
    quiescent = []
    for _ in range(max(5, iters)):
        t0 = time.perf_counter()
        sched.run_once(cluster)
        quiescent.append(time.perf_counter() - t0)
    q_p99 = _p99(quiescent)

    # -- the storm ---------------------------------------------------
    router = IntakeRouter(IntakeConfig(
        lanes=4, lane_capacity=1 << 17, batch=1024)).start()
    chunk = 500
    n_chunks = max(1, events // (2 * chunk))  # create + delete pairs
    producers = 2
    accepted = [0] * producers

    def produce(tid: int) -> None:
        for c in range(tid, n_chunks, producers):
            names = [f"storm-{c}-{i}" for i in range(chunk)]
            creates = [("upsert", "pods",
                        nm, {"name": nm, "group": f"storm-g{c % 64}",
                             "resources": {"accel": 1.0, "cpu": 1.0,
                                           "memory": 1.0}})
                       for nm in names]
            deletes = [("delete", "pods", nm, nm) for nm in names]
            for ops in (creates, deletes):
                out = router.submit_ops(ops)
                accepted[tid] += out["accepted"]
                while out["shed"]:  # bounded lanes: wait, don't drop
                    time.sleep(0.002)
                    out = router.submit_ops(out["shed_ops"])
                    accepted[tid] += out["accepted"]

    storm_cycles: list[float] = []
    coalesce_s: list[float] = []
    cycle_period = 0.25  # pace cycles like a schedule period — the
    t_start = time.perf_counter()  # storm streams between boundaries
    threads = [threading.Thread(target=produce, args=(t,), daemon=True)
               for t in range(producers)]
    for t in threads:
        t.start()
    next_cycle = t_start
    while any(t.is_alive() for t in threads):
        now = time.perf_counter()
        if now < next_cycle:
            time.sleep(min(0.01, next_cycle - now))
            continue
        next_cycle = now + cycle_period
        t0 = time.perf_counter()
        summary = router.coalesce(cluster)
        sched.run_once(cluster)
        storm_cycles.append(time.perf_counter() - t0)
        coalesce_s.append(summary["seconds"])
    for t in threads:
        t.join()
    router.drain_inline(timeout=120)
    final = router.coalesce(cluster)
    coalesce_s.append(final["seconds"])
    wall = time.perf_counter() - t_start
    router.stop()
    total_accepted = sum(accepted)
    health = router.health()
    ingest_eps = health["coalesced_events"] / max(wall, 1e-9)

    # -- deliberate overload: tiny lanes, no drain headroom ----------
    # metric check is a DELTA over this phase: the main storm already
    # incremented the process-global shed counter (producers overflow
    # + retry), so an absolute read could mask a metering regression
    shed_metric_before = (_metrics.intake_shed.value("0")
                          + _metrics.intake_shed.value("1"))
    shed_router = IntakeRouter(IntakeConfig(lanes=2, lane_capacity=2048))
    shed_submitted = 0
    for c in range(64):
        ops = [("upsert", "pods", f"over-{c}-{i}",
                {"name": f"over-{c}-{i}", "group": "over-g"})
               for i in range(500)]
        shed_submitted += len(ops)
        shed_router.submit_ops(ops)
    shed_health = shed_router.health()
    shed_frac = shed_health["shed"] / max(shed_submitted, 1)

    # quiescent boundary overhead: a coalesce with nothing staged is
    # what every cycle pays once the storm is over — it must be noise
    # (microseconds) next to the cycle itself, or intake would tax
    # every quiet cycle
    empty = []
    idle_router = IntakeRouter(IntakeConfig(lanes=4))
    for _ in range(50):
        t0 = time.perf_counter()
        idle_router.coalesce(cluster)
        empty.append(time.perf_counter() - t0)
    empty_us = round(_p99(empty) * 1000.0, 1)

    storm_p99 = _p99(storm_cycles) if storm_cycles else 0.0
    extra = {
        "events_requested": events,
        "events_accepted": total_accepted,
        "events_coalesced": health["coalesced_events"],
        "storm_wall_s": round(wall, 2),
        "ingest_events_per_s": round(ingest_eps),
        "quiescent_cycle_p99_ms": round(q_p99, 1),
        "storm_cycle_p99_ms": round(storm_p99, 1),
        "storm_cycles": len(storm_cycles),
        "coalesce_p99_ms": round(_p99(coalesce_s), 1),
        "empty_coalesce_p99_us": empty_us,
        "lane_rejected": health["rejected"],
        "overload_shed_fraction": round(shed_frac, 3),
        "overload_shed_events": shed_health["shed"],
        "overload_metered": (_metrics.intake_shed.value("0")
                             + _metrics.intake_shed.value("1")
                             - shed_metric_before) > 0,
        "environment_note": (
            "CPU-only container, GIL-shared producer/worker/cycle "
            "threads; ingest includes drain + admission + final "
            "coalesce.  Cycle p99 under storm includes the coalesce."),
    }
    return {"metric": (f"kai-intake sustained ingest @ {events} "
                       f"create/delete storm vs {num_nodes} nodes x "
                       f"{num_gangs * tasks_per_gang} pods cycling "
                       f"(quiescent cycle p99 {round(q_p99, 1)} ms, "
                       f"storm {round(storm_p99, 1)} ms)"),
            "value": round(ingest_eps),
            "unit": "events/s",
            # the ROADMAP-3 bar: >= 100k events/s sustained → >= 1.0
            "vs_baseline": round(ingest_eps / 100_000.0, 3),
            "extra": extra}


def _frag_cluster_10k(num_racks: int = 40, nodes_per_rack: int = 250,
                      node_accel: int = 8, fill: int = 7,
                      gang_pods: int = 256, preemptible: bool = False):
    """A fragmented 10k-node cluster (ROADMAP item 5's scenario,
    pre-staged): every node holds ``fill``/``node_accel`` devices of
    NON-preemptible fillers, so each rack strands ``nodes_per_rack``
    single free devices — a rack-required ``gang_pods``-pod gang is
    cluster-feasible (10k free devices) but unplaceable in any single
    rack until capacity consolidates."""
    from kai_scheduler_tpu.apis import types as apis
    from kai_scheduler_tpu.runtime.cluster import Cluster
    level = "topo/rack"
    topo = apis.Topology(name="default",
                         levels=[level, "kubernetes.io/hostname"])
    nodes, pods, groups = [], [], []
    queues = [
        apis.Queue("fill", accel=apis.QueueResource(
            quota=float(num_racks * nodes_per_rack * fill))),
        apis.Queue("big", accel=apis.QueueResource(
            quota=float(gang_pods)))]
    for rack in range(num_racks):
        g = apis.PodGroup(
            f"fill-{rack}", queue="fill",
            min_member=nodes_per_rack * fill,
            preemptibility=(apis.Preemptibility.PREEMPTIBLE
                            if preemptible
                            else apis.Preemptibility.NON_PREEMPTIBLE),
            last_start_timestamp=0.0)
        groups.append(g)
        for j in range(nodes_per_rack):
            i = rack * nodes_per_rack + j
            name = f"node-{i}"
            nodes.append(apis.Node(
                name, apis.ResourceVec(node_accel, 64, 256),
                labels={level: f"rack-{rack}",
                        "kubernetes.io/hostname": name}))
            for t in range(fill):
                pods.append(apis.Pod(
                    f"fill-{i}-{t}", g.name, apis.ResourceVec(1, 1, 4),
                    status=apis.PodStatus.RUNNING, node=name))
    gang = apis.PodGroup(
        "big-gang", queue="big", min_member=gang_pods,
        topology_constraint=apis.TopologyConstraint(
            topology="default", required_level=level))
    groups.append(gang)
    for t in range(gang_pods):
        pods.append(apis.Pod(f"big-{t}", "big-gang",
                             apis.ResourceVec(1, 1, 4)))
    return Cluster.from_objects(nodes, queues, groups, pods, topo)


def bench_frag(iters: int, **scale) -> dict:
    """kai-pulse fragmentation scenario @ 10k nodes / 70k running pods:
    a rack-required 256-pod gang is unplaceable while ~10k free devices
    sit stranded one-per-node across 40 racks.  Measures the full cycle
    p99 WITH the analytics pass against an analytics-off twin (the
    <10%-overhead acceptance bar), proves the fragmentation gauge is
    predictive (high while stranded, dropping once a rack frees), and —
    BENCH_r06+ — runs the kai-repack solver on a movable-filler twin
    (repack_solve_ms / migrations_per_unblocked_gang /
    cycles_to_unblock) plus a repack-off twin proving zero overhead and
    identical wire bytes while the trigger sits below threshold."""
    import numpy as np

    from kai_scheduler_tpu.binder import Binder
    from kai_scheduler_tpu.framework.scheduler import (Scheduler,
                                                       SchedulerConfig)
    gang_pods = scale.get("gang_pods", 256)

    def timed_cycles(every: int, repack_enable: bool = True,
                     repack_threshold: float = 1.1):
        # repack idles through the timed loop: the threshold sits above
        # any possible score, so enabled-vs-disabled twins measure the
        # trigger's pure host overhead (the zero-overhead bar)
        cluster = _frag_cluster_10k(**scale)
        sched = Scheduler(SchedulerConfig(
            analytics_every=every, repack_enable=repack_enable,
            repack_frag_threshold=repack_threshold))
        res = sched.run_once(cluster)  # compile
        times, an_s, wire = [], [], []
        for _ in range(max(3, iters)):
            t0 = time.perf_counter()
            res = sched.run_once(cluster)
            times.append(time.perf_counter() - t0)
            an_s.append(res.analytics_seconds)
            wire.append(res.wire["bytes"])
        return _p99(times), float(np.mean(an_s)), res, sched, cluster, \
            wire

    p99_on, analytics_ms, res, sched, cluster, wire_on = \
        timed_cycles(every=1)
    analytics_ms *= 1e3
    p99_off, _, _, _, _, _ = timed_cycles(every=0)
    frag = res.analytics["fragmentation"]
    stranded = {
        "score": frag["score"],
        "largest_rack_unit_pods": frag["largest_rack_unit_pods"],
        "total_unit_pods": frag["total_unit_pods"],
        "rung256_cluster_feasible": [
            r["cluster_feasible"] for r in frag["gang_ladder"]
            if r["pods"] == 256][0],
        "rung256_rack_placeable": [
            r["rack_placeable"] for r in frag["gang_ladder"]
            if r["pods"] == 256][0],
    }
    # free one rack: evict 6 fillers on distinct rack-0 nodes so the
    # rack holds 256 whole devices, reap, rerun — the gang must place
    # and the gauge must drop
    for i in range(6):
        cluster.evict_pod(f"fill-{i}-0")
    cluster.tick()
    cluster.tick()
    res2 = sched.run_once(cluster)
    frag2 = res2.analytics["fragmentation"]

    # --- kai-repack columns (BENCH_r06+) ------------------------------
    # (a) zero-overhead twin: the headline run above is repack-ENABLED
    # with the gauge pinned below its threshold (repack_threshold=1.1),
    # so comparing it to a repack-DISABLED twin measures the trigger's
    # whole untriggered cost — wall time and wire bytes must match
    p99_rp_off, _, _, _, _, wire_off = timed_cycles(
        every=1, repack_enable=False)
    repack_off_twin = {
        "p99_ms_repack_idle": round(p99_on, 1),
        "p99_ms_repack_off": round(p99_rp_off, 1),
        "wire_bytes_identical": wire_off == wire_on,
    }
    # (b) proactive unblock: the SAME scenario with movable fillers and
    # consolidation excluded (isolating the proactive path) — cycles
    # from trigger firing to the 256-pod gang's placement
    rp_cluster = _frag_cluster_10k(preemptible=True, **scale)
    rp_sched = Scheduler(SchedulerConfig(
        actions=("allocate", "reclaim", "preempt", "stalegangeviction"),
        repack_frag_threshold=0.2, repack_trigger_cycles=2,
        repack_cooldown=4))
    binder = Binder()
    # warm the solver's compile cache at the production shapes (a
    # throwaway scheduler on a cluster copy, trigger tuned to fire on
    # its 2nd cycle) so the recorded repack_solve_ms is the
    # steady-state dispatch cost, not trace+XLA-compile of the
    # first-ever firing
    import copy
    warm_cluster = copy.deepcopy(rp_cluster)
    warm_sched = Scheduler(SchedulerConfig(
        actions=("allocate", "reclaim", "preempt", "stalegangeviction"),
        repack_frag_threshold=0.2, repack_trigger_cycles=1,
        repack_cooldown=0))
    warm_sched.run_once(warm_cluster)
    warm_sched.run_once(warm_cluster)
    fired = placed = None
    solve_ms = migrations = 0.0
    for cyc in range(1, 12):
        r = rp_sched.run_once(rp_cluster)
        if r.repack and fired is None:
            fired = cyc
            solve_ms = r.repack_seconds * 1e3
            migrations = r.repack["migrations_executed"]
        if sum(b.pod_name.startswith("big-")
               for b in r.bind_requests) >= gang_pods:
            placed = cyc
            break
        binder.reconcile(rp_cluster)
        rp_cluster.tick()
    repack_cols = {
        "repack_solve_ms": round(solve_ms, 2),
        "migrations_per_unblocked_gang": migrations,
        "cycles_to_unblock": (placed - fired
                              if placed and fired else None),
        "unblocked": bool(placed),
    }
    extra = {
        "p99_ms_analytics_off": round(p99_off, 1),
        "analytics_dispatch_ms": round(analytics_ms, 2),
        "analytics_overhead_pct": round(
            (p99_on - p99_off) / max(p99_off, 1e-9) * 100.0, 1),
        "stranded": stranded,
        "freed": {"score": frag2["score"],
                  "largest_rack_unit_pods":
                      frag2["largest_rack_unit_pods"],
                  "binds": len(res2.bind_requests)},
        # the BENCH_r06+ tracking columns
        "fragmentation": stranded["score"],
        "goodput": res.analytics["goodput"],
        "fairness_drift": res.analytics["fairness"]["drift_max"],
        "predictive": bool(
            stranded["score"] > frag2["score"]
            and len(res2.bind_requests) >= gang_pods),
        "repack": repack_cols,
        "repack_off_twin": repack_off_twin,
    }
    return {"metric": ("frag cycle p99 @ 10k nodes / 70k running pods, "
                       "256-pod rack-required gang stranded "
                       "(analytics ON; gauge "
                       f"{stranded['score']}→{frag2['score']} after "
                       "rack freed)"),
            "value": round(p99_on, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(p99_on, 1e-9), 3),
            "extra": extra}


def bench_e2e(iters: int) -> dict:
    """Full production cycle — snapshot → default action pipeline →
    commit, measured as ONE wall-clock number per cycle (the VERDICT r2
    gap: the kernel met the bar while the host path cost seconds).

    Runs on a SATURATED shape — running pods fill the cluster exactly
    (40k running pods x 1 accel = 10k nodes x 4), the 10k pending pods sit
    in under-served queues — so allocate fails capacity, reclaim finds
    real victims, and preempt/consolidation/stale all execute: the
    worst-case production cycle.  Cluster state is restored between
    cycles outside the timed region.  Reports the host/device split
    alongside p99.
    """
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.runtime.cluster import Cluster
    from kai_scheduler_tpu.state import make_cluster
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=10_000, node_accel=4.0, num_gangs=6250, tasks_per_gang=8,
        running_fraction=0.8, queue_accel_quota=1000.0,
        partition_queues_by_running=True)
    cluster = Cluster.from_objects(nodes, queues, groups, pods, topo)
    # restorable bits mutated by a cycle: pod status/devices, group flags
    pod_state = {p.name: (p.status, p.node, tuple(p.accel_devices))
                 for p in pods}
    grp_state = {g.name: (g.fit_failures, g.unschedulable, g.phase,
                          g.last_start_timestamp) for g in groups}

    def restore():
        cluster.bind_requests.clear()
        cluster.restarting.clear()
        for p in pods:
            st, nd, devs = pod_state[p.name]
            p.status, p.node, p.accel_devices = st, nd, list(devs)
        for g in groups:
            (g.fit_failures, g.unschedulable, g.phase,
             g.last_start_timestamp) = grp_state[g.name]

    import numpy as np
    sched = Scheduler()
    res = sched.run_once(cluster)  # compile
    times, opens, commits = [], [], []
    for _ in range(iters):
        restore()
        t0 = time.perf_counter()
        res = sched.run_once(cluster)
        times.append(time.perf_counter() - t0)
        opens.append(res.open_seconds)
        commits.append(res.commit_seconds)
    p99 = _p99(times)
    pipelined = int(np.asarray(res.tensors.pipelined).sum())
    return {"metric": ("END-TO-END cycle p99 @ 10k nodes x 50k pods, "
                       "saturated worst case (snapshot+actions+commit; "
                       f"{len(res.bind_requests)} binds, "
                       f"{pipelined} pipelined onto victim capacity, "
                       f"{len(res.evictions)} evictions; "
                       f"open {_p99(opens):.0f} ms, "
                       f"commit+sync {_p99(commits):.0f} ms)"),
            "value": round(p99, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(p99, 1e-9), 3)}


def bench_e2e_alloc(iters: int) -> dict:
    """Full cycle on the HEADLINE allocate shape (empty cluster, 50k
    pending) — isolates the host path (snapshot build + commit
    translation) around the allocate kernel; victim actions run but find
    nothing.  This is the shape VERDICT r2 measured at ~9 s host cost."""
    from kai_scheduler_tpu.framework.scheduler import Scheduler
    from kai_scheduler_tpu.runtime.cluster import Cluster
    from kai_scheduler_tpu.state import make_cluster
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=10_000, node_accel=8.0, num_gangs=6250, tasks_per_gang=8)
    cluster = Cluster.from_objects(nodes, queues, groups, pods, topo)
    grp_state = {g.name: (g.fit_failures, g.unschedulable, g.phase,
                          g.last_start_timestamp) for g in groups}
    sched = Scheduler()
    res = sched.run_once(cluster)  # compile
    times, opens, commits = [], [], []
    for _ in range(iters):
        cluster.bind_requests.clear()
        for g in groups:
            (g.fit_failures, g.unschedulable, g.phase,
             g.last_start_timestamp) = grp_state[g.name]
        t0 = time.perf_counter()
        res = sched.run_once(cluster)
        times.append(time.perf_counter() - t0)
        opens.append(res.open_seconds)
        commits.append(res.commit_seconds)
    p99 = _p99(times)
    return {"metric": ("END-TO-END cycle p99 @ 10k nodes x 50k pending "
                       "pods, allocate-heavy (snapshot+actions+commit; "
                       f"{len(res.bind_requests)} binds; "
                       f"open {_p99(opens):.0f} ms, "
                       f"commit+sync {_p99(commits):.0f} ms)"),
            "value": round(p99, 3), "unit": "ms",
            "vs_baseline": round(50.0 / max(p99, 1e-9), 3)}


def bench_twin(iters: int) -> dict:
    """kai-twin replay throughput: a mid-size fuzz-generated stream
    driven through the twin replayer, raw (digest=False) vs through
    the full differential oracle — reports events/s and the oracle's
    digesting overhead."""
    from kai_scheduler_tpu.twin import fuzz, replay as twin_replay
    stream = fuzz.generate("diurnal", seed=0, scale=2.0)
    twin_replay.replay(stream, digest=False)  # compile
    raw_eps, oracle_eps = [], []
    ok = True
    for _ in range(max(1, iters // 3)):
        r = twin_replay.replay(stream, digest=False)
        raw_eps.append(r.events_per_s)
        v = twin_replay.oracle(stream)
        ok = ok and v["ok"]
        oracle_eps.append(
            (v["replay"]["events_per_s"] + v["verify"]["events_per_s"])
            / 2)
    raw = max(raw_eps)
    withd = max(oracle_eps)
    overhead_pct = 100.0 * (raw - withd) / max(raw, 1e-9)
    return {"metric": ("kai-twin replay events/s (raw, digest off) on "
                       f"a {len(stream.events)}-event diurnal stream; "
                       f"oracle overhead {overhead_pct:.1f}%, "
                       f"bit-exact={ok}"),
            "value": round(raw, 1), "unit": "events/s",
            "vs_baseline": round(raw / 1000.0, 3),
            "extra": {"twin": {
                "events": len(stream.events),
                "raw_events_per_s": round(raw, 1),
                "oracle_events_per_s": round(withd, 1),
                "oracle_overhead_pct": round(overhead_pct, 1),
                "oracle_ok": ok}}}


CONFIGS = {
    "1": bench_fairshare, "fairshare": bench_fairshare,
    "2": bench_scoring, "scoring": bench_scoring,
    "3": bench_gang, "gang": bench_gang,
    "4": bench_topology, "topology": bench_topology,
    "5": bench_reclaim, "reclaim": bench_reclaim,
    # quick single-config target for the victim-wavefront hot path
    # (BENCH_CONFIG=preempt — same config as the full artifact's
    # preempt_many_queues row)
    "preempt": bench_preempt_many_queues,
    "preempt_many_queues": bench_preempt_many_queues,
    "churn": bench_churn,
    "phases": bench_phases,
    "frag": bench_frag,
    "storm": bench_storm,
    "headline": bench_headline,
    "e2e": bench_e2e,
    "e2e_alloc": bench_e2e_alloc,
    "twin": bench_twin,
}


def _load_artifact(path: str) -> dict:
    """Read a previous driver artifact — either the raw JSON line or the
    driver's wrapper ({"parsed": {...}})."""
    with open(path) as f:
        doc = json.load(f)
    return doc.get("parsed", doc)


def _compare(cur: dict, prev_path: str) -> dict:
    """benchstat-style per-config deltas vs a previous artifact (ref the
    reference's `make benchstat` comparison across counts,
    ``Makefile:124-130``): negative delta_pct = faster.  Folded into the
    artifact's extra AND printed as a table to stderr."""
    prev = _load_artifact(prev_path)
    pe, ce = prev.get("extra", {}), cur.get("extra", {})
    single = os.environ.get("BENCH_CONFIG")
    if single in ("fairshare", "scoring", "gang", "topology", "reclaim",
                  "preempt", "preempt_many_queues", "churn",
                  "1", "2", "3", "4", "5"):
        # single-config run: compare ONLY against the matching prev row
        names = {"1": "fairshare", "2": "scoring", "3": "gang",
                 "4": "topology", "5": "reclaim",
                 "preempt": "preempt_many_queues"}
        name = names.get(single, single)
        return_rows = {name: (pe.get(name, {}).get("p99_ms"),
                              cur.get("value"))}
        rows = return_rows
    else:
        rows = {"headline": (prev.get("value"), cur.get("value"))}
        for name in ("fairshare", "scoring", "gang", "topology",
                     "reclaim"):
            rows[name] = (pe.get(name, {}).get("p99_ms"),
                          ce.get(name, {}).get("p99_ms"))
        pc = pe.get("headline_per_cycle", {})
        cc = ce.get("headline_per_cycle", {})
        rows["per_cycle"] = (pc.get("sync_p99_ms", pc.get("p99_ms")),
                             cc.get("sync_p99_ms", cc.get("p99_ms")))
    out = {}
    print(f"vs {os.path.basename(prev_path)}:", file=sys.stderr)
    for name, (p, c) in rows.items():
        if p is None or c is None:
            continue
        delta = (c - p) / p * 100.0 if p else 0.0
        out[name] = {"prev_ms": p, "cur_ms": c,
                     "delta_pct": round(delta, 1)}
        print(f"  {name:12s} {p:9.2f}ms -> {c:9.2f}ms  "
              f"{delta:+6.1f}%", file=sys.stderr)
    return out


def main() -> None:
    from kai_scheduler_tpu.runtime import compile_cache
    compile_cache.enable()
    # every number this file prints is a device time: no TPU, no run
    stamp = _device_stamp()
    if stamp["platform"] != "tpu":
        sys.exit(f"bench.py measures the chip; JAX found {stamp}")
    quick = "--quick" in sys.argv
    compare_to = None
    if "--compare" in sys.argv:
        compare_to = sys.argv[sys.argv.index("--compare") + 1]
    which = os.environ.get("BENCH_CONFIG",
                           "gang" if quick else "full")
    iters = int(os.environ.get("BENCH_ITERS", 3 if quick else 10))
    if which == "full":
        out = bench_headline_full(iters)
        if compare_to:
            out["extra"]["vs_prev"] = _compare(out, compare_to)
        print(json.dumps({**out, "device": stamp}))
        return
    if which == "all":
        for name in ("fairshare", "scoring", "gang", "topology", "reclaim",
                     "e2e", "e2e_alloc"):
            print(json.dumps({**CONFIGS[name](iters), "device": stamp}),
                  file=sys.stderr)
        print(json.dumps({**bench_headline(iters), "device": stamp}))
        return
    out = CONFIGS[which](iters)
    if compare_to:
        out.setdefault("extra", {})["vs_prev"] = _compare(out, compare_to)
    print(json.dumps({**out, "device": stamp}))


if __name__ == "__main__":
    main()
