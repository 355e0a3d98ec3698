"""kai-twin replayer + bit-exact differential oracle.

The replayer drives a FRESH ``Scheduler`` + ``Cluster`` through a
recorded stream using the same shared apply path the live server uses
(``intake/apply.py`` — PR 12's choke point), so twin-vs-live is a
shared-code identity rather than a parallel reimplementation.  Every
``cycle`` event produces a :func:`cycle_digest`: the commit set (binds
+ evictions, in commit order), the cycle's DecisionLog events, the
journal generation and the consumed cursor batch, the canonicalized
analytics document, the cluster clock, and the kai-twin
``(cycle_index, cycle_seed)`` determinism anchors.

The **differential oracle** (:func:`oracle`) replays a stream twice and
diffs the digest sequences field-by-field — any divergence is a
determinism bug by definition (same stream, same code).  The live
differential (``tests/test_twin.py``) computes the SAME digests on the
live run via :func:`cycle_digest` and diffs them against the replay of
the recorded stream — the twin == live bit-exactness bar.
"""
from __future__ import annotations

import dataclasses
import time

from .. import conf as conf_mod
from ..framework.scheduler import Scheduler, SchedulerConfig
from ..intake import apply as intake_apply
from ..runtime.cluster import Cluster
from ..runtime.snapshot import load_cluster
from . import stream as stream_mod

#: the journal cursor fields the oracle compares (state/incremental.py
#: ``JournalBatch`` — sets/lists of dirty keys plus the time flag)
CURSOR_FIELDS = ("pods_dirty", "pods_added", "pods_removed",
                 "gangs_dirty", "gangs_added", "gangs_removed",
                 "nodes_dirty", "structural", "time_dirty")

#: DecisionLog event fields digested per cycle (runtime/events.py)
_DECISION_FIELDS = ("gang", "queue", "outcome", "detail")


def _plain(x):
    """Canonicalize a value for digesting: numpy scalars → python,
    containers recursed, everything else passed through."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    item = getattr(x, "item", None)
    if callable(item) and getattr(x, "shape", None) == ():
        return x.item()
    return x


def _canon_analytics(doc: dict) -> dict:
    """The analytics document minus wall-clock noise: any ``*seconds``
    key is a timing, excluded from bit-exactness (the oracle compares
    DECISIONS, not how long they took to compute)."""
    def strip(d):
        if isinstance(d, dict):
            return {k: strip(v) for k, v in d.items()
                    if not str(k).endswith("seconds")}
        if isinstance(d, (list, tuple)):
            return [strip(v) for v in d]
        return _plain(d)
    return strip(doc or {})


def _batch_doc(batch) -> dict:
    out = {}
    for f in CURSOR_FIELDS:
        v = getattr(batch, f)
        out[f] = bool(v) if isinstance(v, bool) else sorted(v)
    return out


def cycle_digest(cluster, scheduler, result, batch) -> dict:
    """Everything one cycle decided, in a comparable form.  Binds and
    evictions keep their COMMIT ORDER (stronger than set equality);
    DecisionLog events are the cycle's own, sorted (the log may cap and
    drop — order within a cycle is presentation, membership is not)."""
    evs = scheduler.decisions.events(limit=100000)
    cycles = [e["cycle"] for e in evs]
    last = max(cycles, default=None)
    decisions = sorted(tuple(e[f] for f in _DECISION_FIELDS)
                       for e in evs if e["cycle"] == last)
    return {
        "cycle_index": result.cycle_index,
        "cycle_seed": result.cycle_seed,
        "now": cluster.now,
        "binds": [(br.pod_name, br.selected_node,
                   br.received_resource_type.value,
                   _plain(br.received_accel_count),
                   _plain(br.received_accel_portion),
                   _plain(br.received_accel_memory_gib),
                   tuple(br.selected_accel_groups or ()))
                  for br in (list(result.bind_requests)
                             + list(result.move_bind_requests))],
        "evictions": [(ev.pod_name, ev.group, ev.move_to)
                      for ev in result.evictions],
        "decisions": decisions,
        "journal_generation": cluster.journal.generation,
        "cursor": _batch_doc(batch),
        "analytics": _canon_analytics(result.analytics),
    }


def diff_digests(a: list[dict], b: list[dict], limit: int = 20) -> list[str]:
    """Field-by-field divergence report between two digest sequences —
    empty means bit-exact."""
    out: list[str] = []
    if len(a) != len(b):
        out.append(f"cycle count diverged: {len(a)} != {len(b)}")
    for i, (da, db) in enumerate(zip(a, b)):
        for key in sorted(da.keys() | db.keys()):
            if da.get(key) != db.get(key):
                out.append(f"cycle[{i}].{key} diverged: "
                           f"{da.get(key)!r} != {db.get(key)!r}")
                if len(out) >= limit:
                    out.append("... (diff truncated)")
                    return out
    return out


@dataclasses.dataclass
class ReplayReport:
    """One replay run's outcome (``doc()`` is the /debug/twin form)."""

    digests: list[dict] = dataclasses.field(default_factory=list)
    events_applied: int = 0
    apply_errors: int = 0
    cycles: int = 0
    wall_seconds: float = 0.0
    cluster: Cluster | None = None
    scheduler: Scheduler | None = None

    @property
    def events_per_s(self) -> float:
        return self.events_applied / max(self.wall_seconds, 1e-9)

    def doc(self) -> dict:
        return {"events_applied": self.events_applied,
                "apply_errors": self.apply_errors,
                "cycles": self.cycles,
                "wall_seconds": round(self.wall_seconds, 6),
                "events_per_s": round(self.events_per_s, 1)}


def replay_config(stream: stream_mod.Stream,
                  base: SchedulerConfig | None = None,
                  overlay: dict | None = None) -> SchedulerConfig:
    """The replaying scheduler's config: stream overlay over ``base``
    (over compiled defaults), an extra ``overlay`` doc on top (the
    tuner's candidate), and the stream's seed pinned last so the
    determinism anchor always comes from the stream header."""
    cfg = conf_mod.load_config(stream.config, base=base)
    if overlay:
        cfg = conf_mod.load_config(overlay, base=cfg)
    return dataclasses.replace(cfg, seed=stream.seed)


def replay(stream: stream_mod.Stream,
           base: SchedulerConfig | None = None,
           overlay: dict | None = None,
           pace_s: float = 0.0,
           digest: bool = True,
           on_cycle=None) -> ReplayReport:
    """Drive a fresh scheduler through the stream.

    ``pace_s`` > 0 sleeps that long after every cycle event (paced
    replay for live-dashboard demos); 0 replays as fast as possible.
    ``digest=False`` skips per-cycle digesting — the raw-throughput
    mode ``bench.py twin`` measures oracle overhead against.
    ``on_cycle(cluster, result, digest_or_None)`` runs after each
    cycle — the fuzzer's per-cycle invariant probe.
    """
    from ..framework import metrics
    cfg = replay_config(stream, base=base, overlay=overlay)
    cluster = (load_cluster(stream.snapshot) if stream.snapshot
               else Cluster())
    sched = Scheduler(cfg)
    cursor = cluster.journal.register()
    cursor.consume()  # the snapshot itself is not a delta
    report = ReplayReport(cluster=cluster, scheduler=sched)
    errors: list = []
    t0 = time.perf_counter()
    for ev in stream.events:
        op = ev["op"]
        if op == "events":
            report.events_applied += intake_apply.apply_events(
                cluster,
                [tuple(e) for e in ev["events"]], errors=errors)
        elif op == "delta":
            report.events_applied += intake_apply.apply_events(
                cluster, intake_apply.decompose_delta(ev["delta"]),
                errors=errors)
        elif op == "tick":
            cluster.tick(float(ev["seconds"]))
        elif op == "reconcile":
            from ..binder.binder import Binder
            Binder().reconcile(cluster)
        elif op == "cycle":
            result = sched.run_once(cluster)
            report.cycles += 1
            d = None
            if digest:
                d = cycle_digest(cluster, sched, result,
                                 cursor.consume())
                report.digests.append(d)
            if on_cycle is not None:
                on_cycle(cluster, result, d)
            if pace_s > 0:
                time.sleep(pace_s)
    report.wall_seconds = time.perf_counter() - t0
    report.apply_errors = len(errors)
    metrics.twin_replayed_events.inc(by=report.events_applied)
    metrics.twin_replay_cycles.inc(by=report.cycles)
    return report


def oracle(stream: stream_mod.Stream,
           base: SchedulerConfig | None = None,
           overlay: dict | None = None) -> dict:
    """The determinism oracle: replay the stream twice through the
    shared apply path and diff the digest sequences.  Returns the
    verdict document (``/debug/twin``'s ``last_replay``)."""
    from ..framework import metrics
    ra = replay(stream, base=base, overlay=overlay)
    rb = replay(stream, base=base, overlay=overlay)
    divergences = diff_digests(ra.digests, rb.digests)
    checks = len(ra.digests) * 8  # digest fields compared per cycle
    metrics.twin_oracle_checks.inc(by=checks)
    if divergences:
        metrics.twin_oracle_divergences.inc(by=len(divergences))
    return {"ok": not divergences,
            "checks": checks,
            "divergences": divergences,
            "replay": ra.doc(),
            "verify": rb.doc()}
