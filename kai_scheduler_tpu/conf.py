"""Config layering — a YAML/JSON document merged over compiled defaults.

The reference loads a ``SchedulerConfiguration`` document from a
ConfigMap and merges it over built-in defaults
(``conf_util/scheduler_conf_util.go:36-90``: the default actions string
and plugin tiers; absent fields keep defaults), with a pflag CLI on top
(``cmd/scheduler/app/options/options.go:90-131``).  This module is that
stack for the TPU scheduler: ``load_config`` parses the same document
shape (``actions`` string, ``tiers`` with per-plugin ``arguments``,
``queueDepthPerAction``, usage-db / kValue knobs) into a
:class:`~kai_scheduler_tpu.framework.scheduler.SchedulerConfig`, and
``kai_scheduler_tpu.__main__`` is the CLI entry point.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any

from .framework.scheduler import SchedulerConfig, action_names
from .framework.session import SessionConfig
from .ops.scoring import PlacementConfig
from .plugins import registry

#: ref ``conf_util/scheduler_conf_util.go:37`` defaultSchedulerConf
DEFAULT_ACTIONS = "allocate, consolidation, reclaim, preempt, stalegangeviction"


def parse_document(text: str) -> dict:
    """Parse a YAML (or JSON — a YAML subset) config document."""
    # lazy on purpose: PyYAML is optional — JSON-only deployments (and
    # the sidecar wire path) never pay or require the dependency
    import yaml  # kai-lint: disable=KAI052
    doc = yaml.safe_load(text)
    if doc is None:
        return {}
    if not isinstance(doc, dict):
        raise ValueError("scheduler config document must be a mapping")
    return doc


def _parse_actions(spec: str) -> tuple[str, ...]:
    acts = tuple(s for s in spec.replace(",", " ").split() if s)
    known = set(action_names())
    unknown = [a for a in acts if a not in known]
    if unknown:
        raise ValueError(
            f"unknown actions {unknown}; registered: {sorted(known)}")
    return acts


def _merge_tiers(doc_tiers: list, session: SessionConfig) -> SessionConfig:
    """Apply the ConfigMap ``tiers`` list: plugin ORDER/selection for the
    score registry, plus per-plugin ``arguments`` (nodeplacement's
    binpack/spread — ref ``conf_util/scheduler_conf_util.go:54-57`` —
    gpupack/gpuspread, and proportion's kValue)."""
    names: list[str] = []
    placement = session.allocate.placement
    k_value = session.k_value
    for tier in doc_tiers or []:
        for plugin in tier.get("plugins", []):
            name = plugin["name"]
            args = plugin.get("arguments") or {}
            if name == "nodeplacement":
                placement = dataclasses.replace(
                    placement,
                    binpack_accel=args.get("gpu", "binpack") == "binpack",
                    binpack_cpu=args.get("cpu", "binpack") == "binpack")
            elif name == "gpupack":
                placement = dataclasses.replace(placement, device_pack=True)
            elif name == "gpuspread":
                placement = dataclasses.replace(placement,
                                                device_pack=False)
            elif name == "proportion":
                k_value = float(args.get("kValue", k_value))
            names.append(name)
    # score-registry plugins keep the configured order; the rest of the
    # reference's plugin list is compiled into the kernels (predicates,
    # topology, elastic, ... — see SURVEY §2.5 rows) and participates
    # whenever the snapshot carries the matching constraints.
    scoreable = set(registry.available_plugins())
    tiers = tuple(n for n in names if n in scoreable)
    if tiers:
        placement = dataclasses.replace(placement, tiers=tiers)
    return dataclasses.replace(
        session, k_value=k_value,
        allocate=dataclasses.replace(session.allocate, placement=placement),
        # VictimConfig.placement is the victim solver's AllocateConfig;
        # the strategy knobs sit one level deeper
        victims=dataclasses.replace(
            session.victims,
            placement=dataclasses.replace(session.victims.placement,
                                          placement=placement)))


def load_config(doc: dict | str | None,
                base: SchedulerConfig | None = None) -> SchedulerConfig:
    """Merge a scheduler-configuration document over defaults.

    Accepts the reference ConfigMap schema::

        actions: "allocate, reclaim"
        tiers:
        - plugins:
          - name: nodeplacement
            arguments: {gpu: spread, cpu: binpack}
        queueDepthPerAction: {allocate: 100, reclaim: 10}
        kValue: 0.5
        schedulePeriod: 1.0

    Absent fields keep the compiled defaults (ref
    ``conf_util/scheduler_conf_util.go:80-90`` merge semantics).
    """
    if isinstance(doc, str):
        doc = parse_document(doc)
    doc = doc or {}
    cfg = base or SchedulerConfig()
    session = cfg.session
    if "tiers" in doc:
        session = _merge_tiers(doc["tiers"], session)
    if "kValue" in doc:
        session = dataclasses.replace(session,
                                      k_value=float(doc["kValue"]))
    depths: dict[str, Any] = doc.get("queueDepthPerAction") or {}
    if depths:
        def depth(action, current):
            # explicit 0 means "attempt nothing", distinct from absent
            # (keep default) — never collapse it to unlimited; null IS
            # unlimited, so the effective doc round-trips (kai-twin
            # replays a recorded stream through its own header config)
            if action not in depths:
                return current
            v = depths[action]
            return None if v is None else int(v)

        allocate = dataclasses.replace(
            session.allocate,
            queue_depth=depth("allocate", session.allocate.queue_depth))
        victims = dataclasses.replace(
            session.victims,
            queue_depth=depth("reclaim", session.victims.queue_depth),
            queue_depth_preempt=depth(
                "preempt", session.victims.queue_depth_preempt))
        session = dataclasses.replace(session, allocate=allocate,
                                      victims=victims)
    victims_doc = doc.get("victims") or {}
    if victims_doc:
        # kai-twin tuner surface: the victim solver's sparse-scatter
        # unit (KU) and the per-cycle victim pool bound
        sk = victims_doc.get("sparseUnitK",
                             session.victims.sparse_unit_k)
        session = dataclasses.replace(
            session, victims=dataclasses.replace(
                session.victims,
                sparse_unit_k=None if sk is None else int(sk),
                max_victim_pods=int(victims_doc.get(
                    "maxVictimPods", session.victims.max_victim_pods))))
    if "staleGangGracePeriodSeconds" in doc:
        session = dataclasses.replace(
            session, stale_grace_s=float(doc["staleGangGracePeriodSeconds"]))
    if "rackLevel" in doc:
        # THE rack-domain knob: one document key sets the topology level
        # the kai-pulse fragmentation gauges AND the kai-repack solver
        # treat as the rack.  Repack has no rack knob of its own — it
        # derives its domains from this AnalyticsConfig by construction
        # (ops/repack.RepackConfig embeds it), so a mismatch between
        # trigger and solver is unrepresentable.
        session = dataclasses.replace(
            session, analytics=dataclasses.replace(
                session.analytics, rack_level=int(doc["rackLevel"])))
    out = dataclasses.replace(cfg, session=session)
    repack_doc = doc.get("repack") or {}
    if repack_doc:
        out = dataclasses.replace(
            out,
            repack_enable=bool(repack_doc.get(
                "enabled", out.repack_enable)),
            repack_frag_threshold=float(repack_doc.get(
                "fragThreshold", out.repack_frag_threshold)),
            repack_trigger_cycles=int(repack_doc.get(
                "triggerCycles", out.repack_trigger_cycles)),
            repack_cooldown=int(repack_doc.get(
                "cooldownCycles", out.repack_cooldown)),
            repack_max_migrations=int(repack_doc.get(
                "maxMigrations", out.repack_max_migrations)))
    intake_doc = doc.get("intake") or {}
    if intake_doc:
        # kai-intake multi-lane mutation front end (intake/router.py):
        # lane fan-out, per-lane bound, and the overflow policy the
        # server's POST /intake route enforces
        out = dataclasses.replace(
            out,
            intake_lanes=int(intake_doc.get("lanes", out.intake_lanes)),
            intake_lane_capacity=int(intake_doc.get(
                "laneCapacity", out.intake_lane_capacity)),
            intake_policy=str(intake_doc.get(
                "policy", out.intake_policy)),
            intake_batch=int(intake_doc.get("batch", out.intake_batch)))
    if "actions" in doc:
        out = dataclasses.replace(out,
                                  actions=_parse_actions(doc["actions"]))
    if "schedulePeriod" in doc:
        out = dataclasses.replace(
            out, schedule_period_s=float(doc["schedulePeriod"]))
    if "incremental" in doc:
        out = dataclasses.replace(out,
                                  incremental=bool(doc["incremental"]))
    if "resident" in doc:
        # refused, not ignored: an operator who still sets it would
        # otherwise believe they run a path that no longer exists
        raise ValueError(
            "config key 'resident': the device-resident snapshot path "
            "was removed in PR 30; delete the key (every cycle patches "
            "the snapshot and ships its changed leaves)")
    if "verifyIncremental" in doc:
        out = dataclasses.replace(
            out, verify_incremental=bool(doc["verifyIncremental"]))
    if "incrementalDirtyThreshold" in doc:
        out = dataclasses.replace(
            out, incremental_dirty_threshold=float(
                doc["incrementalDirtyThreshold"]))
    if "analyticsEvery" in doc:
        out = dataclasses.replace(
            out, analytics_every=int(doc["analyticsEvery"]))
    if "starvationAlarmCycles" in doc:
        out = dataclasses.replace(
            out, starvation_alarm_cycles=int(doc["starvationAlarmCycles"]))
    if "seed" in doc:
        # the kai-twin determinism anchor: every cycle derives its
        # cycle_seed from (seed, cycle_index), so replaying a recorded
        # stream with the same header seed reproduces the run bit-exact
        out = dataclasses.replace(out, seed=int(doc["seed"]))
    if "twinRecord" in doc:
        out = dataclasses.replace(out, twin_record=bool(doc["twinRecord"]))
    if "pyroscopeAddress" in doc:
        out = dataclasses.replace(
            out, pyroscope_address=str(doc["pyroscopeAddress"] or ""))
    if "profilerSampleHz" in doc:
        hz = doc["profilerSampleHz"]
        out = dataclasses.replace(
            out, profiler_sample_hz=None if hz is None else float(hz))
    return out


def effective_config_doc(cfg: SchedulerConfig) -> dict:
    """The fully-resolved configuration, for ``--print-config`` and the
    operator's shard rendering."""
    placement = cfg.session.allocate.placement
    return {
        "actions": ", ".join(cfg.actions),
        "schedulePeriod": cfg.schedule_period_s,
        "kValue": cfg.session.k_value,
        "queueDepthPerAction": {
            "allocate": cfg.session.allocate.queue_depth,
            "reclaim": cfg.session.victims.queue_depth,
            "preempt": (cfg.session.victims.queue_depth_preempt
                        if cfg.session.victims.queue_depth_preempt
                        is not None else cfg.session.victims.queue_depth),
        },
        "placement": {
            "gpu": "binpack" if placement.binpack_accel else "spread",
            "cpu": "binpack" if placement.binpack_cpu else "spread",
            "device": "pack" if placement.device_pack else "spread",
            "tiers": list(placement.tiers),
        },
        "staleGangGracePeriodSeconds": cfg.session.stale_grace_s,
        "rackLevel": cfg.session.analytics.rack_level,
        "repack": {
            "enabled": cfg.repack_enable,
            "fragThreshold": cfg.repack_frag_threshold,
            "triggerCycles": cfg.repack_trigger_cycles,
            "cooldownCycles": cfg.repack_cooldown,
            "maxMigrations": cfg.repack_max_migrations,
        },
        "intake": {
            "lanes": cfg.intake_lanes,
            "laneCapacity": cfg.intake_lane_capacity,
            "policy": cfg.intake_policy,
            "batch": cfg.intake_batch,
        },
        "victims": {
            "sparseUnitK": cfg.session.victims.sparse_unit_k,
            "maxVictimPods": cfg.session.victims.max_victim_pods,
        },
        "analyticsEvery": cfg.analytics_every,
        "starvationAlarmCycles": cfg.starvation_alarm_cycles,
        "seed": cfg.seed,
        "twinRecord": cfg.twin_record,
        "incremental": cfg.incremental,
        "verifyIncremental": cfg.verify_incremental,
        "incrementalDirtyThreshold": cfg.incremental_dirty_threshold,
        "pyroscopeAddress": cfg.pyroscope_address,
        # None (unset) round-trips as null: an address alone means
        # 100 Hz, while an explicit 0 disables — collapsing unset to
        # 0.0 would silently turn the sampler off on reload
        "profilerSampleHz": cfg.profiler_sample_hz,
    }


def dumps_effective(cfg: SchedulerConfig) -> str:
    return json.dumps(effective_config_doc(cfg), indent=2)
