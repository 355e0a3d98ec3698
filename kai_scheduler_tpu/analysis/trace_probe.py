"""Layer 2 — jaxpr probe over every registered op.

The AST lint (layer 1) sees source; this layer sees the *program*.  Each
registered op — the compiled kernels the cycle actually dispatches — is
traced at canonical padded shapes and checked for:

* **forbidden primitives**: host callbacks (``pure_callback`` /
  ``io_callback`` / ``debug_callback``) and infeed/outfeed would smuggle
  a host round trip into "one dispatch per cycle"; f64 avals outside
  the allowlist break the f32 device discipline (``utils/numerics.py``);
* **recompilation**: re-tracing the op against a *freshly rebuilt*
  equivalent snapshot (same shape bucket, different host objects and
  clock) must hit the jit cache — this is the end-to-end determinism
  property: any unordered iteration or unstable static config between
  two equivalent builds shows up here as a second compile;
* **constant/eqn bloat**: per-op jaxpr eqn counts and closed-over
  constant bytes are recorded against ``baseline.json`` — a change that
  bakes a fat table into the program (recompiled and re-uploaded per
  shape bucket) fails loudly instead of shipping silently.

Run via ``python -m kai_scheduler_tpu.analysis --probe`` or the tier-1
``tests/test_analysis.py``.  ``--update-baseline`` refreshes the stats.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import budgets
from ..framework.scheduler import (_fused_pipeline, run_actions,
                                   stale_eviction_jit)
from ..framework.session import (SessionConfig, _pack_commit,
                                 _set_fair_share_jit)
from ..ops import analytics as pulse
from ..ops import drf
from ..ops import repack as repack_ops
from ..ops.allocate import (AllocateConfig, allocate, allocate_jit,
                            init_result)
from ..ops.stale import stale_gang_eviction
from ..ops.victims import (VictimConfig, run_victim_action,
                           run_victim_action_jit)
from ..state.cluster_state import build_snapshot
from ..state.synthetic import make_cluster
from ..utils import numerics

#: module-scope jit wrapper for the numerics helper (the production
#: call sites inline it into larger kernels; the probe needs it
#: addressable on its own)
_CUMSUM_JIT = jax.jit(numerics.cumsum_ds)

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "baseline.json")

#: the Session-auto-tuned shape that engages the sparse preempt
#: wavefront (``ops/victims._sparse_preempt_ok``) — the canonical
#: cluster is uniform/no-fraction, so this mirrors what production
#: would compile for it
_VCFG_SPARSE = VictimConfig(placement=AllocateConfig(
    dynamic_order=False, track_devices=False, uniform_tasks=True,
    subgroup_topology=False, extended=False))

#: primitive names that must never appear in a cycle kernel's jaxpr
FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call", "infeed", "outfeed",
})

#: eqn-count headroom over baseline before the probe fails (compiler /
#: minor-refactor jitter); constants get less slack — they are the
#: regression this guard exists for
EQN_TOLERANCE = 0.25
CONST_TOLERANCE = 0.10
CONST_SLACK_BYTES = 1024


@dataclasses.dataclass
class ProbeSpec:
    """One registered op: how to build its canonical invocation."""

    name: str
    #: pure function for ``jax.make_jaxpr`` (static kwargs prebound)
    trace_fn: Callable
    #: the production jitted wrapper, for the compile-cache assertion
    jit_fn: Callable
    #: (args, kwargs) builder from a canonical env — called once per
    #: env so the cache check sees two independent builds
    make_args: Callable


@dataclasses.dataclass
class OpReport:
    name: str
    eqns: int
    const_bytes: int
    forbidden: list[str]
    f64_avals: list[str]
    cache_hit: bool | None      # None = wrapper exposes no cache probe


def _canonical_env(now: float, *, num_nodes: int = 8):
    """A small canonical cluster at production-padded shapes: running
    pods (victim paths need prey), a pending backlog, a 2-level
    topology, and a 2-deep queue hierarchy.  ``num_nodes`` widens the
    node axis only (the kai-cost scaling mode re-traces key entries at
    2-3 padded node widths to fit the peak-memory growth exponent)."""
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=num_nodes, num_gangs=8, tasks_per_gang=2,
        running_fraction=0.5, partition_queues_by_running=True,
        topology_levels=(2, 2), priority_spread=3,
        pending_priority_boost=2)
    # pad=32 EXPLICITLY: the test conftest widens the default pad to 32
    # for shape unification — pinning it here keeps the CLI probe and
    # the tier-1 probe tracing the same shapes (one baseline serves
    # both, and they share compile-cache entries with the suite)
    state, index = build_snapshot(nodes, queues, groups, pods, topo,
                                  now=now, pad=32)
    return state, index


def _registry() -> list[ProbeSpec]:
    """Every op the cycle dispatches, with canonical arguments.

    Grown alongside the kernels: a new jitted entry point in
    ``framework/`` or ``ops/`` belongs here (the coverage meta-test in
    ``tests/test_analysis.py`` cross-checks against the lint call
    graph's entry points).
    """
    cfg = SessionConfig()
    nl = cfg.num_levels
    acfg, vcfg = AllocateConfig(), VictimConfig()
    actions = ("allocate", "consolidation", "reclaim", "preempt",
               "stalegangeviction")

    def fair_share(state):
        if isinstance(jax.tree_util.tree_leaves(state)[0],
                      jax.ShapeDtypeStruct):
            # abstract env (kai-cost model-only re-trace, e.g. the
            # bench's cost_model_peak_mb column at 10k×50k): compute
            # the fair-share AVAL without compiling or dispatching the
            # standalone jit at this shape
            return jax.eval_shape(
                functools.partial(drf.set_fair_share, num_levels=nl),
                state, k_value=jnp.float32(0.0))
        return _set_fair_share_jit(state, num_levels=nl,
                                   k_value=jnp.float32(0.0))

    def state_fs_args(env):
        state, _ = env
        return (state, fair_share(state)), {}

    def victim_args(env, mode):
        state, _ = env
        return (state, fair_share(state), init_result(state)), {}

    specs = [
        ProbeSpec(
            "set_fair_share",
            functools.partial(drf.set_fair_share, num_levels=nl),
            _set_fair_share_jit,
            lambda env: ((env[0],),
                         dict(num_levels=nl,
                              k_value=jnp.float32(0.0)))),
        ProbeSpec(
            "allocate",
            functools.partial(allocate, num_levels=nl, config=acfg),
            allocate_jit,
            lambda env: (state_fs_args(env)[0],
                         dict(num_levels=nl, config=acfg))),
        *[
            ProbeSpec(
                f"victims_{mode}",
                functools.partial(run_victim_action, num_levels=nl,
                                  mode=mode, config=vcfg),
                run_victim_action_jit,
                functools.partial(
                    lambda env, m: (victim_args(env, m)[0],
                                    dict(num_levels=nl, mode=m,
                                         config=vcfg)), m=mode))
            for mode in ("reclaim", "preempt", "consolidate")
        ],
        ProbeSpec(
            # the sparse/optimistic preempt wavefront (ops/victims.py):
            # same jit entry point, but the sparse protocol only traces
            # under the uniform/no-device/no-extended/no-subgroup shape
            # the Session auto-tunes to — probed explicitly so its
            # jaxpr stays under the callback/f64/eqn budgets too
            "victims_preempt_sparse",
            functools.partial(run_victim_action, num_levels=nl,
                              mode="preempt", config=_VCFG_SPARSE),
            run_victim_action_jit,
            lambda env: (victim_args(env, "preempt")[0],
                         dict(num_levels=nl, mode="preempt",
                              config=_VCFG_SPARSE))),
        ProbeSpec(
            "stale_gang_eviction",
            functools.partial(stale_gang_eviction,
                              grace_s=cfg.stale_grace_s, num_levels=nl),
            stale_eviction_jit,
            lambda env: ((env[0], init_result(env[0])),
                         dict(grace_s=cfg.stale_grace_s,
                              num_levels=nl))),
        ProbeSpec(
            "fused_pipeline",
            functools.partial(run_actions, actions=actions,
                              num_levels=nl, acfg=acfg, vcfg=vcfg,
                              grace_s=cfg.stale_grace_s),
            _fused_pipeline,
            lambda env: (state_fs_args(env)[0],
                         dict(actions=actions, num_levels=nl, acfg=acfg,
                              vcfg=vcfg, grace_s=cfg.stale_grace_s))),
        ProbeSpec(
            "pack_commit",
            functools.partial(getattr(_pack_commit, "__wrapped__",
                                      _pack_commit),
                              track_devices=False,
                              track_analytics=False),
            _pack_commit,
            lambda env: ((_probe_result(env), env[0]),
                         dict(track_devices=False,
                              track_analytics=False))),
        ProbeSpec(
            # kai-pulse cluster-health kernel (ops/analytics.py): runs
            # over the post-decision snapshot every K cycles and rides
            # the packed commit — probed with a zeroed pending-age
            # vector at the canonical shapes
            "analytics",
            functools.partial(pulse.cluster_analytics,
                              config=pulse.AnalyticsConfig()),
            pulse.cluster_analytics_jit,
            lambda env: ((env[0], _probe_result(env),
                          jnp.zeros((env[0].gangs.g,), jnp.float32)),
                         dict(config=pulse.AnalyticsConfig()))),
        ProbeSpec(
            # kai-repack defragmentation solver (ops/repack.py):
            # dispatched only on fired trigger cycles, but its jaxpr
            # must honor the same no-callback/f32/compile-once budgets
            # as the every-cycle kernels — probed with a zeroed
            # pending-age vector at the canonical shapes
            "repack",
            functools.partial(repack_ops.plan_repack,
                              config=repack_ops.RepackConfig()),
            repack_ops.plan_repack_jit,
            lambda env: ((env[0],
                          jnp.zeros((env[0].gangs.g,), jnp.float32),
                          env[0].nodes.free),
                         dict(config=repack_ops.RepackConfig()))),
        ProbeSpec(
            "cumsum_ds",
            numerics.cumsum_ds,
            _CUMSUM_JIT,
            lambda env: ((jnp.ones((64,), jnp.float32),), {})),
    ]
    return specs


def _probe_result(env):
    return init_result(env[0])


def registered_ops() -> list[str]:
    return [s.name for s in _registry()]


# ---------------------------------------------------------------------------
# jaxpr walking

def eqn_sub_jaxprs(eqn) -> list:
    """Sub-jaxprs nested in an eqn's params — THE structural scan for
    every consumer of a walked entry (this walk and the kai-cost
    liveness sweep in ``costmodel.py``), so the layers can never
    disagree on nesting."""
    subs = []
    for p in eqn.params.values():
        for x in (p if isinstance(p, (tuple, list)) else (p,)):
            if hasattr(x, "eqns") or hasattr(x, "jaxpr"):
                subs.append(x)
    return subs


def _walk_jaxpr(jaxpr, eqns, prims, avals, consts):
    """Recursively visit eqns/sub-jaxprs of a (Closed)Jaxpr."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for c in getattr(jaxpr, "consts", ()) or ():
        consts.append(c)
    for v in list(inner.invars) + list(inner.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None:
            avals.append(aval)
    for eqn in inner.eqns:
        eqns.append(eqn)
        prims.append(eqn.primitive.name)
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None:
                avals.append(aval)
        for sub in eqn_sub_jaxprs(eqn):
            _walk_jaxpr(sub, eqns, prims, avals, consts)


@dataclasses.dataclass
class EntryTrace:
    """One entry's walked jaxpr — THE shared per-entry walk.

    Both consumers of a traced entry run off this one object: the
    probe's eqn/const/forbidden-primitive stats (``probe_op``) and the
    kai-cost auditor's liveness/FLOP/traffic analysis
    (``costmodel.py``).  Tracing the big fused entries costs seconds
    each, so a full-gate CLI run builds each trace once and feeds it to
    both layers.
    """

    name: str
    #: the ClosedJaxpr from ``jax.make_jaxpr`` (costmodel's liveness
    #: scan needs the nested eqn structure, not just the flat lists)
    closed: object
    #: flattened across every nesting level (``_walk_jaxpr``)
    eqns: list
    prims: list
    avals: list
    consts: list


def trace_entry(spec: ProbeSpec, env) -> EntryTrace:
    """Trace one registered op at the canonical env and walk its jaxpr
    once — the shared front half of ``probe_op`` and every kai-cost
    entry report."""
    args, kwargs = spec.make_args(env)
    trace_kwargs = {k: v for k, v in kwargs.items()
                    if k in ("k_value",)}
    closed = jax.make_jaxpr(spec.trace_fn)(*args, **trace_kwargs)
    eqns, prims, avals, consts = [], [], [], []
    _walk_jaxpr(closed, eqns, prims, avals, consts)
    return EntryTrace(name=spec.name, closed=closed, eqns=eqns,
                      prims=prims, avals=avals, consts=consts)


def trace_entries(names: list[str] | None = None, *,
                  env=None) -> list[EntryTrace]:
    """Walked traces for the selected (default: all) registered ops."""
    specs = _registry()
    if names:
        specs = [s for s in specs if s.name in set(names)]
    if env is None:
        env = _canonical_env(now=1000.0)
    return [trace_entry(s, env) for s in specs]


def _const_bytes(consts) -> int:
    total = 0
    for c in consts:
        try:
            total += np.asarray(c).nbytes
        except Exception:
            pass
    return total


def _cache_size(fn) -> int | None:
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


def probe_op(spec: ProbeSpec, trace: EntryTrace | None = None) -> OpReport:
    """Trace + execute one op: jaxpr walk, then the two-build
    compile-cache assertion.  Pass a pre-built ``trace`` (the shared
    per-entry walk) to skip the re-trace — the cache assertion still
    runs its own two fresh builds either way."""
    env_a = _canonical_env(now=1000.0)
    args, kwargs = spec.make_args(env_a)
    if trace is None:
        trace = trace_entry(spec, env_a)
    forbidden = sorted({p for p in trace.prims
                        for f in FORBIDDEN_PRIMITIVES if f in p})
    f64 = sorted({str(a) for a in trace.avals
                  if getattr(a, "dtype", None) is not None
                  and str(a.dtype) in ("float64", "complex128")})

    # compile-cache discipline: two independent builds of an equivalent
    # cluster (fresh objects, different clock) must share one compile
    jit_fn = spec.jit_fn
    before = _cache_size(jit_fn)
    jax.block_until_ready(jit_fn(*args, **kwargs))
    mid = _cache_size(jit_fn)
    env_b = _canonical_env(now=2000.0)
    args_b, kwargs_b = spec.make_args(env_b)
    jax.block_until_ready(jit_fn(*args_b, **kwargs_b))
    after = _cache_size(jit_fn)
    cache_hit = None
    if mid is not None and after is not None:
        cache_hit = after == mid and (before is None or mid - before <= 1)
    return OpReport(name=spec.name, eqns=len(trace.eqns),
                    const_bytes=_const_bytes(trace.consts),
                    forbidden=forbidden, f64_avals=f64,
                    cache_hit=cache_hit)


def run_probe(names: list[str] | None = None, *,
              traces: list[EntryTrace] | None = None) -> list[OpReport]:
    specs = _registry()
    if names:
        specs = [s for s in specs if s.name in set(names)]
    by_name = {t.name: t for t in traces} if traces else {}
    return [probe_op(s, by_name.get(s.name)) for s in specs]


# ---------------------------------------------------------------------------
# baseline

def load_stats_baseline(path: str = BASELINE_PATH) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f).get("probe", {})


def check_invariants(reports: list[OpReport]) -> list[str]:
    """The baseline-independent properties: no host callbacks, no f64,
    one compile per shape bucket.  These are NEVER absorbed by
    ``--update-baseline`` — there is no legitimate new value."""
    problems = []
    for r in reports:
        if r.forbidden:
            problems.append(
                f"{r.name}: forbidden host-callback primitives in "
                f"jaxpr: {r.forbidden}")
        if r.f64_avals:
            problems.append(
                f"{r.name}: f64 avals on device: {r.f64_avals[:4]}")
        if r.cache_hit is False:
            problems.append(
                f"{r.name}: re-trace against an equivalent rebuilt "
                f"snapshot MISSED the compile cache (nondeterministic "
                f"signature or unstable static config)")
    return problems


def check_against_baseline(reports: list[OpReport], baseline: dict,
                           *, full_coverage: bool = True) -> list[str]:
    """Human-readable regression messages ([] = clean).

    ``full_coverage=False`` (an ``--ops`` subset run) skips the
    stale-baseline-entry sweep — ops that were not probed are not
    missing, just unselected."""
    problems = check_invariants(reports)
    for r in reports:
        base = baseline.get(r.name)
        if base is None:
            problems.append(
                f"{r.name}: no baseline entry — run "
                f"`python -m kai_scheduler_tpu.analysis --probe "
                f"--update-baseline`")
            continue
        # the shared tolerance helper (analysis/budgets.py) — one
        # formula for every baseline-diffed layer (probe AND kai-cost)
        p = budgets.budget_problem(
            r.name, "jaxpr eqn count", r.eqns, base["eqns"],
            tolerance=EQN_TOLERANCE, slack=8, unit=" eqns")
        if p:
            problems.append(p)
        p = budgets.budget_problem(
            r.name, "closed-over constants", r.const_bytes,
            base["const_bytes"], tolerance=CONST_TOLERANCE,
            slack=CONST_SLACK_BYTES, unit="B",
            hint="a baked-in table re-uploads per shape bucket")
        if p:
            problems.append(p)
    if full_coverage:
        for name in sorted(set(baseline) - {r.name for r in reports}):
            problems.append(
                f"baseline lists unknown op `{name}` — stale entry, "
                f"refresh with --update-baseline")
    return problems


def update_baseline(reports: list[OpReport],
                    path: str = BASELINE_PATH) -> None:
    """MERGE the given reports' stats into the baseline — a targeted
    ``--ops X --update-baseline`` must not delete the other ops'
    budgets.  Entries for ops dropped from the registry are pruned
    only on a full-registry update."""
    data = {"lint": [], "probe": {}}
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    probe = data.setdefault("probe", {})
    probe.update({
        r.name: {"eqns": r.eqns, "const_bytes": r.const_bytes}
        for r in sorted(reports, key=lambda r: r.name)})
    live = set(registered_ops())
    if {r.name for r in reports} >= live:
        for name in sorted(set(probe) - live):
            del probe[name]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
