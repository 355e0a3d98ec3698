"""Layer 4 — kai-cost: static dataflow auditor over the entry jaxprs.

The probe (layer 2, ``trace_probe.py``) counts eqns and const bytes —
enough to catch program bloat, but silent on the binding constraint of
the 100k-node mesh target (ROADMAP 2): **peak live device memory per
entry**.  Nothing before PR 14 could say *before a run* whether a
sharded config fits in HBM or whether an intermediate silently
materializes at N× its inputs (the PR-5 ``[B,N,*]`` lane-prefix cumsum
class).  This module runs three static analyses off the **shared
per-entry jaxpr walk** (``trace_probe.EntryTrace`` — one trace feeds
probe and cost):

* **liveness** — a def/last-use linear scan over each entry's eqn
  list.  Level inputs are caller-held for the whole dispatch; internal
  values are live from their defining eqn to their last use;
  sub-jaxprs of ``cond``/``scan``/``while``/``pjit`` are charged
  **worst-case-resident** (their internal peak stacks on the outer
  live set at the call eqn).  Yields peak-live-bytes plus the top-K
  largest intermediates with their producing primitive.
* **FLOPs / memory traffic** — a per-primitive cost table
  (``dot_general`` from its dimension numbers, scatter/gather, the
  reduce and cumulative families, ``sort``/``top_k``, elementwise).
  Primitives outside the table are charged bytes-only and reported in
  ``unknown_prims`` so the table's coverage can't silently rot.
  ``scan`` bodies multiply by trip count; ``while`` bodies are charged
  one trip and counted in ``unbounded_whiles``; ``cond`` charges the
  worst branch.
* **broadcast-blowup (KAI201)** — any intermediate aval exceeding
  ``blowup_factor ×`` the entry's largest input (padding-era default
  16×; entries with a checked-in ``max_blowup`` get that ratio plus
  tolerance headroom instead, exactly like the eqn budgets).

Findings ride the engine's machinery: :class:`engine.Finding` objects
under ``file="jaxpr:<entry>"`` filtered through the same count-based
baseline rows (``cost_baseline.json`` ``"baselined"``, shipped empty —
program-level findings have no source line, so inline suppressions
don't apply; a deliberate exception is a justified baseline row).
Numeric budgets (peak/FLOPs/traffic/blowup) diff against the
``"entries"`` section with the shared tolerance helper
(``analysis/budgets.py``).

A **scaling mode** re-traces key entries at 2-3 padded node widths and
fits the peak-memory growth exponent (log-log least squares) — an
entry whose peak grows super-linearly in N is the mesh-sharding
go/no-go signal for ROADMAP 2, flagged before anyone burns an HBM OOM
discovering it.

Run via ``python -m kai_scheduler_tpu.analysis --cost`` (text/JSON;
``--scaling`` adds the exponent fit; ``--update-baseline`` refreshes
``cost_baseline.json``).  Tier-1: ``tests/test_costmodel.py``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from . import budgets
from . import trace_probe as tp
from .engine import PROGRAM_RULES, Finding, _apply_baseline

COST_BASELINE_PATH = os.path.join(os.path.dirname(__file__),
                                  "cost_baseline.json")

#: tolerance headroom over the checked-in per-entry budgets — same
#: shape as the probe's eqn/const budgets (analysis/budgets.py is the
#: one shared formula).  Cost stats are deterministic at the pinned
#: canonical shapes, so the headroom absorbs compiler/minor-refactor
#: jitter, not measurement noise.
PEAK_TOLERANCE = 0.25
FLOP_TOLERANCE = 0.25
TRAFFIC_TOLERANCE = 0.25
BLOWUP_TOLERANCE = 0.25
PEAK_SLACK_BYTES = 4096
FLOP_SLACK = 16384
TRAFFIC_SLACK_BYTES = 16384

#: peak-memory growth exponent above which a scaling-mode entry is
#: flagged super-linear (the go/no-go bar for mesh-sharding the node
#: axis: peak ∝ N^1.0 shards; N^2 does not)
SUPERLINEAR_EXPONENT = 1.15

#: the KAI2xx catalog — program-level rules implemented here, listed
#: jax-free in ``engine.PROGRAM_RULES`` (one source for --list-rules;
#: the KAI3xx slice belongs to layer 5, ``comms.py``)
COST_RULES = {k: v for k, v in PROGRAM_RULES.items()
              if k.startswith("KAI2")}


@dataclasses.dataclass(frozen=True)
class CostConfig:
    """Knobs for the auditor (defaults are the shipped gate)."""

    #: flag intermediates above this multiple of the largest entry
    #: input when the entry has no baselined ``max_blowup`` (fresh
    #: entries); baselined entries get ``max_blowup × (1+tolerance)``
    #: if that is larger
    blowup_factor: float = 16.0
    #: how many largest intermediates each report retains
    top_k: int = 8


DEFAULT_CONFIG = CostConfig()


@dataclasses.dataclass
class CostReport:
    """One entry's static cost profile (the ``--cost`` unit)."""

    name: str
    peak_live_bytes: int
    input_bytes: int
    largest_input_bytes: int
    flops: int
    traffic_bytes: int
    #: max intermediate bytes / largest input bytes
    max_blowup: float
    #: top-K largest intermediates: {bytes, primitive, aval}
    top_intermediates: list
    #: primitive -> eqn count charged bytes-only (outside the table)
    unknown_prims: dict
    #: while-loops charged a single trip (trip count is dynamic)
    unbounded_whiles: int
    #: KAI201 findings (engine.Finding), pre-baseline
    findings: list


# ---------------------------------------------------------------------------
# jaxpr helpers

def _is_var(v) -> bool:
    """A binding variable (not an inline Literal constant)."""
    return not hasattr(v, "val")


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(np.prod(shape)) * np.dtype(dtype).itemsize
    except Exception:  # noqa: BLE001 — abstract tokens etc.
        return 0


def _aval_str(aval) -> str:
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", ())
    try:
        d = np.dtype(dtype).name if dtype is not None else "?"
    except TypeError:       # extended dtypes (PRNG keys etc.)
        d = str(dtype)
    return f"{d}[{','.join(str(s) for s in shape)}]"


#: one structural scan shared with the probe walk — the two layers
#: must agree on nesting by construction, not by parallel edits
_sub_jaxprs = tp.eqn_sub_jaxprs


# ---------------------------------------------------------------------------
# per-primitive FLOP table

#: one output-element = one op (the elementwise/unary/binary family)
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "pow",
    "integer_pow", "exp", "exp2", "log", "log1p", "expm1", "tanh",
    "logistic", "sqrt", "rsqrt", "cbrt", "abs", "neg", "sign", "floor",
    "ceil", "round", "is_finite", "not", "and", "or", "xor",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "eq", "ne", "lt", "le", "gt", "ge", "eq_to", "ne_to", "lt_to",
    "le_to", "gt_to", "ge_to", "select_n", "clamp",
    "convert_element_type", "erf", "erf_inv", "erfc", "sin", "cos",
    "tan", "asin", "acos", "atan", "atan2", "nextafter",
    "population_count", "clz", "square", "real", "imag", "conj",
    "add_any",
})

#: one input-element = one op (reductions and cumulatives)
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce_precision", "cumsum", "cumprod", "cummax", "cummin",
    "cumlogsumexp",
})

#: pure data movement — zero FLOPs, bytes-only traffic
_MOVEMENT = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "squeeze", "rev", "iota", "copy", "stop_gradient", "device_put",
    "split", "expand_dims", "gather", "bitcast_convert_type",
})


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _eqn_flops(eqn) -> tuple[int, bool]:
    """(flops, known?) for one leaf eqn of the cost table."""
    name = eqn.primitive.name
    out_elems = sum(_prod(getattr(v.aval, "shape", ()))
                    for v in eqn.outvars if _is_var(v))
    in_elems = sum(_prod(getattr(v.aval, "shape", ()))
                   for v in eqn.invars
                   if getattr(v, "aval", None) is not None)
    if name == "dot_general":
        (lc, _rc), (lb, _rb) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        batch = _prod(lhs[d] for d in lb)
        contract = _prod(lhs[d] for d in lc)
        m = _prod(lhs[d] for d in range(len(lhs))
                  if d not in set(lc) | set(lb))
        n = _prod(rhs[d] for d in range(len(rhs))
                  if d not in set(eqn.params["dimension_numbers"][0][1])
                  | set(eqn.params["dimension_numbers"][1][1]))
        return 2 * batch * m * n * contract, True
    if name in _ELEMENTWISE:
        return out_elems, True
    if name in _REDUCE:
        return in_elems, True
    if name.startswith("scatter"):
        # operand, indices, updates: one op per update element
        upd = eqn.invars[-1]
        return _prod(getattr(upd.aval, "shape", ())), True
    if name == "sort":
        n = max(out_elems, 1)
        return int(n * max(1.0, math.log2(n))), True
    if name == "top_k":
        k = int(eqn.params.get("k", 1))
        n = max(in_elems, 1)
        return int(n * max(1.0, math.log2(k + 1))), True
    if name in _MOVEMENT:
        return 0, True
    return 0, False


# ---------------------------------------------------------------------------
# liveness + rollup (one recursive sweep per entry)

@dataclasses.dataclass
class _LevelCost:
    peak: int
    flops: int
    traffic: int
    inters: list          # (nbytes, primitive, aval str)
    unknown: Counter
    whiles: int
    #: the bounded candidate list dropped smaller intermediates — any
    #: count derived from it is a lower bound, not exact
    truncated: bool = False


def _level_cost(jaxpr_like, config: CostConfig) -> _LevelCost:
    """Cost of one jaxpr level's *internal* values.

    Level invars/constvars belong to the caller's frame (the entry
    wrapper charges top-level inputs as resident for the whole
    dispatch), so the liveness scan here tracks only values this level
    defines: live from their producing eqn to their last use, jaxpr
    outvars live to the end of the level.  An eqn carrying sub-jaxprs
    is charged worst-case-resident: the largest sub-level peak stacks
    on the outer running set at that eqn.
    """
    inner = getattr(jaxpr_like, "jaxpr", jaxpr_like)
    eqns = inner.eqns
    n = len(eqns)
    last_use: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
    out_set = {v for v in inner.outvars if _is_var(v)}

    deaths: list[list] = [[] for _ in range(n)]
    sizes: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            if not _is_var(v) or _is_drop(v):
                continue
            sizes[v] = _aval_bytes(v.aval)
            if v in out_set:
                continue        # alive to level end
            deaths[max(last_use.get(v, i), i)].append(v)

    running = 0
    out = _LevelCost(peak=0, flops=0, traffic=0, inters=[],
                     unknown=Counter(), whiles=0)
    for i, eqn in enumerate(eqns):
        name = eqn.primitive.name
        sub_peak = 0
        subs = _sub_jaxprs(eqn)
        if subs:
            mult = 1
            if name == "scan":
                mult = max(1, int(eqn.params.get("length", 1) or 1))
            elif name == "while":
                out.whiles += 1
            sub_costs = [_level_cost(s, config) for s in subs]
            sub_peak = max(c.peak for c in sub_costs)
            if name == "cond":
                out.flops += max(c.flops for c in sub_costs)
                out.traffic += max(c.traffic for c in sub_costs)
            else:
                out.flops += mult * sum(c.flops for c in sub_costs)
                out.traffic += mult * sum(c.traffic for c in sub_costs)
            for c in sub_costs:
                out.inters.extend(c.inters)
                out.unknown.update(c.unknown)
                out.whiles += c.whiles
                out.truncated |= c.truncated
        else:
            fl, known = _eqn_flops(eqn)
            out.flops += fl
            if not known:
                out.unknown[name] += 1
            out.traffic += sum(
                _aval_bytes(getattr(v, "aval", None))
                for v in list(eqn.invars) + list(eqn.outvars)
                if getattr(v, "aval", None) is not None)
        for v in eqn.outvars:
            if _is_var(v) and not _is_drop(v):
                running += sizes[v]
                if v not in out_set:
                    out.inters.append((sizes[v], name,
                                       _aval_str(v.aval)))
        out.peak = max(out.peak, running + sub_peak)
        for v in deaths[i]:
            running -= sizes[v]
    # keep the level's candidate list bounded before it bubbles up
    out.inters.sort(key=lambda t: (-t[0], t[1], t[2]))
    cap = max(config.top_k * 4, 32)
    if len(out.inters) > cap:
        out.truncated = True
        del out.inters[cap:]
    return out


def _report_from_closed(name: str, closed, *, config: CostConfig,
                        base_entry: dict | None) -> CostReport:
    """Build one entry's report from its ClosedJaxpr — the shared back
    half of production entries and the KAI201 fixtures."""
    inner = closed.jaxpr
    input_avals = ([v.aval for v in inner.invars]
                   + [v.aval for v in inner.constvars])
    input_bytes = sum(_aval_bytes(a) for a in input_avals)
    largest_input = max((_aval_bytes(a) for a in input_avals),
                        default=0)
    lc = _level_cost(closed, config)
    peak = input_bytes + lc.peak
    top = [{"bytes": b, "primitive": p, "aval": a}
           for b, p, a in lc.inters[:config.top_k]]
    max_inter = lc.inters[0][0] if lc.inters else 0
    blowup = max_inter / max(largest_input, 1)

    findings: list[Finding] = []
    allowed_ratio = config.blowup_factor
    if base_entry is not None and "max_blowup" in base_entry:
        allowed_ratio = max(
            allowed_ratio,
            float(base_entry["max_blowup"]) * (1 + BLOWUP_TOLERANCE))
    offenders = [t for t in lc.inters
                 if t[0] > allowed_ratio * max(largest_input, 1)]
    if offenders:
        worst = offenders[0]
        # the candidate list is bounded per level, so after truncation
        # the offender count is only a lower bound
        count = f"{len(offenders)}{'+' if lc.truncated else ''}"
        findings.append(Finding(
            file=f"jaxpr:{name}", line=0, col=0, code="KAI201",
            message=(
                f"{count} intermediate(s) exceed "
                f"{allowed_ratio:.1f}× the entry's largest input "
                f"({largest_input}B); worst: {worst[2]} ({worst[0]}B, "
                f"{worst[0] / max(largest_input, 1):.1f}×) from "
                f"`{worst[1]}` — a silently materialized broadcast "
                f"scales this entry's HBM footprint past its inputs "
                f"(the PR-5 [B,N,*] lane-prefix class); restructure, "
                f"or absorb an intentional ratio with --cost "
                f"--update-baseline"),
            function=name))
    return CostReport(
        name=name, peak_live_bytes=peak, input_bytes=input_bytes,
        largest_input_bytes=largest_input, flops=lc.flops,
        traffic_bytes=lc.traffic, max_blowup=round(blowup, 2),
        top_intermediates=top, unknown_prims=dict(
            sorted(lc.unknown.items())),
        unbounded_whiles=lc.whiles, findings=findings)


# ---------------------------------------------------------------------------
# entry audit driver

def registered_cost_entries() -> list[str]:
    """Cost coverage == probe coverage: one shared registry."""
    return tp.registered_ops()


#: CompileWatcher entry -> the cost-report names that audit it.  The
#: watcher's production entry list is the coverage oracle: the
#: meta-test in tests/test_costmodel.py pins this map against
#: ``WATCHER.entries()`` in both directions, so a new watched jit
#: entry cannot dodge the auditor.
WATCHER_COVERAGE = {
    "allocate": {"allocate"},
    "run_victim_action": {"victims_reclaim", "victims_preempt",
                          "victims_consolidate",
                          "victims_preempt_sparse"},
    "set_fair_share": {"set_fair_share"},
    "pack_commit": {"pack_commit"},
    "stale_gang_eviction": {"stale_gang_eviction"},
    "fused_pipeline": {"fused_pipeline"},
    "analytics": {"analytics"},
    "repack": {"repack"},
}


def run_cost(names: list[str] | None = None, *,
             traces: list | None = None,
             baseline: dict | None = None,
             config: CostConfig = DEFAULT_CONFIG) -> list[CostReport]:
    """Audit the selected (default: all) registered entries.

    ``traces`` accepts pre-built :class:`trace_probe.EntryTrace`
    objects (the shared walk) so a combined probe+cost run traces each
    entry once.  ``baseline`` (the ``entries`` dict of
    ``cost_baseline.json``) feeds the per-entry blowup allowance.
    """
    baseline = baseline or {}
    if traces is None:
        traces = tp.trace_entries(names)
    elif names:
        sel = set(names)
        traces = [t for t in traces if t.name in sel]
    return [_report_from_closed(t.name, t.closed, config=config,
                                base_entry=baseline.get(t.name))
            for t in traces]


# ---------------------------------------------------------------------------
# baseline

def load_cost_baseline(path: str = COST_BASELINE_PATH) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def check_against_cost_baseline(reports: list[CostReport],
                                baseline: dict, *,
                                full_coverage: bool = True
                                ) -> list[str]:
    """Numeric budget regressions ([] = clean) — peak/FLOPs/traffic
    against the checked-in per-entry stats, via the shared tolerance
    helper.  Blowup regressions surface as KAI201 findings instead
    (:func:`cost_findings`), not here."""
    entries = baseline.get("entries", {})
    problems: list[str] = []
    for r in reports:
        base = entries.get(r.name)
        if base is None:
            problems.append(
                f"{r.name}: no cost baseline entry — run "
                f"`python -m kai_scheduler_tpu.analysis --cost "
                f"--update-baseline`")
            continue
        for metric, value, key, tol, slack, unit, hint in (
                ("peak live bytes", r.peak_live_bytes,
                 "peak_live_bytes", PEAK_TOLERANCE, PEAK_SLACK_BYTES,
                 "B", "the entry's HBM watermark grew — check the "
                 "top_intermediates diff before absorbing"),
                ("FLOPs", r.flops, "flops", FLOP_TOLERANCE,
                 FLOP_SLACK, "", ""),
                ("memory traffic", r.traffic_bytes, "traffic_bytes",
                 TRAFFIC_TOLERANCE, TRAFFIC_SLACK_BYTES, "B", "")):
            p = budgets.budget_problem(r.name, metric, value,
                                       base[key], tolerance=tol,
                                       slack=slack, unit=unit,
                                       hint=hint)
            if p:
                problems.append(p)
    if full_coverage:
        for name in sorted(set(entries) - {r.name for r in reports}):
            problems.append(
                f"cost baseline lists unknown entry `{name}` — stale, "
                f"refresh with --cost --update-baseline")
    return problems


def cost_findings(reports: list[CostReport],
                  baseline: dict | None = None) -> list[Finding]:
    """All KAI2xx findings, filtered through the engine's count-based
    baseline rows (``cost_baseline.json`` ``"baselined"`` — the same
    machinery as the lint baseline; shipped empty)."""
    findings = sorted(f for r in reports for f in r.findings)
    rows = (baseline or {}).get("baselined", [])
    if rows:
        findings, _eaten = _apply_baseline(findings, rows)
    return findings


def update_cost_baseline(reports: list[CostReport],
                         path: str = COST_BASELINE_PATH) -> None:
    """MERGE the reports' stats (an ``--ops`` subset must not drop the
    other entries' budgets); stale entries pruned only on a
    full-registry update.  The ``baselined`` finding rows are
    preserved verbatim."""
    data = {"baselined": [], "entries": {}}
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    entries = data.setdefault("entries", {})
    entries.update({
        r.name: {"peak_live_bytes": r.peak_live_bytes,
                 "flops": r.flops,
                 "traffic_bytes": r.traffic_bytes,
                 "max_blowup": r.max_blowup}
        for r in sorted(reports, key=lambda r: r.name)})
    live = set(registered_cost_entries())
    if {r.name for r in reports} >= live:
        for name in sorted(set(entries) - live):
            del entries[name]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# scaling mode — peak-memory growth exponent over the node axis

def fit_exponent(node_counts, peaks) -> float:
    """Least-squares slope of log(peak) vs log(N) — f32 is plenty for
    a growth exponent (the f64 allowlist stays closed)."""
    xs = np.log(np.asarray(node_counts, dtype=np.float32))
    ys = np.log(np.maximum(np.asarray(peaks, dtype=np.float32), 1.0))
    return float(np.polyfit(xs, ys, 1)[0])


def scaling_report(names: tuple = ("fused_pipeline",),
                   node_counts: tuple = (32, 64, 128), *,
                   config: CostConfig = DEFAULT_CONFIG) -> dict:
    """Re-trace key entries at 2-3 padded node widths and fit each
    entry's peak-memory growth exponent.  ``superlinear`` entries
    (exponent > :data:`SUPERLINEAR_EXPONENT`) are the mesh-sharding
    go/no-go signal: their per-shard peak would not drop linearly with
    shard count."""
    unknown = set(names) - set(registered_cost_entries())
    if unknown:
        # a renamed/typoed entry must not vanish into a clean report
        # that reads as "nothing super-linear"
        raise ValueError(
            f"scaling_report: unknown entries {sorted(unknown)} — "
            f"not in the probe/cost registry")
    out: dict = {"node_counts": list(node_counts),
                 "threshold": SUPERLINEAR_EXPONENT, "entries": {}}
    peaks: dict[str, list[int]] = {n: [] for n in names}
    for count in node_counts:
        env = tp._canonical_env(now=1000.0, num_nodes=count)
        for t in tp.trace_entries(list(names), env=env):
            rep = _report_from_closed(t.name, t.closed, config=config,
                                      base_entry=None)
            peaks[t.name].append(rep.peak_live_bytes)
    for name in names:
        if len(peaks[name]) != len(node_counts):
            # a partially-traced entry must not vanish into a clean
            # report, same contract as the unknown-name ValueError
            raise RuntimeError(
                f"scaling_report: entry `{name}` traced at "
                f"{len(peaks[name])}/{len(node_counts)} node widths")
        exp = fit_exponent(node_counts, peaks[name])
        out["entries"][name] = {
            "peak_live_bytes": peaks[name],
            "exponent": round(exp, 3),
            "superlinear": exp > SUPERLINEAR_EXPONENT,
        }
    return out


def peak_mb_for_state(state, names: tuple = ("fused_pipeline",)
                      ) -> dict[str, float]:
    """Peak-live-bytes (MB) of the named entries traced AT the given
    snapshot's shapes — the bench artifact's ``cost_model_peak_mb``
    column (model-side HBM watermark next to the measured columns).
    The state is abstracted to ``ShapeDtypeStruct`` leaves first, so
    this is a pure re-trace: no compile, no dispatch at this shape."""
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                       jnp.result_type(x)), state)
    out = {}
    for t in tp.trace_entries(list(names), env=(abstract, None)):
        rep = _report_from_closed(t.name, t.closed,
                                  config=DEFAULT_CONFIG,
                                  base_entry=None)
        out[t.name] = round(rep.peak_live_bytes / 1e6, 2)
    return out


# ---------------------------------------------------------------------------
# KAI2xx fixtures — jax functions, not AST snippets (the rules judge
# programs); tests/test_costmodel.py runs both directions of each,
# mirroring the engine's per-rule fixture self-tests

def _fixture_blowup_bad(x):
    """f32[8] in, an f32[8,8,8,8,8] (4096×) intermediate mid-trace."""
    big = jnp.broadcast_to(x, (8, 8, 8, 8, 8)) * jnp.float32(2.0)
    return jnp.sum(big)


def _fixture_blowup_good(x):
    return x * jnp.float32(2.0) + jnp.float32(1.0)


def audit_fixture(code: str, kind: str = "bad") -> list[Finding]:
    """Run one KAI2xx fixture through the same audit path as
    production entries and return its findings."""
    x = jnp.zeros((8,), jnp.float32)
    if code == "KAI201":
        fn = (_fixture_blowup_bad if kind == "bad"
              else _fixture_blowup_good)
        closed = jax.make_jaxpr(fn)(x)
        rep = _report_from_closed(f"fixture_{code}_{kind}", closed,
                                  config=DEFAULT_CONFIG,
                                  base_entry=None)
        return rep.findings
    raise ValueError(f"unknown cost rule {code}")
