"""kai-cost tests — liveness/FLOP units, KAI2xx fixtures, production
audit, coverage meta-tests, cross-validation, scaling, CLI.

Mirrors the three-layer guarantee structure of ``test_analysis.py``:

1. **Unit pins** — the liveness scan, the per-primitive FLOP table,
   and the worst-case-resident sub-jaxpr rule against hand-computed
   jaxprs (the model itself is under test, not just its outputs).
2. **Rule fixtures** — KAI201 carries must-trigger and
   must-not-trigger fixtures like every AST rule; both directions run.
3. **Package invariants** — every CompileWatcher-tracked production
   entry has a cost report and a checked-in budget (the watcher entry
   list is the coverage oracle, so a new jit entry cannot dodge the
   auditor), the production package audits clean with zero baselined
   findings, and the model's memory-traffic ranking agrees with measured
   dispatch ordering (model vs reality, tolerance-gated).
"""
import importlib.util
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

from kai_scheduler_tpu.analysis import costmodel as cm
from kai_scheduler_tpu.analysis import trace_probe as tp
from kai_scheduler_tpu.analysis.callgraph import PackageGraph

pytestmark = pytest.mark.core

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cost_reports():
    """One full audit (the shared walk) for the module."""
    base = cm.load_cost_baseline()
    reports = cm.run_cost(baseline=base.get("entries", {}))
    return base, {r.name: r for r in reports}


# ---------------------------------------------------------------------------
# 1. model unit pins (hand-computed jaxprs)

def test_liveness_chain_peak():
    """Three sequential elementwise steps over f32[256]: inputs are
    caller-held (1024B) and at every eqn exactly two internal values
    overlap (operand + result, 2048B) — peak 3072B, not the 4096B a
    no-liveness sum-of-intermediates would charge."""
    def chain(x):
        a = x * jnp.float32(2.0)
        b = a + jnp.float32(1.0)
        return b * b
    closed = jax.make_jaxpr(chain)(jnp.zeros((256,), jnp.float32))
    r = cm._report_from_closed("chain", closed,
                               config=cm.DEFAULT_CONFIG,
                               base_entry=None)
    assert r.peak_live_bytes == 3072
    assert r.flops == 3 * 256
    assert r.unknown_prims == {}


def test_flops_dot_general_from_dimension_numbers():
    """(8,16) @ (16,4) = 2·M·N·K = 1024 FLOPs."""
    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))
    closed = jax.make_jaxpr(dot)(jnp.zeros((8, 16), jnp.float32),
                                 jnp.zeros((16, 4), jnp.float32))
    r = cm._report_from_closed("dot", closed,
                               config=cm.DEFAULT_CONFIG,
                               base_entry=None)
    assert r.flops == 2 * 8 * 4 * 16


def test_cond_branches_are_worst_case_resident():
    """A cond whose big branch materializes 2×64KB must charge the big
    branch's internal peak on top of the inputs — and the small branch
    must NOT dilute it (worst case, not average)."""
    def condfn(x, p):
        return jax.lax.cond(
            p,
            lambda v: jnp.sum(jnp.broadcast_to(v, (64, 256))
                              * jnp.float32(1.5)),
            jnp.sum, x)
    closed = jax.make_jaxpr(condfn)(jnp.zeros((256,), jnp.float32),
                                    True)
    r = cm._report_from_closed("cond", closed,
                               config=cm.DEFAULT_CONFIG,
                               base_entry=None)
    assert r.peak_live_bytes > 2 * 64 * 256 * 4   # both 64KB temps live


def test_scan_flops_multiply_by_trip_count():
    def scanfn(x):
        def body(c, _):
            return c * jnp.float32(2.0), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out
    closed = jax.make_jaxpr(scanfn)(jnp.zeros((256,), jnp.float32))
    r = cm._report_from_closed("scan", closed,
                               config=cm.DEFAULT_CONFIG,
                               base_entry=None)
    assert r.flops == 10 * 256
    assert r.unbounded_whiles == 0


def test_unknown_primitives_are_reported_not_silently_zeroed():
    """A primitive outside the cost table must land in unknown_prims —
    the table's coverage rots loudly."""
    def rng(x):
        key = jax.random.PRNGKey(0)
        return x + jax.random.uniform(key, (8,))
    closed = jax.make_jaxpr(rng)(jnp.zeros((8,), jnp.float32))
    r = cm._report_from_closed("rng", closed,
                               config=cm.DEFAULT_CONFIG,
                               base_entry=None)
    assert r.unknown_prims, "random bits should be outside the table"


# ---------------------------------------------------------------------------
# 2. KAI2xx fixtures — both directions, like every AST rule

@pytest.mark.parametrize("code", sorted(cm.COST_RULES))
def test_cost_rule_fixture_triggers(code):
    findings = cm.audit_fixture(code, "bad")
    assert any(f.code == code for f in findings), (
        f"{code} must-trigger fixture produced no {code} finding: "
        f"{findings}")


@pytest.mark.parametrize("code", sorted(cm.COST_RULES))
def test_cost_rule_fixture_negative(code):
    findings = cm.audit_fixture(code, "good")
    assert not any(f.code == code for f in findings), (
        f"{code} must-NOT-trigger fixture still fires: "
        f"{[f.render() for f in findings]}")


def test_cost_rules_listed_in_catalog():
    from kai_scheduler_tpu.analysis.engine import rule_catalog
    cat = rule_catalog()
    for code in cm.COST_RULES:
        assert code in cat


def test_blowup_allowance_respects_baselined_ratio():
    """An entry with a checked-in max_blowup gets ratio×(1+tol)
    headroom — the same measured ratio passes with its baseline and
    fails as a fresh entry."""
    def blow(x):
        return jnp.sum(jnp.broadcast_to(x, (64, 8)) * jnp.float32(2.0))
    closed = jax.make_jaxpr(blow)(jnp.zeros((8,), jnp.float32))
    fresh = cm._report_from_closed(
        "blow", closed, config=cm.CostConfig(blowup_factor=16.0),
        base_entry=None)
    assert [f.code for f in fresh.findings] == ["KAI201"]
    assert fresh.max_blowup == 64.0
    based = cm._report_from_closed(
        "blow", closed, config=cm.CostConfig(blowup_factor=16.0),
        base_entry={"max_blowup": 64.0})
    assert based.findings == []


def test_cost_findings_ride_engine_baseline_rows():
    """KAI2xx findings flow through the engine's count-based baseline
    machinery (cost_baseline.json 'baselined' rows)."""
    findings = cm.audit_fixture("KAI201", "bad")
    eaten = cm.cost_findings(
        [cm.CostReport(name="f", peak_live_bytes=0, input_bytes=0,
                       largest_input_bytes=0, flops=0, traffic_bytes=0,
                       max_blowup=0.0, top_intermediates=[],
                       unknown_prims={}, unbounded_whiles=0,
                       findings=findings)],
        {"baselined": [{"file": findings[0].file, "code": "KAI201",
                        "count": 1}]})
    assert eaten == []
    kept = cm.cost_findings([cm.CostReport(
        name="f", peak_live_bytes=0, input_bytes=0,
        largest_input_bytes=0, flops=0, traffic_bytes=0,
        max_blowup=0.0, top_intermediates=[], unknown_prims={},
        unbounded_whiles=0, findings=findings)], {})
    assert [f.code for f in kept] == ["KAI201"]


# ---------------------------------------------------------------------------
# 3. the package itself

def test_production_package_audits_clean(cost_reports):
    """The acceptance bar: every production entry within its budgets,
    zero KAI2xx findings beyond the (empty) baselined rows."""
    base, reports = cost_reports
    problems = cm.check_against_cost_baseline(
        list(reports.values()), base)
    assert not problems, "\n".join(problems)
    findings = cm.cost_findings(list(reports.values()), base)
    assert findings == [], "\n".join(f.render() for f in findings)
    for row in base.get("baselined", []):
        # the documented escape hatch: a parked KAI2xx finding is
        # allowed ONLY with an inline justification (the KAI032
        # precedent) — an unjustified row fails tier-1
        assert row.get("justification", "").strip(), (
            f"unjustified baselined cost finding: {row}")


def test_peak_mb_for_state_is_a_pure_retrace(cost_reports):
    """The bench's cost_model_peak_mb column traces with
    ShapeDtypeStruct leaves (no compile, no dispatch at the bench
    shape) and must agree exactly with the concrete-state report at
    the same canonical shapes."""
    _base, reports = cost_reports
    state, _ = tp._canonical_env(now=1000.0)
    peak_mb = cm.peak_mb_for_state(state)["fused_pipeline"]
    assert peak_mb == round(
        reports["fused_pipeline"].peak_live_bytes / 1e6, 2)


def test_watcher_entries_are_the_coverage_oracle(cost_reports):
    """Every CompileWatcher-tracked production entry maps to cost
    coverage, both directions — a new watched jit entry fails here
    until WATCHER_COVERAGE, the registry, and the baseline learn it
    (mirrors the probe-coverage meta-test in test_analysis.py)."""
    # ground truth: the callgraph's jit entry set, via the same
    # qualname->watcher-entry map test_wire_ledger.py pins
    entry_to_watch = {
        "_fused_pipeline": "fused_pipeline",
        "_pack_commit": "pack_commit",
        "allocate_jit": "allocate",
        "set_fair_share": "set_fair_share",
        "stale_gang_eviction": "stale_gang_eviction",
        "run_victim_action_jit": "run_victim_action",
        "cluster_analytics": "analytics",
        "plan_repack": "repack",
        "cumsum_ds": None,      # analysis-only probe helper
    }
    graph = PackageGraph(ROOT)
    entries = {q for _m, q in graph._entries()}
    assert entries == set(entry_to_watch), (
        f"jit entry set changed: {sorted(entries)} — extend "
        f"costmodel.WATCHER_COVERAGE and this map")
    watched = {w for w in entry_to_watch.values() if w is not None}
    assert set(cm.WATCHER_COVERAGE) == watched
    _base, reports = cost_reports
    ops = set(cm.registered_cost_entries())
    covered = set().union(*cm.WATCHER_COVERAGE.values())
    for watcher_entry, names in cm.WATCHER_COVERAGE.items():
        missing = names - set(reports)
        assert not missing, (
            f"watcher entry `{watcher_entry}` lost cost reports "
            f"{missing}")
    assert ops - covered == {"cumsum_ds"}, (
        "every registered op except the analysis-only helper must "
        "audit a watcher entry")


def test_every_entry_has_cost_baseline_budget(cost_reports):
    """Report coverage == checked-in budget coverage == the probe
    baseline's coverage (one registry; scripts/lint.py drift-checks
    the same equality jax-free pre-commit)."""
    base, reports = cost_reports
    assert sorted(base["entries"]) == sorted(reports)
    assert sorted(base["entries"]) == sorted(
        cm.registered_cost_entries())
    with open(os.path.join(ROOT, "kai_scheduler_tpu", "analysis",
                           "baseline.json"), encoding="utf-8") as f:
        probe_keys = set(json.load(f)["probe"])
    assert probe_keys == set(base["entries"])


def test_cost_registry_rides_the_shared_walk(cost_reports):
    """The probe and cost layers consume ONE EntryTrace per entry: a
    pre-built trace feeds probe_op without a re-trace and yields the
    same eqn count the probe baselines."""
    _base, reports = cost_reports
    spec = {s.name: s for s in tp._registry()}["pack_commit"]
    trace = tp.trace_entries(["pack_commit"])[0]
    rep = tp.probe_op(spec, trace)
    assert rep.eqns == len(trace.eqns)
    assert reports["pack_commit"].peak_live_bytes > 0


# ---------------------------------------------------------------------------
# 3b. cross-validation — model vs measured (tolerance-gated)

def test_traffic_ranking_matches_measured_dispatch_order(cost_reports):
    """Model-vs-reality sanity pin at canonical shapes: for entry
    pairs where the model's memory-traffic estimate differs by ≥64×,
    the measured dispatch time must order the same way.  Only
    clear-margin pairs are asserted (tolerance gate: CPU dispatch has
    a ~100µs floor, and the loaded tier-1 container adds scheduling
    noise on top — two sub-ms dispatches a few × apart can invert, so
    the gate keeps only pairs where the fat fused entries face the
    tiny commit/analytics kernels).  Best-of-5 timing for the same
    reason."""
    _base, reports = cost_reports
    entries = ["fused_pipeline", "pack_commit", "analytics",
               "stale_gang_eviction", "set_fair_share"]
    env = tp._canonical_env(now=1000.0)
    specs = {s.name: s for s in tp._registry()}
    measured = {}
    for name in entries:
        spec = specs[name]
        args, kwargs = spec.make_args(env)
        jax.block_until_ready(spec.jit_fn(*args, **kwargs))  # warm
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(spec.jit_fn(*args, **kwargs))
            samples.append(time.perf_counter() - t0)
        measured[name] = min(samples)
    checked = 0
    for hi in entries:
        for lo in entries:
            model_hi = reports[hi].traffic_bytes
            model_lo = reports[lo].traffic_bytes
            if model_hi >= 64 * max(model_lo, 1):
                checked += 1
                assert measured[hi] > measured[lo], (
                    f"model ranks {hi} ({model_hi}B) ≥64× over {lo} "
                    f"({model_lo}B) but measured {measured[hi]*1e3:.3f}"
                    f"ms !> {measured[lo]*1e3:.3f}ms")
    assert checked >= 4, "margin gate left nothing to cross-validate"


@pytest.mark.slow
def test_cost_ranking_at_phases_bench_shape():
    """The satellite's full-size pin: at the `phases` bench snapshot
    shape (10k nodes × 50k pods) the model's traffic/peak ordering
    holds and the bench's cost_model_peak_mb column is derivable."""
    from kai_scheduler_tpu.state import make_cluster
    from kai_scheduler_tpu.state.cluster_state import build_snapshot
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=10_000, node_accel=8.0, num_gangs=6250,
        tasks_per_gang=8, running_fraction=0.5)
    state, _index = build_snapshot(nodes, queues, groups, pods, topo,
                                   now=1000.0)
    traces = tp.trace_entries(
        ["fused_pipeline", "pack_commit", "analytics"],
        env=(state, None))
    reps = {t.name: cm._report_from_closed(
        t.name, t.closed, config=cm.DEFAULT_CONFIG, base_entry=None)
        for t in traces}
    assert (reps["fused_pipeline"].traffic_bytes
            > 8 * reps["pack_commit"].traffic_bytes)
    assert (reps["fused_pipeline"].traffic_bytes
            > 8 * reps["analytics"].traffic_bytes)
    assert (reps["fused_pipeline"].peak_live_bytes
            > reps["pack_commit"].peak_live_bytes)
    peak_mb = cm.peak_mb_for_state(state)["fused_pipeline"]
    assert peak_mb > 0


# ---------------------------------------------------------------------------
# 4. scaling mode

def test_fit_exponent_flags_superlinear():
    lin = cm.fit_exponent([32, 64, 128], [32_000, 64_000, 128_000])
    quad = cm.fit_exponent([32, 64, 128],
                           [32_000, 128_000, 512_000])
    assert abs(lin - 1.0) < 0.05
    assert abs(quad - 2.0) < 0.05
    assert lin <= cm.SUPERLINEAR_EXPONENT < quad


def test_scaling_report_rejects_unknown_entries():
    """A renamed/typoed entry must raise, never vanish into a clean
    'nothing super-linear' report — and the shipped default names must
    stay registry-valid."""
    import inspect
    with pytest.raises(ValueError, match="ghost"):
        cm.scaling_report(names=("ghost",), node_counts=(32, 64))
    defaults = inspect.signature(
        cm.scaling_report).parameters["names"].default
    assert set(defaults) <= set(cm.registered_cost_entries())


def test_scaling_report_on_a_real_entry():
    """End-to-end over the cheap fair-share entry at two padded node
    widths: structure, monotone peaks, and a sane (sub-quadratic)
    exponent for a production kernel."""
    rep = cm.scaling_report(names=("set_fair_share",),
                            node_counts=(32, 64))
    row = rep["entries"]["set_fair_share"]
    assert len(row["peak_live_bytes"]) == 2
    assert row["peak_live_bytes"][1] >= row["peak_live_bytes"][0]
    assert row["exponent"] < 2.0
    assert rep["threshold"] == cm.SUPERLINEAR_EXPONENT


# ---------------------------------------------------------------------------
# 5. CLI + scripts/lint.py registration

def test_cost_cli_json_section(capsys):
    from kai_scheduler_tpu.analysis.__main__ import main
    rc = main(["--cost", "--ops", "pack_commit,cumsum_ds", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert {r["name"] for r in out["cost"]} == {"pack_commit",
                                                "cumsum_ds"}
    assert out["cost_problems"] == []
    assert out["cost_findings"] == []
    for r in out["cost"]:
        assert r["peak_live_bytes"] > 0
        assert r["traffic_bytes"] > 0


@pytest.mark.parametrize("argv", [
    ["--probe", "--scaling"],       # cost AND comms stages skipped
    ["--no-probe", "--scaling"],
    ["--no-probe", "--select", "KAI201"],   # not an engine rule
    ["--no-probe", "--select", "KAI301"],   # kai-comms: also jaxpr-level
])
def test_cli_rejects_flags_the_selected_stages_would_ignore(argv):
    """--scaling without a scaling-capable stage, or a KAI2xx/KAI3xx
    code on the lint --select path, must be an argparse error — never a
    clean exit that silently dropped the requested check (the
    --race/--select precedent)."""
    from kai_scheduler_tpu.analysis.__main__ import main
    with pytest.raises(SystemExit) as exc:
        main(argv)
    assert exc.value.code == 2


def test_list_rules_includes_cost_family(capsys):
    from kai_scheduler_tpu.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "KAI201" in out and "KAI301" in out


def test_update_baseline_refreshes_all_in_one_invocation(
        tmp_path, monkeypatch, capsys):
    """The satellite contract: one default-mode ``--update-baseline``
    invocation rewrites the probe stats, the cost budgets, AND the
    kai-comms collective budgets."""
    from kai_scheduler_tpu.analysis import comms
    from kai_scheduler_tpu.analysis.__main__ import main
    pkg = os.path.join(ROOT, "kai_scheduler_tpu", "analysis")
    probe_tmp = tmp_path / "baseline.json"
    cost_tmp = tmp_path / "cost_baseline.json"
    comm_tmp = tmp_path / "comm_baseline.json"
    with open(os.path.join(pkg, "baseline.json"),
              encoding="utf-8") as f:
        probe_data = json.load(f)
    with open(os.path.join(pkg, "cost_baseline.json"),
              encoding="utf-8") as f:
        cost_data = json.load(f)
    with open(os.path.join(pkg, "comm_baseline.json"),
              encoding="utf-8") as f:
        comm_data = json.load(f)
    probe_data["probe"].pop("cumsum_ds")
    cost_data["entries"].pop("cumsum_ds")
    comm_data["entries"].pop("cumsum_ds")
    probe_tmp.write_text(json.dumps(probe_data))
    cost_tmp.write_text(json.dumps(cost_data))
    comm_tmp.write_text(json.dumps(comm_data))
    monkeypatch.setattr(cm, "COST_BASELINE_PATH", str(cost_tmp))
    monkeypatch.setattr(comms, "COMM_BASELINE_PATH", str(comm_tmp))
    rc = main(["--root", ROOT, "--baseline", str(probe_tmp),
               "--ops", "cumsum_ds", "--update-baseline", "--json"])
    assert rc == 0
    assert "cumsum_ds" in json.loads(
        probe_tmp.read_text())["probe"]
    assert "cumsum_ds" in json.loads(
        cost_tmp.read_text())["entries"]
    assert "cumsum_ds" in json.loads(
        comm_tmp.read_text())["entries"]


def test_update_baseline_is_joint_or_nothing(tmp_path, monkeypatch):
    """A probe-invariant failure holds ALL baselines back: neither the
    cost stats nor the comm budgets are absorbed while baseline.json
    stays stale (a half-refresh would tolerate growth caused by the
    very change the probe blocked on)."""
    from kai_scheduler_tpu.analysis import comms, trace_probe
    from kai_scheduler_tpu.analysis.__main__ import main
    pkg = os.path.join(ROOT, "kai_scheduler_tpu", "analysis")
    probe_tmp = tmp_path / "baseline.json"
    cost_tmp = tmp_path / "cost_baseline.json"
    comm_tmp = tmp_path / "comm_baseline.json"
    shutil.copy(os.path.join(pkg, "baseline.json"), probe_tmp)
    shutil.copy(os.path.join(pkg, "cost_baseline.json"), cost_tmp)
    shutil.copy(os.path.join(pkg, "comm_baseline.json"), comm_tmp)
    probe_before = probe_tmp.read_text()
    cost_before = cost_tmp.read_text()
    comm_before = comm_tmp.read_text()
    monkeypatch.setattr(cm, "COST_BASELINE_PATH", str(cost_tmp))
    monkeypatch.setattr(comms, "COMM_BASELINE_PATH", str(comm_tmp))
    monkeypatch.setattr(trace_probe, "check_invariants",
                        lambda reports: ["synthetic invariant failure"])
    rc = main(["--root", ROOT, "--baseline", str(probe_tmp),
               "--ops", "cumsum_ds", "--update-baseline", "--json"])
    assert rc == 1
    assert probe_tmp.read_text() == probe_before
    assert cost_tmp.read_text() == cost_before
    assert comm_tmp.read_text() == comm_before


def _load_lint_script():
    spec = importlib.util.spec_from_file_location(
        "lint_script", os.path.join(ROOT, "scripts", "lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_script_cost_baseline_drift_check(tmp_path):
    """scripts/lint.py's jax-free stage: probe/cost baseline coverage
    in sync == clean; a missing cost budget (or a stale one) is a
    nonzero-exit drift message naming --update-baseline."""
    lint = _load_lint_script()
    assert lint.check_cost_baseline() == []
    pkg = os.path.join(ROOT, "kai_scheduler_tpu", "analysis")
    probe_tmp = tmp_path / "baseline.json"
    cost_tmp = tmp_path / "cost_baseline.json"
    shutil.copy(os.path.join(pkg, "baseline.json"), probe_tmp)
    with open(os.path.join(pkg, "cost_baseline.json"),
              encoding="utf-8") as f:
        cost_data = json.load(f)
    cost_data["entries"].pop("allocate")
    cost_data["entries"]["ghost_entry"] = {"peak_live_bytes": 1,
                                           "flops": 1,
                                           "traffic_bytes": 1,
                                           "max_blowup": 1.0}
    cost_tmp.write_text(json.dumps(cost_data))
    problems = lint.check_cost_baseline(str(probe_tmp), str(cost_tmp))
    assert any("allocate" in p for p in problems)
    assert any("ghost_entry" in p for p in problems)
    assert any("--update-baseline" in p for p in problems)
    assert lint.check_cost_baseline(
        str(probe_tmp), str(tmp_path / "missing.json"))
    # a missing PROBE baseline is the same graceful one-line drift
    # message, never an unhandled FileNotFoundError in the pre-commit
    assert lint.check_cost_baseline(
        str(tmp_path / "missing.json"), str(cost_tmp))
