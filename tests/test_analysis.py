"""kai-lint tests — rule self-tests, package cleanliness, jaxpr probe.

Three layers of guarantees:

1. **Rule fixtures** — every registered KAI rule carries a must-trigger
   and a must-not-trigger snippet; both are exercised, so a rule edit
   that stops detecting its own hazard (or starts flagging the clean
   idiom) fails here, not in production review.
2. **Package invariants** — the whole package lints clean with NO
   baseline, every inline ``kai-lint: disable`` still matches a live
   finding (no suppression rot), and the shipped lint baseline is
   empty (the tree owes nothing).
3. **Trace probe** — every registered op (cross-checked against the
   call graph's jit entry points, so a new jitted kernel cannot dodge
   coverage) traces without host callbacks or f64, compiles exactly
   once per shape bucket across two independent snapshot builds, and
   stays within the eqn/const budgets of ``analysis/baseline.json``.
"""
import json
import os

import pytest

from kai_scheduler_tpu.analysis import lint_package, lint_source
from kai_scheduler_tpu.analysis.callgraph import PackageGraph
from kai_scheduler_tpu.analysis.engine import RULES, rule_catalog

pytestmark = pytest.mark.core

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

rule_catalog()  # force rule registration


# ---------------------------------------------------------------------------
# 1. per-rule fixture self-tests

_FIXTURED = sorted(c for c in RULES if RULES[c].fixture_bad)


def test_every_rule_has_fixtures():
    # KAI000 is emitted by the engine's suppression bookkeeping, not a
    # checker — everything else must ship its own self-test snippets
    assert _FIXTURED == sorted(c for c in RULES if c != "KAI000")


@pytest.mark.parametrize("code", _FIXTURED)
def test_rule_fixture_triggers(code):
    findings = lint_source(RULES[code].fixture_bad)
    assert any(f.code == code for f in findings), (
        f"{code} must-trigger fixture produced no {code} finding: "
        f"{findings}")


@pytest.mark.parametrize("code", _FIXTURED)
def test_rule_fixture_negative(code):
    findings = lint_source(RULES[code].fixture_good)
    assert not any(f.code == code for f in findings), (
        f"{code} must-NOT-trigger fixture still fires: "
        f"{[f.render() for f in findings if f.code == code]}")


def test_jit_region_scoping():
    """Host-only code is exempt from the trace-safety families: the
    same .item() that is a finding inside @jax.jit is legal outside."""
    hot = """
import jax

@jax.jit
def op(x):
    return x.item()
"""
    cold = """
def commit(x):
    return x.item()
"""
    assert any(f.code == "KAI001" for f in lint_source(hot))
    assert not lint_source(cold)


def test_jit_region_grows_through_calls():
    """A helper only *called from* a jitted entry is in the region."""
    src = """
import jax
import numpy as np

def helper(x):
    return np.asarray(x)

@jax.jit
def op(x):
    return helper(x)
"""
    findings = lint_source(src)
    assert any(f.code == "KAI002" and f.function == "helper"
               for f in findings)


# ---------------------------------------------------------------------------
# 2. suppression + baseline mechanics

def test_suppression_silences_finding():
    src = """
def f(xs):
    for x in set(xs):  # kai-lint: disable=KAI041
        print(x)
"""
    assert lint_source(src) == []


def test_own_line_suppression_covers_next_line():
    src = """
def f(xs):
    # kai-lint: disable=KAI041
    for x in set(xs):
        print(x)
"""
    assert lint_source(src) == []


def test_stale_suppression_is_a_finding():
    src = """
def f(xs):
    return sorted(xs)  # kai-lint: disable=KAI041
"""
    findings = lint_source(src)
    assert [f.code for f in findings] == ["KAI000"]


def test_docstring_disable_examples_are_inert():
    src = '''
def f(xs):
    """Docs showing `# kai-lint: disable=KAI041` syntax."""
    return sorted(xs)
'''
    assert lint_source(src) == []


# ---------------------------------------------------------------------------
# 3. the package itself

def test_package_lints_clean_without_baseline():
    res = lint_package(ROOT)
    assert res.findings == [], "\n".join(
        f.render() for f in res.findings)


def test_no_stale_suppressions_in_package():
    """Every inline ``kai-lint: disable`` still matches a live finding."""
    res = lint_package(ROOT)
    assert res.stale_suppressions == [], "\n".join(
        f.render() for f in res.stale_suppressions)


def test_lint_baseline_stays_empty():
    """The shipped baseline carries probe stats ONLY — lint findings
    are fixed or inline-suppressed, never parked."""
    path = os.path.join(ROOT, "kai_scheduler_tpu", "analysis",
                        "baseline.json")
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    assert data.get("lint", []) == []


def test_known_jit_entry_points_probed():
    """Every jit entry the call graph detects maps to probe coverage —
    add a new jitted kernel and this fails until the probe registry
    (and its baseline) learn about it."""
    from kai_scheduler_tpu.analysis.trace_probe import registered_ops
    entry_to_ops = {
        "_fused_pipeline": {"fused_pipeline"},
        "_pack_commit": {"pack_commit"},
        "allocate_jit": {"allocate"},
        "set_fair_share": {"set_fair_share"},
        "stale_gang_eviction": {"stale_gang_eviction"},
        "run_victim_action_jit": {"victims_reclaim", "victims_preempt",
                                  "victims_consolidate"},
        "cumsum_ds": {"cumsum_ds"},
        # kai-pulse cluster-health kernel (ops/analytics.py)
        "cluster_analytics": {"analytics"},
        # kai-repack defragmentation solver (ops/repack.py)
        "plan_repack": {"repack"},
    }
    graph = PackageGraph(ROOT)
    entries = {q for _m, q in graph._entries()}
    ops = set(registered_ops())
    for q in sorted(entries):
        assert q in entry_to_ops, (
            f"new jit entry point `{q}` — register it in "
            f"analysis/trace_probe.py::_registry and refresh the "
            f"baseline (--probe --update-baseline)")
        missing = entry_to_ops[q] - ops
        assert not missing, f"probe registry lost ops {missing} for {q}"


def test_cost_coverage_rides_the_probe_registry():
    """kai-cost (PR 14) audits the SAME registry the probe traces —
    one shared per-entry walk, one coverage surface.  A jit entry that
    passes the probe-coverage test above therefore cannot dodge the
    cost auditor (its own meta-tests live in test_costmodel.py; this
    pin keeps the two registries from ever forking)."""
    from kai_scheduler_tpu.analysis.costmodel import (
        registered_cost_entries)
    from kai_scheduler_tpu.analysis.trace_probe import registered_ops
    assert registered_cost_entries() == registered_ops()


# ---------------------------------------------------------------------------
# 3b. kai-race — thread-root discovery, guarded-by map coverage, and
#     the package's race cleanliness (all pure AST, jax-free)

@pytest.fixture(scope="module")
def race_report():
    from kai_scheduler_tpu.analysis import concurrency
    graph = PackageGraph(ROOT)
    return concurrency.analyze_package(graph,
                                       concurrency.load_guarded_map())


def test_package_races_clean_with_empty_baseline(race_report):
    """The whole package passes the KAI1xx race pass with no baseline
    and zero stale annotations (the PR-4 acceptance bar)."""
    report = race_report
    assert report.findings == [], "\n".join(
        f.render() for f in report.findings)


def test_every_thread_root_covered_by_guarded_by_map(race_report):
    """Discovery == the checked-in audit map, both directions: a new
    daemon thread fails here until its state-sharing is audited, and a
    removed thread leaves no stale map row."""
    from kai_scheduler_tpu.analysis import concurrency
    report = race_report
    mapped = set(concurrency.load_guarded_map()["thread_roots"])
    discovered = {r.root_id for r in report.roots}
    assert discovered == mapped, (
        f"uncovered roots: {sorted(discovered - mapped)}; "
        f"stale map rows: {sorted(mapped - discovered)}")


def test_known_thread_roots_discovered(race_report):
    """The pass must see the package's actual daemon threads — if
    discovery regresses, the race rules silently check nothing."""
    report = race_report
    discovered = {r.root_id for r in report.roots}
    for expected in (
            "kai_scheduler_tpu/runtime/status_updater.py::"
            "AsyncStatusUpdater._worker",
            "kai_scheduler_tpu/runtime/profiling.py::"
            "ContinuousProfiler._run",
            "kai_scheduler_tpu/framework/server.py::"
            "SchedulerServer.__init__.Handler.do_GET",
            "kai_scheduler_tpu/framework/server.py::"
            "SchedulerServer.__init__.Handler.do_POST",
            "kai_scheduler_tpu/intake/router.py::"
            "IntakeRouter._worker"):
        assert expected in discovered, (expected, sorted(discovered))
    # handler threads are per-request: multi-instance conflicts count
    multi = {r.root_id for r in report.roots if r.multi}
    assert any("do_GET" in r for r in multi)
    assert any("_worker" in r for r in multi)
    # the kai-intake worker pool spawns one drain thread per lane — it
    # must register as multi-instance or lane races check nothing
    assert ("kai_scheduler_tpu/intake/router.py::IntakeRouter._worker"
            in multi)


def test_race_pass_sees_intake_lane_discipline(race_report):
    """Detection power for the PR-12 surface: the pass must actually
    OBSERVE _Lane state shared between the drain-worker root and
    handler/coalesce contexts under the lane lock — if type resolution
    of the lane helpers regresses, the lane annotations go stale and
    the race rules silently stop covering the intake path."""
    recs = [r for r in race_report.interp_accesses
            if r.cls == "_Lane" and r.attr in ("queued", "staged")]
    roots = {r.root for r in recs}
    assert any("IntakeRouter._worker" in r for r in roots), roots
    assert len(roots) >= 2, roots
    assert all(("_Lane", "_lock") in r.held for r in recs), [
        (r.function, r.line) for r in recs if ("_Lane", "_lock")
        not in r.held]


def test_guarded_by_annotations_are_live(race_report):
    """The package documents its lock discipline inline and the checker
    verifies every annotation still matches live shared state."""
    report = race_report
    assert report.live_annotations >= 5
    assert not any(f.code == "KAI100" for f in report.findings)


def test_race_pass_catches_dropped_journal_lock():
    """Detection power: deleting the journal lock from a mark path must
    surface KAI102 — the analyzer, not luck, guards the journal."""
    import ast as _ast

    from kai_scheduler_tpu.analysis import concurrency
    from kai_scheduler_tpu.analysis.callgraph import ModuleInfo
    graph = PackageGraph(ROOT)
    target = "kai_scheduler_tpu/state/incremental.py"
    for name, mod in graph.modules.items():
        if mod.relpath != target:
            continue
        src = mod.source.replace(
            "    def mark_time(self) -> None:\n"
            "        with self._lock:\n"
            "            self._apply_mark(\"time\", \"\")",
            "    def mark_time(self) -> None:\n"
            "        if True:\n"
            "            self._apply_mark(\"time\", \"\")")
        assert src != mod.source, "mark_time shape changed — update test"
        graph.modules[name] = ModuleInfo(
            relpath=mod.relpath, modname=mod.modname,
            tree=_ast.parse(src), source=src)
    report = concurrency.analyze_package(graph,
                                         concurrency.load_guarded_map())
    hits = [f for f in report.findings if f.code == "KAI102"
            and "generation" in f.message]
    assert hits, [f.render() for f in report.findings]


def test_race_cli_json_section(capsys):
    """``--race --json`` emits the race section: thread roots, zero
    findings, live annotations (the CI consumer contract)."""
    from kai_scheduler_tpu.analysis.__main__ import main
    rc = main(["--race", "--json", "--root", ROOT])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["findings"] == []
    assert out["race"]["findings"] == []
    assert len(out["race"]["thread_roots"]) >= 4
    assert out["race"]["live_annotations"] >= 5


def test_list_rules_includes_race_family(capsys):
    from kai_scheduler_tpu.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in ("KAI100", "KAI101", "KAI102", "KAI103", "KAI104",
                 "KAI105"):
        assert code in out


def test_race_suppression_and_staleness():
    """KAI1xx findings ride the same inline-suppression machinery as
    the KAI0xx rules, including KAI000 staleness."""
    bad = RULES["KAI101"].fixture_bad.replace(
        "        self.count += 1",
        "        self.count += 1  # kai-lint: disable=KAI101")
    assert lint_source(bad) == []
    stale = RULES["KAI101"].fixture_good.replace(
        "            self.count += 1",
        "            self.count += 1  # kai-lint: disable=KAI101")
    findings = lint_source(stale)
    assert [f.code for f in findings] == ["KAI000"]


def test_lock_order_fixture_is_directional():
    """KAI103 keys on *inverted* order, not on nesting per se."""
    from kai_scheduler_tpu.analysis.engine import RULES as _rules
    consistent = _rules["KAI103"].fixture_good
    assert not any(f.code == "KAI103" for f in lint_source(consistent))


# ---------------------------------------------------------------------------
# 4. jaxpr probe (compiles the real kernels — shares the suite's
#    persistent compile cache and padded shapes)

@pytest.fixture(scope="module")
def probe_reports():
    from kai_scheduler_tpu.analysis.trace_probe import run_probe
    return {r.name: r for r in run_probe()}


def test_probe_covers_all_registered_ops(probe_reports):
    from kai_scheduler_tpu.analysis.trace_probe import registered_ops
    assert sorted(probe_reports) == sorted(registered_ops())


def test_probe_no_forbidden_primitives(probe_reports):
    bad = {n: r.forbidden for n, r in probe_reports.items()
           if r.forbidden}
    assert not bad, f"host callbacks inside compiled ops: {bad}"


def test_probe_no_f64_on_device(probe_reports):
    bad = {n: r.f64_avals for n, r in probe_reports.items()
           if r.f64_avals}
    assert not bad, f"f64 avals leaked into device programs: {bad}"


def test_probe_compiles_once_per_shape_bucket(probe_reports):
    """Two independent builds of an equivalent cluster (fresh host
    objects, different wall clock) must share ONE compile per op —
    the end-to-end nondeterministic-signature guard.  ``is True``, not
    ``is not False``: if a jax upgrade drops the ``_cache_size`` probe,
    every report degrades to None and this must fail LOUDLY rather
    than pass vacuously (re-wire the probe, don't soften the test)."""
    not_hit = {n: r.cache_hit for n, r in probe_reports.items()
               if r.cache_hit is not True}
    assert not not_hit, (
        f"compile-once check not confirmed for {not_hit} (False = "
        f"re-trace missed the jit cache: some input shape/dtype/"
        f"static-config is build-dependent; None = the cache probe "
        f"is gone)")


def test_probe_stats_within_baseline(probe_reports):
    from kai_scheduler_tpu.analysis.trace_probe import (
        check_against_baseline, load_stats_baseline)
    problems = check_against_baseline(list(probe_reports.values()),
                                      load_stats_baseline())
    assert not problems, "\n".join(problems)
