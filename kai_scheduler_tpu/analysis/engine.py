"""kai-lint rule engine — registry, suppressions, baseline, drivers.

A rule is a function ``(RuleCtx) -> Iterator[Finding]`` registered
under a stable ``KAI0xx`` code with a one-line title and a pair of
self-test fixtures (a snippet that must trigger and one that must not —
``tests/test_analysis.py`` runs every rule against its own fixtures so
a refactor can't silently lobotomize a check).

Suppressions are inline comments, pylint-style::

    x = foo()  # kai-lint: disable=KAI001
    # kai-lint: disable=KAI007,KAI009   (own line: applies to the next)

Every suppression must keep matching a live finding: one that stops
matching is reported as ``KAI000 stale-suppression`` so disables rot
loudly instead of silently (the meta-test pins this).

The optional baseline (``--baseline``) holds ``{file, code, count}``
rows; findings are only *new* beyond the baselined count per (file,
code).  The shipped package baselines nothing — the tree lints clean —
but the mechanism lets a consumer adopt the linter before finishing
their own sweep.
"""
from __future__ import annotations

import ast
import dataclasses
import io
import json
import re
import tokenize
from typing import Callable, Iterable, Iterator

from .callgraph import ModuleInfo, PackageGraph

_SUPPRESS_RE = re.compile(r"#\s*kai-lint:\s*disable=([A-Z0-9,\s]+)")


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One lint hit, stable across runs (sortable for diffing)."""

    file: str
    line: int
    col: int
    code: str
    message: str
    function: str = ""

    def render(self) -> str:
        where = f" [{self.function}]" if self.function else ""
        return (f"{self.file}:{self.line}:{self.col}: "
                f"{self.code} {self.message}{where}")


@dataclasses.dataclass
class Rule:
    code: str
    title: str
    check: Callable[["RuleCtx"], Iterator[Finding]]
    #: (must-trigger, must-not-trigger) source snippets for self-test
    fixture_bad: str = ""
    fixture_good: str = ""


RULES: dict[str, Rule] = {}


def rule(code: str, title: str, *, bad: str = "", good: str = ""):
    """Register a rule under its KAI code (see ``rules.py``)."""
    def deco(fn):
        RULES[code] = Rule(code=code, title=title, check=fn,
                           fixture_bad=bad, fixture_good=good)
        return fn
    return deco


#: program-level (jaxpr) rule codes — the checks live in
#: ``costmodel.py`` (KAI2xx, layer 4) and ``comms.py`` (KAI3xx, layer
#: 5), both needing jax, but the catalog must stay jax-free for
#: ``--list-rules`` and ``scripts/lint.py``; their fixtures are jax
#: functions exercised by ``tests/test_costmodel.py`` /
#: ``tests/test_comms.py``, not AST snippets, so they are NOT engine
#: ``Rule`` entries
PROGRAM_RULES = {
    "KAI201": "intermediate aval exceeds blowup_factor × the entry's "
              "largest input (broadcast blowup, jaxpr-level)",
    "KAI301": "intermediate materializes the full node axis "
              "REPLICATED on every device above the size threshold "
              "(accidental node-axis replication, jaxpr-level)",
    "KAI302": "declared mesh.state_shardings leaf disagrees with the "
              "kai-comms inferred seed spec (sharding drift, "
              "mesh-level, both directions)",
    "KAI303": "collective inside scan/while charged trip-count × "
              "exceeds the loop comm budget (collective-under-loop, "
              "jaxpr-level)",
}


def rule_catalog() -> dict[str, str]:
    """code -> title, for --list-rules and the docs (AST rules plus
    the program-level KAI2xx family)."""
    from . import concurrency as _conc  # noqa: F401  (registers on import)
    from . import rules as _rules  # noqa: F401  (registers on import)
    out = {c: RULES[c].title for c in sorted(RULES)}
    out.update(PROGRAM_RULES)
    return dict(sorted(out.items()))


@dataclasses.dataclass
class RuleCtx:
    """Everything a rule sees for one module."""

    mod: ModuleInfo
    #: qualnames of this module's functions inside the jit region
    jit_quals: set[str]
    #: module relpaths allowed to hold host-side f64 (see rules.KAI030)
    f64_allowlist: frozenset[str]

    def jit_nodes(self) -> Iterator[tuple[str, ast.AST]]:
        for q in sorted(self.jit_quals):
            node = self.mod.functions.get(q)
            if node is not None:
                yield q, node

    def finding(self, code: str, node: ast.AST, message: str,
                function: str = "") -> Finding:
        return Finding(file=self.mod.relpath,
                       line=getattr(node, "lineno", 1),
                       col=getattr(node, "col_offset", 0),
                       code=code, message=message, function=function)


#: modules whose f64 is the documented host-side precision boundary —
#: usage integrals (usagedb) and unix-epoch timestamps (snapshot
#: builders), all reduced to f32 deltas before any device transfer.
#: The f32-device side of the boundary is utils/numerics.py (cumsum_ds
#: double-single compensation instead of f64).  See COVERAGE.md.
F64_HOST_ALLOWLIST = frozenset({
    "kai_scheduler_tpu/runtime/usagedb.py",
    "kai_scheduler_tpu/state/cluster_state.py",
    "kai_scheduler_tpu/state/incremental.py",
    # kai-intake admission sweep: bound checks need full double
    # precision (float32's 64-unit ulp at the 1e9 cap would round
    # out-of-range values ONTO the bound); host-only, nothing crosses
    # to the device
    "kai_scheduler_tpu/intake/apply.py",
})


@dataclasses.dataclass
class LintResult:
    findings: list[Finding]
    #: stale-suppression findings (KAI000), already included in findings
    stale_suppressions: list[Finding]
    #: raw finding count before suppressions/baseline (telemetry)
    raw_count: int
    baselined: int = 0
    #: the kai-race layer's report (thread roots, disciplines) when the
    #: KAI1xx family ran — see ``concurrency.py``
    race: "object" = None


def _suppressions(source: str) -> dict[int, set[str]]:
    """line -> suppressed codes.  An own-line comment binds to the next
    line; a trailing comment binds to its own line.  Only real COMMENT
    tokens count — example disables inside docstrings are inert."""
    out: dict[int, set[str]] = {}
    try:
        tokens = list(tokenize.generate_tokens(
            io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError):
        return out
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
        row, col = tok.start
        own_line = tok.line[:col].strip() == ""
        out.setdefault(row + 1 if own_line else row, set()).update(codes)
    return out


def _apply_suppressions(mod: ModuleInfo, findings: list[Finding],
                        selected: set[str] | None = None,
                        ) -> tuple[list[Finding], list[Finding]]:
    """Drop suppressed findings; report unused suppressions (KAI000).

    A suppression only counts as stale when its rule actually RAN this
    pass (``selected``) — ``--select KAI041`` must not condemn a live
    KAI052 disable it never gave a chance to match."""
    supp = _suppressions(mod.source)
    used: set[tuple[int, str]] = set()
    kept = []
    for f in findings:
        codes = supp.get(f.line, ())
        if f.code in codes:
            used.add((f.line, f.code))
        else:
            kept.append(f)
    stale = [
        Finding(file=mod.relpath, line=line, col=0, code="KAI000",
                message=(f"stale suppression: no live {code} finding on "
                         f"this line — remove the disable comment"))
        for line in sorted(supp)
        for code in sorted(supp[line])
        if (line, code) not in used
        and (selected is None or code in selected)
    ]
    return kept, stale


def load_baseline(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return data.get("lint", [])


def _apply_baseline(findings: list[Finding],
                    baseline: list[dict]) -> tuple[list[Finding], int]:
    budget = {(b["file"], b["code"]): int(b.get("count", 0))
              for b in baseline}
    kept, eaten = [], 0
    for f in sorted(findings):
        key = (f.file, f.code)
        if budget.get(key, 0) > 0:
            budget[key] -= 1
            eaten += 1
        else:
            kept.append(f)
    return kept, eaten


def _lint_module(mod: ModuleInfo, jit_quals: set[str],
                 select: Iterable[str] | None,
                 f64_allowlist: frozenset[str]) -> list[Finding]:
    from . import concurrency as _conc  # noqa: F401  (registers on import)
    from . import rules as _rules  # noqa: F401  (registers on import)
    ctx = RuleCtx(mod=mod, jit_quals=jit_quals,
                  f64_allowlist=f64_allowlist)
    out: list[Finding] = []
    for code in sorted(RULES):
        if select is not None and code not in select:
            continue
        out.extend(RULES[code].check(ctx))
    return out


def _race_by_module(graph: PackageGraph,
                    select: set[str] | None,
                    guarded_map: dict | None):
    """Run the graph-level kai-race pass (``concurrency.py``) and group
    its findings per module so suppressions apply alongside the
    per-module rules.  Returns ``(findings by modname, RaceReport)``;
    the pass is skipped entirely when ``--select`` names no KAI1xx
    code."""
    from . import concurrency
    codes = set(concurrency.race_codes())
    if select is not None and not (codes & select):
        return {}, None
    report = concurrency.analyze_package(
        graph, concurrency.load_guarded_map()
        if guarded_map is None else guarded_map)
    relpath_to_mod = {m.relpath: name
                      for name, m in graph.modules.items()}
    by_mod: dict[str, list[Finding]] = {}
    for f in report.findings:
        if select is not None and f.code not in select:
            continue
        modname = relpath_to_mod.get(f.file)
        if modname is not None:
            by_mod.setdefault(modname, []).append(f)
    return by_mod, report


def lint_package(root: str, *, package: str = "kai_scheduler_tpu",
                 select: Iterable[str] | None = None,
                 baseline: list[dict] | None = None,
                 f64_allowlist: frozenset[str] = F64_HOST_ALLOWLIST,
                 guarded_map: dict | None = None,
                 ) -> LintResult:
    """Lint every module of ``package`` under repo ``root`` — the
    per-module KAI0xx rules plus the graph-level KAI1xx race pass."""
    graph = PackageGraph(root, package=package)
    select = set(select) if select is not None else None
    race_hits, race_report = _race_by_module(graph, select, guarded_map)
    findings: list[Finding] = []
    stale: list[Finding] = []
    raw = 0
    for modname in sorted(graph.modules):
        mod = graph.modules[modname]
        hits = _lint_module(mod, graph.jit_functions(modname), select,
                            f64_allowlist)
        hits.extend(race_hits.get(modname, ()))
        raw += len(hits)
        kept, dead = _apply_suppressions(mod, hits, select)
        findings.extend(kept)
        stale.extend(dead)
    findings.extend(stale)
    eaten = 0
    if baseline:
        findings, eaten = _apply_baseline(findings, baseline)
    return LintResult(findings=sorted(findings),
                      stale_suppressions=sorted(stale),
                      raw_count=raw, baselined=eaten,
                      race=race_report)


def lint_source(source: str, *, filename: str = "<fixture>.py",
                select: Iterable[str] | None = None,
                f64_allowlist: frozenset[str] = frozenset(),
                ) -> list[Finding]:
    """Lint one in-memory module (rule fixtures / editor integration).

    The snippet is its own universe: jit entry points declared inside it
    (``@jax.jit`` etc.) grow its jit region exactly as in a package run,
    and thread spawns inside it seed the kai-race pass the same way.
    """
    graph = PackageGraph.__new__(PackageGraph)
    graph.root = "."
    graph.package = "<fixture>"
    mod = ModuleInfo(relpath=filename, modname="fixture",
                     tree=ast.parse(source, filename=filename),
                     source=source)
    graph.modules = {"fixture": mod}
    graph.jit_region = set()
    graph._grow()
    select = set(select) if select is not None else None
    hits = _lint_module(mod, graph.jit_functions("fixture"), select,
                        f64_allowlist)
    race_hits, _report = _race_by_module(graph, select, guarded_map={})
    hits.extend(race_hits.get("fixture", ()))
    kept, stale = _apply_suppressions(mod, hits, select)
    return sorted(kept + stale)
