"""kai-lint — static trace-safety, determinism, and recompile-hazard
analysis for the TPU hot path.

The scheduling cycle's whole value proposition is that it stays on
device as a fixed-shape compiled program (SURVEY §7): one dispatch per
cycle, one compile per (shape-bucket, config).  Nothing in Python
*enforces* that property — a stray ``.item()``, a branch on a tracer,
an f64 leak past the ``utils/numerics.py`` f32 discipline, or an
unordered-``set`` iteration feeding a snapshot buffer silently
reintroduces host syncs, recompiles, or nondeterministic signatures.
This package machine-checks those invariants in two layers:

* **Layer 1 — AST lint** (``engine``/``rules``/``callgraph``): a rule
  registry (``KAI0xx`` codes) over a jit-region call graph grown from
  the ``jax.jit`` entry points in ``framework/scheduler.py``,
  ``framework/session.py`` and ``ops/*``.  Pure AST — importing it
  never touches jax, so ``scripts/lint.py`` stays pre-commit fast.
* **Layer 2 — jaxpr probe** (``trace_probe``): traces every registered
  op at canonical padded shapes, walks the jaxpr for forbidden
  primitives (callbacks, f64), asserts compile-cache hits on re-trace
  within a shape bucket, and diffs per-op eqn/const-size stats against
  the checked-in ``baseline.json`` so constant bloat fails loudly.
* **Layer 3 — kai-race** (``concurrency``): thread-root call graphs +
  guarded-by lock-discipline analysis for the HOST runtime (the
  status-updater pool, the ThreadingHTTPServer handlers, the profiler
  sampler, the mutation journal).  ``KAI1xx`` codes, inline
  ``# kai-race: guarded-by=`` annotations, and the checked-in
  ``guarded_by.json`` audit map.  Pure AST, part of the lint layer.
* **Layer 4 — kai-cost** (``costmodel``): a static dataflow audit
  over the same per-entry jaxpr walk the probe uses — def/last-use
  liveness for peak-live-bytes (sub-jaxprs worst-case-resident), a
  per-primitive FLOPs/traffic cost table, the ``KAI201`` broadcast-
  blowup check, per-entry budgets in ``cost_baseline.json``, and a
  scaling mode that fits the peak-memory growth exponent over the node
  axis (the mesh-sharding go/no-go signal).
* **Layer 5 — kai-comms** (``comms``): a static SPMD sharding &
  collective-cost audit over the same shared walk — PartitionSpec
  propagation seeded from ``parallel/mesh.state_shardings``, a ring
  byte model per collective-inducing eqn (trip-count-charged under
  loops), the ``KAI301`` node-axis-replication / ``KAI302``
  declared-vs-inferred drift / ``KAI303`` collective-under-loop
  checks, per-entry budgets in ``comm_baseline.json``, an HLO
  lowering cross-validation on the virtual 8-device mesh, and a
  scaling mode that fits modeled comm bytes against device count
  (sublinear = the ROADMAP-2 "go" signal).

CLI: ``python -m kai_scheduler_tpu.analysis`` (see ``__main__``).
Suppression syntax: ``# kai-lint: disable=KAI001`` (own line → next
line; trailing → that line).  Stale suppressions are themselves
findings (``KAI000``), so every disable comment must keep matching a
live finding.
"""
from .engine import (Finding, LintResult, lint_package, lint_source,
                     load_baseline, rule_catalog)

__all__ = [
    "Finding", "LintResult", "lint_package", "lint_source",
    "load_baseline", "rule_catalog",
]
