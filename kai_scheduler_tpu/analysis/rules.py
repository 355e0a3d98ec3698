"""The KAI rule catalog.

Code families (stable — suppressions and baselines reference them):

* ``KAI000``        stale suppression (emitted by the engine itself)
* ``KAI001-KAI004`` host syncs inside the jit region
* ``KAI011-KAI012`` Python control flow on traced values
* ``KAI021-KAI022`` precision-discipline / dtype-signature hazards
* ``KAI031-KAI032`` recompile hazards
* ``KAI041``        determinism hazards
* ``KAI051-KAI052`` generic hygiene
* ``KAI061``        observability discipline (tracer calls in traces)
* ``KAI071``        wire discipline (raw device transfers outside the
  ledger choke point)
* ``KAI091``        intake discipline (direct hub-journal mark writes
  outside the journal's module and the kai-intake gate)
* ``KAI2xx``        kai-cost program-level family (``costmodel.py``,
  catalog in ``engine.PROGRAM_RULES``): KAI201 broadcast blowup — an
  intermediate aval exceeding ``blowup_factor ×`` the entry's largest
  input.  These judge the traced *program*, not source: their fixtures
  are jax functions (``tests/test_costmodel.py``), their findings ride
  the engine's count-based baseline rows (``cost_baseline.json``), and
  inline source suppressions do not apply.
* ``KAI3xx``        kai-comms program-level family (``comms.py``,
  catalog in ``engine.PROGRAM_RULES``): KAI301 accidental node-axis
  replication — an intermediate materializing the full node axis
  replicated on every device above the size threshold; KAI302
  declared-vs-inferred sharding drift — a ``mesh.state_shardings``
  leaf disagreeing with the auditor's seed registry, checked
  leaf-exact both directions; KAI303 collective-under-loop — a
  collective inside ``scan``/``while`` whose trip-count-charged bytes
  exceed the loop comm budget.  Same program-level conventions as
  KAI2xx: jax-function fixtures (``tests/test_comms.py``),
  justification-required baseline rows (``comm_baseline.json``), no
  inline source suppressions.

"Jit region" is the transitive call graph grown from the package's
``jax.jit`` entry points (see ``callgraph.py``); host-only code is
exempt from the trace-safety families.  Every rule carries a
must-trigger and a must-not-trigger fixture, exercised by
``tests/test_analysis.py`` — edit a rule, keep its fixtures honest.
"""
from __future__ import annotations

import ast
from typing import Iterator

from .engine import Finding, RuleCtx, rule

# ---------------------------------------------------------------------------
# shared AST helpers

#: numpy attributes that are dtype/constant handles, not host kernels —
#: legal inside a trace (they parametrize jnp calls, nothing executes)
_NP_DTYPE_ATTRS = frozenset({
    "float16", "bfloat16", "float32", "float64", "int8", "int16",
    "int32", "int64", "uint8", "uint16", "uint32", "uint64", "bool_",
    "dtype", "iinfo", "finfo", "ndarray", "generic", "newaxis",
})

#: method names whose call on an array forces a device→host sync
_SYNC_METHODS = frozenset({"item", "tolist", "block_until_ready"})

#: the kai-trace recording surface (runtime/tracing.py CycleTracer) —
#: a span call inside a jit-traced function executes at TRACE time, so
#: it would record compilation (once) instead of execution (per cycle)
#: and silently measure nothing
_TRACER_METHODS = frozenset({
    "span", "cycle", "add_span", "device_sync", "begin_cycle",
    "end_cycle",
})

#: jnp functions whose output shape depends on input *values* — inside
#: jit they either fail to trace or (via fallback paths) force
#: per-value recompiles; all have ``size=`` escape hatches
_DATA_DEP_SHAPE = frozenset({
    "nonzero", "flatnonzero", "argwhere", "unique", "unique_values",
    "compress", "extract", "union1d", "intersect1d", "setdiff1d",
})


def _dotted(node: ast.AST) -> str | None:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _rooted(ctx: RuleCtx, node: ast.AST, roots: tuple[str, ...]
            ) -> str | None:
    """If ``node`` is an attribute chain whose base name aliases one of
    ``roots`` (prefix match), return the chain's final attribute."""
    d = _dotted(node)
    if d is None or "." not in d:
        return None
    base, rest = d.split(".", 1)
    target = ctx.mod.alias_root(base)
    if target is None:
        return None
    full = target + "." + rest
    for r in roots:
        if full == r or full.startswith(r + "."):
            return full[len(r) + 1:] if full != r else ""
    return None


def _numpy_attr(ctx: RuleCtx, node: ast.AST) -> str | None:
    return _rooted(ctx, node, ("numpy",))


def _jnp_attr(ctx: RuleCtx, node: ast.AST) -> str | None:
    return _rooted(ctx, node, ("jax.numpy",))


def _jax_attr(ctx: RuleCtx, node: ast.AST) -> str | None:
    return _rooted(ctx, node, ("jax",))


def _arrayish(ctx: RuleCtx, node: ast.AST) -> bool:
    """Does this subtree *compute on arrays* (so its truth value would
    concretize a tracer)?  Conservative: jnp/jax-family calls and
    ``.any()``/``.all()`` style reductions; plain config/name tests
    (static under jit) stay silent."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            if _jax_attr(ctx, sub.func) is not None:
                return True
            if (isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in ("any", "all", "item")):
                return True
    return False


def _body_nodes(fn: ast.AST) -> set[ast.AST]:
    """Nodes inside a def's *body* — decorators and defaults are
    evaluated at definition time in the enclosing scope, so they must
    not count as "inside the function" (a module-level ``@jax.jit``
    decorator is not a jit-in-function hazard)."""
    if not hasattr(fn, "_descendants"):
        out: set[ast.AST] = set()
        for stmt in fn.body:
            out.add(stmt)
            out.update(ast.walk(stmt))
        fn._descendants = out
    return fn._descendants


def _in_function(ctx: RuleCtx, node: ast.AST) -> str | None:
    """Qualname of the innermost function containing ``node``, if any."""
    best = None
    for qual, fn in ctx.mod.functions.items():
        if node in _body_nodes(fn):
            if best is None or len(qual) > len(best):
                best = qual
    return best


def _index_descendants(ctx: RuleCtx) -> None:
    for fn in ctx.mod.functions.values():
        _body_nodes(fn)


def _jit_body(ctx: RuleCtx) -> Iterator[tuple[str, ast.AST]]:
    """(qualname, node) for every AST node inside a jit-region def."""
    for qual, fn in ctx.jit_nodes():
        yield from ((qual, node) for node in _body_nodes(fn))


# ---------------------------------------------------------------------------
# KAI000 — emitted by the engine's suppression bookkeeping; registered
# here so the catalog and --select know the code

@rule("KAI000", "stale suppression (disable comment with no live "
      "finding)")
def _stale_suppression(ctx: RuleCtx) -> Iterator[Finding]:
    return iter(())


# ---------------------------------------------------------------------------
# KAI001-KAI004 — host syncs in the jit region

@rule(
    "KAI001", "host-sync method (.item/.tolist/.block_until_ready) in "
    "jit region",
    bad="""
import jax

@jax.jit
def op(x):
    return x.item()
""",
    good="""
import jax

@jax.jit
def op(x):
    return x + 1
""")
def _host_sync_method(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, node in _jit_body(ctx):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _SYNC_METHODS):
            yield ctx.finding(
                "KAI001", node,
                f".{node.func.attr}() forces a device→host sync inside "
                f"a compiled op — keep the value on device or move the "
                f"readback to the commit path", qual)


@rule(
    "KAI002", "numpy call on traced values in jit region",
    bad="""
import jax
import numpy as np

@jax.jit
def op(x):
    return np.asarray(x) * 2
""",
    good="""
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def op(x):
    return jnp.asarray(x, np.float32) * 2
""")
def _numpy_in_jit(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, node in _jit_body(ctx):
        if not isinstance(node, ast.Call):
            continue
        attr = _numpy_attr(ctx, node.func)
        if attr and attr.split(".")[-1] not in _NP_DTYPE_ATTRS:
            yield ctx.finding(
                "KAI002", node,
                f"np.{attr} concretizes its operands (host round trip "
                f"mid-trace) — use the jnp equivalent", qual)


@rule(
    "KAI003", "python scalar cast (int/float/bool) on traced value",
    bad="""
import jax

@jax.jit
def op(x):
    return x * float(x)
""",
    good="""
import jax

@jax.jit
def op(x):
    return x * float(x.shape[0])
""")
def _scalar_cast(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, fn in ctx.jit_nodes():
        params = {a.arg for a in (fn.args.args + fn.args.kwonlyargs
                                  + fn.args.posonlyargs)}
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in ("int", "float", "bool")
                    and len(node.args) == 1):
                continue
            arg = node.args[0]
            # static under jit: literals and shape/len arithmetic
            sub = list(ast.walk(arg))
            if any(isinstance(s, ast.Attribute) and s.attr == "shape"
                   for s in sub):
                continue
            if any(isinstance(s, ast.Call)
                   and isinstance(s.func, ast.Name)
                   and s.func.id in ("len", "range") for s in sub):
                continue
            traced = (isinstance(arg, ast.Name) and arg.id in params) \
                or any(isinstance(s, ast.Call)
                       and _jax_attr(ctx, s.func) is not None
                       for s in sub)
            if traced:
                yield ctx.finding(
                    "KAI003", node,
                    f"{node.func.id}() on a traced value aborts the "
                    f"trace (ConcretizationError) or syncs the host — "
                    f"stay in array land or hoist to a static arg", qual)


@rule(
    "KAI004", "explicit device transfer in jit region",
    bad="""
import jax

@jax.jit
def op(x):
    return jax.device_get(x)
""",
    good="""
import jax

def host_commit(x):
    return jax.device_get(x)
""")
def _device_transfer(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, node in _jit_body(ctx):
        if isinstance(node, ast.Call):
            attr = _jax_attr(ctx, node.func)
            if attr in ("device_get", "block_until_ready"):
                yield ctx.finding(
                    "KAI004", node,
                    f"jax.{attr} inside a compiled op is a host round "
                    f"trip — transfers belong on the commit path", qual)


# ---------------------------------------------------------------------------
# KAI011-KAI012 — Python control flow on traced values

@rule(
    "KAI011", "python branch on traced value in jit region",
    bad="""
import jax
import jax.numpy as jnp

@jax.jit
def op(x):
    if jnp.any(x > 0):
        return x
    return -x
""",
    good="""
import jax
import jax.numpy as jnp

@jax.jit
def op(x, flag=True):
    if flag:
        return jnp.abs(x)
    return -x
""")
def _branch_on_tracer(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, node in _jit_body(ctx):
        test = None
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            test = node.test
        if test is not None and _arrayish(ctx, test):
            kind = type(node).__name__.lower()
            yield ctx.finding(
                "KAI011", node,
                f"python {kind} on an array-valued test concretizes the "
                f"tracer (recompile per value, or TracerBoolError) — use "
                f"jnp.where / lax.cond / lax.while_loop", qual)


@rule(
    "KAI012", "assert in jit region (stripped under -O)",
    bad="""
import jax

@jax.jit
def op(x, n_static=4):
    assert n_static > 0, "bad config"
    return x * n_static
""",
    good="""
import jax

@jax.jit
def op(x, n_static=4):
    if n_static <= 0:
        raise ValueError("bad config")
    return x * n_static
""")
def _assert_in_jit(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, node in _jit_body(ctx):
        if isinstance(node, ast.Assert):
            yield ctx.finding(
                "KAI012", node,
                "assert in a kernel construction path: stripped under "
                "python -O (invariant silently vanishes), and a "
                "traced-value test would concretize — raise explicitly "
                "on static config instead", qual)


# ---------------------------------------------------------------------------
# KAI021-KAI022 — precision / dtype-signature discipline

@rule(
    "KAI021", "f64 outside the host-side allowlist (f32 device "
    "discipline, see utils/numerics.py)",
    bad="""
import jax
import jax.numpy as jnp

@jax.jit
def op(x):
    return x.astype(jnp.float64)
""",
    good="""
import jax
import jax.numpy as jnp

@jax.jit
def op(x):
    return x.astype(jnp.float32)
""")
def _f64_leak(ctx: RuleCtx) -> Iterator[Finding]:
    _index_descendants(ctx)
    jit_ids = set()
    for _q, fn in ctx.jit_nodes():
        jit_ids |= fn._descendants
    host_ok = ctx.mod.relpath in ctx.f64_allowlist
    # "float64" STRINGS only count in np/jnp call-argument (dtype)
    # position — a linter's own rule tables are not dtype leaks
    dtype_strings: set[ast.AST] = set()
    for node in ast.walk(ctx.mod.tree):
        if isinstance(node, ast.Call) and (
                _numpy_attr(ctx, node.func) is not None
                or _jnp_attr(ctx, node.func) is not None):
            for e in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(e, ast.Constant) and e.value == "float64":
                    dtype_strings.add(e)
    for node in ast.walk(ctx.mod.tree):
        name = None
        if isinstance(node, ast.Attribute) and node.attr in (
                "float64", "double", "complex128"):
            if _jnp_attr(ctx, node) is not None:
                name = f"jnp.{node.attr}"        # device f64: never OK
            elif _numpy_attr(ctx, node) is not None and (
                    not host_ok or node in jit_ids):
                name = f"np.{node.attr}"
        elif node in dtype_strings and (not host_ok or node in jit_ids):
            name = '"float64"'
        if name is not None:
            qual = _in_function(ctx, node) or ""
            yield ctx.finding(
                "KAI021", node,
                f"{name} breaks the f32-device / f64-host precision "
                f"boundary — device math uses compensated f32 "
                f"(utils/numerics.cumsum_ds); host f64 lives only in "
                f"allowlisted modules", qual)


@rule(
    "KAI022", "x64-flag-dependent builtin dtype (float/int/complex)",
    bad="""
import numpy as np

def table(n):
    return np.zeros(n, dtype=float)
""",
    good="""
import numpy as np

def table(n):
    return np.zeros(n, dtype=np.float32)
""")
def _builtin_dtype(ctx: RuleCtx) -> Iterator[Finding]:
    _index_descendants(ctx)
    for node in ast.walk(ctx.mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if (_numpy_attr(ctx, node.func) is None
                and _jnp_attr(ctx, node.func) is None):
            continue
        exprs = list(node.args) + [k.value for k in node.keywords]
        for e in exprs:
            if isinstance(e, ast.Name) and e.id in ("float", "int",
                                                    "complex"):
                yield ctx.finding(
                    "KAI022", e,
                    f"builtin dtype `{e.id}` resolves differently under "
                    f"jax_enable_x64 — the compile signature (and f32 "
                    f"discipline) silently changes with a flag; pin an "
                    f"explicit np dtype", _in_function(ctx, node) or "")


# ---------------------------------------------------------------------------
# KAI031-KAI032 — recompile hazards

@rule(
    "KAI031", "data-dependent output shape in jit region",
    bad="""
import jax
import jax.numpy as jnp

@jax.jit
def op(x):
    return jnp.nonzero(x)
""",
    good="""
import jax
import jax.numpy as jnp

@jax.jit
def op(x):
    return jnp.nonzero(x, size=8, fill_value=-1)
""")
def _data_dep_shape(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, node in _jit_body(ctx):
        if not isinstance(node, ast.Call):
            continue
        attr = _jnp_attr(ctx, node.func)
        if attr is None:
            continue
        kw = {k.arg for k in node.keywords}
        if attr in _DATA_DEP_SHAPE and "size" not in kw:
            yield ctx.finding(
                "KAI031", node,
                f"jnp.{attr} without size= has a value-dependent output "
                f"shape — untraceable (or a per-value recompile); pass "
                f"size=/fill_value= at the padded bound", qual)
        elif (attr == "where" and len(node.args) == 1
                and not {"x", "y"} & kw):
            yield ctx.finding(
                "KAI031", node,
                "single-argument jnp.where is jnp.nonzero in disguise "
                "(value-dependent shape) — use the three-argument form "
                "or pass size=", qual)


@rule(
    "KAI032", "jit constructed inside a function (per-call cache miss)",
    bad="""
import jax

def run(xs):
    op = jax.jit(lambda x: x + 1)
    return [op(x) for x in xs]
""",
    good="""
import jax

_op = jax.jit(lambda x: x + 1)

def run(xs):
    return [_op(x) for x in xs]
""")
def _jit_in_function(ctx: RuleCtx) -> Iterator[Finding]:
    _index_descendants(ctx)
    for node in ast.walk(ctx.mod.tree):
        if not isinstance(node, ast.Call):
            continue
        is_jit = _jax_attr(ctx, node.func) == "jit"
        if not is_jit:
            # functools.partial(jax.jit, ...) counts the same
            f = _dotted(node.func)
            if f is not None and f.split(".")[-1] == "partial" \
                    and node.args \
                    and _jax_attr(ctx, node.args[0]) == "jit":
                is_jit = True
        if not is_jit:
            continue
        qual = _in_function(ctx, node)
        if qual is not None:
            yield ctx.finding(
                "KAI032", node,
                "jax.jit built inside a function: each call makes a "
                "fresh callable whose closure/identity misses the "
                "compile cache — hoist the jitted wrapper to module "
                "scope", qual)


# ---------------------------------------------------------------------------
# KAI041 — determinism

@rule(
    "KAI041", "iteration over an unordered set/dict-view expression",
    bad="""
def ports(pods):
    out = []
    for p in set(pods):
        out.append(p)
    return out
""",
    good="""
def ports(pods):
    out = []
    for p in sorted(set(pods)):
        out.append(p)
    return out
""")
def _unordered_iteration(ctx: RuleCtx) -> Iterator[Finding]:
    _index_descendants(ctx)

    def is_setish(e: ast.AST) -> bool:
        if isinstance(e, (ast.Set, ast.SetComp)):
            return True
        if isinstance(e, ast.Call) and isinstance(e.func, ast.Name) \
                and e.func.id in ("set", "frozenset"):
            return True
        if isinstance(e, ast.BinOp) and isinstance(
                e.op, (ast.BitOr, ast.BitAnd, ast.Sub, ast.BitXor)):
            return any(
                is_setish(side)
                or (isinstance(side, ast.Call)
                    and isinstance(side.func, ast.Attribute)
                    and side.func.attr == "keys")
                for side in (e.left, e.right))
        return False

    iters = []
    for node in ast.walk(ctx.mod.tree):
        if isinstance(node, (ast.For, ast.AsyncFor)):
            iters.append(node.iter)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                               ast.DictComp)):
            iters.extend(g.iter for g in node.generators)
    for it in iters:
        if is_setish(it):
            yield ctx.finding(
                "KAI041", it,
                "iterating an unordered set expression: order is "
                "hash-seed dependent, so anything it feeds (snapshot "
                "buffers, scheduling signatures, journals) loses "
                "determinism — wrap in sorted()",
                _in_function(ctx, it) or "")


# ---------------------------------------------------------------------------
# KAI061 — observability discipline

@rule(
    "KAI061", "tracer/span call inside the jit region (records trace "
    "time, not run time)",
    bad="""
import jax

from kai_scheduler_tpu.runtime.tracing import CycleTracer

tracer = CycleTracer()


@jax.jit
def op(x):
    with tracer.span("solve"):
        return x + 1
""",
    good="""
import jax

from kai_scheduler_tpu.runtime.tracing import CycleTracer

tracer = CycleTracer()


@jax.jit
def op(x):
    # a device scope is metadata on the traced operations, not a timer:
    # it names them in the profiler's trace and is welcome here
    with jax.named_scope("solve"):
        return x + 1


def run(x):
    with tracer.span("solve"):
        return op(x)
""")
def _tracer_in_jit(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, node in _jit_body(ctx):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _TRACER_METHODS):
            continue
        base = _dotted(node.func.value)
        if base is not None and "tracer" in base.lower():
            yield ctx.finding(
                "KAI061", node,
                f".{node.func.attr}() on `{base}` inside a compiled op "
                f"runs at trace time — the span would bracket "
                f"compilation, not execution, and its timestamps would "
                f"be meaningless.  Instrument around the dispatch on "
                f"the host path instead", qual)


# ---------------------------------------------------------------------------
# KAI071 — wire discipline

#: the TransferLedger choke point: the only module allowed to touch
#: the raw host↔device transfer API.  Every other call site must route
#: through ``wire_ledger.LEDGER.device_put`` so per-leaf upload
#: accounting (bytes, reasons, redundancy) can never silently rot as
#: code grows.
_WIRE_CHOKE_POINT = frozenset({
    "kai_scheduler_tpu/runtime/wire_ledger.py",
})


@rule(
    "KAI071", "raw jax.device_put/device_get outside the wire-ledger "
    "choke point",
    bad="""
import jax

def ship(x):
    return jax.device_put(x)
""",
    good="""
from kai_scheduler_tpu.runtime.wire_ledger import LEDGER

def ship(x):
    return LEDGER.device_put(x, reason="full-build")
""")
def _raw_device_transfer(ctx: RuleCtx) -> Iterator[Finding]:
    if ctx.mod.relpath in _WIRE_CHOKE_POINT:
        return
    _index_descendants(ctx)
    for node in ast.walk(ctx.mod.tree):
        if not isinstance(node, ast.Call):
            continue
        attr = _jax_attr(ctx, node.func)
        if attr == "device_put":
            yield ctx.finding(
                "KAI071", node,
                "raw jax.device_put bypasses the TransferLedger — "
                "every host→device transfer must flow through "
                "runtime/wire_ledger.LEDGER.device_put so per-leaf "
                "bytes, reasons, and redundancy stay on the books",
                _in_function(ctx, node) or "")
        elif attr == "device_get":
            yield ctx.finding(
                "KAI071", node,
                "raw jax.device_get is an unaccounted device→host "
                "readback — the package's D2H budget is ONE packed "
                "commit transfer per cycle (Session.gather_host); "
                "route readbacks through the packed commit bundle "
                "instead of ad-hoc transfers the wire ledger cannot "
                "see", _in_function(ctx, node) or "")


# ---------------------------------------------------------------------------
# KAI091 — intake discipline

#: the hub-journal write choke point: the journal's own module plus the
#: kai-intake package (whose ``gate`` module owns the mark mapping and
#: whose router/applier are the sanctioned bulk writers).  Everything
#: else — hub mutators, binder write-backs, wire codecs, new
#: subsystems — must mark through ``intake/gate.py``, so the
#: storm-vs-sequential differential (one shared upsert/delete → mark
#: mapping) can never silently fork as code grows.  Mirrors KAI071's
#: device_put discipline.
_JOURNAL_CHOKE_POINT = frozenset({
    "kai_scheduler_tpu/state/incremental.py",
})
_JOURNAL_CHOKE_PREFIX = "kai_scheduler_tpu/intake/"

#: the MutationJournal mark surface (state/incremental.py) — calling
#: any of these on a journal object IS a hub-journal write
_JOURNAL_MARK_METHODS = frozenset({
    "mark_pod", "mark_pod_added", "mark_pod_removed", "mark_gang",
    "mark_gang_added", "mark_gang_removed", "mark_node",
    "mark_structural", "mark_time", "mark_stored", "merge",
})


@rule(
    "KAI091", "direct hub-journal mark outside the intake gate",
    bad="""
def evict(cluster, name):
    cluster.journal.mark_pod(name)
""",
    good="""
from kai_scheduler_tpu.intake import gate

def evict(cluster, name):
    gate.pod_touched(cluster.journal, name)
""")
def _raw_journal_mark(ctx: RuleCtx) -> Iterator[Finding]:
    if (ctx.mod.relpath in _JOURNAL_CHOKE_POINT
            or ctx.mod.relpath.startswith(_JOURNAL_CHOKE_PREFIX)):
        return
    _index_descendants(ctx)
    for node in ast.walk(ctx.mod.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _JOURNAL_MARK_METHODS):
            continue
        # scope to journal receivers: `<x>.journal.mark_*` chains and
        # names that smell like a journal — `merge` alone is far too
        # generic to flag on arbitrary objects
        base = _dotted(node.func.value)
        if base is None or "journal" not in base.lower():
            continue
        yield ctx.finding(
            "KAI091", node,
            f".{node.func.attr}() writes the hub MutationJournal "
            f"directly — route the mark through the kai-intake gate "
            f"(intake/gate.py), the package's single journal-write "
            f"choke point: one shared upsert/delete→mark mapping is "
            f"what keeps the async-lane coalesce bit-identical to the "
            f"sequential classic path (KAI091, mirrors KAI071)",
            _in_function(ctx, node) or "")


# ---------------------------------------------------------------------------
# KAI051-KAI052 — generic hygiene

@rule(
    "KAI051", "mutable default argument",
    bad="""
def collect(x, acc=[]):
    acc.append(x)
    return acc
""",
    good="""
def collect(x, acc=None):
    acc = [] if acc is None else acc
    acc.append(x)
    return acc
""")
def _mutable_default(ctx: RuleCtx) -> Iterator[Finding]:
    for qual, fn in ctx.mod.functions.items():
        args = fn.args
        for default in list(args.defaults) + [
                d for d in args.kw_defaults if d is not None]:
            mutable = isinstance(default, (ast.List, ast.Dict, ast.Set)) \
                or (isinstance(default, ast.Call)
                    and isinstance(default.func, ast.Name)
                    and default.func.id in ("list", "dict", "set",
                                            "bytearray"))
            if mutable:
                yield ctx.finding(
                    "KAI051", default,
                    "mutable default argument is shared across calls — "
                    "default to None and materialize inside", qual)


@rule(
    "KAI052", "function-level absolute import (package-relative "
    "cycle-breakers are exempt)",
    bad="""
def flush():
    import time
    return time.monotonic()
""",
    good="""
import time

def flush():
    from .sibling import helper
    return helper(time.monotonic())
""")
def _function_level_import(ctx: RuleCtx) -> Iterator[Finding]:
    _index_descendants(ctx)
    for node in ast.walk(ctx.mod.tree):
        absolute = isinstance(node, ast.Import) or (
            isinstance(node, ast.ImportFrom) and node.level == 0)
        if not absolute:
            continue
        qual = _in_function(ctx, node)
        if qual is not None:
            names = ", ".join(a.name for a in node.names)
            yield ctx.finding(
                "KAI052", node,
                f"import of `{names}` inside a function re-runs the "
                f"module lookup on every call (and hides the "
                f"dependency) — move to module scope; only "
                f"package-relative cycle-breakers stay local", qual)
