"""Layer 5 — kai-comms: static SPMD sharding & collective-cost auditor.

kai-cost (layer 4) told us each entry's peak memory scales ~linearly
in the node axis — the "go" signal for ROADMAP item 2 (mesh-shard the
node axis to 100k nodes).  This layer answers the question that comes
next: **are the entry jaxprs actually shardable under the layout
``parallel/mesh.py`` declares**, and what does the sharding cost in
cross-device traffic?  A single accidental node-axis gather, or a
collective trapped inside the per-gang scan, would erase the win — and
before this pass the first place that showed up was real hardware.

The auditor is a sharding-propagation abstract interpreter over the
same ``trace_probe.EntryTrace`` per-entry jaxpr walk the probe and the
cost model share.  Entry inputs are seeded from a registry mirroring
``mesh.state_shardings`` (node-axis arrays sharded over
:data:`~kai_scheduler_tpu.parallel.mesh.NODE_AXIS`, everything else
replicated); each eqn then either *follows* its operands' sharding
(elementwise, transpose, slice-in-place, ``dot_general`` free dims) or
*induces a collective* (all-reduce for reductions over a sharded dim,
all-gather when a sharded dim must materialize, reduce-scatter /
reshard for layout moves), with modeled cross-device bytes per
collective (ring cost: ``b·(d-1)/d``, all-reduce ``2×``).
``dot_general`` / the reduce family / ``scatter`` are exact from their
dimension numbers; unknown primitives are conservatively gathered to
replicated and *reported* (``conservative_prims``) so table coverage
can't silently rot.

Program-level findings (KAI3xx, on the shared ``engine.Finding``
machinery, listed jax-free in ``engine.PROGRAM_RULES``):

* **KAI301 accidental node-axis replication** — an intermediate
  materializes the full node axis replicated on every device above a
  size threshold: the footprint that sharding exists to remove.
* **KAI302 declared-vs-inferred sharding drift** — the
  ``mesh.state_shardings`` pytree and this auditor's seed registry
  must agree leaf-exact, both directions; a new snapshot section can't
  silently default to replicated on one side only.
* **KAI303 collective-under-loop** — a collective inside
  ``scan``/``while`` is charged trip-count× (the comm analogue of
  kai-cost's worst-case-resident rule) and flagged above a byte
  threshold: hoist it, or absorb a justified baseline row.

Per-entry collective-site counts and comm-byte budgets diff against
``comm_baseline.json`` via the shared tolerance helper
(``analysis/budgets.py``); ``--update-baseline`` refreshes probe, cost
and comm baselines atomically or not at all.  A **lowering
cross-validation** stage jits the fused entries with the real
``in_shardings`` on an 8-virtual-device CPU mesh and asserts the
collective ops in the compiled HLO are within the model's predicted
set — UNVERIFIABLE introspection blocks baseline updates.
``--comms --scaling`` fits modeled comm bytes vs device count
{2, 4, 8}: the sub-linear-comm go/no-go signal for the sharded solver.

Run via ``python -m kai_scheduler_tpu.analysis --comms``.  Tier-1:
``tests/test_comms.py``; the mesh meta-test lives in
``tests/test_mesh.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import warnings
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from . import budgets
from . import trace_probe as tp
from .costmodel import (_aval_bytes, _aval_str, _is_drop, _is_var,
                        fit_exponent)
from .engine import PROGRAM_RULES, Finding, _apply_baseline
from ..parallel import mesh as mesh_mod
from ..state.cluster_state import ClusterState

COMM_BASELINE_PATH = os.path.join(os.path.dirname(__file__),
                                  "comm_baseline.json")

#: tolerance headroom over the checked-in per-entry comm budgets —
#: the shared formula (analysis/budgets.py), same shape as probe/cost
COMM_TOLERANCE = 0.25
SITE_SLACK = 4
COMM_SLACK_BYTES = 4096

#: comm-bytes-vs-devices exponent at or above which an entry's
#: modeled comm grows linearly-or-worse with mesh width — the no-go
#: bar for ROADMAP 2 (ring collectives plateau at (d-1)/d ≈ const, so
#: a healthy entry fits well under 1.0)
SUBLINEAR_EXPONENT_BAR = 1.0

#: the KAI3xx catalog — program-level rules implemented here, listed
#: jax-free in ``engine.PROGRAM_RULES`` (one source for --list-rules)
COMM_RULES = {k: v for k, v in PROGRAM_RULES.items()
              if k.startswith("KAI3")}

#: the fused production entry the HLO cross-validation stage lowers
#: with real in_shardings on the virtual CPU mesh
LOWERING_ENTRIES = ("fused_pipeline",)


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Knobs for the auditor (defaults are the shipped gate)."""

    #: mesh width the byte model charges (the virtual CPU mesh the
    #: lowering stage compiles against — one shared constant)
    num_devices: int = mesh_mod.VIRTUAL_DEVICE_COUNT
    #: KAI301 fires when a REPLICATED intermediate carrying the node
    #: axis exceeds this many bytes (canonical 32-wide shapes stay far
    #: under; bench/production widths do not)
    node_materialize_bytes: int = 1 << 20
    #: KAI303 fires when trip-count-charged loop collectives exceed
    #: this many modeled cross-device bytes per entry
    loop_comm_bytes: int = 8 << 20
    #: how many largest collectives each report retains
    top_k: int = 8


DEFAULT_CONFIG = CommConfig()


# ---------------------------------------------------------------------------
# PartitionSpec lattice

@dataclasses.dataclass(frozen=True)
class Spec:
    """An inferred PartitionSpec: one mesh-axis name (or None) per
    dim.  Unregistered dataclass on purpose — a pytree LEAF, so a
    ClusterState-shaped tree of Specs flattens 1:1 with the state."""

    dims: tuple

    @property
    def sharded(self) -> bool:
        return any(d is not None for d in self.dims)


def _ndim(x) -> int:
    s = getattr(x, "shape", None)
    if s is not None:
        return len(s)
    return int(np.ndim(x))


def _replicated(ndim: int) -> Spec:
    return Spec((None,) * int(ndim))


def _meet(a: Spec, b: Spec) -> Spec:
    """Lattice meet toward replicated: a dim keeps its axis name only
    when both sides agree (monotone — the fixpoint loops terminate)."""
    if len(a.dims) != len(b.dims):
        return _replicated(max(len(a.dims), len(b.dims)))
    return Spec(tuple(x if x == y else None
                      for x, y in zip(a.dims, b.dims)))


def _dedupe(dims: list) -> Spec:
    """A mesh axis can shard at most one dim — first occurrence wins
    (matches GSPMD's prefix resolution for our single-axis mesh)."""
    seen: set = set()
    out = []
    for d in dims:
        if d is not None and d in seen:
            out.append(None)
        else:
            if d is not None:
                seen.add(d)
            out.append(d)
    return Spec(tuple(out))


def collective_bytes(kind: str, nbytes: int, num_devices: int) -> int:
    """Modeled cross-device bytes for one collective over a ``nbytes``
    full (unsharded) array on a ``num_devices`` ring: gather/scatter
    families move ``b·(d-1)/d``; all-reduce is reduce-scatter +
    all-gather, ``2×`` that."""
    d = max(2, int(num_devices))
    base = int(nbytes) * (d - 1) // d
    if kind == "all_reduce":
        return 2 * base
    return base


# ---------------------------------------------------------------------------
# seed registry — the auditor's own, deliberately independent
# reimplementation of mesh.state_shardings (KAI302 cross-checks the
# two leaf-exact, both directions)

#: NodeState tables that carry the node axis SECOND ([X, N]); every
#: other node-section array is node-axis-first
NODE_AXIS_SECOND = frozenset({"filter_masks", "soft_scores"})

_STATE_SECTIONS = ("nodes", "queues", "gangs", "running")


def seed_state_specs(state: ClusterState):
    """A ClusterState-shaped pytree of :class:`Spec` seeds: node-axis
    arrays sharded over :data:`mesh.NODE_AXIS`, everything else
    replicated.  A snapshot section this registry does not know is a
    hard error — a new section must be classified here (and in
    ``mesh.state_shardings``) before it can ride the mesh."""
    sections = {f.name for f in dataclasses.fields(type(state))}
    unknown = sections - set(_STATE_SECTIONS)
    if unknown:
        raise ValueError(
            f"seed_state_specs: unclassified ClusterState section(s) "
            f"{sorted(unknown)} — add them to the kai-comms seed "
            f"registry AND mesh.state_shardings (KAI302 pins the two "
            f"against each other)")

    def repl(x):
        return _replicated(_ndim(x))

    node_specs = {}
    for f in dataclasses.fields(type(state.nodes)):
        if not f.metadata.get("pytree_node", True):
            continue
        nd = _ndim(getattr(state.nodes, f.name))
        if f.name in NODE_AXIS_SECOND:
            dims = (None, mesh_mod.NODE_AXIS) + (None,) * (nd - 2)
        else:
            dims = (mesh_mod.NODE_AXIS,) + (None,) * (nd - 1)
        node_specs[f.name] = Spec(dims)
    return state.replace(
        nodes=state.nodes.replace(**node_specs),
        queues=jax.tree.map(repl, state.queues),
        gangs=jax.tree.map(repl, state.gangs),
        running=jax.tree.map(repl, state.running))


def _entry_seed_specs(spec: tp.ProbeSpec, env, closed) -> list:
    """Flat per-invar :class:`Spec` seeds for one registered entry —
    built from the SAME ``make_args``/kwargs-filter path as
    ``trace_probe.trace_entry``, so the flattened seed list lines up
    with ``closed.jaxpr.invars`` by construction (and a structural
    drift raises instead of silently seeding replicated)."""
    args, kwargs = spec.make_args(env)
    trace_kwargs = {k: v for k, v in kwargs.items()
                    if k in ("k_value",)}

    def seed_arg(a):
        if isinstance(a, ClusterState):
            return seed_state_specs(a)
        return jax.tree.map(lambda x: _replicated(_ndim(x)), a)

    seed_tree = (tuple(seed_arg(a) for a in args),
                 {k: _replicated(_ndim(v))
                  for k, v in trace_kwargs.items()})
    leaves = jax.tree_util.tree_leaves(seed_tree)
    invars = closed.jaxpr.invars
    if len(leaves) != len(invars):
        raise RuntimeError(
            f"{spec.name}: seed-spec structure drifted — "
            f"{len(leaves)} seed leaves vs {len(invars)} jaxpr "
            f"invars (make_args and trace_entry must flatten alike)")
    out = []
    for s, v in zip(leaves, invars):
        nd = _ndim(getattr(v, "aval", None))
        out.append(s if len(s.dims) == nd else _replicated(nd))
    return out


# ---------------------------------------------------------------------------
# the abstract interpreter

@dataclasses.dataclass
class _Site:
    """One modeled collective: ``nbytes`` is the FULL array size the
    collective moves (the byte model scales it by ring cost), ``mult``
    the trip-count multiplier at the recording site."""

    kind: str            # all_reduce | all_gather | reduce_scatter | reshard
    primitive: str
    nbytes: int
    mult: int
    in_while: bool


@dataclasses.dataclass
class _Ctx:
    config: CommConfig
    node_extent: int
    sites: list
    conservative: Counter
    #: (nbytes, primitive, aval-str) replicated node-axis candidates
    node_candidates: list


def _site_cost(s: _Site, num_devices: int) -> int:
    return collective_bytes(s.kind, s.nbytes, num_devices) * s.mult


def _spec_of(env: dict, v) -> Spec:
    if not _is_var(v):                       # inline Literal
        return _replicated(_ndim(getattr(v, "aval", v.val)))
    return env.get(v) or _replicated(_ndim(v.aval))


def _emit(ctx: _Ctx, kind: str, prim: str, nbytes: int, mult: int,
          in_while: bool) -> None:
    if nbytes > 0:
        ctx.sites.append(_Site(kind=kind, primitive=prim,
                               nbytes=int(nbytes), mult=int(mult),
                               in_while=in_while))


def _gather_sharded_inputs(eqn, in_specs, ctx, mult, in_while) -> None:
    for v, s in zip(eqn.invars, in_specs):
        if s.sharded:
            _emit(ctx, "all_gather", eqn.primitive.name,
                  _aval_bytes(getattr(v, "aval", None)), mult, in_while)


def _conservative(eqn, in_specs, ctx, mult, in_while) -> list:
    """Unknown primitive: gather every sharded input, outputs
    replicated, and count it (reported, never silent)."""
    ctx.conservative[eqn.primitive.name] += 1
    _gather_sharded_inputs(eqn, in_specs, ctx, mult, in_while)
    return [_replicated(_ndim(getattr(v, "aval", None)))
            for v in eqn.outvars]


def _walk_closed(jaxpr_like, in_specs, ctx: _Ctx, mult: int = 1,
                 in_while: bool = False) -> list:
    """Propagate specs through one jaxpr level; returns outvar specs.
    Records collective sites / KAI301 candidates into ``ctx``."""
    inner = getattr(jaxpr_like, "jaxpr", jaxpr_like)
    env: dict = {}
    for v in inner.constvars:
        env[v] = _replicated(_ndim(v.aval))
    for v, s in zip(inner.invars, in_specs):
        env[v] = s if len(s.dims) == _ndim(v.aval) \
            else _replicated(_ndim(v.aval))
    for eqn in inner.eqns:
        e_in = [_spec_of(env, v) for v in eqn.invars]
        e_out = _propagate_eqn(eqn, e_in, ctx, mult, in_while)
        for v, s in zip(eqn.outvars, e_out):
            if not _is_var(v) or _is_drop(v):
                continue
            env[v] = s
            aval = v.aval
            shape = getattr(aval, "shape", ())
            if (not s.sharded and ctx.node_extent > 1
                    and ctx.node_extent in shape):
                nb = _aval_bytes(aval)
                if nb >= ctx.config.node_materialize_bytes:
                    ctx.node_candidates.append(
                        (nb, eqn.primitive.name, _aval_str(aval)))
    return [_spec_of(env, v) for v in inner.outvars]


# -- control flow -----------------------------------------------------------

def _sub_ctx(ctx: _Ctx) -> _Ctx:
    return _Ctx(config=ctx.config, node_extent=ctx.node_extent,
                sites=[], conservative=Counter(), node_candidates=[])


def _fixpoint_carry(body, nconsts_specs, carry_specs, extra_specs,
                    ctx) -> list:
    """Iterate the loop body on a throwaway ctx until the carry specs
    stabilize (the meet is monotone toward replicated, so this
    terminates — capped defensively anyway)."""
    for _ in range(16):
        probe = _sub_ctx(ctx)
        outs = _walk_closed(body,
                            list(nconsts_specs) + list(carry_specs)
                            + list(extra_specs), probe)
        new = [_meet(c, o) for c, o in
               zip(carry_specs, outs[:len(carry_specs)])]
        if new == list(carry_specs):
            return new
        carry_specs = new
    return [_replicated(len(c.dims)) for c in carry_specs]


def _rule_scan(eqn, in_specs, ctx, mult, in_while) -> list:
    num_consts = int(eqn.params["num_consts"])
    num_carry = int(eqn.params["num_carry"])
    length = max(1, int(eqn.params.get("length", 1) or 1))
    body = eqn.params["jaxpr"]
    consts = in_specs[:num_consts]
    carry = in_specs[num_consts:num_consts + num_carry]
    xs = in_specs[num_consts + num_carry:]
    xs_vars = eqn.invars[num_consts + num_carry:]
    slices = []
    for v, s in zip(xs_vars, xs):
        if s.dims and s.dims[0] is not None:
            # scanning over a sharded leading dim serializes the whole
            # array through every device: gather it once up front
            _emit(ctx, "all_gather", "scan",
                  _aval_bytes(getattr(v, "aval", None)), mult, in_while)
        slices.append(Spec(tuple(s.dims[1:])))
    carry = _fixpoint_carry(body, consts, carry, slices, ctx)
    outs = _walk_closed(body, list(consts) + list(carry) + slices,
                        ctx, mult=mult * length, in_while=in_while)
    ys = [Spec((None,) + tuple(s.dims))
          for s in outs[num_carry:]]
    return list(carry) + ys


def _rule_while(eqn, in_specs, ctx, mult, in_while) -> list:
    cn = int(eqn.params["cond_nconsts"])
    bn = int(eqn.params["body_nconsts"])
    cond = eqn.params["cond_jaxpr"]
    body = eqn.params["body_jaxpr"]
    cond_consts = in_specs[:cn]
    body_consts = in_specs[cn:cn + bn]
    carry = in_specs[cn + bn:]
    carry = _fixpoint_carry(body, body_consts, carry, (), ctx)
    # trip count is dynamic: charge ONE trip but mark every collective
    # in_while so KAI303 and the loop budget still see it
    _walk_closed(body, list(body_consts) + list(carry), ctx,
                 mult=mult, in_while=True)
    _walk_closed(cond, list(cond_consts) + list(carry), ctx,
                 mult=mult, in_while=True)
    return list(carry)


def _rule_cond(eqn, in_specs, ctx, mult, in_while) -> list:
    branches = eqn.params["branches"]
    ops = in_specs[1:]                       # invars = [pred] + ops
    results = []
    for br in branches:
        sub = _sub_ctx(ctx)
        outs = _walk_closed(br, ops, sub, mult=mult, in_while=in_while)
        results.append((sub, outs))
    # charge the worst branch's collectives (upper bound, like the
    # cost model's worst-branch FLOPs)
    worst = max(results, key=lambda t: sum(
        _site_cost(s, ctx.config.num_devices) for s in t[0].sites))
    ctx.sites.extend(worst[0].sites)
    ctx.conservative.update(worst[0].conservative)
    ctx.node_candidates.extend(worst[0].node_candidates)
    outs = results[0][1]
    for _, o in results[1:]:
        outs = [_meet(a, b) for a, b in zip(outs, o)]
    return outs


# -- leaf rules -------------------------------------------------------------

#: sharding-transparent elementwise family (rank-preserving, per-dim
#: shape match) — the cost model's table plus pure data movement that
#: keeps layout
_COMM_ELEMENTWISE = frozenset({
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
    "add_any", "copy", "stop_gradient", "device_put",
    "reduce_precision",
})

_COMM_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
})

_COMM_CUMULATIVE = frozenset({
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})


def _rule_elementwise(eqn, in_specs, ctx, mult, in_while) -> list:
    out = eqn.outvars[0]
    out_shape = getattr(out.aval, "shape", ())
    rank = len(out_shape)
    dims: list = []
    for j in range(rank):
        nm = None
        for v, s in zip(eqn.invars, in_specs):
            sh = getattr(getattr(v, "aval", None), "shape", ())
            if (len(sh) == rank and sh[j] == out_shape[j]
                    and s.dims[j] is not None):
                nm = s.dims[j]
                break
        dims.append(nm)
    spec = _dedupe(dims)
    # an input whose sharded dim did not survive at its position needs
    # a reshard first (cannot happen on a single-axis mesh with
    # rank-matched operands, kept for robustness)
    for v, s in zip(eqn.invars, in_specs):
        sh = getattr(getattr(v, "aval", None), "shape", ())
        if len(sh) != rank:
            continue
        for j, d in enumerate(s.dims):
            if d is not None and spec.dims[j] != d:
                _emit(ctx, "reshard", eqn.primitive.name,
                      _aval_bytes(v.aval), mult, in_while)
                break
    return [spec for _ in eqn.outvars]


def _rule_leaf(eqn, in_specs, ctx, mult, in_while) -> list:
    name = eqn.primitive.name
    params = eqn.params
    out_avals = [getattr(v, "aval", None) for v in eqn.outvars]

    if name in _COMM_ELEMENTWISE:
        return _rule_elementwise(eqn, in_specs, ctx, mult, in_while)

    if name == "iota":
        return [_replicated(_ndim(a)) for a in out_avals]

    if name == "broadcast_in_dim":
        src = in_specs[0]
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        out_shape = params["shape"]
        bdims = params["broadcast_dimensions"]
        dims = [None] * len(out_shape)
        for i, j in enumerate(bdims):
            if in_shape[i] == out_shape[j]:
                dims[j] = src.dims[i]
        return [_dedupe(dims)]

    if name == "transpose":
        perm = params["permutation"]
        return [Spec(tuple(in_specs[0].dims[p] for p in perm))]

    if name == "squeeze":
        drop = set(params["dimensions"])
        return [Spec(tuple(d for i, d in enumerate(in_specs[0].dims)
                           if i not in drop))]

    if name == "expand_dims":
        newdims = set(params["dimensions"])
        src = iter(in_specs[0].dims)
        dims = [None if j in newdims else next(src)
                for j in range(_ndim(out_avals[0]))]
        return [Spec(tuple(dims))]

    if name == "reshape":
        if params.get("dimensions") is not None:
            return _conservative(eqn, in_specs, ctx, mult, in_while)
        src = in_specs[0]
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        out_shape = params["new_sizes"]
        sharded = [(i, d) for i, d in enumerate(src.dims)
                   if d is not None]
        if not sharded:
            return [_replicated(len(out_shape))]
        if len(sharded) > 1:
            _gather_sharded_inputs(eqn, in_specs, ctx, mult, in_while)
            return [_replicated(len(out_shape))]
        i, nm = sharded[0]
        pre = int(np.prod(in_shape[:i], dtype=np.int64))
        for j in range(len(out_shape)):
            if (out_shape[j] == in_shape[i]
                    and int(np.prod(out_shape[:j],
                                    dtype=np.int64)) == pre):
                dims = [None] * len(out_shape)
                dims[j] = nm
                return [Spec(tuple(dims))]
        _emit(ctx, "all_gather", name,
              _aval_bytes(eqn.invars[0].aval), mult, in_while)
        return [_replicated(len(out_shape))]

    if name == "concatenate":
        dim = int(params["dimension"])
        rank = _ndim(out_avals[0])
        gathered = False
        for v, s in zip(eqn.invars, in_specs):
            if s.dims[dim] is not None:
                _emit(ctx, "all_gather", name, _aval_bytes(v.aval),
                      mult, in_while)
                gathered = True
        dims = []
        for j in range(rank):
            if j == dim:
                dims.append(None)
                continue
            nm = None
            for s in in_specs:
                if s.dims[j] is not None:
                    nm = s.dims[j]
                    break
            dims.append(nm)
        del gathered
        return [_dedupe(dims)]

    if name == "split":
        axis = int(params["axis"])
        src = in_specs[0]
        if src.dims[axis] is not None:
            _emit(ctx, "all_gather", name,
                  _aval_bytes(eqn.invars[0].aval), mult, in_while)
            dims = list(src.dims)
            dims[axis] = None
            return [Spec(tuple(dims)) for _ in eqn.outvars]
        return [src for _ in eqn.outvars]

    if name == "slice":
        src = in_specs[0]
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        starts = params["start_indices"]
        limits = params["limit_indices"]
        strides = params.get("strides") or (1,) * len(in_shape)
        dims = []
        for j, d in enumerate(src.dims):
            full = (starts[j] == 0 and limits[j] == in_shape[j]
                    and strides[j] == 1)
            if d is not None and not full:
                _emit(ctx, "all_gather", name,
                      _aval_bytes(eqn.invars[0].aval), mult, in_while)
                dims.append(None)
            else:
                dims.append(d)
        return [Spec(tuple(dims))]

    if name == "dynamic_slice":
        src = in_specs[0]
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        sizes = params["slice_sizes"]
        dims = []
        for j, d in enumerate(src.dims):
            if d is not None and sizes[j] != in_shape[j]:
                _emit(ctx, "all_gather", name,
                      _aval_bytes(eqn.invars[0].aval), mult, in_while)
                dims.append(None)
            else:
                dims.append(d)
        return [Spec(tuple(dims))]

    if name == "dynamic_update_slice":
        operand, update = in_specs[0], in_specs[1]
        if update.sharded and update.dims != operand.dims[:len(
                update.dims)] and update.dims != operand.dims:
            _emit(ctx, "reshard", name,
                  _aval_bytes(eqn.invars[1].aval), mult, in_while)
        elif operand.sharded:
            op_shape = getattr(eqn.invars[0].aval, "shape", ())
            up_shape = getattr(eqn.invars[1].aval, "shape", ())
            if any(operand.dims[j] is not None
                   and up_shape[j] != op_shape[j]
                   for j in range(len(op_shape))):
                # updating a window of a sharded dim crosses shards
                _emit(ctx, "reshard", name,
                      _aval_bytes(eqn.invars[1].aval), mult, in_while)
        return [operand]

    if name == "pad":
        src = in_specs[0]
        cfg = params["padding_config"]
        dims = []
        for j, d in enumerate(src.dims):
            if d is not None and tuple(cfg[j]) != (0, 0, 0):
                _emit(ctx, "all_gather", name,
                      _aval_bytes(eqn.invars[0].aval), mult, in_while)
                dims.append(None)
            else:
                dims.append(d)
        return [Spec(tuple(dims))]

    if name == "rev":
        src = in_specs[0]
        if any(src.dims[j] is not None for j in params["dimensions"]):
            # reversing a sharded dim permutes shard ownership
            _emit(ctx, "reshard", name,
                  _aval_bytes(eqn.invars[0].aval), mult, in_while)
        return [src]

    if name in _COMM_REDUCE:
        axes = params.get("axes")
        src = in_specs[0]
        if axes is None:
            return [src for _ in eqn.outvars]
        axes = set(int(a) for a in axes)
        if any(src.dims[a] is not None for a in axes):
            _emit(ctx, "all_reduce", name,
                  sum(_aval_bytes(a) for a in out_avals), mult,
                  in_while)
        dims = tuple(d for j, d in enumerate(src.dims)
                     if j not in axes)
        return [Spec(dims) for _ in eqn.outvars]

    if name in _COMM_CUMULATIVE:
        axis = int(params.get("axis", 0))
        src = in_specs[0]
        if src.dims[axis] is not None:
            _emit(ctx, "all_gather", name,
                  _aval_bytes(eqn.invars[0].aval), mult, in_while)
            dims = list(src.dims)
            dims[axis] = None
            return [Spec(tuple(dims))]
        return [src]

    if name == "sort":
        dim = int(params.get("dimension", -1))
        outs = []
        for v, s in zip(eqn.invars, in_specs):
            if s.dims[dim] is not None:
                _emit(ctx, "all_gather", name, _aval_bytes(v.aval),
                      mult, in_while)
                dims = list(s.dims)
                dims[dim] = None
                outs.append(Spec(tuple(dims)))
            else:
                outs.append(s)
        return outs[:len(eqn.outvars)] or [
            _replicated(_ndim(a)) for a in out_avals]

    if name == "top_k":
        src = in_specs[0]
        if src.dims[-1] is not None:
            _emit(ctx, "all_gather", name,
                  _aval_bytes(eqn.invars[0].aval), mult, in_while)
        dims = Spec(tuple(src.dims[:-1]) + (None,))
        return [dims for _ in eqn.outvars]

    if name == "dot_general":
        (lc, rc), (lb, rb) = params["dimension_numbers"]
        lhs, rhs = in_specs[0], in_specs[1]
        lhs_shape = getattr(eqn.invars[0].aval, "shape", ())
        rhs_shape = getattr(eqn.invars[1].aval, "shape", ())
        dims = []
        for dl, dr in zip(lb, rb):
            dims.append(lhs.dims[dl]
                        if lhs.dims[dl] is not None else rhs.dims[dr])
        for d in range(len(lhs_shape)):
            if d not in set(lc) | set(lb):
                dims.append(lhs.dims[d])
        for d in range(len(rhs_shape)):
            if d not in set(rc) | set(rb):
                dims.append(rhs.dims[d])
        if (any(lhs.dims[d] is not None for d in lc)
                or any(rhs.dims[d] is not None for d in rc)):
            _emit(ctx, "all_reduce", name,
                  sum(_aval_bytes(a) for a in out_avals), mult,
                  in_while)
        return [_dedupe(dims)]

    if name == "gather":
        dnums = params["dimension_numbers"]
        sizes = params["slice_sizes"]
        operand, indices = in_specs[0], in_specs[1]
        op_shape = getattr(eqn.invars[0].aval, "shape", ())
        if indices.sharded:
            _emit(ctx, "all_gather", name,
                  _aval_bytes(eqn.invars[1].aval), mult, in_while)
        start_map = set(dnums.start_index_map)
        bad = [d for d in range(len(op_shape))
               if operand.dims[d] is not None
               and (d in start_map or sizes[d] != op_shape[d])]
        if bad:
            _emit(ctx, "all_gather", name,
                  _aval_bytes(eqn.invars[0].aval), mult, in_while)
            return [_replicated(_ndim(out_avals[0]))]
        collapsed = set(dnums.collapsed_slice_dims)
        kept = [d for d in range(len(op_shape)) if d not in collapsed]
        dims = [None] * _ndim(out_avals[0])
        for off, d in zip(dnums.offset_dims, kept):
            if off < len(dims):
                dims[off] = operand.dims[d]
        return [_dedupe(dims)]

    if name.startswith("scatter"):
        dnums = params["dimension_numbers"]
        operand, indices, updates = in_specs[0], in_specs[1], in_specs[2]
        if any(operand.dims[d] is not None
               for d in dnums.scatter_dims_to_operand_dims):
            _emit(ctx, "reshard", name,
                  _aval_bytes(eqn.invars[2].aval), mult, in_while)
        if indices.sharded:
            _emit(ctx, "all_gather", name,
                  _aval_bytes(eqn.invars[1].aval), mult, in_while)
        if updates.sharded:
            _emit(ctx, "all_gather", name,
                  _aval_bytes(eqn.invars[2].aval), mult, in_while)
        return [operand]

    if name == "bitcast_convert_type":
        # same rank: layout-preserving; rank±1: the split/merged
        # trailing dim is the itemsize factor (never the node axis)
        src = in_specs[0]
        out_nd = _ndim(out_avals[0])
        if len(src.dims) == out_nd:
            return [src]
        if out_nd == len(src.dims) + 1:
            return [Spec(tuple(src.dims) + (None,))]
        if out_nd == len(src.dims) - 1 and src.dims[-1] is None:
            return [Spec(tuple(src.dims[:-1]))]
        return _conservative(eqn, in_specs, ctx, mult, in_while)

    return _conservative(eqn, in_specs, ctx, mult, in_while)


def _propagate_eqn(eqn, in_specs, ctx, mult, in_while) -> list:
    name = eqn.primitive.name
    if name == "scan":
        return _rule_scan(eqn, in_specs, ctx, mult, in_while)
    if name == "while":
        return _rule_while(eqn, in_specs, ctx, mult, in_while)
    if name == "cond":
        return _rule_cond(eqn, in_specs, ctx, mult, in_while)
    if name.startswith("scatter"):
        # scatter's update_jaxpr param would otherwise divert it into
        # the generic sub-jaxpr branch — its rule is exact from the
        # dimension numbers, use it
        return _rule_leaf(eqn, in_specs, ctx, mult, in_while)
    subs = tp.eqn_sub_jaxprs(eqn)
    if subs:
        # pjit / closed_call / remat / custom_jvp|vjp: recurse 1:1
        # into the call jaxpr when the arity lines up
        inner = getattr(subs[0], "jaxpr", subs[0])
        if (len(inner.invars) == len(in_specs)
                and len(inner.outvars) == len(eqn.outvars)):
            return _walk_closed(subs[0], in_specs, ctx, mult, in_while)
        return _conservative(eqn, in_specs, ctx, mult, in_while)
    return _rule_leaf(eqn, in_specs, ctx, mult, in_while)


# ---------------------------------------------------------------------------
# per-entry report

@dataclasses.dataclass
class CommReport:
    """One entry's static comm profile (the ``--comms`` unit)."""

    name: str
    num_devices: int
    #: number of modeled collective sites (loop sites count once here;
    #: their BYTES are trip-count-charged)
    collective_sites: int
    #: total modeled cross-device bytes (trip-count-charged)
    comm_bytes: int
    #: the slice of ``comm_bytes`` under scan/while (the KAI303 mass)
    loop_comm_bytes: int
    #: sorted collective kinds present (the lowering stage's predicted
    #: set)
    kinds: list
    #: top-K largest collectives: {kind, primitive, bytes, total_bytes,
    #: mult, in_while}
    top_collectives: list
    #: primitive -> eqn count handled conservatively (gather+replicate)
    conservative_prims: dict
    #: KAI301/KAI303 findings (engine.Finding), pre-baseline
    findings: list
    #: raw _Site list (scaling mode re-prices these per device count);
    #: not part of ``doc()``
    sites: list

    def doc(self) -> dict:
        return {
            "name": self.name,
            "num_devices": self.num_devices,
            "collective_sites": self.collective_sites,
            "comm_bytes": self.comm_bytes,
            "loop_comm_bytes": self.loop_comm_bytes,
            "kinds": list(self.kinds),
            "top_collectives": list(self.top_collectives),
            "conservative_prims": dict(self.conservative_prims),
        }


def analyze_closed(name: str, closed, seed_specs: list, *,
                   config: CommConfig = DEFAULT_CONFIG,
                   node_extent: int = 0) -> CommReport:
    """Run the sharding interpreter over one ClosedJaxpr — the shared
    back half of production entries and the KAI301/KAI303 fixtures."""
    ctx = _Ctx(config=config, node_extent=int(node_extent), sites=[],
               conservative=Counter(), node_candidates=[])
    _walk_closed(closed, seed_specs, ctx)
    d = config.num_devices
    comm = sum(_site_cost(s, d) for s in ctx.sites)
    loop_sites = [s for s in ctx.sites if s.mult > 1 or s.in_while]
    loop_comm = sum(_site_cost(s, d) for s in loop_sites)
    ranked = sorted(ctx.sites, key=lambda s: -_site_cost(s, d))
    top = [{"kind": s.kind, "primitive": s.primitive,
            "bytes": collective_bytes(s.kind, s.nbytes, d),
            "total_bytes": _site_cost(s, d), "mult": s.mult,
            "in_while": s.in_while}
           for s in ranked[:config.top_k]]

    findings: list[Finding] = []
    if ctx.node_candidates:
        worst = max(ctx.node_candidates)
        findings.append(Finding(
            file=f"jaxpr:{name}", line=0, col=0, code="KAI301",
            message=(
                f"{len(ctx.node_candidates)} intermediate(s) "
                f"materialize the full node axis REPLICATED on every "
                f"device above {config.node_materialize_bytes}B; "
                f"worst: {worst[2]} ({worst[0]}B) from `{worst[1]}` — "
                f"a replicated node-axis buffer is the footprint "
                f"mesh-sharding exists to remove (ROADMAP 2); keep "
                f"the node axis sharded through the op, or absorb a "
                f"justified baseline row"),
            function=name))
    if loop_sites and loop_comm > config.loop_comm_bytes:
        worst_s = max(loop_sites, key=lambda s: _site_cost(s, d))
        findings.append(Finding(
            file=f"jaxpr:{name}", line=0, col=0, code="KAI303",
            message=(
                f"{len(loop_sites)} collective(s) under scan/while "
                f"charged trip-count x: {loop_comm}B modeled loop "
                f"comm (> {config.loop_comm_bytes}B); worst: "
                f"{worst_s.kind} of {worst_s.nbytes}B from "
                f"`{worst_s.primitive}` x{worst_s.mult} — hoist the "
                f"collective out of the loop, or absorb a justified "
                f"baseline row"),
            function=name))
    return CommReport(
        name=name, num_devices=d, collective_sites=len(ctx.sites),
        comm_bytes=comm, loop_comm_bytes=loop_comm,
        kinds=sorted({s.kind for s in ctx.sites}),
        top_collectives=top,
        conservative_prims=dict(sorted(ctx.conservative.items())),
        findings=findings, sites=ctx.sites)


def registered_comm_entries() -> list[str]:
    """Comm coverage == probe coverage == cost coverage: ONE registry."""
    return tp.registered_ops()


def run_comms(names: list[str] | None = None, *,
              traces: list | None = None,
              config: CommConfig = DEFAULT_CONFIG,
              env=None) -> list[CommReport]:
    """Audit the selected (default: all) registered entries.

    ``traces`` accepts pre-built :class:`trace_probe.EntryTrace`
    objects (the shared walk) so a combined probe+cost+comms run
    traces each entry once.  ``env`` accepts an abstract
    ``ShapeDtypeStruct`` state (the bench's dispatch-free re-trace).
    """
    if env is None:
        env = tp._canonical_env(now=1000.0)
    if traces is None:
        traces = tp.trace_entries(names, env=env)
    elif names:
        sel = set(names)
        traces = [t for t in traces if t.name in sel]
    specs = {s.name: s for s in tp._registry()}
    node_extent = int(env[0].nodes.valid.shape[0])
    reports = []
    for t in traces:
        seeds = _entry_seed_specs(specs[t.name], env, t.closed)
        reports.append(analyze_closed(t.name, t.closed, seeds,
                                      config=config,
                                      node_extent=node_extent))
    return reports


# ---------------------------------------------------------------------------
# KAI302 — declared vs inferred sharding drift

def _sharding_dims(sharding, ndim: int) -> tuple:
    """A NamedSharding's PartitionSpec as per-dim axis names, padded
    to rank (P() / P(axis) are rank prefixes)."""
    spec = tuple(getattr(sharding, "spec", ()) or ())
    out = []
    for j in range(ndim):
        el = spec[j] if j < len(spec) else None
        if isinstance(el, (tuple, list)):
            el = el[0] if el else None
        out.append(el)
    return tuple(out)


def check_declared_shardings(state: ClusterState | None = None, *,
                             mesh=None, seeds=None,
                             declared=None) -> list[Finding]:
    """Leaf-exact, both-direction compare of ``mesh.state_shardings``
    against :func:`seed_state_specs` — one KAI302 finding per
    divergent leaf ([] = the two registries agree).  ``seeds`` /
    ``declared`` overrides exist for the rule fixtures."""
    if state is None:
        state, _ = tp._canonical_env(now=1000.0)
    if mesh is None:
        # spec extraction only needs mesh axis NAMES — a 1-device mesh
        # works on any host (the 8-device lowering stage is separate)
        mesh = mesh_mod.make_mesh(list(jax.devices())[:1])
    if declared is None:
        declared = mesh_mod.state_shardings(state, mesh)
    if seeds is None:
        seeds = seed_state_specs(state)
    paths = jax.tree_util.tree_flatten_with_path(state)[0]
    decl_leaves = jax.tree_util.tree_leaves(declared)
    seed_leaves = jax.tree_util.tree_leaves(seeds)
    findings: list[Finding] = []
    if not (len(paths) == len(decl_leaves) == len(seed_leaves)):
        findings.append(Finding(
            file="mesh:state_shardings", line=0, col=0, code="KAI302",
            message=(
                f"declared/inferred sharding pytrees do not even "
                f"flatten alike ({len(decl_leaves)} vs "
                f"{len(seed_leaves)} leaves over {len(paths)} state "
                f"leaves) — state_shardings and seed_state_specs "
                f"have structurally diverged"),
            function="<structure>"))
        return findings
    for (path, leaf), decl, seed in zip(paths, decl_leaves,
                                        seed_leaves):
        nd = _ndim(leaf)
        ddims = _sharding_dims(decl, nd)
        if ddims != tuple(seed.dims):
            where = jax.tree_util.keystr(path)
            findings.append(Finding(
                file="mesh:state_shardings", line=0, col=0,
                code="KAI302",
                message=(
                    f"declared sharding {ddims} != inferred seed "
                    f"{tuple(seed.dims)} for state leaf `{where}` — "
                    f"mesh.state_shardings and the kai-comms seed "
                    f"registry must agree leaf-exact (whichever side "
                    f"is wrong, fix it there; drift in either "
                    f"direction ships a silently mis-sharded solver)"),
                function=where))
    return findings


# ---------------------------------------------------------------------------
# baseline

def load_comm_baseline(path: str = COMM_BASELINE_PATH) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def check_against_comm_baseline(reports: list[CommReport],
                                baseline: dict, *,
                                full_coverage: bool = True
                                ) -> list[str]:
    """Numeric budget regressions ([] = clean) — collective sites and
    comm bytes against the checked-in per-entry stats, via the shared
    tolerance helper.  KAI301/KAI303 surface as findings instead
    (:func:`comm_findings`), not here."""
    entries = baseline.get("entries", {})
    problems: list[str] = []
    base_d = baseline.get("num_devices")
    if base_d is not None and any(r.num_devices != base_d
                                  for r in reports):
        problems.append(
            f"comm baseline modeled at {base_d} devices but this run "
            f"models {sorted({r.num_devices for r in reports})} — "
            f"refresh with --comms --update-baseline")
    for row in baseline.get("baselined", []):
        if (str(row.get("code", "")).startswith("KAI3")
                and not str(row.get("justification", "")).strip()):
            problems.append(
                f"baselined row {row.get('file')}/{row.get('code')} "
                f"lacks a non-empty justification — a KAI3xx "
                f"absorption must say WHY the comm hazard is "
                f"acceptable")
    for r in reports:
        base = entries.get(r.name)
        if base is None:
            problems.append(
                f"{r.name}: no comm baseline entry — run "
                f"`python -m kai_scheduler_tpu.analysis --comms "
                f"--update-baseline`")
            continue
        for metric, value, key, slack, unit in (
                ("collective sites", r.collective_sites,
                 "collective_sites", SITE_SLACK, " sites"),
                ("modeled comm bytes", r.comm_bytes, "comm_bytes",
                 COMM_SLACK_BYTES, "B"),
                ("loop comm bytes", r.loop_comm_bytes,
                 "loop_comm_bytes", COMM_SLACK_BYTES, "B")):
            p = budgets.budget_problem(
                r.name, metric, value, base[key],
                tolerance=COMM_TOLERANCE, slack=slack, unit=unit,
                hint="a new collective changed the entry's mesh "
                     "traffic profile — check top_collectives before "
                     "absorbing" if key == "comm_bytes" else "")
            if p:
                problems.append(p)
    if full_coverage:
        for name in sorted(set(entries) - {r.name for r in reports}):
            problems.append(
                f"comm baseline lists unknown entry `{name}` — "
                f"stale, refresh with --comms --update-baseline")
    return problems


def comm_findings(reports: list[CommReport],
                  baseline: dict | None = None, *,
                  extra=()) -> list[Finding]:
    """All KAI3xx findings (per-entry KAI301/KAI303 plus any ``extra``
    such as the KAI302 drift check), filtered through the engine's
    count-based baseline rows (``comm_baseline.json`` ``"baselined"``
    — shipped empty; absorptions additionally require a justification,
    enforced in :func:`check_against_comm_baseline`)."""
    findings = sorted(list(extra)
                      + [f for r in reports for f in r.findings])
    rows = (baseline or {}).get("baselined", [])
    if rows:
        findings, _eaten = _apply_baseline(findings, rows)
    return findings


def update_comm_baseline(reports: list[CommReport],
                         path: str = COMM_BASELINE_PATH) -> None:
    """MERGE the reports' stats (an ``--ops`` subset must not drop the
    other entries' budgets); stale entries pruned only on a
    full-registry update.  The ``baselined`` rows are preserved
    verbatim."""
    data = {"baselined": [], "entries": {}}
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    if reports:
        data["num_devices"] = reports[0].num_devices
    entries = data.setdefault("entries", {})
    entries.update({
        r.name: {"collective_sites": r.collective_sites,
                 "comm_bytes": r.comm_bytes,
                 "loop_comm_bytes": r.loop_comm_bytes}
        for r in sorted(reports, key=lambda r: r.name)})
    live = set(registered_comm_entries())
    if {r.name for r in reports} >= live:
        for name in sorted(set(entries) - live):
            del entries[name]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# lowering cross-validation — compile with REAL in_shardings on the
# virtual CPU mesh and diff the HLO's collectives against the model

_HLO_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\b")

_HLO_TO_MODEL = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "reshard",
    "collective-permute": "reshard",
}

#: GSPMD freely rewrites between these forms (an all-reduce may lower
#: as reduce-scatter + all-gather; a reshard as gather + slice), so a
#: predicted kind licenses its decompositions in the compiled HLO
_MODEL_KIND_IMPLIES = {
    "all_reduce": frozenset({"reduce_scatter", "all_gather"}),
    "all_gather": frozenset(),
    "reduce_scatter": frozenset(),
    "reshard": frozenset({"all_gather"}),
}


def _compiled_hlo_text(compiled) -> str | None:
    """Compiled-executable HLO text, ``None`` when the jax build
    exposes no introspection (report UNVERIFIABLE, never silently
    pass)."""
    try:
        mods = compiled.runtime_executable().hlo_modules()
        return "\n".join(m.to_string() for m in mods)
    except Exception:  # noqa: BLE001 — jax/jaxlib API drift
        try:
            return compiled.as_text()
        except Exception:  # noqa: BLE001
            return None


def _hlo_collective_kinds(text: str) -> set:
    return {_HLO_TO_MODEL[m.group(1)]
            for m in _HLO_COLLECTIVE_RE.finditer(text)}


def _allowed_hlo_kinds(predicted) -> set:
    allowed = set(predicted)
    for k in predicted:
        allowed |= _MODEL_KIND_IMPLIES.get(k, frozenset())
    return allowed


def lowering_check(names=LOWERING_ENTRIES, *,
                   num_devices: int | None = None,
                   config: CommConfig = DEFAULT_CONFIG,
                   reports: list | None = None,
                   env=None) -> list[dict]:
    """Jit each named entry with the REAL ``mesh.state_shardings``
    ``in_shardings`` on a ``num_devices`` virtual CPU mesh, compile,
    and assert the collective kinds in the HLO fall inside the model's
    predicted set (the model is a conservative upper bound).  A doc
    with ``verified: False`` always fails the gate and blocks
    ``--update-baseline``."""
    n = int(num_devices or config.num_devices)
    unknown = set(names) - set(registered_comm_entries())
    if unknown:
        raise ValueError(
            f"lowering_check: unknown entries {sorted(unknown)} — "
            f"not in the probe/cost/comms registry")
    mesh_mod.ensure_virtual_cpu_devices(n)
    try:
        devs = jax.devices("cpu")
    except RuntimeError:
        devs = []
    if len(devs) < n:
        return [{"entry": nm, "num_devices": n, "verified": False,
                 "error": (f"only {len(devs)} CPU devices — the "
                           f"backend initialised before "
                           f"ensure_virtual_cpu_devices could set "
                           f"XLA_FLAGS")} for nm in names]
    mesh = mesh_mod.make_mesh(list(devs[:n]))
    if env is None:
        env = tp._canonical_env(now=1000.0)
    by_name = {r.name: r for r in (reports or [])}
    specs = {s.name: s for s in tp._registry()}
    docs = []
    for nm in names:
        rep = by_name.get(nm)
        if rep is None:
            rep = run_comms([nm], config=config, env=env)[0]
        predicted = set(rep.kinds)
        spec = specs[nm]
        args, kwargs = spec.make_args(env)
        trace_kwargs = {k: v for k, v in kwargs.items()
                        if k in ("k_value",)}
        fn = (functools.partial(spec.trace_fn, **trace_kwargs)
              if trace_kwargs else spec.trace_fn)
        in_sh = tuple(
            mesh_mod.state_shardings(a, mesh)
            if isinstance(a, ClusterState) else mesh_mod.replicated(mesh)
            for a in args)
        doc = {"entry": nm, "num_devices": n,
               "predicted": sorted(predicted)}
        try:
            with warnings.catch_warnings():
                # sharding-propagation chatter is expected while
                # compiling with explicit in_shardings
                warnings.simplefilter("ignore")
                # audit-time jit, built per check on purpose: it is
                # lowered+compiled exactly once per audit and never
                # dispatched, so the KAI032 per-call cache-miss
                # hazard does not apply
                jit_fn = jax.jit(  # kai-lint: disable=KAI032
                    fn, in_shardings=in_sh)
                compiled = jit_fn.lower(*args).compile()
        except Exception as exc:  # noqa: BLE001 — report, don't crash
            doc.update(verified=False,
                       error=f"{type(exc).__name__}: {exc}")
            docs.append(doc)
            continue
        text = _compiled_hlo_text(compiled)
        if text is None:
            doc.update(verified=False,
                       error="compiled executable exposes no HLO "
                             "introspection")
        else:
            hlo = _hlo_collective_kinds(text)
            unexplained = sorted(hlo - _allowed_hlo_kinds(predicted))
            doc.update(hlo=sorted(hlo), unexplained=unexplained,
                       verified=not unexplained)
        docs.append(doc)
    return docs


def lowering_problems(docs: list[dict]) -> list[str]:
    """Gate messages for the cross-validation docs ([] = clean) —
    UNVERIFIABLE always fails."""
    problems = []
    for d in docs:
        if d.get("unexplained"):
            problems.append(
                f"{d['entry']}: compiled HLO contains collective "
                f"kind(s) {d['unexplained']} the sharding model did "
                f"not predict (predicted {d.get('predicted')}) — the "
                f"model's primitive table has a blind spot; extend "
                f"it, don't baseline around it")
        elif not d.get("verified"):
            problems.append(
                f"{d['entry']}: {d['num_devices']}-device lowering "
                f"cross-validation is UNVERIFIABLE "
                f"({d.get('error', 'no HLO introspection')}) — "
                f"re-wire the introspection, don't skip the check")
    return problems


# ---------------------------------------------------------------------------
# scaling mode — modeled comm bytes vs device count

def comm_scaling_report(names=LOWERING_ENTRIES,
                        device_counts=(2, 4, 8), *,
                        config: CommConfig = DEFAULT_CONFIG,
                        reports: list | None = None) -> dict:
    """Re-price each entry's collective sites at several mesh widths
    and fit the comm-bytes growth exponent.  ``sublinear`` entries
    (exponent < :data:`SUBLINEAR_EXPONENT_BAR`) are the ROADMAP-2 "go"
    signal: ring collectives cost ``b·(d-1)/d``, so healthy comm
    plateaus instead of growing with the mesh."""
    unknown = set(names) - set(registered_comm_entries())
    if unknown:
        raise ValueError(
            f"comm_scaling_report: unknown entries {sorted(unknown)} "
            f"— not in the probe/cost/comms registry")
    by_name = {r.name: r for r in (reports or [])}
    missing = [nm for nm in names if nm not in by_name]
    if missing:
        for r in run_comms(missing, config=config):
            by_name[r.name] = r
    out: dict = {"device_counts": list(device_counts),
                 "threshold": SUBLINEAR_EXPONENT_BAR, "entries": {}}
    for nm in names:
        r = by_name[nm]
        totals = [sum(collective_bytes(s.kind, s.nbytes, d) * s.mult
                      for s in r.sites) for d in device_counts]
        exp = fit_exponent(device_counts, totals)
        out["entries"][nm] = {
            "comm_bytes": totals,
            "exponent": round(exp, 3),
            "sublinear": exp < SUBLINEAR_EXPONENT_BAR,
        }
    return out


def comm_bytes_for_state(state, names: tuple = ("fused_pipeline",), *,
                         config: CommConfig = DEFAULT_CONFIG
                         ) -> dict[str, int]:
    """Modeled cross-device bytes of the named entries traced AT the
    given snapshot's shapes — the bench artifact's
    ``comm_model_bytes_per_cycle`` column.  The state is abstracted to
    ``ShapeDtypeStruct`` leaves first, so this is a pure re-trace: no
    compile, no dispatch at this shape."""
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                       jnp.result_type(x)), state)
    reps = run_comms(list(names), config=config,
                     env=(abstract, None))
    return {r.name: r.comm_bytes for r in reps}


# ---------------------------------------------------------------------------
# KAI3xx fixtures — jax functions, not AST snippets (the rules judge
# programs); tests/test_comms.py runs both directions of each,
# mirroring the engine's per-rule fixture self-tests

def _fixture_node_replication_bad(x):
    """cumsum over the sharded node axis forces an all-gather: the
    2MiB result materializes the node axis replicated."""
    return jnp.sum(jnp.cumsum(x, axis=0))


def _fixture_node_replication_good(x):
    """Elementwise + all-reduce of a scalar: the node axis stays
    sharded through the whole program."""
    return jnp.sum(x * jnp.float32(2.0))


def _fixture_loop_collective_bad(x):
    """A 512KiB all-gather trapped inside a 64-trip scan: 64× charged
    loop comm (~28MiB modeled), with each intermediate itself under
    the KAI301 size bar (no cross-fire)."""
    def body(c, _):
        return c + jnp.sum(jnp.cumsum(x, axis=0)), None
    out, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=64)
    return out


def _fixture_loop_collective_good(x):
    """Elementwise-only scan body over the sharded carry: zero
    collectives under the loop."""
    def body(c, _):
        return c * jnp.float32(0.5) + jnp.float32(1.0), None
    out, _ = jax.lax.scan(body, x, None, length=64)
    return out


def audit_fixture(code: str, kind: str = "bad") -> list[Finding]:
    """Run one KAI3xx fixture through the same audit path as
    production entries and return its findings."""
    if code == "KAI301":
        fn = (_fixture_node_replication_bad if kind == "bad"
              else _fixture_node_replication_good)
        x = jnp.zeros((8192, 64), jnp.float32)        # 2MiB
        closed = jax.make_jaxpr(fn)(x)
        seeds = [Spec((mesh_mod.NODE_AXIS, None))]
        rep = analyze_closed(f"fixture_{code}_{kind}", closed, seeds,
                             node_extent=8192)
        return rep.findings
    if code == "KAI303":
        fn = (_fixture_loop_collective_bad if kind == "bad"
              else _fixture_loop_collective_good)
        x = jnp.zeros((4096, 32), jnp.float32)        # 512KiB
        closed = jax.make_jaxpr(fn)(x)
        seeds = [Spec((mesh_mod.NODE_AXIS, None))]
        rep = analyze_closed(f"fixture_{code}_{kind}", closed, seeds,
                             node_extent=4096)
        return rep.findings
    if code == "KAI302":
        state, _ = tp._canonical_env(now=1000.0)
        if kind == "bad":
            seeds = seed_state_specs(state)
            seeds = seeds.replace(nodes=seeds.nodes.replace(
                valid=_replicated(1)))
            return check_declared_shardings(state, seeds=seeds)
        return check_declared_shardings(state)
    raise ValueError(f"unknown comm rule {code}")
