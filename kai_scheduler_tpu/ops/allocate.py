"""The allocate action — gang all-or-nothing placement as one compiled scan.

Reference hot path (``actions/allocate/allocate.go:52-156`` →
``actions/common/allocate.go:26-355``): pop jobs from the fairness heap;
per job open a Statement, greedily place each task on its best-scoring
feasible node, and commit iff at least ``minMember`` tasks landed —
otherwise roll the Statement back.  The per-task inner loop
(``allocateTask``, ``allocate.go:229``) is O(nodes) of predicate +
scoring work per task, fanned out over goroutines.

TPU-native design: one ``lax.scan`` whose carry is the *functional
cluster state* (free [N,R], per-queue allocation [Q,R], placement
tables).  Each step:

1. selects the next gang on-device (``ordering.select_next_gang`` — the
   dynamic two-level heap), then
2. runs a ``fori_loop`` over the gang's task slots; each task does a
   broadcast predicate mask + score over ALL nodes at once (the vmapped
   replacement for the goroutine fan-out) and a masked argmax pick, and
3. commits or discards the whole gang with ``jnp.where`` — checkpoint/
   rollback (``framework/statement.go:43-60``) becomes selection between
   the pre-gang and post-gang carries; no op log needed.

Pipelining: a task that only fits once terminating pods release
(``Releasing`` resources) is placed with ``pipelined=True`` — the
equivalent of ``stmt.Pipeline`` vs ``stmt.Allocate``.  Accounting runs
against the combined idle+releasing pool, matching the reference's
virtual allocation of releasing capacity.

Queue capacity gates (proportion plugin ``capacity_policy``): each task
checks, along the queue's ancestor chain, that allocation stays within
``limit`` (maxAllowed) and — for non-preemptible gangs — within
``quota`` (deserved).  A gang whose first ``minMember`` tasks cannot all
pass the gate fails wholesale via the same rollback mechanism.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from ..apis.types import UNLIMITED
from ..runtime import compile_watch
from ..state.cluster_state import ClusterState
from ..utils.numerics import einsum_exact
from . import ordering
from .predicates import feasible_nodes, feasible_nodes_dual, node_portion
from .scoring import (BIG_NEG, W_NOMINATED, W_TOPOLOGY, PlacementConfig,
                      gpu_sharing_score, pick_device, score_nodes_for_task)

EPS = 1e-6


class AllocationResult(struct.PyTreeNode):
    """The cycle's running commit set — the Statement, as a value.

    Every action (allocate, reclaim, preempt, consolidation) consumes and
    produces one of these, mirroring how reference actions share the
    Session's Statement/snapshot mutations across the per-cycle pipeline
    (``scheduler.go:158-168``).
    """

    placements: jax.Array     # i32 [G, T]  node index per task, -1 unplaced
    #: extended scalar-resource pool after commits — f32 [N, E]
    extended_free: jax.Array
    #: shared-device index per fractional task (-1 = whole-device/none) —
    #: feeds BindRequest.selected_accel_groups
    placement_device: jax.Array  # i32 [G, T]
    pipelined: jax.Array      # bool [G, T] placed onto releasing resources
    allocated: jax.Array      # bool [G]    gang committed this cycle
    attempted: jax.Array      # bool [G]    gang was popped and tried
    free: jax.Array           # f32 [N, R]  *idle* pool after commits (may dip
    #                           negative where pipelined tasks drew on
    #                           releasing capacity; feasibility always checks
    #                           idle+releasing sums)
    device_free: jax.Array    # f32 [N, D]  per-device share pool
    #: capacity freed by THIS cycle's victims — it is releasing, not idle
    #: (the pods have not terminated), so tasks placed on it pipeline.
    #: The tensor equivalent of Statement.Evict flipping a pod to
    #: Releasing status mid-cycle (``framework/statement.go``).
    releasing_extra: jax.Array         # f32 [N, R]
    device_releasing_extra: jax.Array  # f32 [N, D]
    #: extended (MIG) resources freed by this cycle's victims — credited
    #: to the pipeline-fit pool so a preemptor needing a MIG slice held
    #: only by victims can reclaim it (placements drawing on it pipeline)
    extended_releasing_extra: jax.Array  # f32 [N, E]
    queue_allocated: jax.Array  # f32 [Q, R]
    queue_allocated_nonpreemptible: jax.Array  # f32 [Q, R]
    #: running pods evicted this cycle (victims of reclaim/preempt/
    #: consolidation) — bool [M]
    victim: jax.Array
    #: consolidation move target per running pod — i32 [M] node index the
    #: evicted pod is planned to restart on (-1 = not a move); the
    #: equivalent of the pipelined BindRequest the reference creates for
    #: re-placed consolidation victims
    victim_move: jax.Array
    #: why a gang was not placed this cycle (ref ``api/unschedule_info.go``
    #: fit errors): 0 = placed/not tried, 1 = feasibility prefilter (no
    #: nodes for its tasks), 2 = an equivalent gang already failed
    #: (signature skip), 3 = placement attempt failed — i32 [G]
    fit_reason: jax.Array
    #: in-cycle claimed-domain table — bool [TA+1, AD+1]: row = exclusion
    #: term (see ``GangState.anti_marks``; TA = junk row), column = dense
    #: (node, level) domain id with per-node slots appended (AD = junk).
    #: Shared by ALL placement actions (allocate and the victim
    #: wavefronts), so a reclaim-placed preemptor excludes later
    #: conflicting placements within the same cycle.
    anti_used: jax.Array
    #: victim-wavefront observability counters — i32 [2, 5]: row 0 =
    #: reclaim, row 1 = preempt; cols = (chunks run, live lanes seen,
    #: lane slots offered, dense-fallback count of the sparse preempt
    #: path, lane-chunk demotion events from earlier lanes' net
    #: leftover freed capacity).  Rides the packed commit transfer and
    #: feeds the ``kai_victim_wavefront_*`` gauges
    #: (``framework/metrics.py``).
    wavefront_stats: jax.Array
    #: victim actions of this cycle that found no viable preemptor and
    #: built nothing — i32 [3]: reclaim, preempt, consolidation
    #: (``ops/victims.py`` ``VICTIM_ACTIONS``).  Rides the packed commit
    #: beside ``wavefront_stats``; feeds ``kai_victim_action_skipped``.
    victim_skipped: jax.Array
    #: what allocate's topology machinery did this cycle — i32 [4]
    #: (``TOPOLOGY_STATS``): pending gangs attempted under a required
    #: level, of those bound, attempts whose domain gate found no
    #: fitting domain, gangs with a preferred level bound inside one
    #: domain of it.  Zeros, and no operation, in a program compiled
    #: without ``subgroup_topology`` / ``preferred_topology``.  Rides
    #: the packed commit beside ``victim_skipped``; feeds
    #: ``CycleResult.topology``.
    topology_stats: jax.Array


#: the slots of ``AllocationResult.topology_stats``, in order
TOPOLOGY_STATS = ("required_attempted", "required_bound", "domain_misses",
                  "preferred_together")


def init_result(state: ClusterState) -> AllocationResult:
    """Fresh commit set at cycle start (an empty Statement)."""
    g, n, q = state.gangs, state.nodes, state.queues
    G, T = g.g, g.t
    TA = g.anti_term_level.shape[0]
    AD = n.n * n.topology.shape[1] + n.n
    return AllocationResult(
        anti_used=jnp.zeros((TA + 1, AD + 1), bool),
        wavefront_stats=jnp.zeros((2, 5), jnp.int32),
        victim_skipped=jnp.zeros((3,), jnp.int32),
        topology_stats=jnp.zeros((len(TOPOLOGY_STATS),), jnp.int32),
        placements=jnp.full((G, T), -1, jnp.int32),
        extended_free=n.extended_free,
        placement_device=jnp.full((G, T), -1, jnp.int32),
        pipelined=jnp.zeros((G, T), bool),
        allocated=jnp.zeros((G,), bool),
        attempted=jnp.zeros((G,), bool),
        free=n.free,
        device_free=n.device_free,
        releasing_extra=jnp.zeros_like(n.free),
        device_releasing_extra=jnp.zeros_like(n.device_free),
        extended_releasing_extra=jnp.zeros_like(n.extended_free),
        queue_allocated=q.allocated,
        queue_allocated_nonpreemptible=q.allocated_nonpreemptible,
        victim=jnp.zeros((state.running.m,), bool),
        victim_move=jnp.full((state.running.m,), -1, jnp.int32),
        fit_reason=jnp.zeros((G,), jnp.int32),
    )


def anti_domain_tables(state: ClusterState):
    """Static per-LEVEL dense domain ids for the in-cycle exclusion
    table (``AllocationResult.anti_used``): ``dom_static`` [L+1, N] —
    rows 0..L-1 are the topology levels (a node LACKING the level's
    label is its own per-node domain: upstream anti-affinity treats a
    missing topology key as no shared domain), row L is the per-node
    granularity; padded node slots map to the junk id AD."""
    n = state.nodes
    N, L = n.n, n.topology.shape[1]
    ND = N * L
    AD = ND + N
    node_slot = ND + jnp.arange(N)
    rows = []
    for lvl in range(L):
        by = n.topology[:, lvl]
        rows.append(jnp.where(n.valid,
                              jnp.where(by >= 0, by, node_slot), AD))
    rows.append(jnp.where(n.valid, node_slot, AD))
    return jnp.stack(rows), state.gangs.anti_term_level.shape[0]


def anti_forbid_nodes(state: ClusterState, anti_used: jax.Array,
                      dom_static: jax.Array, gang_idx: jax.Array):
    """bool [..., N] — nodes whose domain is already claimed in any of
    the gang's avoid rows this cycle (``gang_idx`` scalar or batched).
    Shared by the allocate wavefront and both victim paths."""
    g = state.gangs
    L = state.nodes.topology.shape[1]
    TA = g.anti_term_level.shape[0]
    if TA <= 0:
        raise ValueError("anti kernels compiled without terms")
    avoids = g.anti_avoids[jnp.maximum(gang_idx, 0)]       # [..., KT]
    t_safe = jnp.clip(avoids, 0, TA - 1)
    lvl = g.anti_term_level[t_safe]
    doms = dom_static[jnp.clip(lvl, 0, L)]                 # [..., KT, N]
    hit = anti_used[t_safe[..., None], doms]
    return jnp.any(hit & (avoids >= 0)[..., None], axis=-2)


def anti_mark_placements(state: ClusterState, anti_used: jax.Array,
                         dom_static: jax.Array, gang_idx: jax.Array,
                         nodes_t: jax.Array, valid: jax.Array):
    """Claim the committed placements' domains in the gang's mark rows
    (junk row/column absorb unused slots; ``valid`` gates whole
    gangs/lanes)."""
    g, n = state.gangs, state.nodes
    L = n.topology.shape[1]
    TA = g.anti_term_level.shape[0]
    if TA <= 0:
        raise ValueError("anti kernels compiled without terms")
    AD = n.n * L + n.n
    marks = g.anti_marks[jnp.maximum(gang_idx, 0)]         # [..., KT]
    t_safe = jnp.clip(marks, 0, TA - 1)
    lvl = g.anti_term_level[t_safe]
    placed = (nodes_t >= 0) & valid[..., None]             # [..., T]
    doms = dom_static[jnp.clip(lvl, 0, L)[..., None],
                      jnp.maximum(nodes_t, 0)[..., None, :]]  # [.., KT, T]
    ok = placed[..., None, :] & (marks >= 0)[..., None]
    rows = jnp.where(ok, t_safe[..., None], TA)
    cols = jnp.where(ok, doms, AD)
    return anti_used.at[rows, cols].max(True)


def anti_defer_lanes(state: ClusterState, cand_g: jax.Array,
                     cand_valid: jax.Array):
    """bool [B] — lanes whose avoid rows intersect an EARLIER valid
    lane's mark rows this chunk: they conflict-retry next chunk against
    the updated table (at most one side of a conflicting pair lands per
    chunk, mirroring the reference's one-at-a-time virtual updates)."""
    g = state.gangs
    B = cand_g.shape[0]
    marks = g.anti_marks[jnp.maximum(cand_g, 0)]           # [B, KT]
    avoids = g.anti_avoids[jnp.maximum(cand_g, 0)]
    inter = jnp.any(
        (avoids[:, None, :, None] == marks[None, :, None, :])
        & (avoids >= 0)[:, None, :, None]
        & (marks >= 0)[None, :, None, :], axis=(2, 3))     # [B, B]
    earlier = jnp.arange(B)[None, :] < jnp.arange(B)[:, None]
    return jnp.any(inter & earlier & cand_valid[None, :], axis=1) \
        & cand_valid


def attract_allow_nodes(state: ClusterState, anti_used: jax.Array,
                        dom_static: jax.Array, gang_idx: jax.Array):
    """bool [..., N] — nodes permitted by the gang's attraction (need)
    rows: EVERY need row must claim the node's domain at the row's
    level, either statically (a running match, ``attract_static``) or
    in-cycle (an anchor gang placed this cycle marked it).  Gangs
    without need slots pass everywhere.  Shared by the allocate
    wavefront and the victim placements (ref upstream InterPodAffinity
    against virtually-allocated state,
    ``k8s_internal/predicates/predicates.go:70-140``)."""
    g = state.gangs
    L = state.nodes.topology.shape[1]
    TA = g.anti_term_level.shape[0]
    if TA <= 0:
        raise ValueError("attract kernels compiled without terms")
    needs = g.attract_needs[jnp.maximum(gang_idx, 0)]      # [..., KP]
    t_safe = jnp.clip(needs, 0, TA - 1)
    lvl = g.anti_term_level[t_safe]
    doms = dom_static[jnp.clip(lvl, 0, L)]                 # [..., KP, N]
    claimed = (anti_used[t_safe[..., None], doms]
               | g.attract_static[t_safe])                 # [..., KP, N]
    ok = claimed | (needs < 0)[..., None]                  # unused pass
    return jnp.all(ok, axis=-2)                            # [..., N]


def attract_defer_lanes(state: ClusterState, cand_g: jax.Array,
                        cand_valid: jax.Array, anti_used: jax.Array):
    """bool [B] — lanes with a still-UNCLAIMED need row that an EARLIER
    valid lane of this chunk would mark: they sit the chunk out and
    retry against the updated table (so an anchor and its depender
    arriving in one chunk land in order instead of the depender failing
    terminally).  Lane 0 never defers, preserving the wavefront's
    progress guarantee."""
    g = state.gangs
    TA = g.anti_term_level.shape[0]
    AD = anti_used.shape[1] - 1
    B = cand_g.shape[0]
    needs = g.attract_needs[jnp.maximum(cand_g, 0)]        # [B, KP]
    marks = g.anti_marks[jnp.maximum(cand_g, 0)]           # [B, KT]
    row_any = (jnp.any(anti_used[:TA, :AD], axis=1)
               | jnp.any(g.attract_static, axis=1))        # [TA]
    open_need = (needs >= 0) & ~row_any[jnp.clip(needs, 0, TA - 1)]
    inter = jnp.any(
        (needs[:, None, :, None] == marks[None, :, None, :])
        & open_need[:, None, :, None]
        & (marks >= 0)[None, :, None, :], axis=(2, 3))     # [B, B]
    earlier = jnp.arange(B)[None, :] < jnp.arange(B)[:, None]
    return jnp.any(inter & earlier & cand_valid[None, :], axis=1) \
        & cand_valid


def sparse_entry_tables(nodes_b: jax.Array, ent_ok: jax.Array, N: int):
    """Node-sorted view of a wavefront chunk's K = B*T sparse placement
    entries — the shared core of the sparse accept-prefix protocol
    (lanes emit placements only; the chunk verifies composed capacity on
    per-entry claims instead of dense [B, N, R] delta cumsums).

    Entries are generated lane-major and sorted stably by node, so
    within a node they stay in lane order and a per-node inclusive
    cumulative claim is exactly the composed demand of lanes ``<= b``.
    Used by the allocate chunk and the victim wavefront's sparse accept.

    Returns (node_e [K] unsorted node per entry with ``N`` as junk,
    lane_e [K] unsorted lane per entry, perm [K] the stable node sort,
    ns [K] sorted nodes, lane_s [K] sorted lanes, sidx [K] index of each
    sorted entry's node-segment start, ok_s [K] sorted entry validity).
    """
    B, T = nodes_b.shape
    node_e = jnp.where(ent_ok, nodes_b, N).ravel()             # [K]
    lane_e = jnp.broadcast_to(
        jnp.arange(B)[:, None], (B, T)).ravel()
    perm = jnp.argsort(node_e, stable=True)
    ns = node_e[perm]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), ns[1:] != ns[:-1]])
    sidx = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first, jnp.arange(ns.shape[0]), -1))
    return node_e, lane_e, perm, ns, lane_e[perm], sidx, \
        ent_ok.ravel()[perm]


def sparse_accept_first_bad(nodes_b: jax.Array, ent_ok: jax.Array,
                            pipe_b: jax.Array, req_b: jax.Array,
                            free: jax.Array, pipe_pool: jax.Array,
                            N: int, credit=None):
    """First lane whose sparse claim entries over-subscribe a node pool
    — THE accept protocol, shared by the allocate chunk and the victim
    wavefront's sparse path (one implementation so a tolerance or
    side= change cannot silently diverge the two).

    Claims sort by node via ``sparse_entry_tables``; each entry's
    node-cumulative demand must fit ``pipe_pool`` (chunk-start free +
    releasing + extra), and the bind-now subset (claims with
    ``~pipe_b``) must collectively fit the chunk-start *idle* pool —
    pipelined flags were derived against chunk-start free, so without
    the second test a later lane could bind immediately onto capacity
    another lane just consumed.  ``credit`` optionally maps
    (lane_s [K], nsafe [K]) to per-entry [K, R] extra capacity granted
    to later lanes (the victim path's lane-prefix freed deltas
    gathered at the claim sites).

    Returns (first_bad lane id — B when every claim fits, node_e [K],
    lane_e [K]: the unsorted entry tables the commit reconstruction
    reuses).
    """
    B = nodes_b.shape[0]
    node_e, lane_e, perm, ns, lane_s, sidx, ok_s = \
        sparse_entry_tables(nodes_b, ent_ok, N)
    req_s = jnp.where(ok_s[:, None], req_b[lane_s], 0.0)      # [K, R]
    cs = jnp.cumsum(req_s, axis=0)
    cum_e = cs - (cs - req_s)[sidx]           # inclusive, per node
    nsafe = jnp.minimum(ns, N - 1)
    real = ns < N
    cap_pipe = pipe_pool[nsafe]
    if credit is not None:
        cap_pipe = cap_pipe + credit(lane_s, nsafe)
    viol = jnp.any(cum_e > cap_pipe + EPS, -1) & real
    bind_e = (ent_ok & ~pipe_b).ravel()[perm]
    reqb_s = jnp.where(bind_e[:, None], req_b[lane_s], 0.0)
    csb = jnp.cumsum(reqb_s, axis=0)
    cumb_e = csb - (csb - reqb_s)[sidx]
    cap_bind = jnp.maximum(free, 0.0)[nsafe] + EPS
    viol = viol | (jnp.any(cumb_e > cap_bind, -1) & real)
    return jnp.min(jnp.where(viol, lane_s, B)), node_e, lane_e


def _replica_count(avail: jax.Array, req: jax.Array,
                   mask: jax.Array) -> jax.Array:
    """i32 [N] whole replicas of ``req`` fitting in each node's ``avail``
    rows, zero outside ``mask`` — the ONE place the count arithmetic
    lives (the uniform kernel's lane path and the chunk-hoisted type
    tables must agree bit-for-bit)."""
    pos = req > EPS
    c = jnp.where(pos[None, :],
                  (avail + EPS) / jnp.maximum(req, EPS)[None, :],
                  jnp.inf)                              # [N, R]
    c = jnp.floor(jnp.min(c, axis=-1))
    return jnp.where(mask, jnp.clip(c, 0.0, 1e9), 0.0).astype(jnp.int32)


def _chain_membership(parent: jax.Array, num_levels: int) -> jax.Array:
    """bool [Q, Q]: ``C[q, a]`` — queue ``a`` is ``q`` or an ancestor of
    ``q``.  Computed once per action; turns per-task ancestor walks into
    single masked reductions."""
    Q = parent.shape[0]
    eye = jnp.eye(Q, dtype=bool)

    def hop(_, carry):
        member, cur = carry
        valid = cur >= 0
        idx = jnp.maximum(cur, 0)
        member = member | (valid[:, None] & eye[idx])
        return member, jnp.where(valid, parent[idx], -1)

    member, _ = lax.fori_loop(
        0, num_levels, hop, (jnp.zeros((Q, Q), bool), jnp.arange(Q)))
    return member


def _ancestor_scatter(parent: jax.Array, q: jax.Array, num_levels: int,
                      arr: jax.Array, delta: jax.Array) -> jax.Array:
    """Add ``delta`` [R] to ``arr`` [Q, R] at queue ``q`` and its ancestors."""
    def hop(_, carry):
        arr, cur = carry
        valid = cur >= 0
        idx = jnp.maximum(cur, 0)
        arr = arr.at[idx].add(jnp.where(valid, delta, 0.0))
        nxt = jnp.where(valid, parent[idx], -1)
        return arr, nxt
    arr, _ = lax.fori_loop(0, num_levels, hop, (arr, q))
    return arr


def _ancestor_gate(parent: jax.Array, q: jax.Array, num_levels: int,
                   used: jax.Array, cap: jax.Array, req: jax.Array) -> jax.Array:
    """True iff ``used[a] + req <= cap[a]`` (per resource, UNLIMITED caps
    skipped) for queue ``q`` and every ancestor ``a``."""
    def hop(_, carry):
        ok, cur = carry
        valid = cur >= 0
        idx = jnp.maximum(cur, 0)
        cap_q = cap[idx]
        unlimited = cap_q <= UNLIMITED + 0.5
        fits = jnp.all(unlimited | (used[idx] + req <= cap_q + EPS))
        ok = ok & (~valid | fits)
        nxt = jnp.where(valid, parent[idx], -1)
        return ok, nxt
    ok, _ = lax.fori_loop(0, num_levels, hop, (jnp.asarray(True), q))
    return ok


@dataclasses.dataclass(frozen=True)
class AllocateConfig:
    """Knobs of the allocate action (ref CLI flags + SchedulingShard)."""

    placement: PlacementConfig = PlacementConfig()
    #: max gangs attempted per QUEUE this action — ref
    #: ``QueueDepthPerAction`` ("max number of jobs to try for action per
    #: queue", ``conf/scheduler_conf.go:56``); None = unlimited.
    queue_depth: int | None = None
    #: order gangs by the PREDICTED pop sequence of the reference's
    #: dynamic two-level heap (hoisted — see allocate()), with a live
    #: per-chunk over-fair-share gate, vs freeze the job order at cycle
    #: start.  Exact while pops succeed; placement failures and elastic
    #: re-pushes perturb the tail of the order within an action.
    dynamic_order: bool = True
    #: gangs attempted in parallel per wavefront chunk.  Each chunk
    #: orders the remaining gangs by live fairness keys, attempts the
    #: first ``batch_size`` independently against chunk-start state, and
    #: accepts the maximal order-prefix whose *cumulative* claims fit
    #: (nodes, devices, queue caps).  Conflict-rejected gangs retry next
    #: chunk, so capacity semantics are exact; only the scoring heuristic
    #: sees ≤1 chunk of staleness.  1 = fully sequential (reference-exact).
    #: 256 measured fastest at the 10k-node × 50k-pod baseline scale.
    batch_size: int = 256
    #: maintain the per-device share table.  Set False when the snapshot
    #: holds no fractional/memory-based tasks — the node-level accel
    #: vector is then exact and the device-granular bookkeeping (the
    #: most op-heavy part of the task step) is skipped.  Session derives
    #: this from the snapshot automatically.
    track_devices: bool = True
    #: every gang's pending tasks are identical (same request/selector,
    #: no fractions) — the overwhelmingly common shape (a gang IS T
    #: replicas).  Enables the vectorized whole-gang placement that fills
    #: nodes by score order with per-node copy counts instead of T
    #: sequential task steps.  Requires ``track_devices=False``.  Session
    #: derives this from the snapshot automatically.
    uniform_tasks: bool = False
    #: whole-gang feasibility prefilter over the task-type table — gangs
    #: with no feasible nodes for ``min_needed`` tasks are never attempted
    #: (ref ``actions/common/feasible_nodes.go:11`` FeasibleNodesForJob)
    prefilter: bool = True
    #: compile the required-level machinery (per-subgroup domain locks +
    #: capacity-aware, domain-binpacked first placement — gang-level
    #: required levels route through subgroup slot 0).  An O(N) segment
    #: reduction per task step; False when the snapshot holds no required
    #: topology constraint.  Session derives this automatically.
    subgroup_topology: bool = True
    #: compile extended scalar-resource (MIG/DRA) fit + accounting.
    #: False when the snapshot carries none.  Session derives this
    #: automatically.  Enforcement covers allocate AND the victim
    #: scenarios: evicted pods' extended resources are credited back to
    #: their node's pipeline-fit pool (``extended_releasing_extra``), so
    #: a preemptor that needs a MIG slice held only by victims can
    #: reclaim it (see ``freed_by_mask``/``ops/victims.py`` freed_ext).
    extended: bool = False
    #: node feasibility spans the whole node axis (no selectors, filter
    #: classes, anti-affinity, or topology domains anywhere in the
    #: snapshot) — lets the whole-gang kernel use a cheap cyclic lane
    #: rotation instead of the per-attempt feasible-rank cumsum.  Session
    #: derives this automatically; False is always safe.
    dense_feasibility: bool = False
    #: skip gangs whose scheduling signature already failed this action —
    #: ref ``actions/common/minimal_job_comparison.go`` (MinimalJobRepresentatives)
    signature_skip: bool = True
    #: track in-cycle exclusion terms (mutual AND asymmetric required
    #: anti-affinity between pending gangs, plus shared host ports) in
    #: the cycle's claimed-domain table — ref InterPodAffinity /
    #: NodePorts over virtually-allocated session state.  The Session
    #: enables this when the snapshot emitted term rows
    #: (``GangState.anti_marks``); the table is sized from the state.
    anti_groups: bool = False
    #: enforce in-cycle ATTRACTION terms (required positive affinity
    #: toward a gang placed earlier this cycle): gangs with
    #: ``GangState.attract_needs`` slots place only on nodes whose
    #: domains are claimed in every need row (running matches pre-marked
    #: in ``attract_static``; anchors mark through the shared
    #: ``anti_marks`` machinery).  Requires ``anti_groups``.
    attract_groups: bool = False
    #: compile the PREFERRED-level locality band (anchor the gang near
    #: its best node's preferred domain).  The Session derives this from
    #: the snapshot — gangs without preferred levels skip the band's
    #: per-lane argmax + domain compare over the node axis entirely.
    preferred_topology: bool = True


def _attempt_gang_in_domain(
        state: ClusterState, gang_idx: jax.Array,
        free: jax.Array, device_free: jax.Array,
        q_alloc: jax.Array, q_alloc_np: jax.Array,
        num_levels: int, config: AllocateConfig,
        domain_mask: jax.Array,        # bool [N] — allowed nodes
        pref_doms: jax.Array,          # i32 [N]  preferred-level domain ids
        has_pref: jax.Array,           # bool []
        extra_releasing: jax.Array,        # f32 [N, R] victim-freed capacity
        extra_device_releasing: jax.Array, # f32 [N, D]
        lane: jax.Array,               # i32 [] wavefront lane (tie-break)
        chain: jax.Array,              # bool [Q, Q] ancestor membership
        prior_nodes: jax.Array | None = None,  # i32 [T] prior placements
        quota: jax.Array | None = None,    # i32 [] max new placements
        ext_free: jax.Array | None = None,  # f32 [N, E] extended pool
        extra_extended_releasing: jax.Array | None = None,  # f32 [N, E]
        banned_doms: jax.Array | None = None,  # i32 [S] domains to avoid
        score_bias: jax.Array | None = None  # f32 [N] extra score band
):
    """Place one gang greedily within ``domain_mask`` — the task loop of
    ``allocateTask`` (``actions/common/allocate.go:229``) including the
    fractional-device path (``gpu_sharing/gpu_sharing.go:20-105``).

    ``extra_releasing`` joins the snapshot's releasing pool for the
    pipeline-fit check, so tasks landing on victim-freed capacity are
    marked pipelined (bind later) while tasks on genuinely idle capacity
    bind immediately — matching ``stmt.Allocate`` vs ``stmt.Pipeline``.

    ``lane`` seeds a sub-score-resolution cyclic tie-break over nodes so
    the wavefront's parallel lanes spread over *equal-scoring* nodes
    instead of all argmaxing the same one (which would serialize the
    chunk accept-prefix to one gang).  Real score differences dominate
    the jitter; sequential (B=1) behavior has lane 0 ≡ plain first-index
    tie-break on an idle cluster.

    The task loop is unrolled (T is static): each step is small [N]-wide
    work and an on-device loop would cost more in iteration overhead
    than the unrolled graph.
    """
    g = state.gangs
    n = state.nodes
    T = g.t
    D = n.d
    N = n.n
    L = n.topology.shape[1]
    R_DIM = free.shape[1]
    task_req = g.task_req[gang_idx]          # [T, R]
    task_valid = g.task_valid[gang_idx]      # [T]
    task_sel = g.task_selector[gang_idx]     # [T, K]
    task_portion = g.task_portion[gang_idx]  # [T]
    task_mem = g.task_accel_mem[gang_idx]    # [T]
    task_class = g.task_filter_class[gang_idx]  # [T]
    task_nom = g.task_nominated[gang_idx]    # [T]
    task_ext = g.task_extended[gang_idx]     # [T, E]
    if config.extended:
        # MIG g-number accel equivalents per task (ref resource_info.go
        # GetTotalGPURequest: totalGpusQuota += gpuPortion * count) —
        # folded into the QUEUE accel ledger in-cycle so MIG-heavy
        # queues hit quota/over-share gates the same cycle they place;
        # node pools keep tracking the extended scalars themselves
        ext_gq = einsum_exact("te,e->t", task_ext, g.ext_accel)  # [T]
    if ext_free is None:
        ext_free = n.extended_free
    if extra_extended_releasing is None:
        extra_extended_releasing = jnp.zeros_like(ext_free)
    queue = g.queue[gang_idx]
    nonpreempt = ~g.preemptible[gang_idx]
    # gang-internal anti-affinity: no two tasks in the same domain at
    # this level (asl == L means per-node)
    asl = g.anti_self_level[gang_idx]
    has_asl = asl >= 0
    doms_self = jnp.where(asl >= L, jnp.arange(N),
                          n.topology[:, jnp.clip(asl, 0, L - 1)])       # [N]
    # re-push protocol (ref allocate.go:102-104 + getNumTasksToAllocate):
    # an attempt places at most ``quota`` new tasks, skipping tasks a
    # prior attempt already placed; its goal is min(quota, unplaced) and
    # success is all-or-nothing on that chunk.  Legacy callers (victim
    # solver) pass neither and keep quorum semantics.
    legacy = prior_nodes is None and quota is None
    if prior_nodes is None:
        prior_nodes = jnp.full((T,), -1, jnp.int32)
    if quota is None:
        quota = jnp.asarray(T, jnp.int32)
    already = prior_nodes >= 0                                          # [T]
    unplaced_t = task_valid & ~already
    unplaced = jnp.sum(unplaced_t.astype(jnp.int32))
    # seed cross-attempt state from prior placements: anti-self domains
    # and the preferred-level locality anchor
    prior_doms = doms_self[jnp.maximum(prior_nodes, 0)]                 # [T]
    forbidden0 = has_asl & jnp.any(
        (doms_self[:, None] == prior_doms[None, :]) & already[None, :],
        axis=1)                                                         # [N]
    first_prior = jnp.argmax(already)
    pref_dom0 = jnp.where(
        jnp.any(already),
        pref_doms[jnp.maximum(prior_nodes[first_prior], 0)], -1)

    # --- hierarchical subgroups (ref allocateSubGroupSet + the per-
    # subgroup chunks of GetTasksToAllocate): an attempt's eligible task
    # set is, while ANY subgroup is below quorum, the union of per-
    # subgroup quorum chunks (+ extra tasks when the gang's own minMember
    # exceeds the subgroup sum); once quorate, one scale-up task.
    S = g.s
    sub = g.task_subgroup[gang_idx]                                     # [T]
    sub_need = g.subgroup_min_needed[gang_idx]                          # [S]
    srl = g.subgroup_required_level[gang_idx]                           # [S]
    already_s = jax.ops.segment_sum(
        already.astype(jnp.int32), sub, num_segments=S)                 # [S]
    deficit = jnp.maximum(sub_need - already_s, 0)                      # [S]
    in_quorum = jnp.any(deficit > 0) | (
        jnp.sum(already.astype(jnp.int32)) <
        g.min_needed[gang_idx])
    earlier_same_sub = ((sub[None, :] == sub[:, None])
                        & (jnp.arange(T)[None, :] < jnp.arange(T)[:, None]))
    rank_in_sub = jnp.sum(earlier_same_sub & unplaced_t[None, :], axis=1)
    elig_quorum = unplaced_t & (rank_in_sub < deficit[sub])             # [T]
    # extra tasks to honour a gang minMember above the subgroup sum
    extra_needed = jnp.maximum(
        g.min_needed[gang_idx] - jnp.sum(already.astype(jnp.int32))
        - jnp.sum(deficit), 0)
    rest = unplaced_t & ~elig_quorum
    rank_rest = jnp.cumsum(rest.astype(jnp.int32)) - 1
    elig_quorum = elig_quorum | (rest & (rank_rest < extra_needed))
    first_unplaced = unplaced_t & (
        jnp.cumsum(unplaced_t.astype(jnp.int32)) - 1 < 1)
    eligible_new = jnp.where(in_quorum, elig_quorum, first_unplaced)
    goal = jnp.sum(eligible_new.astype(jnp.int32))
    if legacy:
        goal = jnp.minimum(quota, unplaced)
    # remaining per-subgroup request of this attempt's chunk — steers a
    # constrained subgroup's first placement into a domain big enough for
    # the whole chunk (the tensor stand-in for allocateSubGroupSet's
    # subset checkpoint/rollback search)
    sub_rem0 = jax.ops.segment_sum(
        jnp.where((eligible_new if not legacy else task_valid)[:, None],
                  task_req, 0.0),
        sub, num_segments=S)                                            # [S, R]
    # per-domain aggregate availability over the GLOBAL dense domain-id
    # space (all levels share it), computed once per attempt and
    # maintained incrementally — a per-task-step segment reduction blew
    # TPU scratch limits at wavefront width
    ND = N * L
    if config.subgroup_topology:
        avail0 = free + n.releasing + extra_releasing                   # [N, R]
        agg0 = jnp.zeros((ND + 1, R_DIM), avail0.dtype)
        for lvl in range(L):
            ids = jnp.where(n.valid & (n.topology[:, lvl] >= 0),
                            n.topology[:, lvl], ND)
            agg0 = agg0.at[ids].add(jnp.where(n.valid[:, None], avail0,
                                              0.0))
    else:
        agg0 = jnp.zeros((1, R_DIM), free.dtype)

    # Queue capacity gates (capacity_policy.go:26-50), hoisted out of the
    # task loop: all tasks of a gang share one queue chain, so the gate
    # for task t is "qa + cumulative request through t stays within every
    # ancestor's cap".  Computed for all T prefixes in one reduction.
    # (Slightly conservative vs the reference when a mid-gang task fails
    # placement: its request still counts toward later tasks' prefix.)
    anc = chain[queue]                                          # [Q]
    limit_eff = jnp.where(state.queues.limit <= UNLIMITED + 0.5,
                          jnp.inf, state.queues.limit)          # [Q, R]
    quota_eff = jnp.where(state.queues.quota <= UNLIMITED + 0.5,
                          jnp.inf, state.queues.quota)
    eligible_t = task_valid if legacy else eligible_new         # [T]
    req_valid = jnp.where(eligible_t[:, None], task_req, 0.0)   # [T, R]
    if config.extended:
        # the quota/limit prefix gates see the MIG g-equivalents too,
        # matching the snapshot-side rollups (GetTotalGPURequest)
        req_valid = req_valid.at[:, 0].add(
            jnp.where(eligible_t, ext_gq, 0.0))
    cum_req = jnp.cumsum(req_valid, axis=0)                     # [T, R]
    exempt = ~anc[None, :, None]
    gate_lim = jnp.all(
        (q_alloc[None] + cum_req[:, None, :] <= limit_eff[None] + EPS)
        | exempt, axis=(1, 2))                                  # [T]
    gate_quota = jnp.all(
        (q_alloc_np[None] + cum_req[:, None, :] <= quota_eff[None] + EPS)
        | exempt, axis=(1, 2))
    gate_t = gate_lim & jnp.where(nonpreempt, gate_quota, True)  # [T]

    def task_body(t, carry):
        (free_l, dev_l, ext_l, bind_used, dev_bind, ext_bind, forbidden,
         sub_dom, sub_rem, agg, nodes_t, dev_t, pipe_t, count, q_delta,
         pref_dom) = carry
        req = task_req[t]
        is_frac = (task_portion[t] > 0) | (task_mem[t] > 0)
        ok = eligible_t[t] & gate_t[t]

        fit_idle, fit_pipe = feasible_nodes_dual(
            n, req, task_sel[t], task_portion[t], task_mem[t],
            free=free_l, device_free=dev_l,
            extra_releasing=extra_releasing,
            extra_device_releasing=extra_device_releasing,
            devices=config.track_devices,
            task_class=task_class[t])
        if config.extended:
            te = task_ext[t]                                           # [E]
            fit_idle = fit_idle & jnp.all(
                ext_l + EPS >= te[None, :], axis=-1)
            fit_pipe = fit_pipe & jnp.all(
                ext_l + n.extended_releasing + extra_extended_releasing
                + EPS >= te[None, :], axis=-1)
        allowed = domain_mask & ~forbidden
        # per-subgroup required level: once the subgroup's first task
        # lands, its whole domain at that level is locked for the rest.
        # The pick is greedy and single-shot — the aggregate-capacity
        # gate (dom_ok below) stands in for allocateSubGroupSet's
        # per-subset rollback search, so a domain whose aggregate fits
        # but is fragmented across nodes can still fail the attempt
        # (retried next cycle); the whole-gang kernel's per-node replica
        # counts are fragmentation-exact for uniform gangs.
        s_t = sub[t]
        level_t = srl[s_t]
        has_srl = level_t >= 0
        dom_col = jnp.take(n.topology, jnp.clip(level_t, 0, L - 1),
                           axis=1)                                     # [N]
        locked = sub_dom[s_t]
        dom_band = jnp.zeros((N,), jnp.float32)
        if config.subgroup_topology:
            with jax.named_scope("domain_pick"):
                allowed = allowed & (
                    ~has_srl | (locked < 0) | (dom_col == locked))
                # a constrained subgroup's FIRST placement must pick a
                # domain whose aggregate capacity still fits the subgroup's
                # remaining chunk, or the lock would doom the attempt
                needs_pick = has_srl & (locked < 0)
                node_agg = agg[jnp.maximum(dom_col, 0)]            # [N, R]
                dom_ok = jnp.all(
                    node_agg + EPS >= sub_rem[s_t][None, :],
                    axis=-1) & (dom_col >= 0)
                if banned_doms is not None:
                    # in-cycle retry after a fragmented-domain failure:
                    # the previously locked domain is off the table this
                    # attempt
                    dom_ok = dom_ok & (dom_col != banned_doms[s_t])
                allowed = allowed & (~needs_pick | dom_ok)
                # binpack the domain choice: fullest fitting domain first
                # (ref topology/node_scoring.go domain ordering) — scaled
                # into the topology band so node-level bands stay
                # subordinate
                agg_accel = node_agg[:, 0]
                mx = jnp.max(jnp.where(dom_ok, agg_accel, 0.0))
                dom_band = jnp.where(
                    needs_pick & dom_ok,
                    W_TOPOLOGY * (1.0 - agg_accel / jnp.maximum(mx, EPS)),
                    0.0)
        fit_idle = fit_idle & allowed
        fit_pipe = fit_pipe & allowed                                  # [N]
        # preferred-level locality band (topology plugin node scoring):
        # stick with the domain of the gang's first-placed task.
        topo_band = jnp.where(
            has_pref & (pref_dom >= 0) & (pref_doms == pref_dom),
            W_TOPOLOGY, 0.0)                                           # [N]
        # per-lane tie-break by rank WITHIN the feasible set: equal-scoring
        # nodes spread across wavefront lanes even when feasibility is
        # confined to a small domain (an absolute-index rotation would
        # collapse every lane onto the same first feasible node there,
        # serializing the chunk to one accepted gang)
        with jax.named_scope("feasibility"):
            rank_feas = jnp.cumsum(fit_pipe.astype(jnp.int32)) - 1
        tie_jitter = (-1e-4 / N) * jnp.mod(rank_feas - lane, N).astype(
            jnp.float32)                                               # [N]
        # soft filter bands (PreferNoSchedule / preferred pod-affinity)
        # + the nominatednode plugin's dominating bonus + the required-
        # domain binpack band
        extra_bands = (topo_band + dom_band + tie_jitter
                       + n.soft_scores[task_class[t]]
                       + jnp.where(jnp.arange(N) == task_nom[t],
                                   W_NOMINATED, 0.0))
        if score_bias is not None:
            extra_bands = extra_bands + score_bias
        if config.track_devices:
            portion_n = node_portion(n, task_portion[t], task_mem[t])  # [N]
            extra_bands = extra_bands + gpu_sharing_score(
                dev_l, portion_n, is_frac)                             # [N]
        scores = score_nodes_for_task(
            n, free_l, req, fit_idle, fit_pipe, config.placement,
            extra=extra_bands)                                         # [N]
        node = jnp.argmax(scores)
        placed = ok & jnp.any(fit_pipe)
        is_pipe = placed & ~fit_idle[node]

        if config.track_devices:
            # ---- device bookkeeping (GPU-group allocation) --------------
            dev_row = dev_l[node]                                      # [D]
            dev_rel_row = (n.device_releasing[node]
                           + extra_device_releasing[node])
            p = portion_n[node]
            # fractional: GpuOrderFn pick among idle-fitting devices; a
            # pipelined fraction may dip into releasing share (bounded
            # negative, like the node-level free carry)
            frac_row = jnp.where(is_pipe, dev_row + dev_rel_row, dev_row)
            frac_dev = pick_device(frac_row, p,
                                   pack=config.placement.device_pack)
            # whole: take ceil(req) devices, idle-free first then releasing
            k = jnp.round(req[0]).astype(jnp.int32)
            eligible = dev_row + dev_rel_row >= 1.0 - EPS
            rank_key = jnp.where(eligible, -dev_row, jnp.inf)
            rank = jnp.sum(
                (rank_key[None, :] < rank_key[:, None])
                | ((rank_key[None, :] == rank_key[:, None])
                   & (jnp.arange(D)[None, :] < jnp.arange(D)[:, None])),
                axis=-1)                                               # [D]
            take_whole = eligible & (rank < k)
            dev_delta = jnp.where(
                is_frac,
                p * (jnp.arange(D) == frac_dev),
                take_whole.astype(dev_row.dtype))
            dev_delta = jnp.where(placed, dev_delta, 0.0)
            dev_l = dev_l.at[node].add(-dev_delta)
            dev_bind = dev_bind.at[node].add(
                jnp.where(is_pipe, 0.0, dev_delta))
        else:
            p = req[0]
            frac_dev = jnp.asarray(-1, jnp.int32)

        delta = jnp.where(placed, req, 0.0)
        # node-level accel debit uses the node's actual share (memory-
        # based portions differ per node); queue debits stay canonical
        delta_node = delta.at[0].set(
            jnp.where(placed, jnp.where(is_frac, p, req[0]), 0.0))
        free_l = free_l.at[node].add(-delta_node)
        # bind-now claims tracked separately: the wavefront accept check
        # must verify that *immediately bound* tasks collectively fit the
        # chunk-start idle pool (pipelined tasks legitimately overdraw it)
        bind_used = bind_used.at[node].add(
            jnp.where(is_pipe, 0.0, delta_node))
        if config.extended:
            ext_delta = jnp.where(placed, task_ext[t], 0.0)
            ext_l = ext_l.at[node].add(-ext_delta)
            ext_bind = ext_bind.at[node].add(
                jnp.where(is_pipe, 0.0, ext_delta))
        delta_queue = delta
        if config.extended:
            # queue ledger counts MIG g-equivalents in-cycle
            delta_queue = delta.at[0].add(jnp.where(placed, ext_gq[t], 0.0))
        q_delta = q_delta + delta_queue
        # anti-self: the chosen node's whole domain is off-limits for the
        # gang's remaining tasks
        forbidden = forbidden | (
            has_asl & placed & (doms_self == doms_self[node]))
        sub_dom = sub_dom.at[s_t].set(
            jnp.where(placed & has_srl & (locked < 0), dom_col[node],
                      locked))
        sub_rem = sub_rem.at[s_t].add(-jnp.where(placed, req, 0.0))
        if config.subgroup_topology:
            # keep the per-domain aggregate current: the chosen node's
            # domain at EVERY level loses this placement
            for lvl in range(L):
                did = n.topology[node, lvl]
                agg = agg.at[jnp.where(did >= 0, did, ND)].add(
                    -jnp.where(placed, delta_node, 0.0))
        nodes_t = nodes_t.at[t].set(jnp.where(placed, node, -1))
        dev_t = dev_t.at[t].set(
            jnp.where(placed & is_frac, frac_dev, -1))
        pipe_t = pipe_t.at[t].set(is_pipe)
        count = count + placed.astype(jnp.int32)
        pref_dom = jnp.where(placed & (pref_dom < 0), pref_doms[node],
                             pref_dom)
        return (free_l, dev_l, ext_l, bind_used, dev_bind, ext_bind,
                forbidden, sub_dom, sub_rem, agg, nodes_t, dev_t, pipe_t,
                count, q_delta, pref_dom)

    # seed subgroup domain locks from prior placements
    prior_level = srl[sub]                                              # [T]
    prior_sub_dom = n.topology[jnp.maximum(prior_nodes, 0),
                               jnp.clip(prior_level, 0, L - 1)]         # [T]
    sub_dom0 = jnp.full((S,), -1, jnp.int32).at[sub].max(
        jnp.where(already & (prior_level >= 0), prior_sub_dom, -1))

    carry = (free, device_free, ext_free,
             jnp.zeros_like(free), jnp.zeros_like(device_free),
             jnp.zeros_like(ext_free),
             forbidden0, sub_dom0, sub_rem0, agg0,
             jnp.full((T,), -1, jnp.int32), jnp.full((T,), -1, jnp.int32),
             jnp.zeros((T,), bool),
             jnp.asarray(0, jnp.int32), jnp.zeros_like(task_req[0]),
             pref_dom0.astype(jnp.int32))
    # fori_loop, not a static unroll: the task step's graph is large and
    # appears in several kernel variants (wavefront lanes, domain loop,
    # victim solver) — unrolling T copies made compile time the suite's
    # bottleneck while saving only ~µs of loop overhead per step
    carry = lax.fori_loop(0, T, task_body, carry)
    (free2, dev2, ext2, bind_used, dev_bind, ext_bind, _, sub_dom_out, _,
     _, nodes_t, dev_t, pipe_t, count, q_delta, _) = carry
    # queue accounting applied once for the whole gang along its chain
    qa2 = q_alloc + anc[:, None] * q_delta[None, :]
    qan2 = q_alloc_np + jnp.where(nonpreempt,
                                  anc[:, None] * q_delta[None, :], 0.0)
    if legacy:
        # min_needed (not min_member): pods already bound/running count
        # toward the gang's quorum — elastic scale-up and pipelined-
        # remainder gangs (victim-solver semantics).
        success = count >= g.min_needed[gang_idx]
    else:
        # re-push protocol: the attempt's chunk is all-or-nothing
        success = (goal > 0) & (count >= goal)
    return (free2, dev2, qa2, qan2, nodes_t, dev_t, pipe_t, success,
            bind_used, dev_bind, ext2, ext_bind, sub_dom_out)


def _attempt_gang_in_domain_uniform(
        state: ClusterState, gang_idx: jax.Array,
        free: jax.Array, device_free: jax.Array,
        q_alloc: jax.Array, q_alloc_np: jax.Array,
        num_levels: int, config: AllocateConfig,
        domain_mask: jax.Array, pref_doms: jax.Array, has_pref: jax.Array,
        extra_releasing: jax.Array, extra_device_releasing: jax.Array,
        lane: jax.Array, chain: jax.Array,
        prior_nodes: jax.Array | None = None,
        quota: jax.Array | None = None,
        ext_free: jax.Array | None = None,
        extra_extended_releasing: jax.Array | None = None,
        banned_doms: jax.Array | None = None,
        score_bias: jax.Array | None = None,
        topo_tables=None,
        sparse_out: bool = False,
        type_tables_u=None):
    """Whole-gang placement for uniform-task gangs, no per-task loop.

    A gang whose T pending tasks are identical replicas (the dominant
    real shape — and the one the reference's benchmarks use) admits a
    closed-form greedy: per node, how many replicas fit (`copies`); fill
    nodes in score order until the gang is whole.  Equivalent to the
    sequential task loop under binpack scoring (a node's binpack score
    only rises as it fills, so the sequential greedy would keep hitting
    the same node until it is full, which is exactly the capacity-count
    fill); spread scoring drifts from the loop by design.

    Same signature/returns as :func:`_attempt_gang_in_domain`.
    """
    g, n = state.gangs, state.nodes
    T, N = g.t, n.n
    req = g.task_req[gang_idx, 0]                       # [R] the replica
    sel = g.task_selector[gang_idx, 0]                  # [K]
    task_class = g.task_filter_class[gang_idx, 0]       # []
    task_valid = g.task_valid[gang_idx]                 # [T]
    tcount = jnp.sum(task_valid.astype(jnp.int32))
    queue = g.queue[gang_idx]
    nonpreempt = ~g.preemptible[gang_idx]
    # per-node anti-self (one replica per node) is the only granularity
    # this path supports — the snapshot builder gates uniform_gangs on it
    one_per_node = g.anti_self_level[gang_idx] >= 0
    anc = chain[queue]                                  # [Q]
    # re-push protocol (see _attempt_gang_in_domain)
    legacy = prior_nodes is None and quota is None
    if prior_nodes is None:
        prior_nodes = jnp.full((T,), -1, jnp.int32)
    if quota is None:
        quota = jnp.asarray(T, jnp.int32)
    already = prior_nodes >= 0
    already_count = jnp.sum(already.astype(jnp.int32))
    unplaced = tcount - already_count
    goal = jnp.minimum(quota, unplaced)
    # [T, N] compare-and-any, not a scatter-add into zeros: the flag is
    # all the kernel reads, T is small, and the TPU compiler's scatter
    # emitter rejects the scatter once it fuses with this predicate
    # (unplaced slots hold -1 and match no node)
    prior_on_node = jnp.any(
        prior_nodes[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :],
        axis=0)

    # ---- queue capacity gate: max replicas within every ancestor cap ----
    limit_eff = jnp.where(state.queues.limit <= UNLIMITED + 0.5,
                          jnp.inf, state.queues.limit)
    quota_eff = jnp.where(state.queues.quota <= UNLIMITED + 0.5,
                          jnp.inf, state.queues.quota)
    req_pos = req > EPS

    def max_copies(used, cap):
        head = jnp.where(req_pos[None, :],
                         (cap - used) / jnp.maximum(req, EPS)[None, :],
                         jnp.inf)                       # [Q, R]
        head = jnp.where(anc[:, None], head, jnp.inf)
        m = jnp.min(jnp.floor(head + EPS))
        return jnp.clip(m, 0.0, 1e9).astype(jnp.int32)

    m_gate = max_copies(q_alloc, limit_eff)
    m_gate = jnp.where(nonpreempt,
                       jnp.minimum(m_gate, max_copies(q_alloc_np, quota_eff)),
                       m_gate)

    # ---- per-node replica capacity --------------------------------------
    zero = jnp.zeros((), req.dtype)

    def lane_clamp(c, mask):
        """Per-lane adjustments on a raw replica count: domain/feasibility
        mask, then anti-self (one replica per node; nodes holding a
        replica from a prior attempt are off-limits)."""
        c = jnp.where(mask, c, 0)
        c = jnp.where(one_per_node & prior_on_node, 0, c)
        return jnp.where(one_per_node, jnp.minimum(c, 1), c)

    if type_tables_u is not None:
        # chunk-hoisted per-TYPE tables (see allocate()): feasibility,
        # raw replica counts, and base scores depend only on the lane's
        # task type and chunk-start free — the per-lane work left is
        # gathers, masks, and the tie-jitter/top-k passes
        ty = g.task_type[gang_idx, 0]
        fit_idle_y, fit_pipe_y, c_idle_y, c_pipe_y, scores0_y = \
            type_tables_u
        fit_idle = fit_idle_y[ty] & domain_mask
        fit_pipe = fit_pipe_y[ty] & domain_mask
        c_pipe = lane_clamp(c_pipe_y[ty], fit_pipe)     # [N]
    else:
        fit_idle, fit_pipe = feasible_nodes_dual(
            n, req, sel, zero, zero,
            free=free, device_free=device_free,
            extra_releasing=extra_releasing,
            extra_device_releasing=extra_device_releasing, devices=False,
            task_class=task_class)
        fit_idle = fit_idle & domain_mask
        fit_pipe = fit_pipe & domain_mask

    def copies(avail, mask):
        return lane_clamp(_replica_count(avail, req, mask), mask)

    if type_tables_u is None:
        c_pipe = copies(free + n.releasing + extra_releasing,
                        fit_pipe)                       # [N]

    if config.subgroup_topology:
        # required topology level (gang-level routes through subgroup
        # slot 0): choose ONE domain that can host the whole chunk —
        # fullest fitting first (ref topology domain binpack) — and
        # confine the fill to it.  Re-push attempts stay in the domain
        # the quorum locked.
        L = n.topology.shape[1]
        srl0 = g.subgroup_required_level[gang_idx, 0]
        has_req = srl0 >= 0
        dom_col = jnp.take(n.topology, jnp.clip(srl0, 0, L - 1), axis=1)
        NDu = N * L
        want0 = jnp.minimum(goal if not legacy else tcount, m_gate)
        with jax.named_scope("domain_pick"):
            if topo_tables is not None:
                # chunk-hoisted tables (see allocate()): per-lane work is
                # gathers + one cumsum — the vmapped per-lane argsort +
                # segment-sums over the domain axis dominated the
                # wavefront at 5k nodes
                dom_caps_y, level_of_dom, order_by_agg = topo_tables
                dom_caps = dom_caps_y[g.task_type[gang_idx, 0]]   # [ND]
                fits_dom = ((dom_caps >= jnp.maximum(want0, 1))
                            & (level_of_dom == srl0))
                if banned_doms is not None:
                    fits_dom = fits_dom & (
                        jnp.arange(NDu) != jnp.maximum(banned_doms[0], -1))
                fs = fits_dom[order_by_agg]
                n_fit = jnp.sum(fs.astype(jnp.int32))
                sel = jnp.mod(lane, jnp.maximum(n_fit, 1)) + 1
                pos = jnp.argmax(fs & (jnp.cumsum(fs.astype(jnp.int32))
                                       == sel))
                target = jnp.where(n_fit > 0, order_by_agg[pos], -1)
            else:
                ids = jnp.where(n.valid & (dom_col >= 0), dom_col, NDu)
                dom_caps = jax.ops.segment_sum(
                    c_pipe, ids, num_segments=NDu + 1)[:NDu]  # [ND]
                avail_accel = (free[:, 0] + n.releasing[:, 0]
                               + extra_releasing[:, 0])
                agg_accel = jax.ops.segment_sum(
                    jnp.where(n.valid, avail_accel, 0.0), ids,
                    num_segments=NDu + 1)[:NDu]
                fits_dom = dom_caps >= jnp.maximum(want0, 1)
                if banned_doms is not None:
                    fits_dom = fits_dom & (
                        jnp.arange(NDu) != jnp.maximum(banned_doms[0], -1))
                # spread wavefront lanes across the fitting domains,
                # fullest first: lane 0 takes the binpack choice, lane k
                # the k-th-fullest — otherwise every lane of a chunk fills
                # the same domain and the accept prefix caps at one
                # domain's capacity
                order_dom = jnp.argsort(
                    jnp.where(fits_dom, agg_accel, jnp.inf))
                n_fit = jnp.sum(fits_dom.astype(jnp.int32))
                target = order_dom[jnp.mod(lane, jnp.maximum(n_fit, 1))]
                target = jnp.where(jnp.any(fits_dom), target, -1)
            prior_dom = jnp.where(
                jnp.any(already),
                dom_col[jnp.maximum(prior_nodes[jnp.argmax(already)], 0)],
                -1)
            target = jnp.where(prior_dom >= 0, prior_dom, target)
        # target == -1 (no domain fits) must FAIL the gang, not fall
        # through to nodes that lack the level's label (their dom_col is
        # also -1)
        in_dom = ~has_req | ((target >= 0) & (dom_col == target))
        fit_idle = fit_idle & in_dom
        fit_pipe = fit_pipe & in_dom
        c_pipe = jnp.where(in_dom, c_pipe, 0)
        target_out = jnp.where(has_req, target, -1)
    else:
        target_out = jnp.asarray(-1, jnp.int32)

    if type_tables_u is not None:
        c_idle = jnp.minimum(lane_clamp(c_idle_y[ty], fit_idle), c_pipe)
    else:
        c_idle = jnp.minimum(copies(free, fit_idle), c_pipe)

    if config.dense_feasibility:
        # feasibility spans the node axis (no selectors/filters/domains
        # in the snapshot): a stride-apart cyclic rotation spreads lanes
        # equally well without the per-attempt cumsum
        stride = max(1, N // max(1, config.batch_size))
        tie_jitter = (-1e-4 / N) * jnp.mod(
            jnp.arange(N) - lane * stride, N).astype(jnp.float32)
    else:
        # per-lane tie-break by rank WITHIN the feasible set (see the
        # per-task kernel): spreads equal-scoring nodes across lanes even
        # when selectors/filters/domains confine feasibility to a sliver
        # of the index space (an absolute rotation would collapse every
        # lane onto the same first feasible node there)
        with jax.named_scope("feasibility"):
            rank_feas = jnp.cumsum(fit_pipe.astype(jnp.int32)) - 1
        tie_jitter = (-1e-4 / N) * jnp.mod(rank_feas - lane, N).astype(
            jnp.float32)                                # [N]

    # ---- scores (one pass; locality band anchored at the best node) -----
    if type_tables_u is not None:
        # hoisted base already holds the plugin bands + soft scores for
        # the lane's type, masked by TYPE feasibility; the lane adds its
        # jitter/bias and re-masks for its domain restriction
        base_u = scores0_y[ty] + tie_jitter
        if score_bias is not None:
            base_u = base_u + score_bias
        scores0 = jnp.where(fit_pipe, base_u, BIG_NEG)  # [N]
    else:
        extra_bands_u = tie_jitter + n.soft_scores[task_class]
        if score_bias is not None:
            extra_bands_u = extra_bands_u + score_bias
        scores0 = score_nodes_for_task(
            n, free, req, fit_idle, fit_pipe, config.placement,
            extra=extra_bands_u)                        # [N]
    if config.preferred_topology:
        best = jnp.argmax(scores0)
        topo_band = jnp.where(
            has_pref & (pref_doms == pref_doms[best]), W_TOPOLOGY, 0.0)
        scores = jnp.where(fit_pipe, scores0 + topo_band, scores0)
    else:
        scores = scores0

    # ---- greedy fill by score order -------------------------------------
    # top_k instead of a full argsort: at most T replicas place and every
    # feasible node holds >= 1 (c_pipe >= 1 where fit), so the T best-
    # scoring nodes are exactly the prefix the full sort would fill —
    # O(N log T) instead of O(N log N) per lane, the hot win at 10k nodes
    k = min(T, N)
    _, order = jax.lax.top_k(scores, k)                 # [k]
    feas_sorted = fit_pipe[order]
    c_sorted = jnp.where(feas_sorted, c_pipe[order], 0)
    want = jnp.minimum(goal if not legacy else tcount, m_gate)
    cum = jnp.cumsum(c_sorted)                          # [k]
    placed_sorted = jnp.clip(want - (cum - c_sorted), 0, c_sorted)
    total_placed = jnp.minimum(cum[-1], want)

    placed_per_node = jnp.zeros((N,), jnp.int32).at[order].add(placed_sorted)
    # new placements land in the first `total_placed` still-unplaced
    # slots, taking their chosen nodes in ASCENDING NODE ORDER: uniform
    # replicas are interchangeable, so the replica->node bijection is a
    # free choice — canonicalizing it on node id (instead of the score
    # order, whose ties cascade from earlier placements' density/
    # availability deltas) keeps the per-task cells bit-identical
    # between the sequential scan and the victim wavefront whenever
    # both pick the same node multiset, and makes binds deterministic
    # under score-input drift generally
    cum_n = jnp.cumsum(placed_per_node)                 # [N]
    elig_rank = jnp.cumsum((task_valid & ~already).astype(jnp.int32)) - 1
    npos = jnp.where(task_valid & ~already, elig_rank, T)   # [T]
    nidx = jnp.minimum(jnp.searchsorted(cum_n, npos, side="right"),
                       N - 1)                           # [T] node id
    placed_t = task_valid & ~already & (npos < total_placed)
    nodes_t = jnp.where(placed_t, nidx, -1)
    # within a node the first c_idle replicas bind now, the rest pipeline
    rank_in_node = npos - (cum_n[nidx] - placed_per_node[nidx])
    pipe_t = placed_t & (rank_in_node >= c_idle[nidx])
    free2 = free - placed_per_node[:, None].astype(free.dtype) * req[None, :]
    # replicas past a node's idle headroom pipeline; the rest bind now
    bind_per_node = jnp.minimum(placed_per_node, c_idle)
    bind_used = bind_per_node[:, None].astype(free.dtype) * req[None, :]
    q_delta = total_placed.astype(free.dtype) * req
    qa2 = q_alloc + anc[:, None] * q_delta[None, :]
    qan2 = q_alloc_np + jnp.where(nonpreempt,
                                  anc[:, None] * q_delta[None, :], 0.0)
    if legacy:
        success = total_placed >= g.min_needed[gang_idx]
    else:
        success = (goal > 0) & (total_placed >= goal)
    if sparse_out:
        # wavefront sparse protocol: a replica's node + pipeline flag
        # fully determine its free/bind deltas (amount = the uniform
        # replica request), so the chunk reconstructs them from
        # (nodes_t, pipe_t) with K-entry scatters instead of carrying
        # dense [N, R] copies per lane through the vmap
        return (qa2, qan2, nodes_t, pipe_t, success)
    dev_t = jnp.full((T,), -1, jnp.int32)
    # extended resources take the per-task path (snapshot builder gates
    # uniform_gangs off when any exist) — pass the pool through untouched
    if ext_free is None:
        ext_free = state.nodes.extended_free
    sub_dom_out = jnp.full((g.s,), -1, jnp.int32).at[0].set(
        target_out.astype(jnp.int32))
    return (free2, device_free, qa2, qan2, nodes_t, dev_t, pipe_t, success,
            bind_used, jnp.zeros_like(device_free), ext_free,
            jnp.zeros_like(ext_free), sub_dom_out)


def _attempt_gang(state: ClusterState, gang_idx: jax.Array,
                  free: jax.Array, device_free: jax.Array,
                  q_alloc: jax.Array, q_alloc_np: jax.Array,
                  num_levels: int, config: AllocateConfig,
                  extra_releasing: jax.Array | None = None,
                  extra_device_releasing: jax.Array | None = None,
                  lane: jax.Array | None = None,
                  chain: jax.Array | None = None,
                  prior_nodes: jax.Array | None = None,
                  quota: jax.Array | None = None,
                  ext_free: jax.Array | None = None,
                  extra_extended_releasing: jax.Array | None = None,
                  topo_tables=None,
                  domain_mask: jax.Array | None = None,
                  score_bias: jax.Array | None = None,
                  sparse_out: bool = False,
                  type_tables_u=None,
                  with_domains: bool = False):
    """Try to place one gang; returns tentative post-gang state + success
    (and, ``with_domains``, the domain each subgroup slot locked: i32 [S],
    -1 where the domain gate chose none).

    Topology handling (ref ``plugins/topology`` SubsetNodesFn +
    ``topology/job_filtering.go:34``): a *required* level — gang-level
    levels are inherited into every subgroup slot at snapshot build — is
    enforced by the per-subgroup domain locks inside the task kernel: the
    subgroup's first placement picks a domain with aggregate capacity for
    its whole chunk, binpacked fullest-first (``topology/node_scoring.go``
    domain ordering as a score band), and the rest of the subgroup is
    confined to it.  A *preferred* level adds a locality score band
    instead (best-effort).
    """
    g, n = state.gangs, state.nodes
    if extra_releasing is None:
        extra_releasing = jnp.zeros_like(free)
    if extra_device_releasing is None:
        extra_device_releasing = jnp.zeros_like(device_free)
    if lane is None:
        lane = jnp.asarray(0, jnp.int32)
    if chain is None:
        chain = _chain_membership(state.queues.parent, num_levels)

    pl = g.preferred_level[gang_idx]
    has_pref = pl >= 0
    pref_doms = n.topology[:, jnp.maximum(pl, 0)]              # [N]

    if config.uniform_tasks:
        if config.track_devices:
            raise ValueError(
                "uniform_tasks fast path requires track_devices=False")
        in_domain = _attempt_gang_in_domain_uniform
    else:
        in_domain = _attempt_gang_in_domain

    dmask = n.valid if domain_mask is None else (n.valid & domain_mask)

    def run(banned):
        extras = ((topo_tables, sparse_out, type_tables_u)
                  if config.uniform_tasks else ())
        # which of the two kernels ran is in every operation's op_name
        with jax.named_scope("whole_gang_fill" if config.uniform_tasks
                             else "per_task_fill"):
            return in_domain(
                state, gang_idx, free, device_free, q_alloc, q_alloc_np,
                num_levels, config, dmask, pref_doms, has_pref,
                extra_releasing, extra_device_releasing, lane, chain,
                prior_nodes, quota, ext_free, extra_extended_releasing,
                banned, score_bias, *extras)

    out = run(None)
    if config.uniform_tasks and sparse_out:
        return out
    if config.subgroup_topology and not config.uniform_tasks:
        # In-cycle retry over the NEXT domain: the aggregate-capacity
        # domain gate stands in for allocateSubGroupSet's per-subset
        # rollback search, so a fragmented domain can pass the gate and
        # fail the fill — one bounded retry with the failed attempt's
        # locked domains banned places the gang in the next-fullest
        # domain within the same cycle instead of waiting one out.
        # The uniform kernel needs no retry: its domain pick counts real
        # per-node replica capacities, so a picked domain always fits.
        # (Under the wavefront vmap this cond lowers to a select that
        # executes both branches — tolerable on the B<=64 per-task path,
        # ruinous on the wide uniform path.)
        success1, sub_dom1 = out[7], out[12]
        retry_ok = ~success1 & jnp.any(sub_dom1 >= 0)
        out = lax.cond(retry_ok, lambda _: run(sub_dom1),
                       lambda _: out, None)
    return out[:13] if with_domains else out[:12]


def _under_required_level(state: ClusterState) -> jax.Array:
    """bool [G] — gangs placed under a required topology level, their
    own (inherited into every subgroup slot at snapshot build) or a
    subgroup's."""
    return jnp.any(state.gangs.subgroup_required_level >= 0, axis=-1)


def _topology_counts(state: ClusterState, before: AllocationResult,
                     after: AllocationResult) -> jax.Array:
    """What one action's placements did under the tree — i32 [4], the
    slots of ``TOPOLOGY_STATS`` (slot 2, the domain gate's misses, is 0
    here: it is counted chunk by chunk where the lanes run): gangs this
    action attempted and bound under a required level, and the gangs it
    bound whose placed tasks all lie in one domain of their preferred
    level."""
    g, n = state.gangs, state.nodes
    has_req = _under_required_level(state)
    tried = after.attempted & ~before.attempted
    bound = after.allocated & ~before.allocated
    placed = after.placements >= 0                                  # [G, T]
    pref_dom = n.topology[jnp.maximum(after.placements, 0),
                          jnp.maximum(g.preferred_level, 0)[:, None]]
    first = jnp.take_along_axis(
        pref_dom, jnp.argmax(placed, axis=-1)[:, None], axis=-1)    # [G, 1]
    together = jnp.all(~placed | ((pref_dom == first) & (pref_dom >= 0)),
                       axis=-1)
    counts = (tried & has_req, bound & has_req, jnp.zeros_like(bound),
              bound & (g.preferred_level >= 0) & together)
    return jnp.stack([jnp.sum(c.astype(jnp.int32)) for c in counts])


def lane_width(config: AllocateConfig, num_gangs: int) -> int:
    """Gangs one wavefront chunk of :func:`allocate` attempts in
    parallel (the ``vmap`` width B) over ``num_gangs`` padded rows."""
    B = max(1, min(config.batch_size, num_gangs))
    if config.subgroup_topology and not config.uniform_tasks:
        # the per-task kernel's domain segment reduction multiplies lane
        # scratch by the N*L segment count; wide wavefronts exceed TPU
        # scratch limits (observed device faults at B=256, 5k nodes)
        B = min(B, 64)
    return B


def allocate(
    state: ClusterState,
    fair_share: jax.Array,          # f32 [Q, R]  from ops.drf.set_fair_share
    *,
    num_levels: int,
    config: AllocateConfig = AllocateConfig(),
    init: AllocationResult | None = None,
) -> AllocationResult:
    """Run the allocate action over every pending gang.

    Functional equivalent of ``allocate.Execute`` — jit-compatible; all
    shapes static.  ``num_levels`` bounds the queue-hierarchy depth
    (snapshot-known static).  ``init`` continues an in-progress cycle
    (the previous action's commit set).
    """
    g, n, q = state.gangs, state.nodes, state.queues
    G, T = g.g, g.t
    total = state.total_capacity
    B = lane_width(config, G)
    if init is None:
        init = init_result(state)

    extra, extra_dev = init.releasing_extra, init.device_releasing_extra
    rel_floor = -(n.releasing + extra) - EPS          # [N, R] free lower bound
    dev_floor = -(n.device_releasing + extra_dev) - EPS
    limit_eff = jnp.where(q.limit <= UNLIMITED + 0.5, jnp.inf, q.limit)
    quota_eff = jnp.where(q.quota <= UNLIMITED + 0.5, jnp.inf, q.quota)

    remaining0 = g.valid & (g.backoff <= 0) & ~init.allocated
    if config.prefilter:
        # whole-gang feasibility over the task-type table: a gang whose
        # min_needed tasks cannot each find ANY node (ignoring cross-task
        # capacity interaction) is hopeless this cycle — at 50k pending
        # gangs this is the difference between attempting everything and
        # attempting only the schedulable frontier.  Cost: [Y, N] for the
        # Y distinct task types, not [G, T, N].
        type_fit = jax.vmap(lambda y: jnp.any(feasible_nodes(
            n, g.type_req[y], g.type_selector[y], g.type_portion[y],
            g.type_mem[y], task_class=g.type_class[y],
            free=n.free + init.releasing_extra,
            device_free=n.device_free + init.device_releasing_extra,
            include_releasing=True)))(
                jnp.arange(g.type_req.shape[0]))          # [Y]
        task_ok = type_fit[g.task_type] & g.task_valid    # [G, T]
        feas = jnp.sum(task_ok.astype(jnp.int32), -1) >= g.min_needed
        pre_dropped = remaining0 & ~feas
        remaining0 = remaining0 & feas
        init = init.replace(
            fit_reason=jnp.where(pre_dropped, 1, init.fit_reason))
    static_rank = None
    if not config.dynamic_order:
        order0 = ordering.job_order_perm(
            g, q, init.queue_allocated, fair_share, total, remaining0)
        static_rank = jnp.zeros((G,), jnp.int32).at[order0].set(
            jnp.arange(G, dtype=jnp.int32))
    else:
        # Dynamic ordering PREDICTS the reference heap's whole pop
        # sequence, hoisted: when pops succeed, queue allocation after a
        # queue's first j pops is exactly qa_start plus those pops'
        # cumulative request — so every gang's AT-POP queue key
        # (over_fs, over_quota, -priority, dominant share) is a static
        # function of the snapshot, and ONE hoisted lexsort reproduces
        # the interleaved pop order the heap's per-pop re-sort would
        # produce.  Chunks then just take the first B remaining gangs of
        # this order (a cumsum compaction — no in-loop sort at all).
        # Divergence from the prediction — placement failures, accept
        # conflicts, elastic re-pushes — is bounded per action (see the
        # fairness-gate note in the chunk) and corrected next cycle.
        below_min = g.running_count < g.min_member
        sjr_perm = jnp.lexsort((
            g.creation_order.astype(jnp.float32),
            -g.priority.astype(jnp.float32),
            (~below_min).astype(jnp.float32)))
        static_job_rank = jnp.zeros((G,), jnp.int32).at[sjr_perm].set(
            jnp.arange(G, dtype=jnp.int32))                   # [G]
        gq0 = jnp.maximum(g.queue, 0)
        # only gangs this action can actually pop contribute to the
        # prediction — backed-off/prefiltered gangs never pop, and
        # already-allocated gangs' requests are in qa0 already
        gang_req_all = jnp.sum(jnp.where(
            (g.task_valid & remaining0[:, None])[:, :, None],
            g.task_req, 0.0), axis=1)                           # [G, R]
        if config.extended:
            # the predicted at-pop queue keys see MIG g-equivalents like
            # the snapshot rollups and the placement queue delta do
            gang_req_all = gang_req_all.at[:, 0].add(jnp.sum(jnp.where(
                g.task_valid & remaining0[:, None],
                einsum_exact("gte,e->gt", g.task_extended,
                             g.ext_accel), 0.0), axis=1))
        # exclusive per-queue cumulative request along the static job
        # order, O(G·R): queue-major sort, one cumsum, subtract each
        # queue's segment-start prefix (a [G, Q, R] one-hot cumsum
        # would be ~GB-scale at 50k gangs × many queues)
        ord2 = jnp.lexsort((static_job_rank.astype(jnp.float32),
                            gq0.astype(jnp.float32)))
        req2 = gang_req_all[ord2]
        cs_excl = jnp.cumsum(req2, axis=0) - req2               # [G, R]
        qm = gq0[ord2]
        is_first = jnp.concatenate(
            [jnp.ones((1,), bool), qm[1:] != qm[:-1]])
        base = jnp.zeros((q.q + 1,) + req2.shape[1:], req2.dtype).at[
            jnp.where(is_first, qm, q.q)].set(cs_excl)[:q.q]    # [Q, R]
        cum_excl_g = jnp.zeros_like(gang_req_all).at[ord2].set(
            cs_excl - base[qm])                                 # [G, R]
        qa0 = init.queue_allocated
        at_pop = qa0[gq0] + cum_excl_g                          # [G, R]
        pop_fs = jnp.any(at_pop > fair_share[gq0] + EPS, -1)
        pop_qt = jnp.any(at_pop > quota_eff[gq0] + EPS, -1)
        pop_dom = jnp.max(at_pop / jnp.maximum(total, EPS)[None, :], -1)
        nprio_q = -q.priority.astype(jnp.float32)
        pop_order = jnp.lexsort((
            static_job_rank.astype(jnp.float32),
            pop_dom,
            nprio_q[gq0],
            pop_qt.astype(jnp.float32),
            pop_fs.astype(jnp.float32)))                        # [G]

    chain = _chain_membership(q.parent, num_levels)

    L = n.topology.shape[1]
    ND = n.n * L
    hoist_topo = config.uniform_tasks and config.subgroup_topology
    # every operation of the chunk-hoisted domain tables carries this
    # scope in its op_name (docs/TRACING.md)
    topo_scope = functools.partial(jax.named_scope, "topology_tables")
    if hoist_topo:
        # domain-id → topology level (the global dense id space spans
        # all levels; each id belongs to exactly one)
        with topo_scope():
            level_of_dom = jnp.full((ND + 1,), -1, jnp.int32)
            for lvl in range(L):
                ids_l = jnp.where(n.valid & (n.topology[:, lvl] >= 0),
                                  n.topology[:, lvl], ND)
                level_of_dom = level_of_dom.at[ids_l].set(lvl)
            level_of_dom = level_of_dom[:ND]

    if hoist_topo:
        Y = g.type_req.shape[0]
        #: node → dense domain id per level (static; junk ND)
        dom_of = jnp.stack([
            jnp.where(n.valid & (n.topology[:, lvl] >= 0),
                      n.topology[:, lvl], ND)
            for lvl in range(L)])                             # [L, N]
        #: static (capacity-independent + build-capacity) feasibility —
        #: free only SHRINKS within allocate, so a node infeasible at
        #: build never recovers and the live replica count alone tracks
        #: capacity afterwards
        zero_s = jnp.zeros((), n.free.dtype)
        fp_build = jax.vmap(lambda y: feasible_nodes_dual(
            n, g.type_req[y], g.type_selector[y], zero_s, zero_s,
            free=init.free, device_free=init.device_free,
            extra_releasing=extra, extra_device_releasing=extra_dev,
            devices=False, task_class=g.type_class[y])[1])(
                jnp.arange(Y)) & n.valid[None, :]             # [Y, N]

        def _replicas_at(avail_rows):
            """Replica counts per type for the given avail rows [K, R]
            (the capacity part of caps_of_type, recomputable per touched
            node without the feasibility machinery)."""
            def per_type(y):
                req = g.type_req[y]
                c = jnp.where(req[None, :] > EPS,
                              (avail_rows + EPS)
                              / jnp.maximum(req, EPS)[None, :], jnp.inf)
                return jnp.clip(jnp.floor(jnp.min(c, axis=-1)),
                                0.0, 1e9).astype(jnp.int32)
            return jax.vmap(per_type)(jnp.arange(Y))          # [Y, K]

    def topo_tables_build(free):
        """Initial domain tables for the uniform+topology path: per-TYPE
        replica capacity per node (``c_y``, junk column N) and per
        domain (``dom_caps_y``), plus the per-domain aggregate accel.
        Built ONCE per action; chunks maintain all three incrementally —
        only nodes touched by committed placements change, so the
        full per-chunk rebuild (per-type feasibility + divisions + Y·L
        node-axis reductions, the dominant wavefront cost at 5k nodes ×
        3 levels) reduces to placement-sized gathers and L sparse
        scatter-adds."""
        avail = free + n.releasing + extra
        c_all = _replicas_at(avail)                           # [Y, N]
        c_all = jnp.where(fp_build, c_all, 0)
        c_y = jnp.concatenate(
            [c_all, jnp.zeros((Y, 1), jnp.int32)], axis=1)    # [Y, N+1]

        def caps_of_type(c_row):
            caps = jnp.zeros((ND + 1,), jnp.int32)
            for lvl in range(L):
                caps = caps.at[dom_of[lvl]].add(c_row)
            return caps[:ND]

        dom_caps_y = jax.vmap(caps_of_type)(c_all)            # [Y, ND]
        agg = jnp.zeros((ND + 1,), free.dtype)
        for lvl in range(L):
            agg = agg.at[dom_of[lvl]].add(
                jnp.where(n.valid, avail[:, 0], 0.0))
        return dom_caps_y, agg[:ND], c_y

    def topo_tables_update(dom_caps_y, agg, c_y, free_new,
                           take, cand, nodes_b):
        """Incremental maintenance after a chunk's commit: recompute
        replica counts for the touched nodes only (duplicate touches
        write identical values, so scatter-set is well defined), then
        push the per-node deltas into the domain tables."""
        B_, T_ = nodes_b.shape
        placed = take[:, None] & (nodes_b >= 0)               # [B, T]
        idxs = jnp.where(placed, nodes_b, n.n).ravel()        # [K] junk N
        isafe = jnp.minimum(idxs, n.n - 1)
        avail_rows = (free_new + n.releasing + extra)[isafe]  # [K, R]
        c_new = jnp.where(fp_build[:, isafe],
                          _replicas_at(avail_rows), 0)        # [Y, K]
        c_new = jnp.where((idxs < n.n)[None, :], c_new, 0)
        # per-node delta via a junk-columned scratch: duplicates carry
        # the SAME c_new (same node), so .set is deterministic
        c_at = jnp.zeros((Y, n.n + 1), jnp.int32).at[:, idxs].set(c_new)
        touched = jnp.zeros((n.n + 1,), bool).at[idxs].set(True)
        d_node = jnp.where(touched[None, :], c_at - c_y, 0)   # [Y, N+1]
        c_y = jnp.where(touched[None, :], c_at, c_y)
        # accel delta per node: one replica consumes its type's accel —
        # exact for the aggregate regardless of type mix
        ty = g.task_type[jnp.minimum(cand, G - 1), 0]         # [B]
        req0 = g.type_req[ty, 0]                              # [B]
        accel = jnp.where(placed,
                          jnp.broadcast_to(req0[:, None], (B_, T_)),
                          0.0).ravel()
        for lvl in range(L):
            dom_caps_y = dom_caps_y.at[:, dom_of[lvl]].add(
                d_node[:, :n.n], mode="drop")
            dom = jnp.where(idxs < n.n, dom_of[lvl][isafe], ND)
            agg = agg.at[jnp.minimum(dom, ND - 1)].add(
                jnp.where(dom < ND, -accel, 0.0))
        return dom_caps_y, agg, c_y

    # in-cycle exclusion-term tracking (config.anti_groups): dense
    # domain id per (node, level) with per-node slots appended for the
    # hostname granularity; AD+1 = junk slot (see anti_domain_tables)
    AD = ND + n.n
    if config.anti_groups:
        dom_static, TA = anti_domain_tables(state)

    # the uniform kernel's lanes emit placements only (nodes/pipeline
    # flags); the chunk reconstructs capacity deltas with K-entry sparse
    # scatters instead of carrying dense [B, N, R] tensors through the
    # vmap and the accept cumsums — the dominant HBM traffic at
    # 10k nodes x 256 lanes
    sparse = (config.uniform_tasks and not config.extended
              and not config.track_devices
              # measured: sparse lanes lose to the dense path when the
              # required-topology domain machinery is active (the
              # hoisted domain caps already carry the dense tensors)
              and not config.subgroup_topology)
    # chunk-hoisted per-TYPE tables for the uniform kernel: feasibility,
    # raw replica counts, and plugin-band scores depend only on the
    # lane's task TYPE and chunk-start free — computing them [Y, N] once
    # per chunk (instead of [B, N] per lane under the vmap) leaves only
    # gathers + tie-jitter + top-k as per-lane node-axis work
    Yu = g.type_req.shape[0]
    hoist_types = config.uniform_tasks and Yu <= B

    def build_type_tables(free_c, dev_c):
        zero_t = jnp.zeros((), free_c.dtype)

        def per_type(y):
            fi, fp = feasible_nodes_dual(
                n, g.type_req[y], g.type_selector[y], zero_t, zero_t,
                free=free_c, device_free=dev_c, extra_releasing=extra,
                extra_device_releasing=extra_dev, devices=False,
                task_class=g.type_class[y])
            reqy = g.type_req[y]
            cp = _replica_count(free_c + n.releasing + extra, reqy, fp)
            ci = _replica_count(free_c, reqy, fi)
            sc = score_nodes_for_task(
                n, free_c, reqy, fi, fp, config.placement,
                extra=n.soft_scores[g.type_class[y]])
            return fi, fp, ci, cp, sc

        return jax.vmap(per_type)(jnp.arange(Yu))

    def attempt_one(gi, lane, prior, quota, dmask, free, dev, qa, qan,
                    ext, topo_tables, utables):
        return _attempt_gang(state, gi, free, dev, qa, qan, num_levels,
                             config, extra, extra_dev, lane, chain,
                             prior_nodes=prior, quota=quota, ext_free=ext,
                             extra_extended_releasing=init.
                             extended_releasing_extra,
                             topo_tables=topo_tables,
                             domain_mask=dmask, sparse_out=sparse,
                             type_tables_u=utables,
                             with_domains=config.subgroup_topology)

    def cond(carry):
        return jnp.any(carry[1]) & (carry[4] > 0)

    def chunk(carry):
        res, remaining, q_attempts, failed_sig, fuel = carry[:5]
        if hoist_topo:
            dom_caps_y, dom_agg, c_y_store = carry[5:8]
        free, dev, qa, qan = (res.free, res.device_free, res.queue_allocated,
                              res.queue_allocated_nonpreemptible)
        if config.dynamic_order:
            # first B remaining gangs of the hoisted pop order (cumsum
            # compaction — no in-loop sort), with the LIVE over-fs gate:
            # while ANY under-fair-share queue still has remaining
            # gangs, over-fs queues (incl. re-pushed elastic gangs whose
            # quorum already drove their queue over) sit the chunk out —
            # the reference heap's tier-1 treatment
            over_fs_live = jnp.any(
                qa > fair_share + EPS, axis=-1)                   # [Q]
            elig = remaining & ~over_fs_live[jnp.maximum(g.queue, 0)]
            elig = jnp.where(jnp.any(elig), elig, remaining)
            flags = elig[pop_order]                               # [G]
            rnk = jnp.cumsum(flags.astype(jnp.int32)) - 1
            pos = jnp.where(flags & (rnk < B), rnk, B)
            cand = jnp.full((B + 1,), G, jnp.int32).at[pos].set(
                pop_order)[:B]
            cand_valid = jnp.zeros((B + 1,), bool).at[pos].set(
                True)[:B]
            # junk slots KEEP the out-of-range index G: their commit
            # scatters drop (out-of-bounds) instead of racing a real
            # gang's row; gathers at G clamp to harmless reads that
            # cand_valid discards
        else:
            # frozen keys, retired gangs pushed last
            composite = static_rank + jnp.where(remaining, 0, 2 * G)
            cand = jnp.argsort(composite)[:B]                     # [B]
            cand_valid = remaining[cand]
        if config.queue_depth is not None:
            # per-queue attempt budget (ref QueueDepthPerAction): a
            # candidate is eligible while its queue's prior attempts plus
            # its rank among earlier same-queue candidates of this chunk
            # stay under the depth.  Over-budget candidates simply sit out
            # the chunk; fully exhausted queues drain below.
            qc = g.queue[cand]                                    # [B]
            earlier = (jnp.arange(B)[None, :] < jnp.arange(B)[:, None])
            rank_q = jnp.sum(
                (qc[None, :] == qc[:, None]) & earlier
                & cand_valid[None, :], axis=1)                    # [B]
            cand_valid = cand_valid & (
                q_attempts[qc] + rank_q < config.queue_depth)

        # re-push protocol (ref allocate.go:102-104): a below-quorum gang
        # attempts its whole remaining quorum chunk; an at/above-quorum
        # gang scales up ONE task per attempt and re-enters the heap, so
        # elastic growth interleaves fairly with other queues' jobs.
        prior_b = res.placements[cand]                            # [B, T]
        placed_cnt = jnp.sum((prior_b >= 0).astype(jnp.int32), -1)
        need = g.min_needed[cand]
        quota_b = jnp.where(placed_cnt < need, need - placed_cnt, 1)

        # NOTE on mid-action fairness drift: the hoisted pop order is
        # exact while pops succeed; placement failures and accept
        # conflicts can let a queue fall behind its predicted
        # allocation, after which the frozen order may favour it
        # slightly ahead of the live heap for the rest of the action —
        # bounded by the failed requests, corrected next cycle.  (A live
        # per-chunk heap-key lookahead was tried and reverted: its
        # per-chunk op cost exceeded the entire sort it replaced.)

        # independent attempts against chunk-start state (the vmapped
        # replacement for the reference's one-job-at-a-time hot loop);
        # each lane's feasible-rank tie-break starts at its own offset so
        # a chunk of identical gangs fans out over equal-scoring nodes
        # instead of colliding on one
        lanes = jnp.arange(B, dtype=jnp.int32)
        ext = res.extended_free
        if hoist_topo:
            # live caps (incrementally maintained), live fullest-first
            # order (one single-key argsort per chunk)
            with topo_scope():
                order_by_agg = jnp.argsort(
                    jnp.where(level_of_dom >= 0, dom_agg, jnp.inf))
            tables = (dom_caps_y, level_of_dom, order_by_agg)
        else:
            tables = None
        if config.anti_groups:
            # a lane may not use domains already claimed in any of its
            # avoid rows, and only one side of a conflicting pair may
            # land per chunk (the rest conflict-retry against the
            # updated table)
            dmask_b = ~anti_forbid_nodes(state, res.anti_used,
                                         dom_static, cand)       # [B, N]
            dup_b = anti_defer_lanes(state, cand, cand_valid)
            if config.attract_groups:
                # a lane with need rows is confined to claimed domains;
                # one whose unclaimed need an earlier lane would mark
                # retries next chunk against the updated table
                dmask_b = dmask_b & attract_allow_nodes(
                    state, res.anti_used, dom_static, cand)
                dup_b = dup_b | attract_defer_lanes(
                    state, cand, cand_valid, res.anti_used)
        else:
            dmask_b = None
            dup_b = jnp.zeros((B,), bool)
        dmask_ax = None if dmask_b is None else 0
        if dmask_b is None:
            dmask_b = n.valid
        utables = build_type_tables(free, dev) if hoist_types else None
        if sparse:
            (qa2_b, qan2_b, nodes_b, pipe_b, succ_b) = \
                jax.vmap(attempt_one,
                         in_axes=(0, 0, 0, 0, dmask_ax, None, None, None,
                                  None, None, None, None))(
                    cand, lanes, prior_b, quota_b, dmask_b, free, dev, qa,
                    qan, ext, tables, utables)
            devt_b = jnp.full((B, T), -1, jnp.int32)
        else:
            (free2_b, dev2_b, qa2_b, qan2_b, nodes_b, devt_b, pipe_b,
             succ_b, bind_b, devbind_b, ext2_b, extbind_b, *subdom_b) = \
                jax.vmap(attempt_one,
                         in_axes=(0, 0, 0, 0, dmask_ax, None, None, None,
                                  None, None, None, None))(
                    cand, lanes, prior_b, quota_b, dmask_b, free, dev, qa,
                    qan, ext, tables, utables)
        if config.subgroup_topology:
            # the domain gate's miss: a live lane under a required level
            # whose attempt failed with no domain locked in any slot
            # (the sparse lanes never run with a required level)
            has_req_b = _under_required_level(state)[
                jnp.minimum(cand, G - 1)]
            miss_b = (cand_valid & has_req_b & ~succ_b
                      & jnp.all(subdom_b[0] < 0, axis=-1))
            res = res.replace(topology_stats=res.topology_stats.at[2].add(
                jnp.sum(miss_b.astype(jnp.int32))))
        # a same-group duplicate lane is CONFLICT-rejected (retries next
        # chunk), never counted as a genuine fit failure
        succ_all = succ_b & cand_valid
        succ_b = succ_all & ~dup_b

        ok = succ_b[:, None, None]
        d_qa = jnp.where(ok, qa2_b - qa, 0.0)                     # [B, Q, R]
        d_qan = jnp.where(ok, qan2_b - qan, 0.0)

        # maximal order-prefix whose cumulative claims still fit.  Deltas
        # are non-negative, so the per-prefix feasibility flags are
        # monotone and the accept mask IS the prefix mask.
        cum_qa = jnp.cumsum(d_qa, axis=0)
        cum_qan = jnp.cumsum(d_qan, axis=0)
        if sparse:
            # sparse prefix test: each accepted replica claims exactly
            # its gang's uniform request on its node, so sort the K=B*T
            # placement entries by node (stable -> lane-major within a
            # node), segment-cumsum the claims, and the first lane whose
            # cumulative claim overruns a node pool bounds the prefix.
            req_b = g.task_req[jnp.minimum(cand, G - 1), 0]       # [B, R]
            ent_ok = succ_b[:, None] & (nodes_b >= 0)             # [B, T]
            first_bad, node_e, lane_e = sparse_accept_first_bad(
                nodes_b, ent_ok, pipe_b, req_b, free,
                free + n.releasing + extra, n.n)
            prefix_ok = jnp.arange(B) < first_bad                 # [B]
        else:
            d_free = jnp.where(ok, free - free2_b, 0.0)           # [B, N, R]
            d_bind = jnp.where(ok, bind_b, 0.0)                   # [B, N, R]
            cum_free = jnp.cumsum(d_free, axis=0)
            cum_bind = jnp.cumsum(d_bind, axis=0)
            ok_node = jnp.all(free[None] - cum_free >= rel_floor[None],
                              axis=(1, 2))                        # [B]
            # bind-now claims must collectively fit the chunk-start
            # *idle* pool: each lane computed its pipelined flags against
            # chunk-start free, so without this a later lane could bind
            # immediately onto capacity another lane just consumed
            # (capacity that is really still held by terminating pods).
            # Rejected lanes retry next chunk and re-derive their flags
            # against the updated pool.
            ok_bind = jnp.all(
                cum_bind <= jnp.maximum(free[None], 0.0) + EPS,
                axis=(1, 2))                                      # [B]
            prefix_ok = ok_node & ok_bind
        # capacity gates re-checked jointly; queues untouched by the
        # chunk (zero delta) are exempt — they may legitimately sit over
        # limit from pre-existing allocation
        ok_qa = jnp.all((qa[None] + cum_qa <= limit_eff[None] + EPS)
                        | (cum_qa <= EPS), axis=(1, 2))
        ok_qan = jnp.all((qan[None] + cum_qan <= quota_eff[None] + EPS)
                         | (cum_qan <= EPS), axis=(1, 2))
        accept = prefix_ok & ok_qa & ok_qan                       # [B]
        if config.extended:
            d_ext = jnp.where(ok, ext - ext2_b, 0.0)              # [B, N, E]
            d_extbind = jnp.where(ok, extbind_b, 0.0)
            cum_ext = jnp.cumsum(d_ext, axis=0)
            cum_extbind = jnp.cumsum(d_extbind, axis=0)
            ext_floor = -(n.extended_releasing[None]
                          + init.extended_releasing_extra[None]) - EPS
            accept = accept & jnp.all(
                ext[None] - cum_ext >= ext_floor, axis=(1, 2))
            accept = accept & jnp.all(
                cum_extbind <= jnp.maximum(ext[None], 0.0) + EPS,
                axis=(1, 2))
        if config.track_devices:
            d_dev = jnp.where(ok, dev - dev2_b, 0.0)              # [B, N, D]
            d_devbind = jnp.where(ok, devbind_b, 0.0)
            cum_dev = jnp.cumsum(d_dev, axis=0)
            cum_devbind = jnp.cumsum(d_devbind, axis=0)
            accept = accept & jnp.all(
                dev[None] - cum_dev >= dev_floor[None], axis=(1, 2))
            accept = accept & jnp.all(
                cum_devbind <= jnp.maximum(dev[None], 0.0) + EPS,
                axis=(1, 2))

        take = succ_b & accept
        w = take.astype(free.dtype)
        if sparse:
            take_e = take[lane_e] & ent_ok.ravel()                # [K]
            upd = jnp.zeros((n.n + 1, free.shape[1]), free.dtype).at[
                node_e].add(jnp.where(take_e[:, None],
                                      req_b[lane_e], 0.0),
                            mode="drop")
            free = free - upd[:n.n]
        else:
            free = free - einsum_exact("b,bnr->nr", w, d_free)
        qa = qa + einsum_exact("b,bqr->qr", w, d_qa)
        qan = qan + einsum_exact("b,bqr->qr", w, d_qan)
        if config.track_devices:
            dev = dev - einsum_exact("b,bnd->nd", w, d_dev)
        if config.extended:
            ext = ext - einsum_exact("b,bne->ne", w, d_ext)

        nodes_b = jnp.where(take[:, None], nodes_b, -1)
        devt_b = jnp.where(take[:, None], devt_b, -1)
        pipe_b = jnp.where(take[:, None], pipe_b, False)
        new_cnt = jnp.sum((nodes_b >= 0).astype(jnp.int32), -1)   # [B]
        total_cnt = placed_cnt + new_cnt
        valid_cnt = jnp.sum(g.task_valid[cand].astype(jnp.int32), -1)
        # done: the gang is whole (take, nothing left to scale up), or the
        # attempt failed (failure is final — capacity only shrinks).
        # Successful partial gangs re-enter the heap (re-push); conflict-
        # rejected successes (incl. same-anti-group duplicates, whose
        # succ_b was cleared above) retry next chunk.
        done_b = cand_valid & ((take & (total_cnt >= valid_cnt))
                               | ~(succ_b | dup_b))
        fail_b = cand_valid & ~(succ_b | dup_b)
        # a scale-up failure of an already-quorate gang is not a fit
        # failure of the gang (its quorum stands)
        fail_fresh = fail_b & (placed_cnt == 0)
        res = res.replace(
            fit_reason=res.fit_reason.at[cand].set(
                jnp.where(fail_fresh, 3,
                          jnp.where(take, 0, res.fit_reason[cand]))),
        )
        # merge this attempt's new placements over prior attempts'
        new_t = nodes_b >= 0                                      # [B, T]
        res = res.replace(
            free=free, device_free=dev, queue_allocated=qa,
            queue_allocated_nonpreemptible=qan,
            extended_free=ext,
            placements=res.placements.at[cand].set(
                jnp.where(new_t, nodes_b, res.placements[cand])),
            placement_device=res.placement_device.at[cand].set(
                jnp.where(new_t, devt_b, res.placement_device[cand])),
            pipelined=res.pipelined.at[cand].set(
                jnp.where(new_t, pipe_b, res.pipelined[cand])),
            allocated=res.allocated.at[cand].set(
                res.allocated[cand] | (take & (total_cnt >= need))),
            attempted=res.attempted.at[cand].set(
                res.attempted[cand] | cand_valid),
        )
        remaining = remaining.at[cand].set(remaining[cand] & ~done_b)
        if config.queue_depth is not None:
            # retired lanes consume their queue's budget (conflict-
            # rejected lanes re-attempt, so they count only once)
            q_attempts = q_attempts + jax.ops.segment_sum(
                done_b.astype(jnp.int32), g.queue[cand],
                num_segments=q.q)
            remaining = remaining & (
                q_attempts[g.queue] < config.queue_depth)
        if config.signature_skip:
            # one quorum-attempt failure retires every equivalent gang —
            # the signature groups (queue, task types, quorum,
            # constraints).  Scale-up failures of quorate gangs don't
            # poison the signature: equivalents may be at earlier stages.
            failed_sig = failed_sig.at[g.sig[cand]].max(fail_fresh)
            skip_now = remaining & failed_sig[g.sig]
            res = res.replace(
                fit_reason=jnp.where(skip_now, 2, res.fit_reason))
            remaining = remaining & ~skip_now
        if config.anti_groups:
            # taken lanes claim their placements' domains in their mark
            # rows (junk row/column absorb unused slots)
            res = res.replace(anti_used=anti_mark_placements(
                state, res.anti_used, dom_static, cand, nodes_b, take))
        out = (res, remaining, q_attempts, failed_sig, fuel - 1)
        if hoist_topo:
            with topo_scope():
                dom_caps_y, dom_agg, c_y_store = topo_tables_update(
                    dom_caps_y, dom_agg, c_y_store, res.free,
                    take, cand, nodes_b)
            out = out + (dom_caps_y, dom_agg, c_y_store)
        return out

    # fuel: every chunk either retires ≥1 remaining gang (the first
    # remaining gang in order always lands in the accept prefix, or its
    # exhausted queue drains from `remaining`) or places ≥1 new task of a
    # re-pushed gang, so G*(T+1) chunks is a hard upper bound; the common
    # case is ceil(G/B) + elastic re-pushes + a few conflicts.
    carry0 = (init, remaining0, jnp.zeros((q.q,), jnp.int32),
              jnp.zeros((G,), bool), jnp.asarray(G * (T + 1), jnp.int32))
    if hoist_topo:
        with topo_scope():
            carry0 = carry0 + topo_tables_build(init.free)
    with jax.named_scope("placement_loop"):
        out = lax.while_loop(cond, chunk, carry0)
    res = out[0]
    if config.subgroup_topology or config.preferred_topology:
        res = res.replace(topology_stats=res.topology_stats
                          + _topology_counts(state, init, res))
    return res


@functools.partial(jax.jit, static_argnames=("num_levels", "config"))
def allocate_jit(state: ClusterState, fair_share: jax.Array, *,
                 num_levels: int, config: AllocateConfig = AllocateConfig(),
                 init: AllocationResult | None = None) -> AllocationResult:
    return allocate(state, fair_share, num_levels=num_levels, config=config,
                    init=init)


# kai-wire compile watcher: attribute every cache miss of this entry to
# its (entry, abstract-shape-signature) pair (runtime/compile_watch.py)
allocate_jit = compile_watch.watch("allocate", allocate_jit)
