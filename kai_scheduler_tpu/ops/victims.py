"""Victim-scenario engine — reclaim & preempt as compiled scenario search.

Reference (``actions/common/solvers/job_solver.go:47-120``,
``by_pod_solver.go:20-90``): for a pending *preemptor* gang, grow a victim
set one eviction unit at a time (``PodAccumulatedScenarioBuilder``), and
for each scenario simulate "evict victims, re-run allocation" inside a
Statement; the first scenario whose simulation places the preemptor and
passes the scenario validators wins.  The eviction *unit*
(``api/podgroup_info/eviction_info.go:14`` GetTasksToEvict) is a single
task while the victim gang is elastic (above minMember), then the whole
remaining gang at once.  The ``idle_gpus`` accumulated filter
(``accumulated_scenario_filters/idle_gpus.go``) prunes scenarios whose
freed capacity still cannot fit the preemptor.

TPU-native design: victims are *ranked once* per preemptor — victim jobs
by a lexsort over gang keys (the ordered victim-queue generator), pods
within a gang by reverse task order — giving every candidate pod a global
*unit rank*; a scenario is a unit-rank prefix.  A ``lax.while_loop``
walks scenarios in order, each iteration:

1. masks pods with ``unit_rank <= k`` and segment-sums their requests
   into per-node freed capacity (no [scenarios, N, R] materialization),
2. checks the reclaim strategy for the unit being added (against the
   leveled queue's remaining share — see below),
3. runs the same gang-placement kernel the allocate action uses
   (``_attempt_gang``) on ``free + freed`` — first success wins,
   mirroring the reference's minimal-victim greedy.

The idle-capacity prefilter fast-forwards ``k`` to the first scenario
whose aggregate freed + idle covers the preemptor's request.

Validation semantics implemented (see
``plugins/proportion/reclaimable/reclaimable.go`` and
``reclaimable/strategies/strategies.go``):

- **CanReclaimResources gate**: reclaimer queue (and ancestors) must stay
  within fair share after the allocation; a non-preemptible reclaimer's
  non-preemptible allocation must stay within deserved quota.
- **Per-eviction strategy** at the *leveled* queue (the victim-side
  ancestor just below the LCA with the reclaimer —
  ``reclaimable.go getLeveledQueues``): evictable only while that queue
  is above fair share (MaintainFairShare) or, when the reclaimer is under
  deserved quota, above deserved (GuaranteeDeservedQuota) — evaluated
  against the remaining share before the step, exactly like the
  reference's running ``remainingResourcesMap``.
- **Preempt gate** (``actions/preempt/preempt.go:100-110``): a
  non-preemptible preemptor must keep the queue's non-preemptible
  allocation within deserved quota.
- Sibling saturation-order checks degenerate to true under the gate
  (reclaimer saturation ≤ 1) and are omitted; ``minruntime`` victim
  protection is a candidate filter here rather than a separate validator.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..apis.types import UNLIMITED
from ..runtime import compile_watch
from ..utils.numerics import cumsum_ds, einsum_exact
from ..state.cluster_state import ClusterState
from . import ordering, unit_segments
from .allocate import (AllocateConfig, AllocationResult, _ancestor_gate,
                       _attempt_gang, _chain_membership, anti_defer_lanes,
                       anti_domain_tables, anti_forbid_nodes,
                       anti_mark_placements, attract_allow_nodes,
                       attract_defer_lanes, init_result,
                       sparse_accept_first_bad)
from .scoring import W_OWN_FREED

EPS = 1e-6
BIG = jnp.int32(2**30)


@dataclasses.dataclass(frozen=True)
class VictimConfig:
    """Knobs of the victim actions (ref reclaim/preempt action args)."""

    placement: AllocateConfig = AllocateConfig(dynamic_order=False)
    #: max preemptor gangs attempted per QUEUE (QueueDepthPerAction) for
    #: reclaim/consolidation; None = unlimited
    queue_depth: int | None = None
    #: preempt's own depth; None = inherit ``queue_depth``
    queue_depth_preempt: int | None = None
    #: cap on eviction units per consolidation scenario — ref
    #: ``MaxNumberConsolidationPreemptees`` (consolidation.go)
    max_consolidation_preemptees: int = 64
    #: preemptor gangs attempted per wavefront chunk (reclaim/preempt).
    #: Each pod of the frozen eviction-unit order is consumed by the
    #: FIRST lane whose budget covers it and whose queue may evict it
    #: (reclaim: other-queue flow; preempt: queue-segmented per-lane
    #: watermarks), so victim assignment cannot conflict; an
    #: allocate-style accept-prefix re-verifies composed capacity and
    #: queue gates.  1 = fully sequential (reference-exact order).
    #: 64 measured fastest at the 10k-node × 50k-pod baseline.
    batch_size: int = 64
    #: preempt's own chunk width; None = inherit ``batch_size``.
    #: Preempt chunks pack lanes across queues (queue-segmented budget
    #: math), so a many-queue snapshot wants chunks at least as wide as
    #: its preemptor spread, while junk lanes past the live preemptor
    #: count only add freed-pool cost — the Session auto-tunes this
    #: from the snapshot's pending-gang spread and padded node count
    #: (see ``Session.from_state``; measured sweep in BASELINE.md).
    batch_size_preempt: int | None = None
    #: reclaim may use the chunked path — False when the snapshot
    #: carries per-(victim,reclaimer) reclaim-minruntime protection,
    #: whose lane-dependent tables need the sequential path.  The
    #: Session derives this from the snapshot.
    chunk_reclaim: bool = False
    #: cap on victims re-placed per consolidation scenario — ONE knob
    #: for both the ``_replace_victims`` default and the consolidation
    #: call site's ``max(max_victim_pods, max_consolidation_preemptees
    #: * T)`` sizing (was a hard-coded 512 in two places)
    max_victim_pods: int = 512
    #: preempt sparse-lane wavefront: solve each lane against its OWN
    #: queue's freed capacity only (queue-disjoint optimistic solve) and
    #: verify composition with sparse (node-id, delta) segments instead
    #: of dense [B, N, *] lane-prefix cumsums.  None = auto (enabled
    #: whenever the snapshot shape supports the sparse placement
    #: protocol — uniform tasks, no device table, no extended
    #: resources, no subgroup topology); False forces the dense
    #: composed path.  True still requires the structural conditions.
    optimistic_preempt: bool | None = None
    #: width of the compact per-queue eviction-unit tables the sparse
    #: preempt path probes (top-K units per queue, a [Q, K] grid cut
    #: from the per-leaf unit segments).  An action whose
    #: frozen unit order gives any queue more candidate units than this
    #: falls back to the composed path over the full segments at run
    #: time (counted by
    #: the ``kai_victim_wavefront_sparse_fallbacks`` gauge).  None =
    #: auto: the Session derives it from running-pod density per leaf
    #: queue (non-Session callers get 256); an explicit value is
    #: honored as-is, e.g. to bound table memory or force the dense
    #: fallback for debugging.
    sparse_unit_k: int | None = None


def freed_by_mask(state: ClusterState, mask: jax.Array, chain: jax.Array):
    """Resources released by evicting the masked running pods.

    Returns (freed_nodes [N, R], freed_devices [N, D], freed_queues
    [Q, R], freed_queues_nonpreemptible [Q, R], freed_extended [N, E])
    with the queue tensors rolled up the hierarchy via ``chain`` — shared
    by the victim solver and the stalegangeviction action.
    """
    r = state.running
    n, q = state.nodes, state.queues
    D = n.d
    req_m = jnp.where(mask[:, None], r.req, 0.0)
    freed_nodes = jax.ops.segment_sum(
        req_m, jnp.where(mask, jnp.maximum(r.node, 0), n.n),
        num_segments=n.n + 1)[:n.n]
    # device table: fractional pods return their held share to their
    # device; whole-device pods return 1.0 per devices_mask bit
    frac = mask & (r.device >= 0)
    flat = jnp.maximum(r.node, 0) * D + jnp.maximum(r.device, 0)
    freed_dev = jax.ops.segment_sum(
        jnp.where(frac, r.accel_held, 0.0),
        jnp.where(frac, flat, n.n * D),
        num_segments=n.n * D + 1)[:n.n * D].reshape(n.n, D)
    bits = ((r.devices_mask[:, None] >> jnp.arange(D)[None, :]) & 1)
    whole_bits = bits.astype(req_m.dtype) * (mask & (r.device < 0))[:, None]
    freed_dev = freed_dev + jax.ops.segment_sum(
        whole_bits, jnp.where(mask, jnp.maximum(r.node, 0), n.n),
        num_segments=n.n + 1)[:n.n]
    leaf = jax.ops.segment_sum(
        req_m, jnp.where(mask, jnp.maximum(r.queue, 0), q.q),
        num_segments=q.q + 1)[:q.q]
    leaf_np = jax.ops.segment_sum(
        jnp.where((mask & ~r.preemptible)[:, None], r.req, 0.0),
        jnp.where(mask & ~r.preemptible, jnp.maximum(r.queue, 0), q.q),
        num_segments=q.q + 1)[:q.q]
    chain_f = chain.astype(leaf.dtype)
    freed_q = einsum_exact("qa,qr->ar", chain_f, leaf)
    freed_q_np = einsum_exact("qa,qr->ar", chain_f, leaf_np)
    # extended (MIG) scalars held by the victims return to their node's
    # pool — the credit-back that lets a preemptor reclaim a MIG slice
    freed_ext = jax.ops.segment_sum(
        jnp.where(mask[:, None], r.extended, 0.0),
        jnp.where(mask, jnp.maximum(r.node, 0), n.n),
        num_segments=n.n + 1)[:n.n]
    return freed_nodes, freed_dev, freed_q, freed_q_np, freed_ext


def _pod_order_static(state: ClusterState):
    """Within-gang pod order (newest first) — preemptor-independent, so
    it is computed ONCE per action instead of a [M] lexsort per
    preemptor.  Returns (perm0 [M], gang_perm [M])."""
    r = state.running
    G = state.gangs.g
    gang_all = jnp.where(r.valid & (r.gang >= 0), r.gang, G)
    perm0 = jnp.lexsort((r.runtime_s, gang_all))
    return perm0, gang_all[perm0]


def victim_statics(state: ClusterState):
    """Preemptor-independent victim-search inputs, hoisted out of the
    per-preemptor solve (the per-step cost is what bounds cycle latency):

    - ``base0`` [M]: the candidate filter minus the per-preemptor parts
    - ``gang_runtime`` [G]: max pod runtime per gang (minruntime input);
      -1 when the gang never started (nil LastStartTimestamp => NOT
      protected, ref minruntime.go)
    - ``pod_order``: within-gang newest-first order (see
      :func:`_pod_order_static`)
    """
    r = state.running
    G = state.gangs.g
    base0 = (r.valid & ~r.releasing & (r.node >= 0) & r.preemptible
             & (r.gang >= 0))
    gang_runtime = jax.ops.segment_max(
        jnp.where(r.valid & (r.gang >= 0), r.runtime_s, -1.0),
        jnp.where(r.gang >= 0, r.gang, G), num_segments=G + 1)[:G]
    return base0, gang_runtime, _pod_order_static(state)


def frozen_job_rank(state: ClusterState, queue_allocated: jax.Array,
                    fair_share: jax.Array) -> jax.Array:
    """Victim-JOB ordering, frozen at action start — the reference
    regenerates the victim queue order from live shares per preemptor;
    freezing it trades that re-sort for one [G] lexsort per ACTION
    (bounded drift: within one action, shares only move monotonically).
    Most-saturated queue first, lowest priority first, newest first.
    Gangs that turn out to expose no units occupy rank slots but
    contribute nothing to the unit cumsum, so unit ranks stay dense."""
    g = state.gangs
    G = g.g
    sat = jnp.max(
        queue_allocated / jnp.maximum(fair_share, EPS), axis=-1)  # [Q]
    gq = jnp.maximum(g.queue, 0)
    rank_gang = jnp.lexsort((
        -g.creation_order.astype(jnp.float32),
        g.priority.astype(jnp.float32),
        -sat[gq],
    ))
    return jnp.zeros((G,), jnp.int32).at[rank_gang].set(
        jnp.arange(G, dtype=jnp.int32))


def victim_candidates(
    state: ClusterState,
    gang_idx: jax.Array,
    *,
    mode: str,
    already_victim: jax.Array,   # bool [M]
    statics=None,                # victim_statics(state) output
) -> jax.Array:
    """bool [M] — pods eligible as victims for this preemptor.

    Reclaim filter (``actions/reclaim/reclaim.go`` victim generator +
    ``ReclaimVictimFilter``): preemptible running pods of *other* queues
    that have run at least their queue's ``reclaimMinRuntime``.
    Preempt filter (``buildFilterFuncForPreempt``): preemptible running
    pods of the *same* queue whose gang priority is strictly lower, past
    ``preemptMinRuntime``.
    Consolidation (``actions/consolidation``): any preemptible running pod
    of another gang — victims are *moved*, not lost, so no queue or
    priority constraint applies (minruntime still protects).
    """
    r = state.running
    g = state.gangs
    q = state.queues
    if statics is None:
        statics = victim_statics(state)
    base0, gang_runtime, _ = statics
    base = base0 & ~already_victim
    my_queue = g.queue[gang_idx]
    # gang-level minruntime protection (hierarchy/LCA-resolved at
    # snapshot build — ref plugins/minruntime/resolver.go).  A protected
    # gang may still shed ELASTIC surplus pods; only its quorum unit is
    # off-limits (ref reclaimFilterFn returning true for elastic jobs +
    # the scenario validator) — enforced by the unit ranking, which gives
    # protected gangs no whole-gang unit.
    gq = jnp.maximum(g.queue, 0)
    if mode == "reclaim":
        mrt_g = q.reclaim_min_runtime_eff[gq, my_queue]          # [G]
    else:
        mrt_g = q.preempt_min_runtime_eff[gq]
    protected = (gang_runtime >= 0) & (gang_runtime < mrt_g)     # [G]
    if mode == "reclaim":
        return base & (r.queue != my_queue), protected
    if mode == "consolidate":
        return base & (r.gang != gang_idx), protected
    return (base & (r.queue == my_queue)
            & (r.priority < g.priority[gang_idx])), protected


def _rank_eviction_units(
    state: ClusterState,
    cand: jax.Array,             # bool [M]
    queue_allocated: jax.Array,  # f32 [Q, R]
    fair_share: jax.Array,       # f32 [Q, R]
    already_victim: jax.Array,   # bool [M]  victims accumulated this cycle
    protected: jax.Array | None = None,  # bool [G]  minruntime-protected
    pod_order=None,              # (perm0, gang_perm) from _pod_order_static
    job_rank: jax.Array | None = None,   # frozen_job_rank output
):
    """Assign every candidate pod a global eviction-unit rank.

    Victim *jobs* follow ``frozen_job_rank`` — the reference generates
    victims queue-by-queue in reversed queue order (most over-fair-share
    first) and job-by-job in reversed job order (lowest priority, newest
    first).  Within a gang, pods are ordered by reverse task order
    (shortest-running ≈ newest first); each of the first
    ``allocated - minMember`` pods is its own unit (elastic shrink), the
    remaining ``minMember`` pods form one final unit
    (``eviction_info.go GetTasksToEvict``).

    Returns (unit_rank [M] i32 — BIG for non-candidates, num_units []).
    """
    g = state.gangs
    r = state.running
    G, M = g.g, r.m

    gang_of_pod = jnp.where(cand, r.gang, G)                   # [M], G = junk
    pods_per_gang = jax.ops.segment_sum(
        cand.astype(jnp.int32), gang_of_pod, num_segments=G + 1)[:G]
    victim_gang = pods_per_gang > 0

    if job_rank is None:
        job_rank = frozen_job_rank(state, queue_allocated, fair_share)

    # ---- pod order within gang (reverse task order: newest first) -------
    # seq = rank among this gang's CANDIDATES in the hoisted static order:
    # gather→cumsum→scatter instead of a per-preemptor [M] lexsort
    if pod_order is None:
        pod_order = _pod_order_static(state)
    perm0, gang_perm = pod_order
    cand_p = cand[perm0].astype(jnp.int32)
    excl = jnp.cumsum(cand_p) - cand_p                          # [M]
    base = jax.ops.segment_min(excl, gang_perm, num_segments=G + 1)[:G]
    seq_p = excl - base[jnp.minimum(gang_perm, G - 1)]
    seq = jnp.zeros((M,), jnp.int32).at[perm0].set(seq_p)       # [M]

    # ---- unit ids --------------------------------------------------------
    # Surplus is sized from the gang's *effective* active pod count:
    # running_count minus pods already victimised by earlier actions this
    # cycle — the reference's Statement.Evict updates the active-task
    # counts GetTasksToEvict reads, so a gang reclaimed down to minMember
    # by one action is NOT elastic-shrinkable again by the next; the
    # final unit (whole remaining gang) triggers at the right threshold.
    # Pods excluded from candidacy for other reasons (unknown node) still
    # hold the gang above minMember.
    victims_in_gang = jax.ops.segment_sum(
        (already_victim & (r.gang >= 0)).astype(jnp.int32),
        jnp.where(r.gang >= 0, r.gang, G), num_segments=G + 1)[:G]
    effective_active = g.running_count - victims_in_gang        # [G]
    surplus = jnp.clip(
        effective_active - g.min_member, 0, pods_per_gang)      # [G]
    # a minruntime-protected gang keeps its quorum: it exposes only its
    # elastic-surplus units, never the final whole-gang unit (ref the
    # minruntime scenario validators protecting below-minAvailable)
    whole_unit = pods_per_gang > surplus
    if protected is not None:
        whole_unit = whole_unit & ~protected
    units_per_gang = jnp.where(
        victim_gang, surplus + whole_unit, 0)                   # [G]
    units_by_rank = jnp.zeros((G,), units_per_gang.dtype).at[
        job_rank].set(units_per_gang)                           # [G]
    offsets = jnp.cumsum(units_by_rank) - units_by_rank         # [G] excl
    gsafe = jnp.minimum(gang_of_pod, G - 1)
    unit_in_gang = jnp.minimum(seq, surplus[gsafe])
    in_range = unit_in_gang < units_per_gang[gsafe]
    unit_rank = jnp.where(
        cand & in_range,
        offsets[job_rank[gsafe]] + unit_in_gang,
        BIG)
    return unit_rank, jnp.sum(units_per_gang)


def _leveled_queue(chain: jax.Array, depth: jax.Array,
                   vq: jax.Array, rq: jax.Array) -> jax.Array:
    """The victim-side ancestor just below the LCA with the reclaimer —
    ref ``reclaimable.go getLeveledQueues``.  i32 scalar queue index."""
    vchain = chain[vq]                        # bool [Q]
    rchain = chain[rq]
    cand_q = vchain & ~rchain
    d = jnp.where(cand_q, depth, BIG)
    # -1 when every victim ancestor is shared with the reclaimer (victim
    # queue is an ancestor of the reclaimer's) — callers treat -1 as
    # "no leveled queue, strategy check passes".
    return jnp.where(jnp.any(cand_q), jnp.argmin(d), -1)


def solve_for_preemptor(
    state: ClusterState,
    gang_idx: jax.Array,
    result: AllocationResult,
    fair_share: jax.Array,
    chain: jax.Array,            # bool [Q, Q]
    *,
    num_levels: int,
    mode: str,                   # "reclaim" | "preempt" | "consolidate"
    config: VictimConfig,
    statics=None,                # hoisted victim_statics output
    job_rank: jax.Array | None = None,   # hoisted frozen_job_rank
    domain_mask: jax.Array | None = None,   # bool [N] in-cycle anti mask
):
    """One preemptor's scenario search — returns updated commit-set fields.

    (success, victim_mask [M], task placements [T], devices [T],
    pipelined [T], moves [M], free', dev', extra', extra_dev', qa',
    qan', ext', ext_extra')
    """
    reclaim = mode == "reclaim"
    consolidate = mode == "consolidate"
    g, q, n, r = state.gangs, state.queues, state.nodes, state.running
    free = result.free
    dev = result.device_free
    extra = result.releasing_extra
    extra_dev = result.device_releasing_extra
    qa = result.queue_allocated
    qan = result.queue_allocated_nonpreemptible
    queue = g.queue[gang_idx]
    task_req = jnp.where(g.task_valid[gang_idx][:, None],
                         g.task_req[gang_idx], 0.0)
    total_req = task_req.sum(0)                                # [R]
    nonpreempt = ~g.preemptible[gang_idx]

    # ---- gates (before any scenario work) -------------------------------
    nonpreempt_quota_ok = jnp.where(
        nonpreempt,
        _ancestor_gate(q.parent, queue, num_levels, qan, q.quota, total_req),
        True)
    if reclaim:
        # CanReclaimResources: the chain stays within fair share in the
        # POST-SCENARIO state (victims' releases credited) — checked per
        # attempt below against qa_eff, NOT against live qa: a dept at
        # its full fair share must still be able to reclaim WITHIN
        # itself (same-dept victims free the very allocation the
        # reclaimer adds)
        gate = nonpreempt_quota_ok
    elif consolidate:
        # consolidation only serves pending *preemptible* jobs
        # (``consolidation.go`` pending-preemptible filter)
        gate = ~nonpreempt
    else:
        gate = nonpreempt_quota_ok

    if statics is None:
        statics = victim_statics(state)
    cand, protected = victim_candidates(
        state, gang_idx, mode=mode, already_victim=result.victim,
        statics=statics)
    gate &= jnp.any(cand)

    # moved (consolidated) victims stay active gang members — they restart
    # on their target node — so only *removed* victims shrink the gang's
    # effective active count for unit sizing
    removed_victims = result.victim & (result.victim_move < 0)
    unit_rank, num_units = _rank_eviction_units(
        state, cand, qa, fair_share, removed_victims, protected,
        statics[2], job_rank)
    if consolidate:
        num_units = jnp.minimum(num_units,
                                config.max_consolidation_preemptees)
    reclaimer_under_quota = _ancestor_gate(
        q.parent, queue, num_levels, qa, q.quota, total_req)
    quota_eff = jnp.where(q.quota <= UNLIMITED + 0.5, jnp.inf, q.quota)
    m_req = jnp.where(cand[:, None], r.req, 0.0)               # [M, R]
    M = r.m
    urank_safe = jnp.minimum(unit_rank, M)

    # ---- per-unit tables, vectorized over ALL unit ranks at once --------
    unit_req = jax.ops.segment_sum(
        m_req, urank_safe, num_segments=M + 1)[:M]             # [U, R]
    cum_freed = cumsum_ds(unit_req, axis=0)                    # [U, R]
    # idle_gpus-style prefilter: the first scenario whose aggregate
    # free + freed covers the preemptor's request lower-bounds the search
    cluster_free = jnp.sum(
        jnp.where(n.valid[:, None], free + n.releasing + extra, 0.0),
        axis=0)
    enough = jnp.all(cluster_free[None, :] + cum_freed + EPS
                     >= total_req[None, :], axis=-1)           # [U] monotone
    gate_prefilter = jnp.any(enough)

    # FitsReclaimStrategy per unit (the reference's running
    # remainingResourcesMap check), vectorized: unit u passes iff its
    # leveled queue's remaining share BEFORE u (qa minus the freed
    # prefix inside that queue's subtree) is still above fair share /
    # deserved quota.  Scenario validity needs every unit of the prefix
    # to pass, so the first failing unit truncates the search range.
    if reclaim:
        unit_leaf = jax.ops.segment_max(
            jnp.where(cand, r.queue, -1), urank_safe,
            num_segments=M + 1)[:M]                            # [U]
        leaf_safe = jnp.maximum(unit_leaf, 0)
        lq_u = jax.vmap(
            lambda vq: _leveled_queue(chain, q.depth, vq, queue))(
                leaf_safe)                                     # [U]
        contrib = chain[leaf_safe] & (unit_leaf >= 0)[:, None]  # [U, Q]
        with jax.named_scope("unit_tables"):
            inc = contrib[:, :, None] * unit_req[:, None, :]   # [U, Q, R]
            csum_excl = cumsum_ds(inc, axis=0) - inc
        lq_safe = jnp.maximum(lq_u, 0)
        freed_excl = csum_excl[jnp.arange(M), lq_safe]         # [U, R]
        remaining_u = qa[lq_safe] - freed_excl
        over_fs = jnp.any(remaining_u > fair_share[lq_safe] + EPS, -1)
        over_q = jnp.any(remaining_u > quota_eff[lq_safe] + EPS, -1)
        pass_u = (lq_u < 0) | over_fs | (reclaimer_under_quota & over_q)
    else:
        pass_u = jnp.ones((M,), bool)
    bad = (jnp.arange(M) < num_units) & ~pass_u
    first_bad = jnp.where(jnp.any(bad), jnp.argmax(bad), num_units)
    hi = jnp.minimum(num_units, first_bad) - 1   # largest admissible k
    lo = jnp.argmax(enough)                      # smallest k that can fit
    can_search = gate & gate_prefilter & (hi >= lo)

    T = g.t
    alloc_cfg = config.placement
    no_moves = jnp.full((M,), -1, jnp.int32)
    ext_extra = result.extended_releasing_extra

    def attempt(k):
        """Simulate scenario prefix ``k``: evict, credit, re-place."""
        mask_k = cand & (unit_rank <= k)
        freed_nodes, freed_dev, freed_q, _, freed_ext = freed_by_mask(
            state, mask_k, chain)
        # victim capacity is *releasing* until the pods terminate: the
        # preemptor's tasks that land on it pipeline, tasks that fit
        # genuinely idle capacity bind now (stmt.Allocate vs Pipeline)
        extra_eff = extra + freed_nodes
        extra_dev_eff = extra_dev + freed_dev
        ext_extra_eff = ext_extra + freed_ext
        # consolidation victims are moved, not removed — their queue
        # allocation stays (allPodsReallocated validator below)
        qa_eff = qa if consolidate else qa - freed_q
        (free2, dev2, qa2, qan2, nodes_t, dev_t, pipe_t, success,
         _, _, ext2, _) = \
            _attempt_gang(state, gang_idx, free, dev, qa_eff, qan,
                          num_levels, alloc_cfg, extra_eff,
                          extra_dev_eff, chain=chain,
                          ext_free=result.extended_free,
                          extra_extended_releasing=ext_extra_eff,
                          domain_mask=domain_mask)
        if reclaim:
            # CanReclaimResources against the post-scenario state
            success &= _ancestor_gate(q.parent, queue, num_levels,
                                      qa_eff, fair_share, total_req)
        if consolidate:
            free3, dev3, ext3, moves, all_ok = _replace_victims(
                state, mask_k, free2, dev2, n.releasing + extra_eff,
                state.nodes.device_releasing + extra_dev_eff,
                ext2, state.nodes.extended_releasing + ext_extra_eff,
                max_pods=max(config.max_victim_pods,
                             config.max_consolidation_preemptees * T))
            return success & all_ok, (
                free3, dev3, qa2, qan2, nodes_t, dev_t, pipe_t, moves,
                extra_eff, extra_dev_eff, ext3, ext_extra_eff, k)
        return success, (
            free2, dev2, qa2, qan2, nodes_t, dev_t, pipe_t, no_moves,
            extra_eff, extra_dev_eff, ext2, ext_extra_eff, k)

    empty = (free, dev, qa, qan, jnp.full((T,), -1, jnp.int32),
             jnp.full((T,), -1, jnp.int32),
             jnp.zeros((T,), bool), no_moves, extra, extra_dev,
             result.extended_free, ext_extra, jnp.asarray(0, jnp.int32))

    # ---- search over the unit prefix ------------------------------------
    # Freed capacity grows monotonically with k, so placement success is
    # monotone for capacity-style constraints (reclaim/preempt); the
    # search probes the capacity lower bound first (tight in the common
    # case — ONE attempt), then ``hi`` (failing preemptors cost one more)
    # and bisects to the smallest succeeding prefix — the minimal victim
    # set the reference's one-unit-at-a-time walk finds, in O(log U)
    # placement attempts.  Consolidation's allPodsReallocated validator
    # is NOT monotone (extra victims must also re-place), so it keeps
    # the reference's linear first-success walk — num_units is already
    # capped by max_consolidation_preemptees.  Subgroup-topology
    # placement through the per-task kernel is not monotone either: the
    # aggregate-capacity domain gate can pass while the fill fails on a
    # fragmented domain, so attempt(hi) may fail where a smaller prefix
    # succeeds, and the bisect can settle on a non-minimal k — those
    # snapshots take the linear walk too (the uniform kernel's domain
    # pick counts real per-node replica capacities, so it stays
    # monotone and keeps the bisect).
    linear_walk = consolidate or (
        config.placement.subgroup_topology
        and not config.placement.uniform_tasks)
    if linear_walk:
        def search(_):
            def cond_l(c):
                k, done, _ = c
                return (~done) & (k <= hi)

            def body_l(c):
                k, done, best = c
                s, tm = attempt(k)
                best = jax.tree.map(
                    lambda a, b: jnp.where(s, a, b), tm, best)
                return k + 1, s, best

            _, done, best = lax.while_loop(
                cond_l, body_l,
                (lo, jnp.asarray(False), empty))
            return done, best
    else:
        def search(_):
            s_lo, t_lo = attempt(lo)

            def refine(_):
                s_hi, t_hi = attempt(hi)

                def bcond(c):
                    lo_c, hi_c, _ = c
                    return lo_c + 1 < hi_c

                def bbody(c):
                    # invariant: lo_c fails, hi_c succeeds
                    lo_c, hi_c, best = c
                    mid = (lo_c + hi_c) // 2
                    s, tm = attempt(mid)
                    best = jax.tree.map(
                        lambda a, b: jnp.where(s, a, b), tm, best)
                    return (jnp.where(s, lo_c, mid),
                            jnp.where(s, mid, hi_c), best)

                def run_bisect(_):
                    _, _, best = lax.while_loop(bcond, bbody,
                                                (lo, hi, t_hi))
                    return jnp.asarray(True), best

                return lax.cond(s_hi, run_bisect,
                                lambda _: (jnp.asarray(False), empty),
                                None)

            return lax.cond(s_lo, lambda _: (jnp.asarray(True), t_lo),
                            refine, None)

    success, (free2, dev2, qa2, qan2, nodes_t, dev_t, pipe_t, moves,
              extra2, extra_dev2, ext2, ext_extra2, k_win) = lax.cond(
                  can_search, search,
                  lambda _: (jnp.asarray(False), empty), None)

    victim_mask = cand & (unit_rank <= k_win) & success
    return (success, victim_mask, nodes_t, dev_t, pipe_t, moves,
            free2, dev2, extra2, extra_dev2, qa2, qan2, ext2, ext_extra2)


def _replace_victims(state: ClusterState, mask: jax.Array, free: jax.Array,
                     device_free: jax.Array, releasing: jax.Array,
                     device_releasing: jax.Array,
                     ext_free: jax.Array, ext_releasing: jax.Array,
                     max_pods: int):
    """Greedy re-placement of evicted consolidation victims — the
    ``allPodsReallocated`` validator (``consolidation.go:115-120``): the
    scenario is valid only if *every* victim fits somewhere on the
    post-preemptor state.  Feasibility = resources + extended (MIG)
    scalars + the pod's node-filter class (taints/affinity); binpack by
    least free accel.  Moves may draw on releasing capacity (including
    other victims' freed spots) — they are always pipelined rebinds,
    waiting for the old pods to vacate.

    The loop runs over the (bounded) victim set, not the whole pod axis —
    an M-length device loop at 50k running pods faults the TPU.  A
    scenario with more than ``max_pods`` victims is rejected
    (``all_ok=False``), mirroring MaxNumberConsolidationPreemptees-style
    caps; the cap comes from ``VictimConfig.max_victim_pods`` (one knob
    for every call site).

    Returns (free' [N, R], device_free' [N, D], extended_free' [N, E],
    moves [M] i32 node per victim, all_ok [])."""
    r, n = state.running, state.nodes
    M = r.m
    D = n.d
    K = max(1, min(M, max_pods))
    n_vic = jnp.sum(mask.astype(jnp.int32))
    idxs = jnp.nonzero(mask, size=K, fill_value=0)[0]          # [K]
    kvalid = jnp.arange(K) < n_vic

    def body(kk, carry):
        free_l, dev_l, ext_l, moves, all_ok = carry
        m = idxs[kk]
        needed = kvalid[kk] & mask[m]
        req = r.req[m]
        is_frac = r.device[m] >= 0
        # memory-based portions are node-relative: recompute for every
        # candidate target (a 40GiB share is 0.5 of an 80GiB device but
        # 2.5 of a 16GiB one)
        p_n = jnp.where(
            r.accel_mem[m] > 0,
            r.accel_mem[m] / jnp.maximum(n.device_memory_gib, EPS),
            r.accel_held[m])                                   # [N]
        avail = free_l + releasing
        dev_avail = dev_l + device_releasing
        fit = (jnp.all(avail + EPS >= req[None, :], axis=-1) & n.valid
               & n.filter_masks[r.filter_class[m]])
        # extended (MIG) scalars the victim holds must fit the target too
        ext_req = r.extended[m]                                # [E]
        fit &= jnp.all(ext_l + ext_releasing + EPS >= ext_req[None, :],
                       axis=-1)
        frac_fit = jnp.max(dev_avail, axis=-1) >= p_n - EPS
        whole_free = jnp.sum((dev_avail >= 1.0 - EPS).astype(free_l.dtype),
                             axis=-1)
        whole_fit = whole_free + EPS >= req[0]
        fit = fit & jnp.where(is_frac, frac_fit, whole_fit)
        score = jnp.where(fit, -avail[:, 0], -jnp.inf)
        node = jnp.argmax(score)
        placed = needed & jnp.any(fit)
        p = p_n[node]
        delta = jnp.where(placed, req, 0.0)
        delta = delta.at[0].set(
            jnp.where(placed, jnp.where(is_frac, p, req[0]), 0.0))
        free_l = free_l.at[node].add(-delta)
        ext_l = ext_l.at[node].add(-jnp.where(placed, ext_req, 0.0))
        # device debit: fraction joins its best-fitting device; whole
        # takes the first fully-free devices
        dev_row = dev_avail[node]
        frac_dev = jnp.argmax(dev_row)
        k = jnp.round(req[0]).astype(jnp.int32)
        fully = dev_row >= 1.0 - EPS
        take = fully & (jnp.cumsum(fully.astype(jnp.int32)) <= k)
        dev_delta = jnp.where(
            is_frac, p * (jnp.arange(D) == frac_dev),
            take.astype(dev_row.dtype))
        dev_l = dev_l.at[node].add(-jnp.where(placed, dev_delta, 0.0))
        # junk iterations (kk >= n_vic gather the fill index 0) must NOT
        # touch pod 0's recorded move — an unconditional set clobbered a
        # real victim's rebind target back to -1, shipping its eviction
        # without the pipelined re-placement (caught by the scenario
        # catalog's MIG consolidation case)
        moves = moves.at[m].set(
            jnp.where(needed, jnp.where(placed, node, -1), moves[m]))
        all_ok = all_ok & (~needed | placed)
        return free_l, dev_l, ext_l, moves, all_ok

    free2, dev2, ext2, moves, all_ok = lax.fori_loop(
        0, K, body,
        (free, device_free, ext_free,
         jnp.full((M,), -1, jnp.int32), n_vic <= K))
    return free2, dev2, ext2, moves, all_ok


def _freed_by_lane(state: ClusterState, lane: jax.Array, B: int,
                   chain: jax.Array, *, compose: bool = True,
                   track_devices: bool = True, extended: bool = True):
    """Per-lane freed tensors from a pod→lane assignment.

    ``lane`` [M] gives each pod the FIRST wavefront lane that consumes
    it (``B`` = not consumed this chunk).  With ``compose=True`` lane
    ``b``'s pool is the union of lanes ``<= b``: every per-lane prefix
    is a cumsum of per-lane sums — ONE segment_sum over the pod axis
    instead of a vmapped scatter per lane (vmapped scatters dominate
    the chunk cost on TPU).  With ``compose=False`` (the sparse
    preempt wavefront) each lane's pool is its OWN assignment only and
    the lane-prefix cumsum over the dense [B, N, *] tensors is skipped
    entirely — composition is re-verified later on sparse (node, delta)
    segments at the chunk's claim sites.

    The device and extended tables are built only when the placement
    config tracks them: a snapshot without fractional or MIG pods frees
    nothing there, and the dense [B, N, D] table is the single biggest
    HBM tensor of a chunk.

    Returns (freed_nodes [B,N,R], freed_dev [B,N,D] | None,
    freed_queues [B,Q,R], freed_ext [B,N,E] | None, own_incr [B,N] —
    nodes where lane b's OWN assignment freed capacity, the
    W_OWN_FREED score-bias input).
    """
    r, n, q = state.running, state.nodes, state.queues
    N, D, Q = n.n, n.d, q.q
    live = lane < B
    lane_s = jnp.where(live, lane, B)
    req_m = jnp.where(live[:, None], r.req, 0.0)
    node_s = jnp.where(live, jnp.maximum(r.node, 0), N)
    seg_n = lane_s * (N + 1) + node_s
    per_n = jax.ops.segment_sum(
        req_m, seg_n, num_segments=(B + 1) * (N + 1))
    own_n = per_n.reshape(B + 1, N + 1, -1)[:B, :N]            # [B, N, R]
    freed_n = jnp.cumsum(own_n, axis=0) if compose else own_n
    freed_d = None
    if track_devices:
        frac = live & (r.device >= 0)
        seg_d = (jnp.where(frac, lane_s, B) * (N * D + 1)
                 + jnp.where(frac, node_s * D + jnp.maximum(r.device, 0),
                             N * D))
        per_d = jax.ops.segment_sum(
            jnp.where(frac, r.accel_held, 0.0), seg_d,
            num_segments=(B + 1) * (N * D + 1))
        per_d = per_d.reshape(B + 1, N * D + 1)[:B, :N * D].reshape(
            B, N, D)
        bits = ((r.devices_mask[:, None] >> jnp.arange(D)[None, :]) & 1)
        whole = bits.astype(req_m.dtype) * (live & (r.device < 0))[:, None]
        per_w = jax.ops.segment_sum(
            whole, seg_n, num_segments=(B + 1) * (N + 1))
        own_d = per_d + per_w.reshape(B + 1, N + 1, D)[:B, :N]
        freed_d = jnp.cumsum(own_d, axis=0) if compose else own_d
    seg_q = lane_s * (Q + 1) + jnp.where(live, jnp.maximum(r.queue, 0), Q)
    per_q = jax.ops.segment_sum(
        req_m, seg_q, num_segments=(B + 1) * (Q + 1))
    leaf_own = per_q.reshape(B + 1, Q + 1, -1)[:B, :Q]         # [B, Q, R]
    leaf_cum = jnp.cumsum(leaf_own, axis=0) if compose else leaf_own
    freed_q = einsum_exact("qa,bqr->bar", chain.astype(req_m.dtype),
                           leaf_cum)
    freed_e = None
    if extended:
        per_e = jax.ops.segment_sum(
            jnp.where(live[:, None], r.extended, 0.0), seg_n,
            num_segments=(B + 1) * (N + 1))
        own_e = per_e.reshape(B + 1, N + 1, -1)[:B, :N]
        freed_e = jnp.cumsum(own_e, axis=0) if compose else own_e
    own_incr = jnp.sum(own_n, axis=-1) > EPS                   # [B, N]
    return freed_n, freed_d, freed_q, freed_e, own_incr


def _sparse_preempt_ok(config: VictimConfig) -> bool:
    """Static gate of the sparse/optimistic preempt wavefront — the
    same structural conditions as the allocate chunk's sparse protocol
    (lanes emit placements only; a placement's claim is exactly its
    gang's uniform replica request), which is also exactly when the
    per-lane pools can skip the dense composition: uniform tasks, no
    device table, no extended resources, no subgroup topology.
    ``VictimConfig.optimistic_preempt=False`` forces the dense path;
    ``True``/``None`` still require the structural conditions."""
    p = config.placement
    ok = (p.uniform_tasks and not p.track_devices and not p.extended
          and not p.subgroup_topology)
    if config.optimistic_preempt is not None:
        ok = ok and config.optimistic_preempt
    return ok


#: ``AllocationResult.wavefront_stats`` row per chunked action
_STATS_ROW = {"reclaim": 0, "preempt": 1}


def _run_victim_action_chunked(
    state: ClusterState,
    fair_share: jax.Array,
    result: AllocationResult,
    *,
    num_levels: int,
    mode: str,                   # "reclaim" | "preempt"
    config: VictimConfig,
    remaining0: jax.Array,       # bool [G] viability-prefiltered
    chain: jax.Array,
    statics,
    job_rank: jax.Array,
    lq_tab: jax.Array | None,
    cnt_q: jax.Array,
    task_req_g: jax.Array,
) -> AllocationResult:
    """Wavefront victim search: B preemptors per iteration, in frozen
    fairness order, with EXACT per-lane own-queue exclusion.

    The sequential scan's per-step cost is dominated by fixed per-
    preemptor machinery, so latency ∝ steps; on the target hardware a
    loop iteration's cost is ∝ its op count, so everything preemptor-
    independent is hoisted OUT of the loop:

    - the eviction-unit order is frozen once per action.  It is stable
      under per-queue prefix consumption (consuming a prefix of a
      queue's units and re-ranking yields the identical suffix), so the
      per-chunk consumed state is just a per-queue pointer ``c [Q]``
      over the frozen global rank space.
    - all per-unit tables are built once: the units' requests, and
      every queue's units as one rank-ordered SEGMENT of a sorted unit
      axis with its running freed sums — a segment per leaf (``[U]``
      rows) and, for reclaim's strategy bounds, one per subtree
      (``[U * num_levels]`` rows; ``ops/unit_segments.py``).  Chunks
      probe them with binary searches inside a segment and gathers;
      nothing has a column per queue.
    - the preemptor order is frozen once (``job_order_perm`` at action
      start) — the fairness interleaving across queues is baked into
      the order; within a queue the job keys are static anyway.

    Each chunk takes the first B remaining gangs in frozen order (for
    preempt, the first B of the head gang's queue — preempt budgets and
    consumption are own-queue-local, so its lanes must share one
    queue).  Lane
    ``b`` gets a nondecreasing global-rank budget ``K_b`` — the
    smallest rank whose cumulative freed capacity, EXCLUDING lane b's
    own queue (reclaim; own-queue ONLY for preempt), covers the chunk's
    cumulative request — and always covers at least one new unit (the
    scenario builder never yields an empty victim set).  A pod is
    consumed by the first lane whose budget covers it AND whose queue
    may evict it, so a unit skipped by its own queue's lane flows to
    the next other-queue lane instead of being lost — no range-
    collision retirement (the round-3 advisor finding).  Placements
    run vmapped against chunk-start state with a score bias toward the
    lane's own freed nodes (the sequential solver implicitly places
    each preemptor onto its own victims' capacity), and an allocate-
    style strict accept-prefix re-verifies the composed capacity,
    queue-cap and fair-share gates.  Per-pair reclaim-minruntime
    snapshots use the sequential path (``VictimConfig.chunk_reclaim``).

    SPARSE LANE WAVEFRONT (preempt, ``_sparse_preempt_ok``): preempt
    victims are same-queue only, so lanes from distinct queues share
    nothing but node free capacity, and the problem is queue-disjoint
    by construction.  The sparse path exploits that structure:

    - the per-leaf segments (and the composed path's [B, R, U]
      per-chunk lane columns) shrink to compact per-queue top-K unit
      tables ``Cq [Q, KU, R]`` / ``pos_c [Q, KU+1]`` / ``prio_c
      [Q, KU]`` probed with tiny searchsorteds;
    - every lane solves OPTIMISTICALLY against its OWN queue's freed
      capacity only (``_freed_by_lane(compose=False)``) — no [B, N, *]
      lane-prefix cumsum is ever materialized;
    - lanes emit placements only (the allocate chunk's sparse
      protocol, ``sparse_out=True``) and composed node capacity is
      re-verified on sparse (node, delta) segments: claim entries sort
      by node, each entry checks its node-cumulative demand against
      chunk-start capacity PLUS the lane-prefix of the sparse freed
      deltas gathered at the claim sites (``sparse_entry_tables``) —
      node-capacity over-subscription between lanes surfaces as a
      first-bad-lane, the non-conflicting prefix commits in frozen
      fairness order, and the conflicted tail retries next chunk where
      the leading lane's inputs compose exactly;
    - only the LEADING valid lane's gate/placement failure is final
      (a later lane may have failed merely because the optimistic solve
      hid earlier lanes' freed capacity from it);
    - the deficit direction of that hiding is caught by the sparse
      accept (over-subscription), and the SURPLUS direction by LEFTOVER
      DEMOTION (both preempt paths): a committing lane whose victims
      free more than its claims consume exposes net capacity the
      sequential scan would offer every later preemptor, so every lane
      after the first such lane conflict-retries and re-runs as the
      leading lane of the next chunk, where inputs compose exactly.
      The leading lane also solves WITHOUT the ``W_OWN_FREED`` score
      band (a de-collision heuristic with no sequential counterpart
      that outranks the density band), making its solve
      reference-exact.  Demotions are counted in ``wavefront_stats``
      (``kai_victim_wavefront_leftover_demotions``).

    An action whose frozen unit order gives any queue more candidate
    units than ``VictimConfig.sparse_unit_k`` falls back to the composed
    path over the full segments at run time (one ``lax.cond``, counted
    in ``wavefront_stats`` — the incremental engine's auto-fallback
    pattern); snapshots whose shape rejects the sparse placement
    protocol (devices / extended / subgroup topology / non-uniform
    gangs) take the composed path statically.  Reclaim always runs on
    the segments: it prices a lane against EVERY other queue's units in
    the one global rank order and bounds it by a subtree sum, so there
    is no per-queue ``K`` it could cut at.

    Remaining deviations from the reference's one-preemptor-at-a-time
    walk, all chunk-granular: the preemptor and victim-job orders are
    frozen per action, and a lane's budget ignores units of its own
    queue freed by earlier lanes of the same chunk (bounded
    over-eviction, re-synced next chunk).
    """
    reclaim = mode == "reclaim"
    g, q, n, r = state.gangs, state.queues, state.nodes, state.running
    G, T, M, Q = g.g, g.t, r.m, q.q
    R_ = n.free.shape[1]
    bs = (config.batch_size_preempt
          if mode == "preempt" and config.batch_size_preempt is not None
          else config.batch_size)
    B = max(1, min(bs, G))
    total = state.total_capacity
    pcfg = config.placement
    track_dev = pcfg.track_devices
    track_ext = pcfg.extended
    depth = (config.queue_depth_preempt
             if mode == "preempt" and config.queue_depth_preempt is not None
             else config.queue_depth)
    base0, gang_runtime, pod_order = statics
    quota_eff_q = jnp.where(q.quota <= UNLIMITED + 0.5, jnp.inf, q.quota)
    limit_eff_q = jnp.where(q.limit <= UNLIMITED + 0.5, jnp.inf, q.limit)
    gq = jnp.maximum(g.queue, 0)
    chain_f = chain.astype(jnp.float32)
    ROW = _STATS_ROW[mode]
    # minruntime protection: preempt's resolved value is victim-side only
    # (lane-independent); chunked reclaim is gated on no reclaim
    # minruntime, so zeros there
    if reclaim:
        protected = jnp.zeros((G,), bool)
    else:
        mrt_g = q.preempt_min_runtime_eff[gq]
        protected = (gang_runtime >= 0) & (gang_runtime < mrt_g)
    gang_prio_pod = g.priority[jnp.maximum(r.gang, 0)]          # [M]
    anti = pcfg.anti_groups
    if anti:
        dom_static, _TA = anti_domain_tables(state)

    # ---- hoisted: frozen eviction-unit order + per-unit inputs ----------
    cand0 = base0 & ~result.victim                               # [M]
    removed0 = result.victim & (result.victim_move < 0)
    unit_rank, num_units = _rank_eviction_units(
        state, cand0, result.queue_allocated, fair_share, removed0,
        protected, pod_order, job_rank)
    urank_safe = jnp.minimum(unit_rank, M)
    unit_req = jax.ops.segment_sum(
        jnp.where(cand0[:, None], r.req, 0.0), urank_safe,
        num_segments=M + 1)[:M]                                  # [U, R]
    unit_leaf = jax.ops.segment_max(
        jnp.where(cand0, r.queue, -1), urank_safe,
        num_segments=M + 1)[:M]                                  # [U]
    leaf_safe = jnp.maximum(unit_leaf, 0)
    has_leaf = unit_leaf >= 0
    if reclaim:
        C_all_t = cumsum_ds(unit_req.T, axis=1)        # [R, U] inclusive
        unit_prio = None
    else:
        C_all_t = None
        unit_prio = jax.ops.segment_max(
            jnp.where(cand0, gang_prio_pod, -BIG), urank_safe,
            num_segments=M + 1)[:M].astype(jnp.float32)          # [U]

    # ---- hoisted: frozen preemptor order ---------------------------------
    order0 = ordering.job_order_perm(
        g, q, result.queue_allocated, fair_share, total, remaining0)

    lanes = jnp.arange(B, dtype=jnp.int32)
    qidx = jnp.arange(Q)
    pod_leaf = jnp.clip(r.queue, 0, Q - 1)                       # [M]

    sparse_able = (not reclaim) and _sparse_preempt_ok(config)
    # an explicit sparse_unit_k is honored as-is (the documented way to
    # bound table memory or force the dense fallback for debugging);
    # only the non-Session default is floored
    KU = (max(1, int(config.sparse_unit_k))
          if config.sparse_unit_k is not None else 256)

    def make_run(sparse: bool, fell_back: bool):
        """Build one flavor of the chunk loop.  The per-mode hoisted
        tables live INSIDE the closure so the un-taken ``lax.cond``
        branch never materializes the other flavor's tensors."""

        if sparse:
            # compact per-queue unit tables — the first KU rows of every
            # leaf segment, as a [Q, KU] grid.  Each unit's ordinal within
            # its queue comes from one stable [M] argsort (rank order is
            # preserved within a queue), then tiny [Q, KU] scatters.
            perm_u, lk_p = unit_segments.leaf_order(unit_leaf, Q)
            first_u = jnp.concatenate(
                [jnp.ones((1,), bool), lk_p[1:] != lk_p[:-1]])
            seg_start = jax.lax.associative_scan(
                jnp.maximum, jnp.where(first_u, jnp.arange(M), -1))
            r_p = (jnp.arange(M) - seg_start).astype(jnp.int32)
            r_in_q = jnp.zeros((M,), jnp.int32).at[perm_u].set(r_p)
            # pos_c[q, j] = global unit rank of queue q's j-th unit; the
            # KU column (and every missing slot) is the junk rank M —
            # ordinal overflow clamps there, which the action-level
            # overflow cond has already excluded
            rk = jnp.minimum(r_in_q, KU)
            pos_c = jnp.full((Q + 1, KU + 1), M, jnp.int32).at[
                jnp.where(has_leaf, leaf_safe, Q),
                jnp.where(has_leaf, rk, KU)].set(
                jnp.where(has_leaf & (r_in_q < KU),
                          jnp.arange(M, dtype=jnp.int32), M))[:Q]
            pos_k = pos_c[:, :KU]                                # [Q, KU]
            valid_pos = pos_k < M
            pos_safe = jnp.minimum(pos_k, M - 1)
            # per-queue inclusive cumulative unit requests / priorities
            Cq = cumsum_ds(jnp.where(valid_pos[..., None],
                                     unit_req[pos_safe], 0.0),
                           axis=1)                               # [Q, KU, R]
            prio_c = jnp.where(valid_pos, unit_prio[pos_safe],
                               jnp.float32(1e30))                # [Q, KU]
        else:
            # every queue's units as one rank-ordered segment of a
            # sorted [U] axis, with their running sums (unit_segments):
            # U rows, never a column per queue
            leaf = unit_segments.leaf_segments(unit_leaf, unit_req, Q)
            own_cum_t = unit_segments.rank_order_cum(leaf)       # [R, U]
            if reclaim:
                # subtree segments: the strategy bounds' per-ancestor
                # cumulative freed, U * num_levels rows
                sub = unit_segments.subtree_segments(
                    unit_leaf, unit_req, q.parent, num_levels)
            else:
                prio_seg = unit_prio[leaf.pos]                   # [U]

        def chunk(carry):
            res, remaining, c, q_att, fuel = carry
            free, dev = res.free, res.device_free
            qa = res.queue_allocated
            qan = res.queue_allocated_nonpreemptible
            extra = res.releasing_extra
            extra_dev = res.device_releasing_extra
            ext = res.extended_free
            ext_extra = res.extended_releasing_extra

            # ---- lanes: first B remaining gangs in frozen order ---------
            # (any queue mix: preempt's own-queue-local budgets/
            # consumption are kept exact by QUEUE-SEGMENTED cumulative
            # pricing, unit ranks, watermarks and pointers below — a
            # 256-preemptor burst in one queue packs B lanes per chunk
            # like the single-queue code always did, AND 512 queues × 1
            # preemptor each share chunks instead of degrading to one
            # queue per chunk)
            flags = remaining[order0]                            # [G]
            rnk = jnp.cumsum(flags.astype(jnp.int32)) - 1
            pos = jnp.where(flags & (rnk < B), rnk, B)
            cand_g = jnp.full((B + 1,), G, jnp.int32).at[pos].set(
                order0)[:B]
            cand_valid = jnp.zeros((B + 1,), bool).at[pos].set(True)[:B]
            gsafe_b = jnp.minimum(cand_g, G - 1)
            q_b = gq[gsafe_b]                                    # [B]
            # lanes of the same queue (preempt's segmented per-queue math)
            same_q_b = (q_b[None, :] == q_b[:, None])            # [B, B]

            # ---- lane budgets over the frozen unit order ----------------
            lane_req = jnp.where(cand_valid[:, None],
                                 task_req_g[gsafe_b], 0.0)       # [B, R]
            cluster_free = jnp.sum(
                jnp.where(n.valid[:, None],
                          free + n.releasing + extra, 0.0),
                axis=0)
            if reclaim:
                cum_req = jnp.cumsum(lane_req, axis=0)
                targets = cum_req - cluster_free[None, :] - EPS  # [B, R]
            else:
                # QUEUE-SEGMENTED cumulative pricing: a lane's target is
                # the cumulative request of its OWN queue's lanes so far
                # (its victims can only come from there), optimistically
                # assuming the whole idle pool (queues double-counting
                # free under-evict, which the accept prefix rejects and
                # the lane retries next chunk — over-eviction never
                # happens).  For a single-queue chunk this is exactly
                # the full cumulative.
                seg_incl = (same_q_b & (lanes[None, :] <= lanes[:, None])
                            & cand_valid[None, :])               # [B, B]
                cum_req_q = einsum_exact(
                    "bc,cr->br", seg_incl.astype(lane_req.dtype), lane_req)
                targets = cum_req_q - cluster_free[None, :] - EPS
            need_b = cand_valid & jnp.any(targets > 0, axis=-1)
            if sparse:
                # probe the compact per-queue tables: own-queue consumed
                # base at the pointer, then a [KU]-searchsorted per
                # (lane, resource) instead of the dense [B, U, R] gather
                j_c = jax.vmap(
                    lambda row, cv: jnp.searchsorted(
                        row, cv, side="right"))(pos_k, c)        # [Q]
                Cv_c = jnp.where(
                    (j_c > 0)[:, None],
                    Cq[qidx, jnp.maximum(j_c - 1, 0)], 0.0)      # [Q, R]
                base_b = Cv_c[q_b]                               # [B, R]
                v_b = targets + base_b
                pos_full_b = pos_c[q_b]                          # [B, KU+1]
                j_rb = jax.vmap(jax.vmap(jnp.searchsorted,
                                         in_axes=(1, 0)))(
                    Cq[q_b], v_b)                                # [B, R]
                # a non-positive target is already covered by rank 0
                # (the dense searchsorted's answer on the step function)
                k_rb = jnp.where(
                    v_b > 0,
                    jnp.take_along_axis(pos_full_b,
                                        jnp.minimum(j_rb, KU), axis=1),
                    0)
            else:
                Cv_at_c = unit_segments.sum_through(leaf, c)     # [Q, R]
                # the lanes' own-queue columns, built per chunk from
                # the segment sums
                mine_b = unit_leaf[None, :] == q_b[:, None]      # [B, U]
                arr_b = unit_segments.lane_columns(own_cum_t, mine_b)
                if reclaim:
                    arr_b = C_all_t[None] - arr_b                # [B, R, U]
                    base_b = (jnp.sum(Cv_at_c, axis=0)[None, :]
                              - Cv_at_c[q_b])                    # [B, R]
                else:
                    base_b = Cv_at_c[q_b]
                k_rb = jax.vmap(jax.vmap(jnp.searchsorted))(
                    arr_b, targets + base_b)                     # [B, R]
            K_cap = jnp.where(need_b, jnp.max(k_rb, axis=1), -1
                              ).astype(jnp.int32)                # [B]
            # a victim scenario always contains >= 1 NEW eviction unit
            # (the sequential search's smallest scenario is unit-prefix
            # 0 — the scenario builder never yields an empty victim
            # set): lane b consumes at least the (b+1)-th unit still
            # available TO IT
            if reclaim:
                vrank = jnp.cumsum(cand_valid.astype(jnp.int32)) - 1  # [B]
            else:
                # ordinal among the lane's OWN queue's valid lanes: the
                # (k+1)-th same-queue lane needs k+1 available own units
                vrank = jnp.sum(
                    same_q_b & (lanes[None, :] < lanes[:, None])
                    & cand_valid[None, :], axis=1).astype(jnp.int32)
            if sparse:
                av_c = (valid_pos & (pos_k < num_units)
                        & (pos_k > c[:, None]))                  # [Q, KU]
                cav = jnp.cumsum(av_c.astype(jnp.int32), axis=1)
                j_min = jax.vmap(jnp.searchsorted)(cav[q_b], vrank + 1)
                K_min = jnp.take_along_axis(
                    pos_full_b, jnp.minimum(j_min, KU)[:, None],
                    axis=1)[:, 0].astype(jnp.int32)              # [B]
            else:
                avail_u = (has_leaf & (jnp.arange(M) < num_units)
                           & (jnp.arange(M)
                              > c[jnp.clip(unit_leaf, 0, Q - 1)]))
                # available units a lane may take: every queue's but
                # its own (reclaim) / its own queue's only (preempt)
                cum_av_b = unit_segments.lane_available(
                    avail_u, ~mine_b if reclaim else mine_b)     # [B, U]
                K_min = jax.vmap(jnp.searchsorted)(
                    cum_av_b, vrank + 1).astype(jnp.int32)       # [B]
            K_raw = jnp.where(cand_valid, jnp.maximum(K_cap, K_min), -1)
            K_b = jax.lax.associative_scan(jnp.maximum, K_raw)   # sorted
            insufficient_b = cand_valid & (K_raw >= num_units)

            # ---- strategy / priority admissibility bound ----------------
            if reclaim:
                # FitsReclaimStrategy, probed on the hoisted subtree
                # cumulative: unit u passes while its leveled queue's
                # remaining share BEFORE u (live qa corrected by the
                # already-consumed rollup S_cons) stays above fair share
                # — or above deserved quota when the reclaimer is under
                # its own quota.
                S_cons = einsum_exact("va,vr->ar", chain_f,
                                      Cv_at_c)               # [Q, R]
                thr_fs = qa - fair_share - EPS + S_cons          # [Q, R]
                thr_qt = (jnp.where(jnp.isinf(quota_eff_q), -jnp.inf,
                                    qa - quota_eff_q - EPS)
                          + S_cons)
                bnd_fs, bnd_qt = jnp.max(unit_segments.subtree_bound(
                    sub, jnp.stack([thr_fs, thr_qt]), M), axis=-1)  # [Q]
                under_b = jax.vmap(
                    lambda qi, tr: _ancestor_gate(
                        q.parent, qi, num_levels, qa, q.quota, tr))(
                            q_b, lane_req)
                bnd_eff = jnp.where(
                    under_b[None, :],
                    jnp.maximum(bnd_fs, bnd_qt)[:, None],
                    bnd_fs[:, None])                             # [Q, B]
                lq_vb = lq_tab[:, q_b]                           # [Q, B]
                x_vb = jnp.clip(jnp.take_along_axis(
                    bnd_eff, jnp.clip(lq_vb, 0, Q - 1), axis=0), 0, M)
                first_bad_vb = unit_segments.first_at_or_after(
                    leaf, x_vb, M)                               # [Q, B]
                first_bad_vb = jnp.where(lq_vb >= 0, first_bad_vb, M)
                hi_b = jnp.minimum(jnp.min(first_bad_vb, axis=0),
                                   num_units) - 1                # [B]
            elif sparse:
                # victim units are priority-ascending within the queue;
                # a lane may only consume own-queue units strictly below
                # its priority — probed on the compact table
                allowed = jax.vmap(jnp.searchsorted)(
                    prio_c[q_b],
                    g.priority[gsafe_b].astype(jnp.float32))     # [B]
                hi_b = jnp.take_along_axis(
                    pos_full_b, jnp.clip(allowed, 0, KU)[:, None],
                    axis=1)[:, 0] - 1
                hi_b = jnp.where(allowed > 0, hi_b, -1)
            else:
                # victim units are priority-ascending within the queue; a
                # lane may only consume own-queue units strictly below its
                # priority
                allowed, first_not = unit_segments.first_not_below(
                    leaf, prio_seg, q_b,
                    g.priority[gsafe_b].astype(jnp.float32), M)  # [B]
                hi_b = jnp.where(allowed > 0, first_not - 1, -1)

            # ---- lane gates ---------------------------------------------
            nonpre_b = ~g.preemptible[gsafe_b]
            gate_np_b = jax.vmap(
                lambda qi, tr: _ancestor_gate(
                    q.parent, qi, num_levels, qan, q.quota, tr))(
                        q_b, lane_req)
            gate_b = jnp.where(nonpre_b, gate_np_b, True)
            gate_b &= cand_valid & (K_raw <= hi_b) & ~insufficient_b

            # ---- pod → lane assignment + per-lane freed pools -----------
            live0 = cand0 & (unit_rank > c[pod_leaf])
            if reclaim:
                # first lane whose budget covers the pod AND whose queue
                # may evict it: a unit skipped by its own queue's lane
                # flows to the next other-queue lane instead of being
                # lost
                may = q_b[None, :] != jnp.arange(Q)[:, None]     # [Q, B]
                may = may & cand_valid[None, :]
                nxt = jnp.where(may, lanes[None, :], B)          # [Q, B]
                next_ok = jnp.flip(jax.lax.associative_scan(
                    jnp.minimum, jnp.flip(nxt, axis=1), axis=1),
                    axis=1)                                      # [Q, B]
                next_ok = jnp.concatenate(
                    [next_ok, jnp.full((Q, 1), B, jnp.int32)],
                    axis=1)                                      # [Q, B+1]
                lane0 = jnp.searchsorted(K_b, unit_rank)         # [M] 0..B
                lane_of_pod = jnp.where(
                    live0, next_ok[pod_leaf, jnp.minimum(lane0, B)], B)
            else:
                # PER-QUEUE running-max watermark: a unit flows to the
                # first same-queue lane whose watermark covers its rank
                # (exactly the old single-queue assignment, segmented
                # per queue — no cross-queue leak).  [M, B] compare-and-
                # min; B is small.
                K_wm = jnp.max(jnp.where(
                    same_q_b & (lanes[None, :] <= lanes[:, None])
                    & cand_valid[None, :], K_raw[None, :], -1),
                    axis=1)                                      # [B]
                cand_lane = ((pod_leaf[:, None] == q_b[None, :])
                             & cand_valid[None, :]
                             & (K_wm[None, :] >= urank_safe[:, None]))
                lane_of_pod = jnp.where(
                    live0,
                    jnp.min(jnp.where(cand_lane, lanes[None, :], B),
                            axis=1), B)
            (freed_n_b, freed_d_b, freed_q_b, freed_e_b,
             own_incr_b) = _freed_by_lane(
                state, lane_of_pod, B, chain, compose=not sparse,
                track_devices=track_dev, extended=track_ext)
            extra_b = extra[None] + freed_n_b                    # [B, N, R]
            if track_dev:
                extra_dev_b = extra_dev[None] + freed_d_b
                dev_ax = 0
            else:
                extra_dev_b = extra_dev
                dev_ax = None
            if track_ext:
                ext_extra_b = ext_extra[None] + freed_e_b
                ext_ax = 0
            else:
                ext_extra_b = ext_extra
                ext_ax = None
            qa_eff_b = qa[None] - freed_q_b                      # [B, Q, R]
            if reclaim:
                # CanReclaimResources against the POST-SCENARIO state
                # (the lane's own victim credit applied): a dept at its
                # full fair share can still reclaim within itself
                gate_b &= jax.vmap(
                    lambda qi, tr, qae: _ancestor_gate(
                        q.parent, qi, num_levels, qae, fair_share, tr))(
                            q_b, lane_req, qa_eff_b)
            lead = cand_valid & (jnp.cumsum(
                cand_valid.astype(jnp.int32)) == 1)              # [B]
            bias_b = W_OWN_FREED * own_incr_b.astype(jnp.float32)  # [B, N]
            if not reclaim:
                # the LEADING valid lane's inputs compose exactly, so
                # its solve must be reference-exact: the own-freed band
                # is a cross-lane de-collision heuristic with no
                # sequential counterpart, and at 9.5 it outranks the
                # density band (max 9) — keeping it on the leading lane
                # flips placements the sequential scan scores purely by
                # density (e.g. toward an earlier preemptor's leftover
                # freed node)
                bias_b = jnp.where(lead[:, None], 0.0, bias_b)
            if anti:
                dmask_b = ~anti_forbid_nodes(state, res.anti_used,
                                             dom_static, cand_g)  # [B, N]
                dup_b = anti_defer_lanes(state, cand_g, cand_valid)
                if pcfg.attract_groups:
                    dmask_b = dmask_b & attract_allow_nodes(
                        state, res.anti_used, dom_static, cand_g)
                    dup_b = dup_b | attract_defer_lanes(
                        state, cand_g, cand_valid, res.anti_used)
            else:
                dmask_b = jnp.ones((B, n.n), bool)
                dup_b = jnp.zeros((B,), bool)
            if sparse:
                # lanes emit placements only (the allocate chunk's
                # sparse wavefront protocol) — no dense [B, N, R]
                # carries through the vmap
                (qa2_b, qan2_b, nodes_b, pipe_b, succ_b) = jax.vmap(
                    lambda gi, lane, ex_n, ex_d, ex_e, qae, sb, dm:
                        _attempt_gang(
                            state, gi, free, dev, qae, qan, num_levels,
                            pcfg, ex_n, ex_d, lane, chain, ext_free=ext,
                            extra_extended_releasing=ex_e, score_bias=sb,
                            domain_mask=dm, sparse_out=True),
                    in_axes=(0, 0, 0, dev_ax, ext_ax, 0, 0, 0))(
                    cand_g, lanes, extra_b, extra_dev_b, ext_extra_b,
                    qa_eff_b, bias_b, dmask_b)
                devt_b = jnp.full((B, T), -1, jnp.int32)
            else:
                (free2_b, dev2_b, qa2_b, qan2_b, nodes_b, devt_b, pipe_b,
                 succ_b, bind_b, devbind_b, ext2_b, extbind_b) = jax.vmap(
                    lambda gi, lane, ex_n, ex_d, ex_e, qae, sb, dm:
                        _attempt_gang(
                            state, gi, free, dev, qae, qan, num_levels,
                            pcfg, ex_n, ex_d, lane, chain, ext_free=ext,
                            extra_extended_releasing=ex_e, score_bias=sb,
                            domain_mask=dm),
                    in_axes=(0, 0, 0, dev_ax, ext_ax, 0, 0, 0))(
                    cand_g, lanes, extra_b, extra_dev_b, ext_extra_b,
                    qa_eff_b, bias_b, dmask_b)

            # an anti-deferred lane is CONFLICT-rejected (retries next
            # chunk against the updated claimed-domain table), never
            # terminal
            succ_b = succ_b & ~dup_b
            ok_pre = gate_b & succ_b                             # [B]
            okm = ok_pre[:, None, None]
            d_qa = jnp.where(okm, qa2_b - qa_eff_b, 0.0)
            d_qan = jnp.where(okm, qan2_b - qan[None], 0.0)
            cum_qa = jnp.cumsum(d_qa, axis=0)
            cum_qan = jnp.cumsum(d_qan, axis=0)

            if sparse:
                # sparse accept: claim entries sort by node; each entry
                # checks its node-cumulative demand against chunk-start
                # capacity plus the lane-prefix of the sparse freed
                # deltas gathered AT THE CLAIM SITES — the composed-
                # capacity test without any [B, N, R] cumsum
                req_b = g.task_req[gsafe_b, 0]                   # [B, R]
                ent_ok = ok_pre[:, None] & (nodes_b >= 0)        # [B, T]
                first_bad_cap, node_e, lane_e = sparse_accept_first_bad(
                    nodes_b, ent_ok, pipe_b, req_b, free,
                    free + n.releasing + extra, n.n,
                    credit=lambda lane_s, nsafe: jnp.cumsum(
                        freed_n_b[:, nsafe, :], axis=0)[
                        lane_s, jnp.arange(lane_s.shape[0])])
                accept = lanes < first_bad_cap                   # [B]
                qa_comp = (qa[None] - jnp.cumsum(freed_q_b, axis=0)
                           + cum_qa)                             # [B, Q, R]
                # per-lane NET leftover: freed capacity the lane's own
                # claims do not consume (freed_b - claims_b > 0 on any
                # node).  Uniform tasks make claims a per-node entry
                # count times the replica request — no dense [B, N, R]
                # claim grid beyond the own-freed table that already
                # exists.
                nsafe_bt = jnp.where(ent_ok, nodes_b, n.n)       # [B, T]
                cnt_bn = jnp.zeros((B, n.n + 1), req_b.dtype).at[
                    lanes[:, None], nsafe_bt].add(1.0)[:, :n.n]  # [B, N]
                leftover_b = jnp.any(
                    freed_n_b - cnt_bn[:, :, None] * req_b[:, None, :]
                    > EPS, axis=(1, 2))                          # [B]
            else:
                d_free = jnp.where(okm, free[None] - free2_b, 0.0)
                d_bind = jnp.where(okm, bind_b, 0.0)
                cum_free_d = jnp.cumsum(d_free, axis=0)
                cum_bind = jnp.cumsum(d_bind, axis=0)
                rel_floor_b = -(n.releasing[None] + extra_b) - EPS
                ok_node = jnp.all(free[None] - cum_free_d >= rel_floor_b,
                                  axis=(1, 2))
                ok_bind = jnp.all(
                    cum_bind <= jnp.maximum(free[None], 0.0) + EPS,
                    axis=(1, 2))
                accept = ok_node & ok_bind
                qa_comp = qa[None] - freed_q_b + cum_qa          # [B, Q, R]
                if not reclaim:
                    # per-lane NET leftover for the dense composed
                    # fallback: own freed is the lane-diff of the
                    # composed cumsum, claims are d_free — both already
                    # materialized here
                    own_n = freed_n_b - jnp.concatenate(
                        [jnp.zeros_like(freed_n_b[:1]), freed_n_b[:-1]])
                    leftover_b = jnp.any(own_n - d_free > EPS,
                                         axis=(1, 2))            # [B]
            ok_qa = jnp.all((qa_comp <= limit_eff_q[None] + EPS)
                            | (cum_qa <= EPS), axis=(1, 2))
            ok_qan = jnp.all((qan[None] + cum_qan
                              <= quota_eff_q[None] + EPS)
                             | (cum_qan <= EPS), axis=(1, 2))
            accept = accept & ok_qa & ok_qan
            if reclaim:
                chain_b = chain[q_b]                             # [B, Q]
                accept &= jnp.all(
                    (qa_comp <= fair_share[None] + EPS)
                    | ~chain_b[:, :, None], axis=(1, 2))
            if (not sparse) and pcfg.track_devices:
                d_dev = jnp.where(okm, dev[None] - dev2_b, 0.0)
                d_devbind = jnp.where(okm, devbind_b, 0.0)
                cum_dev = jnp.cumsum(d_dev, axis=0)
                if not reclaim:
                    own_d = freed_d_b - jnp.concatenate(
                        [jnp.zeros_like(freed_d_b[:1]), freed_d_b[:-1]])
                    leftover_b |= jnp.any(own_d - d_dev > EPS,
                                          axis=(1, 2))
                accept &= jnp.all(
                    dev[None] - cum_dev
                    >= -(n.device_releasing[None] + extra_dev_b) - EPS,
                    axis=(1, 2))
                accept &= jnp.all(
                    jnp.cumsum(d_devbind, axis=0)
                    <= jnp.maximum(dev[None], 0.0) + EPS, axis=(1, 2))
            if (not sparse) and pcfg.extended:
                d_ext = jnp.where(okm, ext[None] - ext2_b, 0.0)
                cum_ext = jnp.cumsum(d_ext, axis=0)
                if not reclaim:
                    own_e = freed_e_b - jnp.concatenate(
                        [jnp.zeros_like(freed_e_b[:1]), freed_e_b[:-1]])
                    leftover_b |= jnp.any(own_e - d_ext > EPS,
                                          axis=(1, 2))
                accept &= jnp.all(
                    ext[None] - cum_ext
                    >= -(n.extended_releasing[None] + ext_extra_b) - EPS,
                    axis=(1, 2))
                accept &= jnp.all(
                    jnp.cumsum(jnp.where(okm, extbind_b, 0.0), axis=0)
                    <= jnp.maximum(ext[None], 0.0) + EPS, axis=(1, 2))

            # ---- strict accept prefix -----------------------------------
            fail_own = cand_valid & ~(ok_pre & accept)           # [B]
            if reclaim:
                prev_lo = jnp.zeros((B,), bool)
            else:
                # LEFTOVER DEMOTION (preempt exactness): a committing
                # lane whose victims free MORE than its own claims
                # consume leaves net capacity the sequential scan would
                # expose to every later preemptor — but a later lane's
                # optimistic solve never saw it (sparse: own pool only;
                # dense: chunk-start free without earlier claims), so
                # its placement can silently diverge where the accept's
                # over-subscription check has nothing to catch.  Lanes
                # after the first accepted leftover-producing lane are
                # demoted to conflict-retry; next chunk they re-run as
                # the LEADING lane, where inputs compose exactly and
                # the solve is bias-free (reference-exact).  Leftover
                # is rare in the steady state (a preemptor lands on its
                # own victims' capacity and consumes it), so chunks
                # stay wide; the demotion count is exported per cycle.
                lo_i = (ok_pre & accept & leftover_b).astype(jnp.int32)
                prev_lo = (jnp.cumsum(lo_i) - lo_i) > 0
            bad = fail_own | (cand_valid & prev_lo)              # [B]
            bad_cum = jnp.cumsum(bad.astype(jnp.int32))
            take = cand_valid & (bad_cum == 0)                   # [B]
            demoted = cand_valid & prev_lo & ok_pre & accept     # [B]
            # Only a GATE/placement failure of the first bad lane is
            # final — its inputs composed exactly (every earlier valid
            # lane took), and own-queue exclusion is exact here, so the
            # failure is genuine (insufficient admissible victims,
            # capacity, or queue gates) — never a range artifact.  An
            # accept failure there is a cross-lane capacity CONFLICT:
            # the lane retries next chunk, where, as the leading lane,
            # its accept is self-consistent.
            #
            # TERMINATION INVARIANT (the fuel bound relies on it): every
            # chunk retires >=1 lane, because a LEADING valid lane's
            # accept is implied by ok_pre — each accept component (node
            # floors vs its own extra pool, bind vs chunk-start idle,
            # queue caps, the reclaim fair-share term) is already
            # enforced by gate_b/_attempt_gang when no earlier lane
            # contributed deltas.  If you add an accept-ONLY check, also
            # gate it in gate_b, or the loop can spin identical chunks
            # until fuel exhausts.
            first_bad = bad & ((bad_cum - bad.astype(jnp.int32)) == 0)
            if sparse:
                # the optimistic own-pool solve hides earlier lanes'
                # freed capacity: a non-leading lane's gate/placement
                # failure may be that artifact, so only the LEADING
                # valid lane (whose inputs compose exactly) fails
                # terminally — everything else conflict-retries
                first_fail = first_bad & ~ok_pre & ~dup_b & lead
            else:
                # a lane demoted by an earlier leftover had polluted
                # inputs — its failure is never terminal
                first_fail = first_bad & ~ok_pre & ~dup_b & ~prev_lo
            any_take = jnp.any(take)
            star = jnp.argmax(jnp.where(take, lanes, -1))
            victims = (lane_of_pod <= star) & any_take
            # per-queue consumed pointers: the max committed budget among
            # accepted lanes allowed to evict from that queue
            if reclaim:
                M_v = jnp.max(jnp.where(take[None, :] & may,
                                        K_b[None, :], -1), axis=1)  # [Q]
            else:
                # accepted lanes advance their OWN queue's pointer to
                # their per-queue watermark
                M_v = jax.ops.segment_max(
                    jnp.where(take & cand_valid, K_wm, -1),
                    jnp.where(cand_valid, q_b, Q),
                    num_segments=Q + 1)[:Q]
            c2 = jnp.maximum(c, M_v)

            w = take.astype(free.dtype)
            sel = lambda arr, base_v: jnp.where(any_take, arr[star],
                                                base_v)
            if sparse:
                # commits reconstruct capacity deltas from the sparse
                # entries (claims) and the per-lane own freed (pools) —
                # the union of accepted DISJOINT lanes is a plain sum
                take_e = take[lane_e] & ent_ok.ravel()
                upd = jnp.zeros((n.n + 1, R_), free.dtype).at[
                    node_e].add(
                    jnp.where(take_e[:, None], req_b[lane_e], 0.0),
                    mode="drop")
                new_free = free - upd[:n.n]
                new_extra = extra + einsum_exact("b,bnr->nr", w, freed_n_b)
                new_qa = (qa - einsum_exact("b,bqr->qr", w, freed_q_b)
                          + einsum_exact("b,bqr->qr", w, d_qa))
            else:
                new_free = free - einsum_exact("b,bnr->nr", w, d_free)
                new_extra = sel(extra_b, extra)
                new_qa = (sel(qa_eff_b, qa)
                          + einsum_exact("b,bqr->qr", w, d_qa))
            res = res.replace(
                free=new_free,
                device_free=(dev - einsum_exact(
                    "b,bnd->nd", w,
                    jnp.where(okm, dev[None] - dev2_b, 0.0))
                    if (not sparse) and pcfg.track_devices else dev),
                extended_free=(ext - einsum_exact(
                    "b,bne->ne", w,
                    jnp.where(okm, ext[None] - ext2_b, 0.0))
                    if (not sparse) and pcfg.extended else ext),
                releasing_extra=new_extra,
                device_releasing_extra=(sel(extra_dev_b, extra_dev)
                                        if track_dev else extra_dev),
                extended_releasing_extra=(sel(ext_extra_b, ext_extra)
                                          if track_ext else ext_extra),
                queue_allocated=new_qa,
                queue_allocated_nonpreemptible=(
                    qan + einsum_exact("b,bqr->qr", w, d_qan)),
                placements=res.placements.at[cand_g].set(
                    jnp.where(take[:, None], nodes_b,
                              res.placements[cand_g])),
                placement_device=res.placement_device.at[cand_g].set(
                    jnp.where(take[:, None], devt_b,
                              res.placement_device[cand_g])),
                pipelined=res.pipelined.at[cand_g].set(
                    jnp.where(take[:, None], pipe_b,
                              res.pipelined[cand_g])),
                allocated=res.allocated.at[cand_g].set(
                    res.allocated[cand_g] | take),
                attempted=res.attempted.at[cand_g].set(
                    res.attempted[cand_g] | take | first_fail),
                fit_reason=res.fit_reason.at[cand_g].set(
                    jnp.where(first_fail, 3, res.fit_reason[cand_g])),
                victim=res.victim | victims,
                wavefront_stats=res.wavefront_stats
                .at[ROW, 0].add(1)
                .at[ROW, 1].add(jnp.sum(cand_valid.astype(jnp.int32)))
                .at[ROW, 2].add(B)
                .at[ROW, 4].add(jnp.sum(demoted.astype(jnp.int32))),
            )
            if anti:
                res = res.replace(anti_used=anti_mark_placements(
                    state, res.anti_used, dom_static, cand_g,
                    jnp.where(take[:, None], nodes_b, -1), take))
            done_b = take | first_fail
            remaining = remaining.at[cand_g].set(
                remaining[cand_g] & ~done_b)
            if depth is not None:
                q_att = q_att + jax.ops.segment_sum(
                    done_b.astype(jnp.int32), q_b, num_segments=Q)
                remaining = remaining & (q_att[gq] < depth)
            if reclaim:
                # live strategy-viability drop (see the sequential path)
                qa_l = res.queue_allocated
                under_g = jax.vmap(
                    lambda qi, tr: _ancestor_gate(
                        q.parent, qi, num_levels, qa_l, q.quota, tr))(
                            gq, task_req_g)
                lqs2 = jnp.maximum(lq_tab, 0)
                no_lq = lq_tab < 0
                over_fs_vc = no_lq | jnp.any(
                    qa_l[lqs2] > fair_share[lqs2] + EPS, -1)
                over_qt_vc = no_lq | jnp.any(
                    qa_l[lqs2] > quota_eff_q[lqs2] + EPS, -1)
                diff = (qidx[:, None] != qidx[None, :])
                has_v = (cnt_q > 0)[:, None] & diff
                ev_fs_c = jnp.any(has_v & over_fs_vc, axis=0)
                ev_qt_c = jnp.any(has_v & over_qt_vc, axis=0)
                remaining = remaining & (
                    ev_fs_c[gq] | (under_g & ev_qt_c[gq]))
            return res, remaining, c2, q_att, fuel - 1

        def run(res0):
            if fell_back:
                # runtime overflow of the compact unit tables — counted
                # so the sparse-path fallback rate is observable
                res0 = res0.replace(
                    wavefront_stats=res0.wavefront_stats
                    .at[ROW, 3].add(1))
            with jax.named_scope("wavefront"):
                res, _, _, _, fuel_left = lax.while_loop(
                    lambda cr: jnp.any(cr[1]) & (cr[4] > 0), chunk,
                    (res0, remaining0, jnp.full((Q,), -1, jnp.int32),
                     jnp.zeros((Q,), jnp.int32),
                     jnp.asarray(G, jnp.int32)))
            if _DEBUG_CHUNKS:
                # stash the chunk count in the last fit_reason slot
                # (scratch diagnostics only — that slot is snapshot
                # padding in practice)
                res = res.replace(fit_reason=res.fit_reason.at[-1].set(
                    jnp.asarray(G, jnp.int32) - fuel_left))
            return res

        return run

    def tabled_run(sparse: bool, fell_back: bool):
        # make_run's own work is the table build (the loop runs when
        # the closure is called): a scope of its own — the sorts and
        # segmented scans of the per-queue unit segments (or preempt's
        # compact [Q, KU] grid)
        with jax.named_scope("unit_tables"):
            return make_run(sparse, fell_back)

    if not sparse_able:
        return tabled_run(False, False)(result)
    if KU >= M:
        # no queue can ever expose more units than running pods exist:
        # the dense fallback is statically unreachable, so skip the
        # cond (small tier-1 shapes trace ONE loop, not two)
        return tabled_run(True, False)(result)
    cnt_units_q = jax.ops.segment_sum(
        has_leaf.astype(jnp.int32), jnp.where(has_leaf, leaf_safe, Q),
        num_segments=Q + 1)[:Q]
    return lax.cond(jnp.any(cnt_units_q > KU),
                    tabled_run(False, True), tabled_run(True, False),
                    result)


#: scratch diagnostics flag (set True to expose chunk counts)
_DEBUG_CHUNKS = False


#: the victim actions, in the slot order of
#: ``AllocationResult.victim_skipped`` (action names as the pipeline and
#: the ``action`` label of the gauges spell them)
VICTIM_ACTIONS = ("reclaim", "preempt", "consolidation")
_SKIP_SLOT = {"reclaim": 0, "preempt": 1, "consolidate": 2}


def _viable_preemptors(
    state: ClusterState,
    fair_share: jax.Array,
    result: AllocationResult,
    *,
    num_levels: int,
    mode: str,
    chain: jax.Array,
):
    """The action's vectorized viability prefilter: which pending gangs
    could preempt at all.  Returns (``remaining0`` bool [G], ``cnt_q``
    i32 [Q] candidate victims per leaf queue, ``task_req_g`` f32 [G, R]).

    The per-gang scan is the expensive part (a fairness re-sort per
    step); gangs that cannot possibly preempt are dropped upfront.
    Sound because queue allocation only GROWS within the action, so the
    capacity/fair-share gates (re-checked live per attempt) only get
    stricter — a gang failing them at action start can never pass later.

    This is ALL an action with nobody waiting computes (the gate of
    :func:`run_victim_action`): segment sums and per-gang gates only —
    nothing sorted over [M], nothing of [U, Q, *] size.
    """
    g, q, r = state.gangs, state.queues, state.running
    G = g.g
    base = (r.valid & ~r.releasing & (r.node >= 0) & r.preemptible
            & (r.gang >= 0))
    rq = jnp.where(base, r.queue, q.q)
    cnt_q = jax.ops.segment_sum(base.astype(jnp.int32), rq,
                                num_segments=q.q + 1)[:q.q]       # [Q]
    total_cnt = jnp.sum(cnt_q)
    gq = jnp.maximum(g.queue, 0)
    if mode == "reclaim":
        has_cand = (total_cnt - cnt_q[gq]) > 0
    elif mode == "consolidate":
        own = jax.ops.segment_sum(
            base.astype(jnp.int32), jnp.where(base, r.gang, G),
            num_segments=G + 1)[:G]
        has_cand = (total_cnt - own) > 0
    else:  # preempt: a lower-priority candidate in the gang's own queue
        minprio = jax.ops.segment_min(
            jnp.where(base, r.priority, BIG), rq,
            num_segments=q.q + 1)[:q.q]
        has_cand = minprio[gq] < g.priority
    task_req_g = jnp.sum(
        jnp.where(g.task_valid[:, :, None], g.task_req, 0.0), axis=1)
    gate_np = jax.vmap(
        lambda qi, tr: _ancestor_gate(
            q.parent, qi, num_levels,
            result.queue_allocated_nonpreemptible, q.quota, tr)
    )(gq, task_req_g)
    viable = has_cand & jnp.where(~g.preemptible, gate_np, True)
    if mode == "reclaim":
        # the fair-share gate must use a LOWER bound of future queue
        # allocation — reclaim evictions SHRINK allocation as the action
        # proceeds, so gating on the live value would wrongly exclude
        # reclaimers whose chain drops under fair share once victims
        # free up.  Lower bound: current allocation minus everything any
        # candidate could ever free along the chain.
        cand_leaf = jax.ops.segment_sum(
            jnp.where(base[:, None], r.req, 0.0), rq,
            num_segments=q.q + 1)[:q.q]                        # [Q, R]
        freeable = einsum_exact(
            "qa,qr->ar", chain.astype(cand_leaf.dtype), cand_leaf)
        qa_lower = jnp.maximum(result.queue_allocated - freeable, 0.0)
        viable = viable & jax.vmap(
            lambda qi, tr: _ancestor_gate(
                q.parent, qi, num_levels, qa_lower,
                fair_share, tr))(gq, task_req_g)
    elif mode == "consolidate":
        viable = viable & g.preemptible
        # conservation gate: moving victims frees NOTHING in aggregate —
        # a consolidation preemptor must fit the cluster's total spare
        # capacity, or no rearrangement can ever place it.  On a
        # saturated cluster this empties the action outright.
        spare = jnp.sum(jnp.where(
            state.nodes.valid[:, None],
            result.free + state.nodes.releasing + result.releasing_extra,
            0.0), axis=0)
        viable = viable & jnp.all(task_req_g <= spare[None, :] + EPS,
                                  axis=-1)
    remaining0 = g.valid & (g.backoff <= 0) & ~result.allocated & viable
    return remaining0, cnt_q, task_req_g


def _victim_search(
    state: ClusterState,
    fair_share: jax.Array,
    result: AllocationResult,
    *,
    num_levels: int,
    mode: str,
    config: VictimConfig,
    remaining0: jax.Array,       # bool [G] from _viable_preemptors
    chain: jax.Array,
    cnt_q: jax.Array,
    task_req_g: jax.Array,
) -> AllocationResult:
    """The action behind its gate: everything preemptor-independent
    (victim statics, the frozen job and unit orders, the per-queue
    tables) and the search loop itself — chunked wavefront or one gang
    at a time."""
    g, q = state.gangs, state.queues
    G = g.g
    total = state.total_capacity
    gq = jnp.maximum(g.queue, 0)
    depth = (config.queue_depth_preempt
             if mode == "preempt" and config.queue_depth_preempt is not None
             else config.queue_depth)
    statics = victim_statics(state)
    job_rank0 = frozen_job_rank(state, result.queue_allocated, fair_share)
    lq_tab = None
    if mode == "reclaim":
        # [victim leaf, reclaimer leaf] leveled-queue table for the live
        # strategy-viability drop
        qidx = jnp.arange(q.q)
        lq_tab = jax.vmap(lambda v: jax.vmap(
            lambda c: _leveled_queue(chain, q.depth, v, c))(qidx))(qidx)

    if (config.batch_size > 1 and mode in ("reclaim", "preempt")
            and (mode != "reclaim" or config.chunk_reclaim)):
        return _run_victim_action_chunked(
            state, fair_share, result, num_levels=num_levels, mode=mode,
            config=config, remaining0=remaining0, chain=chain,
            statics=statics, job_rank=job_rank0, lq_tab=lq_tab,
            cnt_q=cnt_q, task_req_g=task_req_g)

    quota_eff_q = jnp.where(q.quota <= UNLIMITED + 0.5, jnp.inf, q.quota)
    anti = config.placement.anti_groups
    if anti:
        dom_static, _TA = anti_domain_tables(state)

    def step(carry):
        res, remaining, q_att, fuel = carry
        gi = ordering.select_next_gang(
            g, q, res.queue_allocated, fair_share, total, remaining)
        runnable = remaining[gi] & g.valid[gi] & (g.backoff[gi] <= 0) \
            & ~res.allocated[gi]

        dmask = (~anti_forbid_nodes(state, res.anti_used, dom_static, gi)
                 if anti else None)
        if anti and config.placement.attract_groups:
            dmask = dmask & attract_allow_nodes(
                state, res.anti_used, dom_static, gi)

        def attempt(_):
            return solve_for_preemptor(
                state, gi, res, fair_share, chain,
                num_levels=num_levels, mode=mode, config=config,
                statics=statics, job_rank=job_rank0, domain_mask=dmask)

        def skip(_):
            T = g.t
            return (jnp.asarray(False), jnp.zeros_like(res.victim),
                    jnp.full((T,), -1, jnp.int32),
                    jnp.full((T,), -1, jnp.int32), jnp.zeros((T,), bool),
                    jnp.full((state.running.m,), -1, jnp.int32),
                    res.free, res.device_free, res.releasing_extra,
                    res.device_releasing_extra, res.queue_allocated,
                    res.queue_allocated_nonpreemptible, res.extended_free,
                    res.extended_releasing_extra)

        (success, victims, nodes_t, dev_t, pipe_t, moves,
         free2, dev2, extra2, extra_dev2, qa2, qan2, ext2,
         ext_extra2) = lax.cond(runnable, attempt, skip, None)
        res = res.replace(
            extended_free=jnp.where(success, ext2, res.extended_free),
            extended_releasing_extra=jnp.where(
                success, ext_extra2, res.extended_releasing_extra),
            free=jnp.where(success, free2, res.free),
            device_free=jnp.where(success, dev2, res.device_free),
            releasing_extra=jnp.where(success, extra2, res.releasing_extra),
            device_releasing_extra=jnp.where(
                success, extra_dev2, res.device_releasing_extra),
            queue_allocated=jnp.where(success, qa2, res.queue_allocated),
            queue_allocated_nonpreemptible=jnp.where(
                success, qan2, res.queue_allocated_nonpreemptible),
            placements=res.placements.at[gi].set(
                jnp.where(success, nodes_t, res.placements[gi])),
            placement_device=res.placement_device.at[gi].set(
                jnp.where(success, dev_t, res.placement_device[gi])),
            # tasks on victim/releasing capacity pipeline; tasks that fit
            # genuinely idle capacity bind now (stmt.Allocate vs Pipeline)
            pipelined=res.pipelined.at[gi].set(
                jnp.where(success, pipe_t, res.pipelined[gi])),
            allocated=res.allocated.at[gi].set(res.allocated[gi] | success),
            attempted=res.attempted.at[gi].set(res.attempted[gi] | runnable),
            victim=res.victim | victims,
            victim_move=jnp.where(success & (moves >= 0), moves,
                                  res.victim_move),
        )
        if anti:
            # a victim-action placement claims its domains too, so a
            # later conflicting gang (in this or a later action of the
            # cycle) cannot co-land with a reclaim-placed preemptor
            res = res.replace(anti_used=anti_mark_placements(
                state, res.anti_used, dom_static, gi, nodes_t, success))
        remaining = remaining.at[gi].set(False)
        if depth is not None:
            # per-QUEUE attempt budget (ref QueueDepthPerAction: "max
            # number of jobs to try for action per queue") — exhausted
            # queues drain from the remaining set
            q_att = q_att.at[g.queue[gi]].add(
                runnable.astype(jnp.int32))
            remaining = remaining & (
                q_att[g.queue] < depth)
        if mode == "reclaim":
            # Live strategy-viability drop — SOUND because within the
            # action victim-queue shares only fall and reclaimer
            # allocation only grows, so a (victim queue, reclaimer) pair
            # that stops being strategy-evictable never recovers.  A
            # reclaimer gang stays in `remaining` only while some other
            # leaf queue with candidates is still evictable for it; once
            # shares exhaust, the loop ends in O(successes) steps instead
            # of attempting every remaining pending gang.
            qa_l = res.queue_allocated
            under_g = jax.vmap(
                lambda qi, tr: _ancestor_gate(
                    q.parent, qi, num_levels, qa_l, q.quota, tr))(
                        gq, task_req_g)                            # [G]
            lqs = jnp.maximum(lq_tab, 0)
            no_lq = lq_tab < 0
            over_fs_vc = no_lq | jnp.any(
                qa_l[lqs] > fair_share[lqs] + EPS, -1)             # [Q, Q]
            over_qt_vc = no_lq | jnp.any(
                qa_l[lqs] > quota_eff_q[lqs] + EPS, -1)
            diff = (jnp.arange(q.q)[:, None] != jnp.arange(q.q)[None, :])
            has_v = (cnt_q > 0)[:, None] & diff
            ev_fs_c = jnp.any(has_v & over_fs_vc, axis=0)          # [Q]
            ev_qt_c = jnp.any(has_v & over_qt_vc, axis=0)
            remaining = remaining & (
                ev_fs_c[gq] | (under_g & ev_qt_c[gq]))
        return res, remaining, q_att, fuel - 1

    with jax.named_scope("wavefront"):
        res, _, _, _ = lax.while_loop(
            lambda c: jnp.any(c[1]) & (c[3] > 0), step,
            (result, remaining0, jnp.zeros((q.q,), jnp.int32),
             jnp.asarray(G, jnp.int32)))
    return res


def run_victim_action(
    state: ClusterState,
    fair_share: jax.Array,
    result: AllocationResult,
    *,
    num_levels: int,
    mode: str,                   # "reclaim" | "preempt" | "consolidate"
    config: VictimConfig = VictimConfig(),
) -> AllocationResult:
    """The reclaim / preempt / consolidation action: scan pending
    unallocated gangs in fairness order, solving victim scenarios for each.

    Functional equivalent of ``reclaim.Execute`` / ``preempt.Execute`` /
    ``consolidation.Execute``.  Successful preemptors are committed as
    *pipelined* placements (they wait for their victims' pods to
    terminate — the reference pipelines preemptors onto releasing
    resources the same way); consolidation victims additionally get a
    planned re-placement node in ``victim_move``.

    Like the reference, which walks the pending jobs and returns when
    there are none, the action first asks whether ANY gang is a viable
    preemptor (:func:`_viable_preemptors`) and only then freezes orders,
    ranks eviction units and builds the per-queue tables
    (:func:`_victim_search`, under one ``lax.cond``).  A closed gate
    returns ``result`` as it came — what a search loop of zero
    iterations returns — and counts itself in ``victim_skipped``.
    """
    if mode not in _SKIP_SLOT:
        raise ValueError(f"unknown victim action mode: {mode!r}")
    chain = _chain_membership(state.queues.parent, num_levels)
    remaining0, cnt_q, task_req_g = _viable_preemptors(
        state, fair_share, result, num_levels=num_levels, mode=mode,
        chain=chain)
    anyone = jnp.any(remaining0)
    result = result.replace(
        victim_skipped=result.victim_skipped.at[_SKIP_SLOT[mode]].add(
            (~anyone).astype(jnp.int32)))
    return lax.cond(
        anyone,
        lambda res: _victim_search(
            state, fair_share, res, num_levels=num_levels, mode=mode,
            config=config, remaining0=remaining0, chain=chain,
            cnt_q=cnt_q, task_req_g=task_req_g),
        lambda res: res, result)


@functools.partial(jax.jit,
                   static_argnames=("num_levels", "mode", "config"))
def run_victim_action_jit(state, fair_share, result, *, num_levels,
                          mode, config=VictimConfig()):
    return run_victim_action(state, fair_share, result,
                             num_levels=num_levels, mode=mode,
                             config=config)


# kai-wire compile watcher: per-(entry, signature) cache-miss
# attribution (runtime/compile_watch.py)
run_victim_action_jit = compile_watch.watch("run_victim_action",
                                            run_victim_action_jit)
