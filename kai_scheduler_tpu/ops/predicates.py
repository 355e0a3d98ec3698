"""Pod×node feasibility masks — the predicates plugin, tensorized.

The reference checks each candidate node for a task through a chain of
predicate functions (``plugins/predicates/predicates.go:104-130`` wrapping
upstream kube-scheduler filters, dispatched per node in
``framework/session.go:201-232`` ``FittingNode``).  That is an O(nodes)
host loop per task; here the whole chain is a single broadcast expression
producing a boolean ``[..., N]`` mask, evaluated for every task at once
(vmapped over the task axis) on the MXU-adjacent vector units.

Covered predicate surface (the resource+label subset per SURVEY.md §7
"hard parts" (6); exotic predicates stay host-side fallbacks):

- node validity (schedulable, in-partition)
- resource fit against ``free`` (idle) resources
- resource fit against ``free + releasing`` (the *pipeline* variant the
  reference uses to queue a task behind terminating pods)
- nodeSelector equality matching via the label-vocabulary encoding
- fractional accelerator fit (portion ≤ free accel, cf. gpu_sharing)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..apis.types import RESOURCE_ACCEL
from ..state.cluster_state import NodeState

EPS = 1e-6


def selector_mask(node_labels: jax.Array, task_selector: jax.Array) -> jax.Array:
    """nodeSelector match — ref upstream NodeAffinity/selector filter.

    ``node_labels``  i32 [N, K]  label value-id per selector key (-1 unset)
    ``task_selector`` i32 [..., K] required value-id per key (-1 = any)

    Returns bool [..., N]: True where every required key matches.
    """
    required = task_selector[..., None, :] >= 0              # [..., 1, K]
    matches = node_labels == task_selector[..., None, :]     # [..., N, K]
    return jnp.all(~required | matches, axis=-1)


def resource_fit_mask(
    available: jax.Array,      # f32 [N, R]
    task_req: jax.Array,       # f32 [..., R]
) -> jax.Array:
    """True where the task's request fits the node's available vector.

    The accel component of ``task_req`` already carries fractional /
    memory-based shares (set at snapshot build), so this is a pure
    broadcast compare; device-granular accel checks are layered on by
    :func:`accel_fit_mask`.
    """
    req = jnp.asarray(task_req)
    return jnp.all(available + EPS >= req[..., None, :], axis=-1)


def node_portion(
    nodes: NodeState,
    task_portion: jax.Array,    # f32 [...]
    task_accel_mem: jax.Array | None,  # f32 [...]
) -> jax.Array:
    """Per-node effective share of one device — f32 [..., N].

    Plain fractions are node-independent; memory-based requests divide by
    each node's per-device memory (ref memory-based GPU sharing,
    ``gpu_resource_requirment.go`` gpuMemory / MemoryOfEveryGpuOnNode).
    """
    p = jnp.asarray(task_portion)[..., None] * jnp.ones_like(
        nodes.device_memory_gib)
    if task_accel_mem is not None:
        mem = jnp.asarray(task_accel_mem)[..., None]
        # NO clamp to 1.0: a request larger than a node's device memory
        # yields portion > 1 and is correctly infeasible on that node
        by_mem = mem / jnp.maximum(nodes.device_memory_gib, EPS)
        p = jnp.where(mem > 0, by_mem, p)
    return p


def _accel_pool_ok(
    df: jax.Array,              # f32 [N, D]  the device pool to check
    p: jax.Array,               # f32 [..., N] per-node fractional share
    is_frac: jax.Array,         # bool [...]
    req_accel: jax.Array,       # f32 [...]
) -> jax.Array:
    """Core device-pool check shared by :func:`accel_fit_mask` and the
    allocator's fused :func:`feasible_nodes_dual`: a fractional task needs
    ONE device with enough free share; a whole-device task needs enough
    fully-free devices.  bool [..., N]."""
    frac_ok = jnp.max(df, axis=-1) >= p - EPS                  # [..., N]
    whole_free = jnp.sum((df >= 1.0 - EPS).astype(jnp.float32), axis=-1)
    whole_ok = whole_free + EPS >= jnp.asarray(req_accel)[..., None]
    return jnp.where(jnp.asarray(is_frac)[..., None], frac_ok, whole_ok)


def accel_fit_mask(
    nodes: NodeState,
    task_req: jax.Array,        # f32 [..., R]
    task_portion: jax.Array | None,
    task_accel_mem: jax.Array | None,
    device_free: jax.Array,     # f32 [N, D]
    include_releasing: bool,
) -> jax.Array:
    """Device-granular accel feasibility — the ``FittingGPUs`` check
    (``gpu_sharing/gpu_sharing.go``).  bool [..., N]."""
    df = device_free
    if include_releasing:
        df = df + nodes.device_releasing
    req_accel = jnp.asarray(task_req)[..., RESOURCE_ACCEL]
    if task_portion is None:
        is_frac = jnp.zeros(jnp.shape(req_accel), bool)
        p = jnp.zeros(jnp.shape(req_accel) + (nodes.n,))
    else:
        mem = (jnp.zeros_like(task_portion) if task_accel_mem is None
               else jnp.asarray(task_accel_mem))
        is_frac = (jnp.asarray(task_portion) > 0) | (mem > 0)
        p = node_portion(nodes, task_portion, task_accel_mem)  # [..., N]
    return _accel_pool_ok(df, p, is_frac, req_accel)


def feasible_nodes(
    nodes: NodeState,
    task_req: jax.Array,        # f32 [..., R]
    task_selector: jax.Array,   # i32 [..., K]
    task_portion: jax.Array | None = None,
    task_accel_mem: jax.Array | None = None,
    *,
    task_class: jax.Array | None = None,  # i32 [...] node-filter class
    free: jax.Array | None = None,
    device_free: jax.Array | None = None,
    include_releasing: bool = False,
) -> jax.Array:
    """Full predicate chain → bool [..., N].

    ``free`` / ``device_free`` override the snapshot's idle tensors (the
    allocation kernel passes its *running* tensors as allocation
    proceeds).  ``include_releasing`` gives the pipeline variant: a node
    qualifies if the task fits once terminating pods release their
    resources (ref ``pod_info.IsTaskAllocatableOnReleasingOrIdle``).
    """
    avail = nodes.free if free is None else free
    df = nodes.device_free if device_free is None else device_free
    if include_releasing:
        avail = avail + nodes.releasing
    req = jnp.asarray(task_req)
    if task_portion is not None:
        # fractional / memory-based accel is checked at device granularity
        # (the canonical accel quantity is a cluster-wide accounting value
        # whose per-node share differs) — drop it from the node-sum check
        mem = (jnp.zeros_like(task_portion) if task_accel_mem is None
               else jnp.asarray(task_accel_mem))
        is_frac = (jnp.asarray(task_portion) > 0) | (mem > 0)
        req = req.at[..., RESOURCE_ACCEL].set(
            jnp.where(is_frac, 0.0, req[..., RESOURCE_ACCEL]))
    fit = resource_fit_mask(avail, req)
    accel = accel_fit_mask(nodes, task_req, task_portion, task_accel_mem,
                           df, include_releasing)
    with jax.named_scope("feasibility"):
        # (the operations and their order are the parent's: the scope is
        # metadata, and the compiled program stays the one the cells had)
        sel = selector_mask(nodes.labels, task_selector)
        out = fit & accel & sel & nodes.valid
        if task_class is not None:
            # taints/affinity/pod-affinity, host-evaluated per filter
            # class
            out = out & nodes.filter_masks[task_class]
    return out


def feasible_nodes_dual(
    nodes: NodeState,
    task_req: jax.Array,        # f32 [R]
    task_selector: jax.Array,   # i32 [K]
    task_portion: jax.Array,    # f32 []
    task_accel_mem: jax.Array,  # f32 []
    *,
    free: jax.Array,            # f32 [N, R]
    device_free: jax.Array,     # f32 [N, D]
    extra_releasing: jax.Array,        # f32 [N, R]
    extra_device_releasing: jax.Array, # f32 [N, D]
    devices: bool = True,
    task_class: jax.Array | None = None,  # i32 [] node-filter class
) -> tuple[jax.Array, jax.Array]:
    """(fit_idle, fit_pipe) in one pass — the allocation kernel's hot
    check, sharing the selector/validity work between the idle pool and
    the idle+releasing (pipeline) pool instead of two full chains.

    ``devices=False`` skips the device-granular table (valid when the
    snapshot holds no fractional/memory-based tasks — the node-level
    accel vector is then exact)."""
    mem = jnp.asarray(task_accel_mem)
    portion = jnp.asarray(task_portion)
    is_frac = (portion > 0) | (mem > 0)
    req = jnp.asarray(task_req)
    with jax.named_scope("feasibility"):
        sel = selector_mask(nodes.labels, task_selector) & nodes.valid  # [N]
        if task_class is not None:
            sel = sel & nodes.filter_masks[task_class]

    if not devices:
        fit_idle = jnp.all(free + EPS >= req[None, :], axis=-1) & sel
        avail = free + nodes.releasing + extra_releasing
        fit_pipe = jnp.all(avail + EPS >= req[None, :], axis=-1) & sel
        return fit_idle, fit_pipe

    req_nosum = req.at[RESOURCE_ACCEL].set(
        jnp.where(is_frac, 0.0, req[RESOURCE_ACCEL]))
    p = node_portion(nodes, portion, mem)                              # [N]
    req_accel = req[RESOURCE_ACCEL]

    def pools(avail, df):
        return (resource_fit_mask(avail, req_nosum)
                & _accel_pool_ok(df, p, is_frac, req_accel))

    fit_idle = pools(free, device_free) & sel
    fit_pipe = pools(
        free + nodes.releasing + extra_releasing,
        device_free + nodes.device_releasing + extra_device_releasing) & sel
    return fit_idle, fit_pipe


def gang_feasibility(
    nodes: NodeState,
    task_req: jax.Array,       # f32 [T, R]
    task_valid: jax.Array,     # bool [T]
    task_selector: jax.Array,  # i32 [T, K]
    min_member: jax.Array,     # i32 []
    *,
    free: jax.Array | None = None,
) -> jax.Array:
    """Cheap whole-gang prefilter — ref ``actions/common/feasible_nodes.go:11``
    (FeasibleNodesForJob) and the MinimalJobRepresentatives skip logic.

    A gang is *hopeless* this cycle if fewer than ``min_member`` of its
    tasks have any feasible node at all, counting each node's capacity only
    coarsely (no cross-task capacity interaction — that is the allocation
    kernel's job).  Returns a scalar bool (True = worth attempting).
    """
    per_task = feasible_nodes(nodes, task_req, task_selector, free=free)  # [T, N]
    has_node = jnp.any(per_task, axis=-1) & task_valid
    return jnp.sum(has_node.astype(jnp.int32)) >= min_member
