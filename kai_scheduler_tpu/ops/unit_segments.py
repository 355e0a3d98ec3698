"""Per-queue eviction-unit tables as sorted segments of the unit axis.

The chunked victim search (``ops/victims.py``) freezes one global rank
order of the eviction units and then asks, per chunk, questions of ONE
queue's units at a time: "queue ``q``'s freed sum up to global rank
``u``", "the first unit of ``q`` at or after rank ``x``", "the rank at
which the subtree of ancestor ``a`` has freed a threshold".  A dense
answer table has a column per queue (``[U, Q, R]``, all but one entry
of a row zero); here every queue's units lie contiguously in rank order
instead, ``U`` rows in all (``U·L`` for the subtree tables, ``L`` the
queue-tree depth), with their running sums, and a probe is a binary
search inside one segment.

Ranks without a unit (``unit_leaf < 0``) sort into a junk segment past
``off[Q]`` that no probe reads.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.numerics import segmented_cumsum_ds


class UnitSegments(NamedTuple):
    """Units grouped by a queue key, rank-ascending inside a group."""

    pos: jax.Array   # i32 [n]    global unit rank of row i
    off: jax.Array   # i32 [Q+1]  queue q's rows are off[q]:off[q+1]
    cum: jax.Array   # f32 [n, R] INCLUSIVE running request sum within
    #                             the row's segment (compensated)


def leaf_order(unit_leaf: jax.Array, num_queues: int):
    """Stable sort of the unit ranks by leaf queue: ``(perm, key)`` with
    ``key = unit_leaf[perm]`` ascending (``num_queues`` for ranks that
    hold no unit) and rank order kept inside a queue."""
    key = jnp.where(unit_leaf >= 0, unit_leaf, num_queues).astype(jnp.int32)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    return perm, key[perm]


def _segments(key_sorted, pos, req_sorted, num_queues: int) -> UnitSegments:
    first = jnp.concatenate(
        [jnp.ones((1,), bool), key_sorted[1:] != key_sorted[:-1]])
    off = jnp.searchsorted(
        key_sorted, jnp.arange(num_queues + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    # scanned with the unit axis minor (R is 2-3 wide)
    cum = segmented_cumsum_ds(req_sorted.T, first[None, :], axis=1).T
    return UnitSegments(pos, off, cum)


def leaf_segments(unit_leaf: jax.Array, unit_req: jax.Array,
                  num_queues: int) -> UnitSegments:
    """Every leaf queue's units in rank order (``[U]`` rows)."""
    perm, key = leaf_order(unit_leaf, num_queues)
    return _segments(key, perm, unit_req[perm], num_queues)


def subtree_segments(unit_leaf: jax.Array, unit_req: jax.Array,
                     parent: jax.Array, num_levels: int) -> UnitSegments:
    """Every queue's SUBTREE units in rank order: one row per (ancestor
    ``a``, unit ``u``) with ``a`` on ``u``'s leaf's chain (itself
    included) — ``[U·num_levels]`` rows."""
    Q = parent.shape[0]
    cur, cols = jnp.arange(Q, dtype=jnp.int32), []
    for _ in range(num_levels):
        cols.append(jnp.where(cur >= 0, cur, Q))
        cur = jnp.where(cur >= 0, parent[jnp.maximum(cur, 0)], -1)
    anc = jnp.stack(cols, axis=1).astype(jnp.int32)            # [Q, L]
    # unit-major pairs, so the stable sort keeps rank order inside a key
    key = jnp.where((unit_leaf >= 0)[:, None],
                    anc[jnp.maximum(unit_leaf, 0)], Q).reshape(-1)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = perm // num_levels
    return _segments(key[perm], pos, unit_req[pos], Q)


def segment_search(key_at, lo: jax.Array, hi: jax.Array, v: jax.Array,
                   length: int, *, side: str = "left") -> jax.Array:
    """Per-lane ``searchsorted`` inside rows ``lo:hi`` of a flat table:
    the first row ``i`` in the range with ``key_at(i) >= v`` (``left``;
    ``> v`` for ``right``), ``hi`` if none.  ``key_at`` gathers the
    lanes' keys at a row index array shaped like ``lo``; ``length``
    (static) bounds ``hi - lo`` and fixes the trip count."""
    lo, hi, v = jnp.broadcast_arrays(lo, hi, v)

    def step(_, lh):
        lo, hi = lh
        active = lo < hi
        mid = (lo + hi) // 2
        k = key_at(jnp.minimum(mid, length - 1))
        below = (k < v) if side == "left" else (k <= v)
        return (jnp.where(active & below, mid + 1, lo),
                jnp.where(active & ~below, mid, hi))

    return lax.fori_loop(0, max(1, length.bit_length()), step,
                         (lo, hi))[0]


def sum_through(leaf: UnitSegments, c: jax.Array) -> jax.Array:
    """f32 [Q, R]: per leaf queue, the summed request of its units of
    global rank ``<= c[q]`` (0 before the queue's first unit and for
    ``c < 0``)."""
    n = leaf.pos.shape[0]
    lo, hi = leaf.off[:-1], leaf.off[1:]
    j = segment_search(lambda i: leaf.pos[i], lo, hi, c, n, side="right")
    return jnp.where((j > lo)[:, None],
                     leaf.cum[jnp.maximum(j - 1, 0)], 0.0)


def rank_order_cum(leaf: UnitSegments) -> jax.Array:
    """f32 [R, U]: ``leaf.cum`` back in global rank order (unit axis
    minor) — each unit's own queue's inclusive sum at the unit."""
    cum_t = leaf.cum.T
    return jnp.zeros_like(cum_t).at[:, leaf.pos].set(
        cum_t, unique_indices=True)


def lane_columns(own_cum: jax.Array, mine: jax.Array) -> jax.Array:
    """f32 [B, R, U]: per lane, its queue's inclusive freed sum at every
    global rank (a dense per-queue column), from ``own_cum``
    (:func:`rank_order_cum`, [R, U]) and ``mine`` (bool [B, U]: the unit
    is of the lane's queue).  A column is a step function that rises at
    the queue's own units; requests are non-negative, so the running
    maximum of the own values carries each step forward."""
    return lax.cummax(
        jnp.where(mine[:, None, :], own_cum[None], 0.0), axis=2)


def lane_available(avail: jax.Array, may: jax.Array) -> jax.Array:
    """i32 [B, U]: per lane, how many units of global rank ``<= u`` are
    still available (``avail`` bool [U]) and the lane's to take (``may``
    bool [B, U]: its own queue's units for preempt, every other queue's
    for reclaim)."""
    return jnp.cumsum((avail[None, :] & may).astype(jnp.int32), axis=1)


def subtree_bound(sub: UnitSegments, thr: jax.Array,
                  num_units_axis: int) -> jax.Array:
    """i32 [..., Q, R]: per (queue ``a``, resource), the first global
    rank ``u`` at which the request summed over ``a``'s subtree units of
    rank ``< u`` reaches ``thr`` — ``searchsorted`` on the dense
    exclusive column.  That column is flat between subtree units: the
    answer is 0 for ``thr <= 0``, else the rank after the first unit
    whose INCLUSIVE sum reaches ``thr``, else ``num_units_axis``."""
    n = sub.pos.shape[0]
    lo, hi = sub.off[:-1, None], sub.off[1:, None]
    ridx = jnp.arange(thr.shape[-1])
    k = segment_search(lambda i: sub.cum[i, ridx], lo, hi, thr, n)
    after = sub.pos[jnp.minimum(k, n - 1)] + 1
    return jnp.where(thr <= 0, 0,
                     jnp.where(k < hi, after, num_units_axis))


def first_at_or_after(leaf: UnitSegments, x: jax.Array,
                      none: int) -> jax.Array:
    """i32 [Q, B]: the global rank of each leaf queue's first unit of
    rank ``>= x[q, b]``, ``none`` when the queue has no such unit."""
    n = leaf.pos.shape[0]
    lo, hi = leaf.off[:-1, None], leaf.off[1:, None]
    k = segment_search(lambda i: leaf.pos[i], lo, hi, x, n)
    return jnp.where(k < hi, leaf.pos[jnp.minimum(k, n - 1)], none)


def first_not_below(leaf: UnitSegments, keys: jax.Array, q: jax.Array,
                    v: jax.Array, none: int):
    """Queue ``q``'s units carry ``keys`` (f32 [U], in ``leaf`` row
    order) ascending: ``(count, rank)``, each ``q``'s shape — how many
    of them lie strictly below ``v``, and the global rank of the first
    that does not (``none`` when all do)."""
    n = leaf.pos.shape[0]
    lo, hi = leaf.off[q], leaf.off[q + 1]
    k = segment_search(lambda i: keys[i], lo, hi, v, n)
    return k - lo, jnp.where(k < hi, leaf.pos[jnp.minimum(k, n - 1)], none)
