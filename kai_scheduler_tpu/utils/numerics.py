"""Precision helpers for long f32 reductions on TPU.

The reference's fairness/victim arithmetic runs in Go float64
(``pkg/scheduler/plugins/proportion/resource_division/resource_division.go:26-41``).
TPU kernels run f32; a plain f32 cumulative sum over the 50k-unit
victim tables with GiB-scale values carries ~1e-7 relative error —
measured ~1.4 GiB absolute at the tail, larger than a small pod's
request, so a capacity comparison within that band of its bound could
flip versus exact arithmetic (SURVEY §7 hard-part 5).

``cumsum_ds`` keeps the scan in f32 but carries a double-single
(compensated) error term through an associative two-sum, squaring the
effective precision (~1e-14 relative) for 2× the flops of the plain
scan — the TPU-native answer to "compute it in float64".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: ``jnp.einsum`` for the mask-times-amount contractions that update the
#: free pool and the queue ledgers (accepted-lane deltas, ancestor-chain
#: rollups).  At default precision the TPU multiplies f32 operands in
#: ONE bf16 pass, which rounds the amounts themselves (13.7 GiB becomes
#: 13.6875) — every accepted placement would then leak into ``free`` and
#: ``queue_allocated``.  HIGHEST splits each f32 into three bf16 pieces,
#: so a 0/1 mask times an amount is exact; the products are tiny next
#: to the rest of a chunk step.
einsum_exact = functools.partial(jnp.einsum,
                                 precision=jax.lax.Precision.HIGHEST)


def _two_sum(a: jax.Array, b: jax.Array):
    """Knuth two-sum: s + err == a + b exactly (all f32)."""
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def cumsum_ds(x: jax.Array, axis: int = 0) -> jax.Array:
    """Compensated (double-single) cumulative sum along ``axis``.

    Associative, so it lowers to the same parallel-scan structure XLA
    uses for ``jnp.cumsum``; each combine carries the rounding residue
    of the partial sums instead of dropping it."""

    def combine(ca, cb):
        s_a, e_a = ca
        s_b, e_b = cb
        s, e = _two_sum(s_a, s_b)
        return s, e + e_a + e_b

    s, e = jax.lax.associative_scan(
        combine, (x, jnp.zeros_like(x)), axis=axis)
    return s + e


def segmented_cumsum_ds(x: jax.Array, first: jax.Array,
                        axis: int = 0) -> jax.Array:
    """:func:`cumsum_ds` restarted wherever ``first`` is set.

    ``first`` (bool, broadcastable to ``x``) marks the first element of
    every segment along ``axis``; the flag rides the scan's carry and
    resets sum AND residue there, so a segment's running sums are built
    from that segment's values only, in order — what a dense one-hot
    column's ``cumsum_ds`` sums between its zeros."""

    def combine(ca, cb):
        s_a, e_a, f_a = ca
        s_b, e_b, f_b = cb
        s, e = _two_sum(s_a, s_b)
        return (jnp.where(f_b, s_b, s),
                jnp.where(f_b, e_b, e + e_a + e_b), f_a | f_b)

    s, e, _ = jax.lax.associative_scan(
        combine, (x, jnp.zeros_like(x), jnp.broadcast_to(first, x.shape)),
        axis=axis)
    return s + e
