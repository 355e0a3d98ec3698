"""f32 precision bounds at production scale (SURVEY §7 hard-part 5).

The reference runs its fairness/victim arithmetic in Go float64
(``resource_division.go:26-41``); the TPU kernels run f32.  These
property tests pin the divergence:

- the hierarchical DRF division's f32 result tracks the SAME algorithm
  evaluated in f64 to ~1 ulp at contended GiB-scale shapes;
- the victims' 50k-unit cumulative tables use the compensated
  double-single scan (``utils.numerics.cumsum_ds``), which tracks a
  numpy float64 reference orders of magnitude tighter than the plain
  f32 scan whose tail error (~1.4 GiB measured) exceeded a small pod's
  request.
"""
import jax
import jax.numpy as jnp
import numpy as np

from kai_scheduler_tpu.framework.session import Session
from kai_scheduler_tpu.ops import drf
from kai_scheduler_tpu.state import make_cluster
from kai_scheduler_tpu.utils.numerics import cumsum_ds

import pytest

pytestmark = pytest.mark.core


def _to64(tree):
    return jax.tree.map(
        lambda a: jnp.asarray(np.asarray(a), jnp.float64)
        if a.dtype == jnp.float32 else jnp.asarray(np.asarray(a)), tree)


def test_drf_f32_tracks_f64_at_contended_scale():
    """128 queues in 8 departments with messy GiB-scale requests and
    quotas: the f32 division stays within 1e-6 relative of the f64 run
    of the same passes (deserved, water-fill, remainders)."""
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=1000, node_accel=8.0, num_gangs=3000, tasks_per_gang=4,
        num_departments=8, queues_per_department=16)
    ses = Session.open(nodes, queues, groups, pods, topo)
    q = ses.state.queues
    rng = np.random.default_rng(3)
    req = np.asarray(q.request)
    messy_req = np.where(req > 0, rng.uniform(0.3, 900.0, req.shape), req)
    quota = np.asarray(q.quota)
    messy_quota = np.where(quota > 0, rng.uniform(1.0, 500.0, quota.shape),
                           quota)
    state32 = ses.state.replace(queues=q.replace(
        request=jnp.asarray(messy_req, jnp.float32),
        quota=jnp.asarray(messy_quota, jnp.float32)))
    fs32 = np.asarray(drf.set_fair_share(state32, num_levels=2))

    with jax.enable_x64(True):
        state64 = ses.state.replace(
            queues=_to64(q).replace(
                request=jnp.asarray(messy_req, jnp.float64),
                quota=jnp.asarray(messy_quota, jnp.float64)),
            nodes=_to64(ses.state.nodes))
        fs64 = np.asarray(drf.set_fair_share(state64, num_levels=2))

    rel = np.abs(fs32 - fs64) / np.maximum(np.abs(fs64), 1.0)
    assert rel.max() < 1e-6, rel.max()
    assert np.abs(fs32 - fs64).max() < 1e-2, np.abs(fs32 - fs64).max()


def test_victim_cumulative_tables_track_f64():
    """50k GiB-scale unit requests (the reclaim tables' shape): the
    compensated scan matches numpy float64 to ≤1e-3 absolute, where the
    plain f32 scan drifts by more than a small pod's request."""
    rng = np.random.default_rng(7)
    M = 50_000
    vals = np.stack([
        rng.uniform(0.1, 8.0, M),      # accel fractions
        rng.uniform(0.25, 64.0, M),    # cpu cores
        rng.uniform(0.5, 256.0, M),    # mem GiB
    ], axis=1)
    ref = np.cumsum(vals, axis=0)                    # float64
    comp = np.asarray(cumsum_ds(jnp.asarray(vals, jnp.float32), axis=0))
    plain = np.asarray(jnp.cumsum(jnp.asarray(vals, jnp.float32), axis=0))
    comp_err = np.abs(comp - ref).max()
    plain_err = np.abs(plain - ref).max()
    # representation of the f32 OUTPUT alone costs ~rel 6e-8 of the
    # ~6.4M tail => ~0.4; the compensated scan must sit at that floor
    tail = ref[-1].max()
    assert comp_err <= tail * 1.2e-7 + 1e-3, (comp_err, tail)
    assert comp_err < plain_err, (comp_err, plain_err)


def test_two_sum_carries_residue_exactly():
    """The compensated scan recovers a tiny addend buried under a large
    prefix — the failure mode of the plain f32 scan."""
    big = np.float32(2.0**22)
    x = jnp.asarray([big, 0.25, 0.25, 0.25, 0.25], jnp.float32)
    out = np.asarray(cumsum_ds(x))
    # plain f32: each +0.25 rounds away against 2^22 (ulp = 0.5)
    plain = np.asarray(jnp.cumsum(x))
    assert out[-1] == np.float32(2.0**22 + 1.0), out
    assert plain[-1] == big, plain
