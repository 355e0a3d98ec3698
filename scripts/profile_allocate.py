"""Cost-analysis + scaling probe for the allocate hot path (dev tool)."""
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from kai_scheduler_tpu.framework.session import Session
from kai_scheduler_tpu.state import make_cluster
from kai_scheduler_tpu.ops import drf
from kai_scheduler_tpu.ops.allocate import allocate
import dataclasses


def build(num_nodes=10_000, num_gangs=6250, tasks_per_gang=8, **kw):
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=num_nodes, node_accel=8.0, num_gangs=num_gangs,
        tasks_per_gang=tasks_per_gang, **kw)
    return Session.open(nodes, queues, groups, pods, topo)


def timeit(fn, iters=8, pipeline=5):
    jax.block_until_ready(fn())
    best = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready([fn() for _ in range(pipeline)])
        best.append((time.perf_counter() - t0) / pipeline)
    return np.median(best) * 1e3, np.percentile(best, 99) * 1e3


def main():
    shape = sys.argv[1] if len(sys.argv) > 1 else "headline"
    kw = {}
    if shape == "headline":
        kw = dict(num_nodes=10_000, num_gangs=6250, tasks_per_gang=8)
    elif shape == "gang":
        kw = dict(num_nodes=2000, num_gangs=1000, tasks_per_gang=8)
    elif shape == "half":
        kw = dict(num_nodes=10_000, num_gangs=3125, tasks_per_gang=8)
    ses = build(**kw)
    num_levels = ses.config.num_levels
    config = ses.config.allocate
    for field in ("uniform_tasks", "dense_feasibility", "anti_groups",
                  "track_devices", "extended", "batch_size",
                  "dynamic_order"):
        print(field, getattr(config, field))
    if len(sys.argv) > 2:
        for kv in sys.argv[2].split(","):
            k, v = kv.split("=")
            if v in ("True", "False"):
                val = v == "True"
            else:
                val = int(v)  # raises on anything unrecognized
            config = dataclasses.replace(config, **{k: val})

    @jax.jit
    def cycle(state):
        fair_share = drf.set_fair_share(state, num_levels=num_levels)
        st = state.replace(
            queues=state.queues.replace(fair_share=fair_share))
        res = allocate(st, fair_share, num_levels=num_levels, config=config)
        return res.placements, res.allocated

    lowered = cycle.lower(ses.state)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    print("flops", ca.get("flops"), "bytes", ca.get("bytes accessed"))

    placements, _alloc = jax.block_until_ready(cycle(ses.state))
    placed = int((np.asarray(placements) >= 0).sum())
    med, p99 = timeit(lambda: cycle(ses.state))
    print(f"placed={placed} median={med:.2f}ms p99={p99:.2f}ms")

    @jax.jit
    def drf_only(state):
        return drf.set_fair_share(state, num_levels=num_levels)
    med, p99 = timeit(lambda: drf_only(ses.state))
    print(f"drf only: median={med:.2f}ms p99={p99:.2f}ms")


if __name__ == "__main__":
    main()
