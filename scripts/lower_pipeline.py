#!/usr/bin/env python3
"""Lower ``_fused_pipeline`` at two small test shapes and print a digest
of its StableHLO text — the check that a change left the five-action
program alone.  Run it from the root of each of two checkouts::

    JAX_PLATFORMS=cpu PYTHONPATH=. python scripts/lower_pipeline.py [out.mlir]

Equal digests mean equal programs: ``as_text()`` leaves source
locations out, so only what is computed is compared.  Nothing is
compiled or run.
"""
import hashlib
import sys

from kai_scheduler_tpu.framework.scheduler import (SchedulerConfig,
                                                   _fused_pipeline)
from kai_scheduler_tpu.framework.session import Session
from kai_scheduler_tpu.state.synthetic import make_cluster

#: a saturated cluster whose reclaim has work, and one with priorities
#: and a topology tree
SHAPES = (
    dict(num_nodes=64, num_gangs=48, tasks_per_gang=4,
         running_fraction=0.5, partition_queues_by_running=True,
         queue_accel_quota=64.0, seed=3),
    dict(num_nodes=32, num_gangs=24, tasks_per_gang=2,
         running_fraction=0.25, priority_spread=3,
         topology_levels=(2, 4), seed=5),
)


def main() -> None:
    cfg = SchedulerConfig()
    texts = []
    for kw in SHAPES:
        ses = Session.open(*make_cluster(**kw), config=cfg.session)
        c = ses.config
        texts.append(_fused_pipeline.__kai_jit__.lower(
            ses.state, ses.state.queues.fair_share,
            actions=tuple(cfg.actions), num_levels=c.num_levels,
            acfg=c.allocate, vcfg=c.victims,
            grace_s=c.stale_grace_s).as_text())
    text = "\n=====\n".join(texts)
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w", encoding="utf-8") as f:
            f.write(text)
    print(len(text), "bytes", text.count("\n"), "lines, sha256",
          hashlib.sha256(text.encode()).hexdigest())


if __name__ == "__main__":
    main()
