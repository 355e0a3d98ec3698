"""A fault against the guarantee ``placement`` (``configs/pools-10k.json``),
planted like those of ``faults.py`` under ``POST /cycle/stored``: one
gang that selects a pool is bound onto nodes of another.  Gangs stay
whole, names live and nodes within their capacity, so of the
benchmark's numbers only ``placement_violations`` can see it."""
from __future__ import annotations

import contextlib


def wrong_pool(run, pool_label: str = "gpu.type"):
    """The fault for ``run`` (a ``lib.loop.Run`` about to start): the
    first gang of a commit whose pods select a pool by ``pool_label`` is
    bound, pod by pod, onto nodes of another pool (a node each, going
    round that pool from commit to commit, so that no node fills)."""
    others: dict = {}   # pool -> the nodes outside it, lazily
    turn = [0]

    def fault(doc: dict) -> dict:
        store = run.server.cluster
        moved = None
        out = []
        for br in doc["bind_requests"]:
            want = store.pods[br["pod"]].node_selector.get(pool_label)
            gang = run.churn.gang_of[br["pod"]]
            if want and moved in (None, gang):
                moved = gang
                if want not in others:
                    others[want] = [
                        n.name for n in store.nodes.values()
                        if n.labels.get(pool_label) != want]
                turn[0] += 1
                br = dict(br, node=others[want][turn[0] % len(others[want])])
            out.append(br)
        return dict(doc, bind_requests=out)

    return fault


@contextlib.contextmanager
def planted(fault):
    """Run the block with ``fault`` (a function of the commit document)
    under ``POST /cycle/stored``."""
    from kai_scheduler_tpu.framework import server
    sound = server._commit_doc
    server._commit_doc = lambda result: fault(sound(result))
    try:
        yield
    finally:
        server._commit_doc = sound
