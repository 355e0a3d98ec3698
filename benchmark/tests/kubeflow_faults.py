"""Faults against the guarantee ``subgroup_quorum``
(``configs/kubeflow-10k.json``), planted by ``faults.py``.

``mixed_gangs_left_pending`` is planted where the scheduler turns its
placements into bind requests (``Session.bind_requests_from``), so the
server's own store agrees with what it returns: every bind of a gang
whose pods differ in request is dropped, and the gang stays pending
cycle after cycle though it fits.  Gangs stay whole, names live, nodes
within their capacity and the read-back equal: of the numbers
``correct`` compares only ``gangs_bound_short`` can see it, because the
configuration names ``lib/subgroup_model.py``, which has the reference
fit a gang of unequal pods pod by pod.

``launcher_left_out`` is planted under ``POST /cycle/stored``: the bind
of every pod of the subgroup ``launcher`` is left out of the commit, so
an MPIJob is bound one below its ``min_member`` and its launcher's
subgroup below its quorum (``gangs_below_min_member``,
``subgroups_below_quorum``).
"""
from __future__ import annotations

import contextlib


def _gang(pod_name: str) -> str:
    """A gang's pods share the name up to ``-pod-<t>``."""
    return pod_name.rsplit("-pod-", 1)[0]


@contextlib.contextmanager
def mixed_gangs_left_pending(run):
    """Run the block with every bind of a gang of unequal pods dropped
    before the scheduler commits it."""
    from kai_scheduler_tpu.framework.session import Session
    sound = Session.bind_requests_from

    def faulty(self, result, host=None):
        binds = sound(self, result, host=host)
        store = run.server.cluster
        requests: dict = {}
        for br in binds:
            requests.setdefault(_gang(br.pod_name), set()).add(
                store.pods[br.pod_name].resources.as_tuple())
        return [br for br in binds if len(requests[_gang(br.pod_name)]) == 1]

    Session.bind_requests_from = faulty
    try:
        yield
    finally:
        Session.bind_requests_from = sound


@contextlib.contextmanager
def launcher_left_out(run):
    """Run the block with every launcher's bind left out of the commit
    document."""
    from kai_scheduler_tpu.framework import server
    sound = server._commit_doc

    def faulty(result):
        doc = sound(result)
        store = run.server.cluster
        return dict(doc, bind_requests=[
            br for br in doc["bind_requests"]
            if store.pods[br["pod"]].subgroup != "launcher"])

    server._commit_doc = faulty
    try:
        yield
    finally:
        server._commit_doc = sound

