"""Faults against the guarantee ``topology_required``
(``configs/topology-10k.json``), planted by ``faults.py``.

Both are planted where the scheduler turns its placements into bind
requests (``Session.bind_requests_from``), so the server's own store
agrees with what it returns, gangs stay whole, names live and nodes
within their capacity.

``pod_in_another_rack``: the last pod of every bound gang that asks for
the rack as its required level is bound to a node of another rack that
has an accelerator free.  Of the numbers ``correct`` compares only
``gangs_split_across_domains`` (``lib/topology_model.py``) can see it.

``rack_gang_dropped``: every bind of such a gang is dropped, and the
gang stays pending cycle after cycle though a rack holds all of it:
``domain_left_pending``, and ``gangs_bound_short``, whose free slots
the tree's reference counts inside one rack.
"""
from __future__ import annotations

import collections
import contextlib


def _gang(pod_name: str) -> str:
    """A gang's pods share the name up to ``-pod-<t>``."""
    return pod_name.rsplit("-pod-", 1)[0]


def _rack_required(run, store, gang: str) -> bool:
    tc = store.pod_groups[gang].topology_constraint
    return tc is not None and \
        tc.required_level == run.spec["topology"]["levels"][1]


@contextlib.contextmanager
def _planted(faulty_of):
    from kai_scheduler_tpu.framework.session import Session
    sound = Session.bind_requests_from

    def faulty(self, result, host=None):
        return faulty_of(sound(self, result, host=host))

    Session.bind_requests_from = faulty
    try:
        yield
    finally:
        Session.bind_requests_from = sound


def pod_in_another_rack(run):
    """Run the block with one pod of every rack-required gang bound
    into another rack."""
    block_key, rack_key, _host = run.spec["topology"]["levels"]

    def move(binds):
        store = run.server.cluster
        used = collections.Counter(
            p.node for p in store.pods.values() if p.node)
        used.update(br.selected_node for br in store.bind_requests.values()
                    if store.pods[br.pod_name].node is None)
        used.update(br.selected_node for br in binds)
        last = {}
        for br in binds:
            if _rack_required(run, store, _gang(br.pod_name)):
                last[_gang(br.pod_name)] = br
        for br in last.values():
            here = store.nodes[br.selected_node].labels
            for name, node in store.nodes.items():
                there = (node.labels[block_key], node.labels[rack_key])
                if (there != (here[block_key], here[rack_key])
                        and used[name] + 1 <= node.allocatable.accel):
                    used[br.selected_node] -= 1
                    used[name] += 1
                    br.selected_node = name
                    break
        return binds

    return _planted(move)


def rack_gang_dropped(run):
    """Run the block with every bind of a rack-required gang dropped
    before the scheduler commits it."""
    def drop(binds):
        store = run.server.cluster
        return [br for br in binds
                if not _rack_required(run, store, _gang(br.pod_name))]

    return _planted(drop)

