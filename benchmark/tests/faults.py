"""Faults planted under the timed path, so the harness above sees a
program that misbehaves.  ``partial_gang`` is the control: it breaks one
guarantee every configuration states, gang all-or-nothing.
``state_unchanged``, ``half_left_out`` and ``answer_altered`` are the
faults a served scheduler can have; each alters the commit document
where the server produces it (``framework/server._commit_doc``).  The
rest break a guarantee one configuration states beside those, and are
kept with it: ``pool_faults.py`` (``placement``), ``kubeflow_faults.py``
(``subgroup_quorum``), ``topology_faults.py`` (``topology_required``).
``FAULTS`` names them all; ``planted(name, run)`` plants one.
"""
from __future__ import annotations

import contextlib

import kubeflow_faults
import pool_faults
import topology_faults


def _gang(bind: dict) -> str:
    """A gang's pods share the name up to ``-pod-<t>``."""
    return bind["pod"].rsplit("-pod-", 1)[0]


def partial_gang(doc: dict) -> dict:
    """The control: the last pod of every bound gang is left out, so each
    gang is bound below ``min_member``."""
    seen, kept = set(), []
    for br in reversed(doc["bind_requests"]):
        gang = _gang(br)
        if gang in seen:
            kept.append(br)
        seen.add(gang)
    return dict(doc, bind_requests=kept[::-1])


def state_unchanged(doc: dict) -> dict:
    """A cycle that returns having decided nothing."""
    return dict(doc, bind_requests=[], evictions=[])


def half_left_out(doc: dict) -> dict:
    """Half of the batch left out: every second gang's binds and every
    second eviction are dropped."""
    gangs = sorted({_gang(br) for br in doc["bind_requests"]})
    dropped = set(gangs[1::2]) or set(gangs)
    return dict(doc,
                bind_requests=[br for br in doc["bind_requests"]
                               if _gang(br) not in dropped],
                evictions=doc["evictions"][::2])


def answer_altered(doc: dict) -> dict:
    """Answers altered where they are produced: every bind names the
    node of the commit's first bind."""
    binds = doc["bind_requests"]
    return dict(doc, bind_requests=[dict(br, node=binds[0]["node"])
                                    for br in binds])


@contextlib.contextmanager
def under_commit(fault):
    """Run the block with ``fault`` (a function of the commit document)
    under ``POST /cycle/stored``."""
    from kai_scheduler_tpu.framework import server
    sound = server._commit_doc
    server._commit_doc = lambda result: fault(sound(result))
    try:
        yield
    finally:
        server._commit_doc = sound


#: name -> a function of the ``lib.loop.Run`` about to start that gives
#: the context manager planting the fault
FAULTS = {
    **{f.__name__: (lambda run, f=f: under_commit(f)) for f in
       (partial_gang, state_unchanged, half_left_out, answer_altered)},
    "wrong_pool": lambda run: under_commit(pool_faults.wrong_pool(run)),
    "mixed_gangs_left_pending": kubeflow_faults.mixed_gangs_left_pending,
    "launcher_left_out": kubeflow_faults.launcher_left_out,
    "pod_in_another_rack": topology_faults.pod_in_another_rack,
    "rack_gang_dropped": topology_faults.rack_gang_dropped,
}


def planted(name: str | None, run):
    """The named fault planted for ``run``; ``None`` plants nothing."""
    return contextlib.nullcontext() if name is None else FAULTS[name](run)
