"""Faults planted under the timed path: each alters the commit document
where the server produces it (``framework/server._commit_doc``), so the
harness above sees a program that misbehaves.  ``partial_gang`` is the
control: it breaks one guarantee the configuration states, gang
all-or-nothing.  The others are the faults a served scheduler can have.
"""
from __future__ import annotations

import contextlib


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


FAULTS = {f.__name__: f for f in
          (partial_gang, state_unchanged, half_left_out, answer_altered)}


@contextlib.contextmanager
def planted(name: str | None):
    """Run the block with the named fault under ``POST /cycle/stored``;
    ``None`` plants nothing."""
    from kai_scheduler_tpu.framework import server
    sound = server._commit_doc
    if name is not None:
        fault = FAULTS[name]
        server._commit_doc = lambda result: fault(sound(result))
    try:
        yield
    finally:
        server._commit_doc = sound
