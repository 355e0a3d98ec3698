"""What the program says of a cycle from the inside, as ``/healthz``
serves it after each cycle of a traced run (``last_cycle``):
``span_self_seconds`` (self time of every span of the cycle's tree, by
its path from the root, names joined by ``/``), ``entry_seconds``, ``gc``
and ``startup``.  A program that serves none of these (one from before
they were added) gives every reader here ``None``."""
from __future__ import annotations


def healths(run, key: str) -> list:
    """The window cycles' health documents that carry ``key``."""
    return [c["health"] for c in run.cycles
            if c.get("health") and key in c["health"]]


def mean_ms(rows: list) -> float | None:
    """Seconds, one per cycle, as their mean in ms."""
    return 1e3 * sum(rows) / len(rows) if rows else None


def mean_span_ms(run, name: str, leave_out: tuple = ()) -> float | None:
    """Mean per window cycle of the span ``name`` with everything under
    it (the self times of every path through ``name``), in ms, but for
    what lies under a span in ``leave_out``."""
    return mean_ms([
        sum(secs for path, secs in h["span_self_seconds"].items()
            if name in path.split("/")
            and not set(leave_out) & set(path.split("/")))
        for h in healths(run, "span_self_seconds")])
