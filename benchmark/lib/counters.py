"""What the program counts of a cycle's snapshot, as ``/healthz`` serves
it after each cycle of a traced run (``last_cycle.snapshot``, the
snapshotter's ``stats.last``).  A program that does not count ``key``
gives ``None``."""
from __future__ import annotations

from .spans import healths


def last_snapshot(run, key: str):
    """``key`` as the window's last cycle that carries it read it."""
    rows = [h["snapshot"][key] for h in healths(run, "snapshot")
            if key in h["snapshot"]]
    return rows[-1] if rows else None
