"""The program's own phase spans, as ``/healthz`` serves them after each
cycle of a traced run (``last_cycle.phase_seconds``)."""
from __future__ import annotations


def mean_phase_ms(run, *phases: str) -> float | None:
    """Mean per window cycle of the named phases' sum, in ms; ``None``
    where no cycle carries a health document."""
    rows = [c["health"]["phase_seconds"] for c in run.cycles
            if c.get("health")]
    if not rows:
        return None
    return 1e3 * sum(sum(r[p] for p in phases) for r in rows) / len(rows)
