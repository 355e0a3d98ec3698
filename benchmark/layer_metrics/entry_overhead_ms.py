"""Client wall of ``/cycle/stored`` minus the cycle's own session
seconds from ``/healthz``, mean per cycle: HTTP, JSON, the state lock,
the intake coalesce and the commit document."""


def read(run):
    rows = [c["cycle_post_s"] - c["health"]["total_seconds"]
            for c in run.cycles if c.get("health")]
    return 1e3 * sum(rows) / len(rows) if rows else None
