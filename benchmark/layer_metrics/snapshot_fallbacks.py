"""Window cycles whose snapshot was a full rebuild: the cycle's wire
summary books uploads under ``fallback`` or ``full-build`` instead of
``journal-patch``.  0 where every cycle patches; a pod-group delete is
structural to the program today, so a cycle after any gang finished or
was evicted counts."""


def read(run):
    rows = [c["health"]["wire"]["by_reason"] for c in run.cycles
            if c.get("health")]
    if not rows:
        return None
    return sum(1 for r in rows if "fallback" in r or "full-build" in r)
