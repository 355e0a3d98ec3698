"""Client wall of ``/cycle/stored`` less the request's own seconds up to
the publication of its cycle (``requests["/cycle/stored"].total_seconds``):
the socket both ways, the reply's write, and whatever the program still
does not see.  Mean per cycle."""
from lib.spans import mean_ms


def read(run):
    return mean_ms([
        c["cycle_post_s"]
        - c["health"]["requests"]["/cycle/stored"]["total_seconds"]
        for c in run.cycles if "requests" in (c.get("health") or {})])
