"""Device idle time inside ``cycle_post`` that no span of the program
accounts for, per traced cycle: the profiler's idle there, less the
program's own phases in which the chip has nothing queued (all but
``device_wait``) and what the entry spent before the cycle opened.
Reported as a distance from 0."""
from lib.spans import healths

HOST_PHASES = ("snapshot", "upload", "solve_dispatch", "host_decode",
               "commit")


def read(run):
    t = run.trace
    if not t or not t["cycles"]:
        return None
    traced = healths(run, "entry_seconds")[:t["cycles"]]
    if len(traced) < t["cycles"]:
        return None
    idle = t["idle_by_host_span"].get("cycle_post", 0.0)
    own = sum(sum(h["phase_seconds"][p] for p in HOST_PHASES)
              + sum(h["entry_seconds"].values()) for h in traced)
    return 1e3 * abs(idle - own) / t["cycles"]
