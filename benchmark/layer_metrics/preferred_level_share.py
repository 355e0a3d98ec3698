"""Of the window's bound gangs that name a preferred topology level,
the share bound with all their pods inside one domain of it, in per
cent: the benchmark's reference counts it over the window's commits
(``lib/topology_model.py``).  A preferred level is best effort: this is
reported, not held.  ``None`` where no such gang was bound."""
from lib.topology_model import window_tallies


def read(run):
    tallies = window_tallies(run)
    bound = sum(t["preferred_bound"] for t in tallies)
    if not bound:
        return None
    return 100.0 * sum(t["preferred_together"] for t in tallies) / bound
