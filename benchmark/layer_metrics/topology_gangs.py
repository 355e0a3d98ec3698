"""Pending gangs a cycle's allocate attempted under a required topology
level (``last_cycle.topology``: ``required_attempted``, from the device
counter ``AllocationResult.topology_stats`` that rides the packed
commit), mean per window cycle.  A program that serves no such counter
gives ``None``."""
from lib.spans import healths


def read(run):
    rows = [h["topology"]["required_attempted"]
            for h in healths(run, "topology")]
    return sum(rows) / len(rows) if rows else None
