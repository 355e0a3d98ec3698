"""Placement attempts of a cycle's allocate whose domain gate found no
domain of the gang's required level with room for all of it
(``last_cycle.topology``: ``domain_misses``, from the device counter
``AllocationResult.topology_stats``), mean per window cycle; 0 while
every arrival finds a rack and a block with room.  A program that
serves no such counter gives ``None``."""
from lib.spans import healths


def read(run):
    rows = [h["topology"]["domain_misses"]
            for h in healths(run, "topology")]
    return sum(rows) / len(rows) if rows else None
