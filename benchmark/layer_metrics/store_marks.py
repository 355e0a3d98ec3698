"""Marks the store's own write hooks put into the journal that a cycle's
snapshot consumed (``last_cycle.snapshot.store_marks``, by pods, gangs,
nodes and bind requests; explicit marks not counted), summed, mean per
window cycle.  A program whose store does not mark its writes gives
``None``."""
from lib.spans import healths


def read(run):
    rows = [sum(h["snapshot"]["store_marks"].values())
            for h in healths(run, "snapshot")
            if "store_marks" in h["snapshot"]]
    return sum(rows) / len(rows) if rows else None
