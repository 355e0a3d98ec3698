"""Full (generation-2) collections of the window that ended outside a
cycle's root: inside the iteration's requests or between them."""
from lib.request_spans import outside_cycle
from lib.spans import healths


def read(run):
    rows = healths(run, "gc_iteration")
    return (sum(outside_cycle(h, "collections", (2,)) for h in rows)
            if rows else None)
