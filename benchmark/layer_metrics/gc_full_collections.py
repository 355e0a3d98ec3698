"""Full (generation-2) collections that ended inside a cycle of the
window."""
from lib.spans import healths


def read(run):
    rows = healths(run, "gc")
    return sum(h["gc"]["collections"][2] for h in rows) if rows else None
