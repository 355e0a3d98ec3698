"""Events the cycle's own thread had to admit in the coalesce's
pre-drain (``lanes.admitted_in_coalesce``), mean per window cycle."""
from lib.spans import healths


def read(run):
    rows = [h["lanes"]["admitted_in_coalesce"] for h in healths(run, "lanes")]
    return sum(rows) / len(rows) if rows else None
