"""The ``snapshot`` phase of the process's first cycle: the cold full
build."""
from lib.spans import healths


def read(run):
    rows = healths(run, "startup")
    return rows[0]["startup"]["phase_seconds"]["snapshot"] if rows else None
