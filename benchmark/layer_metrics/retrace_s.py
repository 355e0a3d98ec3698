"""Seconds JAX spent tracing and lowering (not compiling or loading)
up to the first cycle of the window: what a warm start still pays."""
from lib.spans import healths


def read(run):
    rows = healths(run, "startup")
    if not rows:
        return None
    return rows[0]["startup"]["trace_s"] + rows[0]["startup"]["lower_s"]
