"""``snapshot.ledgers``: the per-entity ledgers rebuilt after a full
build, so that a later cycle can patch."""
from lib.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "snapshot.ledgers")
