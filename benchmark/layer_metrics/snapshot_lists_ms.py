"""``snapshot.lists``: the cluster's objects listed and counted, on a
rebuilt cycle."""
from lib.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "snapshot.lists")
