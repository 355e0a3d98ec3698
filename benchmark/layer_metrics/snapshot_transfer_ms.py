"""``snapshot.transfer``: the one ``device_put`` of a rebuilt snapshot,
which the ``upload`` phase does not see."""
from lib.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "snapshot.transfer")
