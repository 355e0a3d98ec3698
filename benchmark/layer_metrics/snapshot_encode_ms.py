"""``snapshot.encode`` with its sections: the objects flattened into
numpy leaves, on a rebuilt cycle."""
from lib.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "snapshot.encode")
