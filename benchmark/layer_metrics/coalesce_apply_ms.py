"""``coalesce.apply``: the taken events through ``apply_events`` into
the stored cluster and its journal, a collector's pause inside it
included."""
from lib.request_spans import mean_request_ms


def read(run):
    return mean_request_ms(run, {"/cycle/stored": ("coalesce.apply",)})
