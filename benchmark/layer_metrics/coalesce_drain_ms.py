"""``coalesce.drain``: the lanes' admission done on the cycle's own
thread, because the client posted ``/cycle/stored`` before the lane
workers had run."""
from lib.request_spans import mean_request_ms


def read(run):
    return mean_request_ms(run, {"/cycle/stored": ("coalesce.drain",)})
