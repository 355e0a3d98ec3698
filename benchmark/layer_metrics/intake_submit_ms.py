"""``intake.submit`` of ``/intake``: decompose, the admission probe and
the lane offers."""
from lib.request_spans import mean_request_ms


def read(run):
    return mean_request_ms(run, {"/intake": ("intake.submit",)})
