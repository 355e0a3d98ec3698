"""``lock_wait`` and ``delta.apply`` of ``/cluster/delta``: the deletes
applied to the stored cluster under the state lock."""
from lib.request_spans import mean_request_ms


def read(run):
    return mean_request_ms(run, {"/cluster/delta": ("lock_wait",
                                                    "delta.apply")})
