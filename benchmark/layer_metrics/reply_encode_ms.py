"""``record`` (the ``/healthz`` document: what tracing costs a cycle)
and ``reply.encode`` (the commit document and its JSON) of
``/cycle/stored``."""
from lib.request_spans import mean_request_ms


def read(run):
    return mean_request_ms(run, {"/cycle/stored": ("record",
                                                   "reply.encode")})
