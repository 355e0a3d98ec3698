"""``http.read`` and ``body.parse`` of the two churn POSTs: the bodies
off the socket and through ``json.loads``."""
from lib.request_spans import mean_request_ms


def read(run):
    names = ("http.read", "body.parse")
    return mean_request_ms(run, {"/cluster/delta": names, "/intake": names})
