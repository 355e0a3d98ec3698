"""``encode.filters``: every distinct node-filter spec (taints against
tolerations, affinity, ports) evaluated against every node on the host,
on a rebuilt cycle.  A program from before the section had its own span
booked this time under ``encode.rollups`` and reads nothing here."""
from lib.spans import healths, mean_span_ms


def read(run):
    if not any("/encode.filters" in path
               for h in healths(run, "span_self_seconds")
               for path in h["span_self_seconds"]):
        return None
    return mean_span_ms(run, "encode.filters")
