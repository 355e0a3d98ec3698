"""Seconds the collector held the process outside the cycle's root:
inside the iteration's requests and between them
(``gc_iteration.in_requests`` + ``.between_requests``), all three
generations, mean per cycle."""
from lib.request_spans import outside_cycle
from lib.spans import healths, mean_ms


def read(run):
    return mean_ms([outside_cycle(h, "pause_seconds")
                    for h in healths(run, "gc_iteration")])
