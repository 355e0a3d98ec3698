"""Seconds the collector held the process inside a cycle, all three
generations, mean per cycle."""
from lib.spans import healths, mean_ms


def read(run):
    return mean_ms([sum(h["gc"]["pause_seconds"])
                    for h in healths(run, "gc")])
