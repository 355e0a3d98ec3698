"""How long a taken event waited in its lane, from the submit that
offered it to the coalesce that took it (``lanes.lane_wait_seconds.mean``),
mean per window cycle."""
from lib.spans import healths, mean_ms


def read(run):
    return mean_ms([h["lanes"]["lane_wait_seconds"]["mean"]
                    for h in healths(run, "lanes")])
