"""Live pod groups with declared subgroups
(``last_cycle.snapshot.nonplain_gangs``), which the snapshot's patch
cannot carry, as the window's last cycle counted them."""
from lib.counters import last_snapshot


def read(run):
    return last_snapshot(run, "nonplain_gangs")
