"""Live pods the snapshot's patch cannot carry
(``last_cycle.snapshot.nonplain_pods``: a declared subgroup, an affinity
term, a port, a fraction, a claim), as the window's last cycle counted
them; one is enough to rebuild every cycle."""
from lib.counters import last_snapshot


def read(run):
    return last_snapshot(run, "nonplain_pods")
