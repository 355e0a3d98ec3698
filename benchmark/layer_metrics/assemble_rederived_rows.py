"""Running rows that fed a key the patch derived again
(``last_cycle.snapshot.rederived_rows``), as the window's last cycle
counted them; 0 where nothing changed, the running pods where every key
was touched."""
from lib.counters import last_snapshot


def read(run):
    return last_snapshot(run, "rederived_rows")
