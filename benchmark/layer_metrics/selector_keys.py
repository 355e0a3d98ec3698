"""Node-label keys that some pod's ``nodeSelector`` names, as the last
window cycle's snapshot held them (``last_cycle.snapshot.selector_keys``):
the width of ``task_selector`` and of the node-label table."""
from lib.counters import last_snapshot


def read(run):
    return last_snapshot(run, "selector_keys")
