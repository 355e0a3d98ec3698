"""Distinct node-filter specs the last window cycle's snapshot held
(``last_cycle.snapshot.filter_classes``): 1, the empty spec, where
every pod is plain; a toleration is one more.  A node selector is no
filter class: it rides ``task_selector`` (``selector_keys``)."""
from lib.counters import last_snapshot


def read(run):
    return last_snapshot(run, "filter_classes")
