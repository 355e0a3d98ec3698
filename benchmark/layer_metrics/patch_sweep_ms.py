"""``patch.sweep``: the guard against a stale answer that is left once
the store marks every write as it lands — three compares of the store's
pod, pod-group and node counts with the ledger's
(``state/incremental.py``: ``_check_membership``)."""
from lib.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "patch.sweep")
