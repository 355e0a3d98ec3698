"""``patch.sweep``: the compare of every pod, gang and node with the
ledger that keeps an unjournaled write from being served stale."""
from lib.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "patch.sweep")
