"""Pods of a cycle's intake that took the generic parser and not the
fast path for plain pods (``last_cycle.intake_parsed_pods``), mean per
window cycle."""
from lib.spans import healths


def read(run):
    rows = [h["intake_parsed_pods"]
            for h in healths(run, "intake_parsed_pods")]
    return sum(rows) / len(rows) if rows else None
