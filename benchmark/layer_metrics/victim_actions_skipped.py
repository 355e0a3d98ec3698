"""Victim actions of a cycle (reclaim, preempt, consolidation) whose
gate stayed closed: no pending gang was a viable preemptor, so the
action froze no order and built no per-tenant table
(``last_cycle.victim_actions_skipped``, one flag per action), mean per
window cycle; 3 where nobody waits for a victim."""
from lib.spans import healths


def read(run):
    rows = [sum(h["victim_actions_skipped"].values())
            for h in healths(run, "victim_actions_skipped")]
    return sum(rows) / len(rows) if rows else None
