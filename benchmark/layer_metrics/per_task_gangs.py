"""Pending gangs a cycle's allocate placed through the per-task kernel:
the cycle's pending gangs where the session chose it
(``last_cycle.kernels``: ``uniform_tasks`` false), 0 where every gang
went through the whole-gang kernel; mean per window cycle."""
from lib.spans import healths


def read(run):
    rows = [0 if h["kernels"]["uniform_tasks"]
            else h["kernels"]["pending_gangs"]
            for h in healths(run, "kernels")]
    return sum(rows) / len(rows) if rows else None
