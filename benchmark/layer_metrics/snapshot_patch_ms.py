"""``snapshot.patch`` with its children (journal, sweep, assemble): the
host work of a patched cycle, its ``upload`` left out."""
from lib.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "snapshot.patch", leave_out=("upload",))
