"""The intake lanes' staged events merged into the journal, under the
commit lock, before the cycle opens (``entry_seconds.coalesce``)."""
from lib.spans import healths, mean_ms


def read(run):
    return mean_ms([h["entry_seconds"]["coalesce"]
                    for h in healths(run, "entry_seconds")])
