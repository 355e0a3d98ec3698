"""``patch.journal`` with its children (``journal.compact``,
``.removed``, ``.gangs``, ``.pods``): the journal's batch into the
ledgers of a patched cycle.  Read where the program serves ``requests``:
the children came with them."""
from lib.spans import healths, mean_span_ms


def read(run):
    if not healths(run, "requests"):
        return None
    return mean_span_ms(run, "patch.journal")
