"""``patch.assemble`` with its children (``assemble.gather``, ``.tables``,
``.rederive``, ``.index``): building the host state of a patched cycle."""
from lib.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "patch.assemble")
