"""The slowest single iteration of the window: the stall that a
whole-window mean hides."""


def read(run):
    return 1e3 * max(c["iter_s"] for c in run.cycles)
