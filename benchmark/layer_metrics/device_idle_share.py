"""1 - (union of device-op intervals) / traced span, over the first
cycles of the traced window."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
