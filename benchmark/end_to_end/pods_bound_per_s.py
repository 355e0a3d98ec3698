"""All ``bind_requests`` of the window's commits over the window's
seconds.  Nothing where the window bound nothing."""


def read(run):
    bound = sum(c["binds"] for c in run.cycles)
    return bound / run.window["seconds"] if bound else None
