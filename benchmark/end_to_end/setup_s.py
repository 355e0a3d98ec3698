"""Process start to the start of the window: imports, cluster build,
server start, first cycle (compile or cache load), warm-up cycles."""


def read(run):
    return run.setup_s
