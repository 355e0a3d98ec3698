"""The whole window over all cycles completed in it, churn POSTs
included: not a median of cycles."""


def read(run):
    return 1e3 * run.window["seconds"] / len(run.cycles)
