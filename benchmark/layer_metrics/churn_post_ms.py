"""Client wall of the two churn POSTs, mean per cycle."""


def read(run):
    if not any(run.mix["per_cycle"].values()):
        return None
    return 1e3 * sum(c["churn_post_s"] for c in run.cycles) / len(run.cycles)
