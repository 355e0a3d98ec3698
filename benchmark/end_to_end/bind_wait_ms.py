"""Mean, over every gang bound in the window, of the time from the start
of the ``/intake`` POST that submitted it to the return of the
``/cycle/stored`` whose commit binds it: how long a submitted job waits.

The mean and not a tail: one client drives cycle after cycle, so a gang's
wait is a whole number of iterations and a window holds as many
independent waits as cycles, 20 to 30 at today's cycle times; a 95th
percentile of those is the slowest or second slowest and spreads by more
than a bound may be (PERF.md).  The quantiles ride in the result line's
``window.bind_wait_ms``."""


def read(run):
    waits = [w for c in run.cycles for w in c["bind_wait_s"]]
    return 1e3 * sum(waits) / len(waits) if waits else None
