"""The program's ``upload`` phase.  A rebuilt cycle transfers inside its
``snapshot`` phase (the wire ledger books it under ``fallback``), so this
reads 0 there and above 0 in a cycle that patches."""
from lib.phases import mean_phase_ms


def read(run):
    return mean_phase_ms(run, "upload")
