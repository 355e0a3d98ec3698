"""The least time the chip needs for one cycle's solve (memory-bound:
``lib/solve_bytes.py`` over the chip's HBM bandwidth) over the
device-busy time per cycle of the same trace."""
from lib.peaks import peaks_for
from lib.solve_bytes import solve_min_bytes


def read(run):
    t = run.trace
    if not t or not t["cycles"] or not t["busy_s"]:
        return None
    least_s = (solve_min_bytes(run.shapes, run.actions)
               / peaks_for(run.device["kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["busy_s"] / t["cycles"])
