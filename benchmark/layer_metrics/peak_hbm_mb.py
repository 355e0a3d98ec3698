def read(run):
    peak = run.window["memory_peak_bytes"]
    return peak / 1e6 if peak else None
