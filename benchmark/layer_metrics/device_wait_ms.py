from lib.phases import mean_phase_ms


def read(run):
    return mean_phase_ms(run, "device_wait")
