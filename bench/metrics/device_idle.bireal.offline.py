"""Share of the traced slice in which no operation ran on the device, %."""
from bench import readers


def read(run):
    return readers.device_idle_pct(run)
