"""Ops of the images answered by the traced steps (Table 2: 1,233,932,288
per image) over the device busy time of the traced slice at the int8
peak, %."""
from bench import readers


def read(run):
    return readers.traced_mfu_pct(run, "step")
