"""The whole bulk forward's share of the int8 peak: ops of the images in
the calls traced in the slice (Table 2: 1,233,932,288 per image) over the
device busy time of the slice at the int8 peak, %."""
from bench import readers


def read(run):
    return readers.traced_mfu_pct(run, "call")
