"""The whole Bi-Real forward's share of the chip's peak: the least compute
time of the images that the traced calls served (binary ops at the int8
peak, real FLOPs at the bf16 peak; bench/bireal_cost.py) over the device
busy time of the traced slice, %."""
from bench import bireal_cost, readers


def read(run):
    spans = readers.traced_spans(run, "call")
    if run.trace is None or not spans or run.trace["busy_s"] <= 0:
        return None
    least = sum(s[3] for s in spans) * bireal_cost.least_compute_s_per_image(
        run.config, run.peaks)
    return 100.0 * least / run.trace["busy_s"]
