"""Least time of Bi-Real's binary convs (kernels/xnor_conv.py, counts out)
over the device time of their kernel events in the traced slice, %. One
chunk of the bulk forward runs each binary conv once; the least time is
the convs' summed roofline for a chunk (bench/bireal_cost.py), times the
chunks the trace saw."""
from bench import bireal_cost


def read(run):
    if run.trace is None:
        return None
    t = run.trace["kernel_s"].get("binconv")
    calls = run.trace["kernel_calls"].get("binconv")
    if not t or not calls:
        return None
    convs = bireal_cost.binary_convs(run.config)
    least = bireal_cost.binconv_least_time(
        run.config, int(run.config["data_micro_batch"]), run.peaks)
    return 100.0 * least * (calls / len(convs)) / t
