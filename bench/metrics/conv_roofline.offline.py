"""Least time of the binary convs, CONV-2 onwards (kernels/xnor_conv.py),
over the device time of the conv kernels' events in the traced slice, %.
One chunk of the bulk forward runs each binary conv once."""
from bench import readers, yardstick


def read(run):
    convs, _ = yardstick.bcnn_layers(run.config)
    return readers.kernel_roofline_pct(
        run, "conv", convs[1:], int(run.config["data_micro_batch"]))
