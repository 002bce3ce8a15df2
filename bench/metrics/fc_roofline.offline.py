"""Least time of the FC layers (kernels/xnor_matmul.py XNOR matmul) over
the device time of its kernel events in the traced slice, %. One chunk of
the bulk forward runs each FC layer once."""
from bench import readers, yardstick


def read(run):
    _, fcs = yardstick.bcnn_layers(run.config)
    return readers.kernel_roofline_pct(
        run, "fc", fcs, int(run.config["data_micro_batch"]))
