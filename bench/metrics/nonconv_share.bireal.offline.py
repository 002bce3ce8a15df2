"""Share of the traced slice's device busy time outside the binary conv
kernels, %: the stem, the affine with its border correction, the residual
adds, the re-sign and re-pack, the downsamples and the head."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    conv = run.trace["kernel_s"].get("binconv", 0.0)
    return 100.0 * (1.0 - conv / run.trace["busy_s"])
