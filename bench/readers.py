"""Reductions the metric readers share: percentiles of request stamps,
span means, and the traced slice. Each returns None where the run holds
nothing to read."""
from __future__ import annotations

import numpy as np

from bench import yardstick


def percentile_ms(values, q: float):
    values = [v for v in values if v is not None]
    return float(np.percentile(values, q)) * 1e3 if values else None


def latency_ms(run, q: float):
    return percentile_ms([r.t_done - r.due for r in run.reqs
                          if r.t_done is not None], q)


def queue_wait_ms(run, q: float):
    return percentile_ms([r.t_admit - r.due for r in run.steady_reqs()
                          if r.t_admit is not None], q)


def step_ms_mean(run, name: str = "step"):
    spans = run.spans_named(name)
    return float(np.mean([s[2] - s[1] for s in spans])) * 1e3 if spans \
        else None


def traced_spans(run, name: str) -> list:
    """The spans of ``name`` whose middle lies in the traced slice."""
    t_on, t_off = run.traced
    return [s for s in run.spans
            if s[0] == name and t_on <= (s[1] + s[2]) / 2 <= t_off]


def traced_mfu_pct(run, name: str):
    """Ops of the images that the traced spans of ``name`` served (the
    configuration's ops per image) over the device busy time of the traced
    slice at the int8 peak, %."""
    spans = traced_spans(run, name)
    if run.trace is None or not spans or run.trace["busy_s"] <= 0:
        return None
    ops = sum(s[3] for s in spans) * yardstick.bcnn_ops_per_image(
        *yardstick.bcnn_layers(run.config))
    return 100.0 * ops / (run.trace["busy_s"] * run.peaks["int8_ops_per_s"])


def device_idle_pct(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def kernel_roofline_pct(run, family: str, layers, chunk: int):
    """Summed least time of ``layers``, each run once per ``chunk`` images
    by one call of the family's kernels, over the chunks the trace saw,
    over the device time of those calls, %."""
    if run.trace is None:
        return None
    t = run.trace["kernel_s"].get(family)
    calls = run.trace["kernel_calls"].get(family)
    if not t or not calls:
        return None
    least, _ = yardstick.kernel_least_time(layers, chunk, run.peaks)
    return 100.0 * least * (calls / len(layers)) / t
