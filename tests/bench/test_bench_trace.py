"""The reduction from a device trace to busy, idle and kernel time."""
import json
from pathlib import Path

import pytest

from bench import devtrace

DATA = Path(__file__).with_name("data")

# one device; times in ns. Window 0..1000. Ops: a kernel 100..300 and
# 250..400 (overlap), a fusion 600..700, one op 950..1100 past the close
SMALL = {
    "device": {"/device:TPU:0": [
        ["xnor_conv2d.5", 100, 200], ["xnor_matmul.2", 250, 150],
        ["fusion.3", 600, 100], ["xnor_conv2d.6", 950, 150],
        ["fusion.9", -50, 80]]},
    "host": [["bench.window", 0, 1000], ["bench.step", 380, 300],
             ["bench.sleep", 700, 250], ["bench.submit", 420, 30]],
}


def test_busy_idle_and_kernels_on_a_hand_made_trace():
    r = devtrace.reduce(SMALL, {"conv": ("xnor_conv2d",),
                                "fc": ("xnor_matmul",)})
    # busy: 0..30 (clipped), 100..400, 600..700, 950..1000
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((30 + 300 + 100 + 50) * 1e-9)
    assert r["kernel_s"]["conv"] == pytest.approx((200 + 50) * 1e-9)
    assert r["kernel_calls"] == {"conv": 2, "fc": 1}
    assert r["kernel_s"]["fc"] == pytest.approx(150e-9)
    # gaps, longest first: 700..950 (sleep), 400..600 (step; the submit
    # span does not cover its middle), 30..100 (no span)
    assert r["idle_gaps"] == [["sleep", pytest.approx(250e-9)],
                              ["step", pytest.approx(200e-9)],
                              ["none", pytest.approx(70e-9)]]
    assert r["device_ops"][0] == ["xnor_conv2d.5", pytest.approx(200e-9)]


def test_an_op_is_named_by_its_instruction_not_its_operands():
    text = ("%fusion.4 = s32[16,32,32,4] fusion(s32[16,32,32,128] "
            "%xnor_conv2d.5), kind=kLoop")
    assert devtrace.op_name(text) == "fusion.4"
    assert devtrace.family("xnor_conv2d.5") == "xnor_conv2d"
    assert devtrace.family("xnor_conv2d_pair.12") == "xnor_conv2d_pair"
    assert devtrace.family("copy-start") == "copy-start"


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce({"device": SMALL["device"], "host": []})


def _busy_by_sweep(ops, t0, t1):
    """Busy ns by a sweep over start/end events, independent of union()."""
    edges = []
    for _, s, d in ops:
        s, e = max(s, t0), min(s + d, t1)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    busy, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_offline_trace():
    """A 120 ms excerpt of a TPU v5e trace of the offline cell."""
    ev = json.loads((DATA / "offline_trace_excerpt.json").read_text())
    r = devtrace.reduce(ev, {"conv": ("xnor_conv2d", "xnor_conv2d_pair"),
                             "fc": ("xnor_matmul",)})
    t0, t1 = devtrace.window_of(ev)
    ops = ev["device"]["/device:TPU:0"]
    assert r["busy_s"] == pytest.approx(_busy_by_sweep(ops, t0, t1) / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    conv = sum(min(s + d, t1) - max(s, t0) for n, s, d in ops
               if n.startswith("xnor_conv2d.") and s < t1 and s + d > t0)
    assert r["kernel_s"]["conv"] == pytest.approx(conv / 1e9)
    # one bulk chunk runs five binary convs and three XNOR matmuls
    assert abs(r["kernel_calls"]["conv"] / 5 - r["kernel_calls"]["fc"] / 3) \
        <= 1
    assert r["kernel_s"]["conv"] + r["kernel_s"]["fc"] <= r["busy_s"]
    assert len(r["idle_gaps"]) == 10
    assert all(g[1] > 0 for g in r["idle_gaps"])


def test_mfu_counts_the_images_of_the_traced_calls():
    """The whole step's share of the int8 peak comes from the traced calls'
    images and the device's busy time, not from the host's clock."""
    from bench import harness, readers, yardstick
    cfg = json.loads((Path(__file__).resolve().parents[2] / "bench"
                      / "configs" / "bcnn-table2.json").read_text())
    run = harness.Run(5.0, config=cfg,
                      peaks=yardstick.load_peaks("TPU v5 lite"))
    # calls of 512 images; the middles of the 2nd..4th lie in the slice
    run.spans = [("call", t, t + 0.9, 512) for t in (0.0, 1.0, 2.0, 3.0,
                                                      4.5)]
    run.traced = (1.2, 4.4)
    assert readers.traced_mfu_pct(run, "call") is None       # no trace
    run.trace = {"busy_s": 0.5, "window_s": 3.2}
    want = 100.0 * 3 * 512 * 1_233_932_288 / (0.5 * 393e12)
    assert readers.traced_mfu_pct(run, "call") == pytest.approx(want)
    assert readers.traced_mfu_pct(run, "step") is None
