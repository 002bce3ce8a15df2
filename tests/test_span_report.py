"""tools/span_report.py: the breakdown of a benchmark cell's time by the
program's spans, on hand-made spans and traces, and through the harness on
the CPU at a size a test run can hold."""
import importlib.util
import json
import shutil
import sys
from pathlib import Path

import pytest

from repro.serve import Span

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _load():
    spec = importlib.util.spec_from_file_location(
        "span_report", ROOT / "tools" / "span_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sr = _load()


def step(sid, t0, parts, parent=-1):
    """An ``engine.step`` span from t0 and its children, one after another:
    parts = [(name, seconds)]."""
    out, t = [], t0
    for k, (name, d) in enumerate(parts):
        out.append(Span(name, t, t + d, sid + 1 + k, sid))
        t += d
    return [Span("engine.step", t0, t, sid, parent)] + out


ONLINE = [("engine.admit", 0.001), ("engine.put", 0.0005),
          ("engine.dispatch", 0.0002), ("engine.wait", 0.0012),
          ("engine.readback", 0.0001), ("engine.complete", 0.0003)]


# ------------------------------------------------------------- the readers
def test_host_and_wait_split_each_step():
    spans = step(0, 1.0, ONLINE) + step(10, 2.0, ONLINE[:3] + [
        ("engine.wait", 0.0022)] + ONLINE[4:])
    rep = sr.host_and_wait(spans, "engine.step")
    assert rep["n"] == 2
    host = sum(d for n, d in ONLINE if n != "engine.wait")
    assert rep["host_ms"] == pytest.approx(host * 1e3)
    assert rep["wait_ms"] == pytest.approx(1.7)           # median of two
    assert rep["parts_ms"]["engine.admit"] == pytest.approx(1.0)
    assert sorted(rep["parts_ms"]) == sorted(n for n, _ in ONLINE)


def test_a_step_without_a_wait_is_not_counted():
    spans = step(0, 0.0, [("engine.admit", 0.001)])
    rep = sr.host_and_wait(spans, "engine.step")
    assert rep["n"] == 0 and rep["host_ms"] is None and rep["wait_ms"] is None


def test_bulk_calls_split_at_their_wait():
    call = [Span("engine.classify_batch", 0.0, 0.05, 0, -1),
            Span("bulk.put", 0.0, 0.002, 1, 0),
            Span("bulk.dispatch", 0.002, 0.006, 2, 0),
            Span("bulk.wait", 0.006, 0.049, 3, 0),
            Span("bulk.readback", 0.049, 0.05, 4, 0)]
    rep = sr.host_and_wait(call, "engine.classify_batch")
    assert rep["host_ms"] == pytest.approx(7.0)
    assert rep["wait_ms"] == pytest.approx(43.0)


def test_window_spans_shift_to_the_window_and_stop_at_its_steady_end():
    spans = step(0, 100.0, ONLINE) + step(10, 104.0, ONLINE)
    got = sr.window_spans(spans, 100.0, 3.0)
    assert [s.id for s in got] == [s.id for s in spans[:7]]
    assert got[0].t0 == pytest.approx(0.0)
    # a span that started before the window opened is left out too
    assert sr.window_spans(spans, 100.5, 10.0)[0].id == 10


def test_a_stall_is_put_down_to_the_innermost_span_it_fell_in():
    spans = step(0, 1.0, ONLINE)
    put = spans[2]
    assert put.name == "engine.put"
    # a stall inside the put; one across the whole step, which goes to
    # the longest part, the wait; one outside every span
    got = sr.stall_spans([(put.t0 + 1e-4, 2e-4), (1.0, 0.0031),
                          (5.0, 0.1)], spans)
    assert [g[2] for g in got] == ["engine.put", "engine.wait", "none"]
    assert got[0][3] == pytest.approx(2e-4)
    # a closed loop gives each stall its call's interval
    got = sr.stall_spans([(1.0, 0.0001)], spans, [(1.0, 1.0008)])
    assert got[0][2] == "engine.admit"


# ------------------------------------------------------- the device trace
EVENTS = {
    "device": {"/device:TPU:0": [
        ["xnor_conv2d.5", 100, 200, "conv2"], ["fusion", 300, 100, "conv2"],
        ["pad.1", 600, 50, "source:bcnn_data_parallel.py:196"],
        ["xnor_matmul.3", 950, 100, "fc1"]]},
    "host": [["bench.window", 0, 1000], ["bench.step", 50, 800],
             ["repro.engine.step", 60, 780], ["repro.engine.wait", 100, 300],
             ["repro.engine.put", 420, 100]],
}


def test_gaps_are_labelled_by_the_innermost_program_span():
    r = sr.reduce(EVENTS)
    # gaps: 0..100 (mid 50: the benchmark's step only), 400..600 (mid 500:
    # the put), 650..950 (mid 800: the program's step)
    assert r["idle_gaps"] == [["repro.engine.step", pytest.approx(300e-9)],
                              ["repro.engine.put", pytest.approx(200e-9)],
                              ["bench.step", pytest.approx(100e-9)]]
    assert r["idle_by_span"]["repro.engine.put"] == pytest.approx(200e-9)


def test_layer_seconds_come_from_the_scopes_inside_the_window():
    r = sr.reduce(EVENTS)
    assert r["layer_s"] == {"conv2": pytest.approx(300e-9),
                            "source:bcnn_data_parallel.py:196":
                                pytest.approx(50e-9),
                            "fc1": pytest.approx(50e-9)}      # clipped
    assert r["device_ops"][0] == ["xnor_conv2d.5", "conv2",
                                  pytest.approx(200e-9)]


def test_scope_is_the_layer_in_the_framework_name_else_the_source_line():
    # the stats of operations in a TPU v5e trace of the bulk path
    assert sr.scope_of({"tf_op": "jit(fwd)/conv3_4/jit(xnor_conv2d_pair)/"
                                 "pallas_call",
                        "source": "src/repro/kernels/ops.py:310"}) == \
        "conv3_4"
    assert sr.scope_of({"tf_op": "jit(fwd)/conv1/jit(round)/round:"}) == \
        "conv1"
    assert sr.scope_of({"tf_op": "jit(dynamic_slice)/dynamic_slice",
                        "source": "src/repro/parallel/bcnn_data_parallel.py:"
                                  "196"}) == \
        "source:bcnn_data_parallel.py:196"
    assert sr.scope_of({"hlo_category": "copy-start"}) == "none"


def test_bireal_scopes_down_to_the_block_and_summed_by_stage():
    assert sr.scope_of({"tf_op": "jit(fwd)/shard_map/bireal/stage2/block1/"
                                 "down/dot_general"}) == \
        "bireal/stage2/block1/down"
    assert sr.scope_of({"tf_op": "jit(fwd)/bireal/stage1/block3/"
                                 "jit(xnor_conv2d)/pallas_call"}) == \
        "bireal/stage1/block3"
    assert sr.scope_of({"tf_op": "jit(fwd)/bireal/head/reduce_sum"}) == \
        "bireal/head"
    events = {"device": {"/device:TPU:0": [
        ["xnor_conv2d.3", 100, 200, "bireal/stage1/block1"],
        ["fusion.2", 300, 100, "bireal/stage1/block2"],
        ["dot.1", 400, 50, "bireal/stage2/block1/down"],
        ["fusion.9", 450, 25, "bireal/head"]]},
        "host": [["bench.window", 0, 1000]]}
    r = sr.reduce(events)
    assert r["stage_s"] == {"bireal/stage1": pytest.approx(300e-9),
                            "bireal/stage2": pytest.approx(50e-9),
                            "bireal/head": pytest.approx(25e-9)}


# An XSpace with one TPU plane, serialized by protobuf from
# tsl/profiler/protobuf/xplane.proto: one operation whose metadata holds a
# string stat (its framework name), a reference stat and an integer stat,
# and one event of it on the "XLA Ops" line.
XSPACE = bytes.fromhex(
    "0ad2010803120d2f6465766963653a5450553a301a111207584c41204f7073220608"
    "011005186422630801125f0801121a25667573696f6e2e35203d206633325b5d2066"
    "7573696f6e28292208667573696f6e2e352a2808072a246a69742873746570292f63"
    "6f6e76312f636f6e765f67656e6572616c5f64696c617465642a04080838092a0508"
    "0a18b9602a0d080712090807120574665f6f702a0d080a1209080a1205666c6f7073"
    "2a130809120f0809120b636f6e766f6c7574696f6e2a14080812100808120c686c6f"
    "5f63617465676f7279")


def test_operation_metadata_is_read_from_the_serialized_profile():
    stats = {"tf_op": "jit(step)/conv1/conv_general_dilated",
             "hlo_category": "convolution"}
    assert sr.op_metadata(XSPACE) == {"/device:TPU:0": {
        "%fusion.5 = f32[] fusion()": stats, "fusion.5": stats}}
    assert sr.scope_of(stats) == "conv1"


# ------------------------------------------------- through the harness
def edit(path: Path, **changes) -> None:
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark with the small traffic of the bench fault tests."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", r / "BENCHMARK.json")
    edit(r / "bench" / "traffic" / "online-poisson.json",
         arrivals={"process": "poisson", "rate_hz": 40}, image_pool=16)
    edit(r / "bench" / "traffic" / "offline-batch512.json", batch=32,
         pool_batches=2)
    edit(r / "bench" / "configs" / "bcnn-table2.json", data_micro_batch=16,
         check_rows=32)
    return r


@pytest.mark.parametrize("workload,top", [
    ("bcnn-online-poisson", "engine.step"),
    ("bcnn-offline-batch512", "engine.classify_batch")])
def test_a_traced_cpu_run_reads_the_program_spans(root, tmp_path, workload,
                                                 top):
    from bench import harness
    saved = (harness.drive_open, harness.drive_closed,
             harness.devtrace.extract)
    # the window is longer than the traced slice, so the steady part holds
    # whole steps or calls
    line = sr.report(workload, 2 ** 31 + 7, 3.6, True, True, root=root,
                     need_chip=False, out=tmp_path)
    assert (harness.drive_open, harness.drive_closed,
            harness.devtrace.extract) == saved
    assert line["result"]["correct"] is True
    rep = line["spans"]
    assert rep["top"] == top and rep["n"] >= 1 and rep["dropped"] == 0
    assert rep["host_ms"] > 0 and rep["wait_ms"] > 0
    assert len(rep["stalls"]) >= 1
    # the online engine has no bulk path; every offline call's batch
    # crossed in the wire form
    counts = line["bulk_input_counts"]
    if top == "engine.step":
        assert counts == {}
    else:
        assert counts["wire"] >= 1 and counts["device_relayout"] == 0
    assert "trace" not in line                  # no device plane on a CPU
    assert len(list(tmp_path.glob("*.json"))) == 1
