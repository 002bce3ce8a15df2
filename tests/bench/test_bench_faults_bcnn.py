"""The BCNN cells' comparison, driven through the harness without a chip at
a size a test run can hold: sound runs pass, a broken timed path and the
control fail."""
import json
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def edit(path: Path, **changes) -> None:
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark with smaller traffic: a few images at a low rate, and
    32-image bulk calls in 16-image chunks."""
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


def run(root, workload, fault=None, seconds=0.25):
    return harness.run_cell(workload, 2 ** 31 + 7, seconds, False,
                            root=root, need_chip=False, fault=fault)


def test_online_sound_run_is_correct(root):
    res = run(root, "bcnn-online-poisson")
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["logit_max_abs_diff"]["value"] <= 1e-4


def test_offline_sound_run_is_correct(root):
    res = run(root, "bcnn-offline-batch512", seconds=0.5)
    assert res["correct"] is True and res["attempted"] > 0
    assert res["checks"]["logit_max_abs_diff"]["value"] <= 1e-4


@pytest.mark.parametrize("workload,fault", [
    ("bcnn-online-poisson", "answer"),
    ("bcnn-offline-batch512", "answer"),
    ("bcnn-offline-batch512", "half"),
])
def test_a_broken_timed_path_is_caught(root, workload, fault):
    res = run(root, workload, fault=fault, seconds=0.5)
    assert res["correct"] is False
    c = res["checks"]["logit_max_abs_diff"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("workload", ["bcnn-online-poisson",
                                      "bcnn-offline-batch512"])
def test_control_bfloat16_reference_fails_the_limit(root, workload):
    """The reference in bfloat16, served in the program's place, comes out
    not correct through the run's own comparison."""
    res = run(root, workload, fault="control", seconds=0.5)
    assert res["correct"] is False and res["failed"] == 0
    c = res["checks"]["logit_max_abs_diff"]
    assert c["limit"] < c["value"] < 1.0
