"""The Bi-Real cell's comparison, driven through the harness without a chip
at a size a test run can hold: a sound run passes, a broken timed path and
the control fail. And the cost model's counts at the published widths."""
import json
import shutil
from pathlib import Path

import pytest

from bench import bireal_cost, harness

ROOT = Path(__file__).resolve().parents[2]
CELL = "bireal18-offline-batch256"


def edit(path: Path, **changes) -> None:
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark with a 32x32 input, widths (32, 64, 64, 128), two
    blocks a stage, and 8-image bulk calls in 4-image chunks."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", r / "BENCHMARK.json")
    edit(r / "bench" / "traffic" / "offline-batch256.json", batch=8,
         pool_batches=2)
    cfg = r / "bench" / "configs" / "bireal18-imagenet.json"
    stem = dict(json.loads(cfg.read_text())["stem"], channels=32)
    edit(cfg, input_shape=[32, 32, 3], stem=stem, n_classes=10,
         stages=[[32, 2, 1], [64, 2, 2], [64, 2, 2], [128, 2, 2]],
         data_micro_batch=4, check_rows=16)
    return r


def run(root, fault=None, seconds=0.5):
    return harness.run_cell(CELL, 2 ** 33 + 5, seconds, False, root=root,
                            need_chip=False, fault=fault)


def test_sound_run_is_correct(root):
    res = run(root)
    assert res["correct"] is True and res["attempted"] > 0
    assert res["checks"]["logit_max_abs_diff"]["value"] <= 1e-4


@pytest.mark.parametrize("fault", ["answer", "half", "control"])
def test_a_broken_timed_path_and_the_control_are_caught(root, fault):
    """A planted fault, or the reference in bfloat16 served in the
    program's place, comes out not correct through the run's own
    comparison."""
    res = run(root, fault=fault)
    assert res["correct"] is False and res["failed"] == 0
    c = res["checks"]["logit_max_abs_diff"]
    assert c["value"] > c["limit"]


def test_cost_model_at_the_published_widths():
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "bireal18-imagenet.json").read_text())
    assert bireal_cost.binary_macs_per_image(cfg) == 1_676_279_808
    assert bireal_cost.real_macs_per_image(cfg) == 137_793_536
    real = bireal_cost.real_macs(cfg)
    assert real["stem"] == 118_013_952 and real["fc"] == 512_000
    downs = [v for k, v in real.items() if k.endswith(".down")]
    assert downs == [6_422_528] * 3
    assert len(bireal_cost.binary_convs(cfg)) == 16
