"""Traffic and inputs come from the seed alone."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import generator
from bench.configs import bcnn

ROOT = Path(__file__).resolve().parents[2]
# a mix with per-request sizes, as a later cell's data file would give it
SIZED = {"loop": "open", "arrivals": {"process": "poisson", "rate_hz": 2.0},
         "lengths": {"prompt_len": {"median": 96, "sigma": 0.7, "min": 16,
                                    "max": 512}}}
ONLINE = generator.load_mix(ROOT / "bench" / "traffic" / "online-poisson.json")
BIG = 2 ** 31 + 12345          # seeds above 32 signed bits are accepted


@pytest.mark.parametrize("mix", [SIZED, ONLINE], ids=["sized", "online"])
def test_one_seed_one_schedule(mix):
    a = generator.schedule(mix, BIG, 30.0)
    b = generator.schedule(mix, BIG, 30.0)
    assert np.array_equal(a.due, b.due)
    for k in a.sizes:
        assert np.array_equal(a.sizes[k], b.sizes[k])


@pytest.mark.parametrize("mix", [SIZED, ONLINE], ids=["sized", "online"])
def test_seeds_reorder_the_same_work(mix):
    a = generator.schedule(mix, 1, 30.0)
    b = generator.schedule(mix, BIG, 30.0)
    assert not np.array_equal(a.due, b.due)
    # the same multiset of gaps and sizes, inside the window
    assert a.n == b.n == round(mix["arrivals"]["rate_hz"] * 30.0)
    assert np.allclose(np.sort(np.diff(a.due, prepend=0)),
                       np.sort(np.diff(b.due, prepend=0)))
    assert 0 < a.due.min() and a.due.max() < 30.0
    for k in a.sizes:
        assert not np.array_equal(a.sizes[k], b.sizes[k])
        assert np.array_equal(np.sort(a.sizes[k]), np.sort(b.sizes[k]))


def test_lengths_follow_the_mix():
    s = generator.schedule(SIZED, 7, 200.0)
    for name, spec in SIZED["lengths"].items():
        x = s.sizes[name]
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 2


def test_images_and_tokens_from_the_seed():
    a = bcnn.make_images(BIG, 8, (32, 32, 3))
    assert np.array_equal(a, bcnn.make_images(BIG, 8, (32, 32, 3)))
    assert not np.array_equal(a, bcnn.make_images(BIG + 1, 8, (32, 32, 3)))
    assert a.dtype == np.float32 and 0 <= a.min() and a.max() < 1
    r1 = generator.rng_for(BIG, "prompts").integers(0, 50257, 64)
    r2 = generator.rng_for(BIG, "prompts").integers(0, 50257, 64)
    r3 = generator.rng_for(BIG + 1, "prompts").integers(0, 50257, 64)
    assert np.array_equal(r1, r2) and not np.array_equal(r1, r3)


def test_mix_files_are_data():
    for path in (ROOT / "bench" / "traffic").iterdir():
        assert path.suffix == ".json"
        assert json.loads(path.read_text())["loop"] in ("open", "closed")
