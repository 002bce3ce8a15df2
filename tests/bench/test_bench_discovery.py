"""A configuration, a traffic mix and a metric dropped into their
directories are found by name, with no edit to any file already there."""
import json
import shutil
from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]

FAMILY = '''
"""A toy family: answers each request on the step after it arrives."""
class Echo:
    last_step_items = 0

    def __init__(self, cfg, sched, fault):
        self.cfg, self.queue, self.steps = cfg, [], 0
        self.answers = {}
        self.fault = fault

    def warmup(self):
        pass

    def submit(self, i):
        self.queue.append(i)

    @property
    def active(self):
        return bool(self.queue)

    def step(self):
        self.steps += 1
        out, self.queue = self.queue, []
        self.last_step_items = len(out)
        for i in out:
            self.answers[i] = i * self.cfg["factor"] + (self.fault == "answer")
        return [(i, "done", self.answers[i]) for i in out]

    def admissions(self):
        return {}

    def counters(self):
        return {"engine_steps": self.steps}

    def kernel_families(self):
        return {}

    def release(self):
        pass

    def check(self, run):
        bad = sum(1 for i, v in self.answers.items()
                  if v != i * self.cfg["factor"])
        return [{"name": "wrong_answers", "value": float(bad), "limit": 0.0}]


def build(cfg, mix, sched, seed, fault=None):
    return Echo(cfg, sched, fault)
'''

METRIC = '''
"""Requests answered in the window."""


def read(run):
    return float(sum(1 for r in run.reqs if r.t_done is not None))
'''


def make_tree(tmp: Path) -> Path:
    """A checkout holding the benchmark, plus one new part of each kind."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "echo.py").write_text(FAMILY)
    (root / "bench" / "configs" / "echo-small.json").write_text(
        json.dumps({"model": "echo", "factor": 3}))
    (root / "bench" / "traffic" / "trickle.json").write_text(json.dumps(
        {"loop": "open", "entry": "step",
         "arrivals": {"process": "poisson", "rate_hz": 200}}))
    (root / "bench" / "metrics" / "answered.echo.py").write_text(METRIC)
    spec["configs"].append({"name": "echo-small", "source": "a toy",
                            "file": "bench/configs/echo-small.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "echo-trickle", "config": "echo-small",
                              "traffic": "trickle", "chips": 1,
                              "why": "toy"})
    spec["end_to_end"][0]["workloads"].append("echo-trickle")
    spec["per_layer"].append({
        "name": "answered.echo", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "toy",
        "moves": spec["end_to_end"][0]["name"],
        "workloads": ["echo-trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_new_parts_are_found_by_name(tmp_path):
    root = make_tree(tmp_path)
    before = {p.name: p.read_bytes() for p in (ROOT / "bench").rglob("*.py")}
    cell = harness.find_cell(root, "echo-trickle")
    assert cell.config["factor"] == 3
    assert cell.mix["arrivals"]["rate_hz"] == 200
    assert [m["name"] for m in cell.per_layer] == ["answered.echo"]
    # the existing cells still resolve as they did
    assert harness.find_cell(root, "bcnn-online-poisson").config["model"] \
        == "bcnn"
    for p in (root / "bench").rglob("*.py"):
        if p.name in before and "__pycache__" not in str(p):
            assert p.read_bytes() == before[p.name]


def test_a_new_cell_runs_end_to_end_without_a_chip(tmp_path):
    root = make_tree(tmp_path)
    res = harness.run_cell("echo-trickle", 5, 0.2, False, root=root,
                           need_chip=False)
    assert res["correct"] is True
    assert res["attempted"] == 40 and res["failed"] == 0
    assert set(res["metrics"]) == {"latency_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    res = harness.run_cell("echo-trickle", 5, 0.2, True, root=root,
                           need_chip=False, fault="answer")
    assert res["correct"] is False
    assert res["metrics"]["answered.echo"]["value"] == 40.0
