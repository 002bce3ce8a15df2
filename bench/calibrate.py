"""Readings the benchmark's limits and fixed rates are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 [--fault control]
    python bench/calibrate.py --workload <cell> --seeds 1 --seconds 10 --rates 500,1000,2000

With ``--seeds``: one window per seed in this one process, printing each
number the check compares and whether the run is correct. ``--fault``
breaks the timed path as the cell's family module defines: ``control``
puts the reference at the next lower precision in the program's place,
and such a run has to come out not correct. With
``--rates``: the knee sweep of an open-loop cell, one window per rate,
printing the latency, the failures and whether the backlog grew (the mean
latency of the last fifth of requests over that of the first fifth). A
summary goes to ``bench_out/calibrate-<cell>.json``. Needs the chip.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def backlog(run) -> dict:
    lat = [r.t_done - r.due for r in run.reqs if r.t_done is not None]
    fifth = max(1, len(lat) // 5)
    head = sum(lat[:fifth]) / fifth
    tail = sum(lat[-fifth:]) / fifth
    return {"tail_over_head": tail / head if head > 0 else None,
            "head_mean_ms": head * 1e3, "tail_mean_ms": tail * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    from bench import harness
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    rows = []
    for rate in rates or [None]:
        for seed in seeds:
            keep: dict = {}
            t0 = time.perf_counter()
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   root=ROOT, t_start=t0, rate_hz=rate,
                                   fault=args.fault, keep=keep)
            row = {"seed": seed, "rate_hz": rate, "fault": args.fault,
                   "correct": res["correct"],
                   "failed": res["failed"], "attempted": res["attempted"],
                   "metrics": {k: v["value"]
                               for k, v in res["metrics"].items()},
                   "checks": {k: v["value"] for k, v in res["checks"].items()},
                   "seconds_total": time.perf_counter() - t0}
            if rate is not None:
                row["backlog"] = backlog(keep["run"])
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = ROOT / harness.OUT_DIR
    out.mkdir(exist_ok=True)
    (out / f"calibrate-{args.workload}.json").write_text(json.dumps(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
