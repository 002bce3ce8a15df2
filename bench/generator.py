"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (``bench/traffic/<name>.json``) is data only:

* ``"loop": "open"`` — independent users: ``arrivals`` gives the process
  (``"poisson"``) and its fixed ``rate_hz``; requests are due on that
  schedule whether or not earlier ones have finished. ``lengths`` names
  per-request sizes (for example ``prompt_len``, ``output_len``), each a
  clipped lognormal given by ``median``, ``sigma``, ``min`` and ``max``.
* ``"loop": "closed"`` — one caller that sends its next ``batch``-item
  call when the last one returns.

Every seed gets the same multiset of gaps and sizes, drawn at stratified
quantiles, in another order: so a seed changes which request comes when,
and the contents, but not the amount of work in a window. The contents
(images, token ids) come from the seed through ``rng_for``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist

import numpy as np


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return mix


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed, 64-bit
    or larger, is accepted."""
    salt = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([int(seed) % (1 << 64), salt % (1 << 64)])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def lognormal_sizes(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


@dataclass
class Schedule:
    """Due times (seconds from the window's start) and sizes of requests."""
    loop: str
    due: np.ndarray                                  # (n,) open loop only
    sizes: dict = field(default_factory=dict)        # name -> (n,) ints
    batch: int = 0

    @property
    def n(self) -> int:
        return len(self.due)


def schedule(mix: dict, seed: int, seconds: float) -> Schedule:
    if mix["loop"] == "closed":
        return Schedule("closed", np.zeros(0), batch=int(mix["batch"]))
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = max(1, int(round(arr["rate_hz"] * seconds)))
    rng = rng_for(seed, "arrivals")
    gaps = rng.permutation(-np.log1p(-_quantiles(n)) / arr["rate_hz"])
    # scale the fixed multiset so that every arrival falls inside the window
    due = np.cumsum(gaps) * (seconds / (gaps.sum() + gaps.mean()))
    sizes = {}
    for name, spec in mix.get("lengths", {}).items():
        if spec.get("dist", "lognormal") != "lognormal":
            raise ValueError(f"unknown size distribution for {name!r}")
        sizes[name] = rng_for(seed, "size:" + name).permutation(
            lognormal_sizes(spec, n))
    return Schedule("open", due, sizes)

