"""ExecutionPlan: every kernel choice of a deployment forward in ONE static,
hashable object (the software analogue of the paper's per-platform
accelerator specialization).

The plan holds:

* the kernel ``path`` ("vpu" XNOR+popcount | "mxu" unpack-dot | "xla"
  reference);
* the per-layer conv ``strategy`` ("direct" | "im2col") of the Table 2
  network;
* the cross-layer fusion flag and each fused pair's spatial tile shape
  (`kernels/xnor_conv_fused.py`);
* the LM decode GEMM mode ("bw" weight-only | "xnor" full-packed) of
  `models/xnor_lm.py::make_serving_engine`.

It is a frozen dataclass of Python statics with no arrays, so a deployment
forward closes over it at trace time: the zero-recompile contract (weights
as jit arguments, statics closed over; `core/packed_model.py::split`) holds,
and ``step_cache_size == 1`` survives tuning.

A plan comes from one of two places. ``default_plan(packed, backend)`` is
the heuristic one, and the plan of every serving entry point that is given
none (`core/bcnn.py::make_packed_forward`,
`serve/bcnn_engine.py::BCNNEngine.from_packed`, the data-parallel and
pipelined forwards, `serve/router.py::Router.from_packed`). The measured
one is `kernels/autotune.py::autotune_packed`'s; tuned plans persist in the
deployment artifact (`core/bcnn_artifact.py` ``tuning`` section) keyed by
(backend, device kind, model geometry) and fall back to ``default_plan``
when the key does not match the serving host. Any other plan is a
``dataclasses.replace`` of one of these (for example ``path="xla"`` for an
on-chip reference, or ``conv_fusion=True``: with no ``group_tiles`` each
fused pair takes `kernels/xnor_conv_fused.py::pick_tiles`' tiles).
"""
from __future__ import annotations

import dataclasses
import json
import zlib

import jax

from repro.core import bcnn, bconv

DEFAULT_LM_MODE = "bw"          # models/xnor_lm.py decode GEMM default
PLAN_PATHS = ("vpu", "mxu", "xla")


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Static kernel-choice bundle for one deployment of one packed model.

    All fields are hashable Python statics (no arrays): a forward closes
    over the plan at trace time, so two engines with the same plan share a
    compilation and a weight hot-swap never invalidates it.

    path:          resolved kernel variant — "vpu" | "mxu" | "xla"
    conv_strategy: per-layer resolved dataflow of the Table 2 network,
                   length `core/bcnn.py` ``N_LAYERS``; "direct"/"im2col" on
                   binary conv layers (indices 1..5), None elsewhere;
                   empty for a network with no such choice (Bi-Real Net).
                   Each packed model checks the plan it is given
                   (`core/packed_model.py::PackedModel.check_plan`)
    conv_fusion:   fuse same-resolution conv pairs into the
                   `kernels/xnor_conv_fused.py` megakernel
    group_tiles:   per fused pair ``(first_layer_idx, th, tw)`` — the
                   spatial output tile of the fused launch (pick_tiles
                   default or a measured winner)
    lm_mode:       LM decode GEMM mode ("bw" | "xnor"), consumed by
                   `models/xnor_lm.py::make_serving_engine`
    tuned:         provenance marker — False for heuristic plans, True when
                   the fields were measured by `kernels/autotune.py`
    """
    path: str = "xla"
    conv_strategy: tuple = (None,) * bcnn.N_LAYERS
    conv_fusion: bool = False
    group_tiles: tuple = ()
    lm_mode: str = DEFAULT_LM_MODE
    tuned: bool = False

    def __post_init__(self):
        if self.path not in PLAN_PATHS:
            raise ValueError(f"unknown kernel path {self.path!r}")
        if self.lm_mode not in ("bw", "xnor"):
            raise ValueError(f"unknown lm_mode {self.lm_mode!r}")

    def strategy_for(self, idx: int) -> str | None:
        """Resolved conv dataflow for layer ``idx`` (None off conv layers)."""
        return self.conv_strategy[idx]

    def tiles_for(self, idx: int) -> tuple[int, int] | None:
        """(th, tw) for the fused group starting at layer ``idx``, or None
        to let `kernels/xnor_conv_fused.py::pick_tiles` decide."""
        for i, th, tw in self.group_tiles:
            if i == idx:
                return th, tw
        return None

    def describe(self) -> dict:
        """JSON-able summary for benchmark plan metadata and manifests."""
        return {
            "path": self.path,
            "conv_strategy": list(self.conv_strategy),
            "conv_fusion": self.conv_fusion,
            "group_tiles": [list(t) for t in self.group_tiles],
            "lm_mode": self.lm_mode,
            "tuned": self.tuned,
        }


def plan_to_dict(plan: ExecutionPlan) -> dict:
    """Serialize for the artifact ``tuning`` section (`plan_from_dict`
    inverts; the pair is exercised by tests/test_autotune.py)."""
    return plan.describe()


def plan_from_dict(d: dict) -> ExecutionPlan:
    return ExecutionPlan(
        path=d["path"],
        conv_strategy=tuple(d["conv_strategy"]),
        conv_fusion=bool(d["conv_fusion"]),
        group_tiles=tuple(tuple(int(x) for x in t)
                          for t in d["group_tiles"]),
        lm_mode=d.get("lm_mode", DEFAULT_LM_MODE),
        tuned=bool(d.get("tuned", False)),
    )


# ---------------------------------------------------------------------------
# Cache key: a plan is only valid for the (backend, device, geometry) it was
# measured on — anything else must fall back to default_plan, never error.
# ---------------------------------------------------------------------------

def geometry_fingerprint(packed) -> str:
    """Stable fingerprint of a packed model's architecture: array shapes +
    dtypes + the static ints (k, filter sizes), independent of the weight
    *values* — a retrain/hot-swap keeps the fingerprint, a different
    architecture changes it."""
    leaves, _ = jax.tree_util.tree_flatten(packed, is_leaf=lambda x: x is None)
    parts = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append(f"{tuple(leaf.shape)}:{leaf.dtype}")
        else:
            parts.append(repr(leaf))
    return f"{zlib.crc32('|'.join(parts).encode()):08x}"


def plan_cache_key(packed, backend: str | None = None) -> dict:
    """The artifact ``tuning`` section key: a cached plan is reused only
    when backend, device kind, AND model geometry all match the serving
    host (`core/bcnn_artifact.py::load_tuning`)."""
    backend = backend or jax.default_backend()
    devices = jax.devices(backend) if backend else jax.devices()
    return {
        "backend": backend,
        "device_kind": devices[0].device_kind,
        "geometry": geometry_fingerprint(packed),
    }


def plan_key_fingerprint(key: dict) -> str:
    """Canonical short form of a cache key (logs, filenames)."""
    blob = json.dumps(key, sort_keys=True, separators=(",", ":"))
    return f"{zlib.crc32(blob.encode()):08x}"


# ---------------------------------------------------------------------------
# default_plan: today's heuristics
# ---------------------------------------------------------------------------

def _conv_resolution(idx: int, input_hw: tuple[int, int]) -> tuple[int, int]:
    """Input spatial extent of conv layer ``idx``: the image halves after
    every pooling layer before it (Table 2)."""
    h, w = input_hw
    for i in range(idx):
        if bcnn.CONV_SPECS[i][2]:
            h, w = h // 2, w // 2
    return h, w


def default_group_tiles(packed, groups, *,
                        input_hw: tuple[int, int] = (32, 32)) -> tuple:
    """The ``pick_tiles`` heuristic choice for every fused pair in
    ``groups`` — exactly what `kernels/ops.py::xnor_conv2d_pair` computes
    internally when no tile override is threaded in."""
    from repro.kernels import xnor_conv_fused as kfused
    tiles = []
    for group in groups:
        if len(group) != 2:
            continue
        i, j = group
        fa, fb = packed.convs[i - 1], packed.convs[j - 1]
        h, w = _conv_resolution(i, input_hw)
        pf = 2 if bcnn.CONV_SPECS[j][2] else 1
        ho, wo = h // pf, w // pf
        oa, la = fa.w_words_hw.shape
        th, tw = kfused.pick_tiles(ho, wo, pf=pf, fhb=fb.fh, fwb=fb.fw,
                                   oa=oa, la=la)
        tiles.append((i, th, tw))
    return tuple(tiles)


def default_plan(packed, backend: str | None = None, *,
                 input_hw: tuple[int, int] = (32, 32)) -> ExecutionPlan:
    """Today's heuristic choices as one plan: the plan of every entry point
    that is given none, and the fallback whenever no (valid) tuned plan
    exists.

    * path: the TPU-native "mxu" on a TPU, the "xla" reference lowering
      elsewhere (Pallas would run in interpret mode);
    * per Table 2 binary conv layer: ``bconv.resolve_strategy``'s "auto"
      rule ("direct" on 32-aligned channels);
    * fusion off, so no fused-pair tiles (``input_hw``, the image size a
      fused pair's tiles are sized for, sizes nothing here);
    * a network with no per-layer conv choice (Bi-Real Net): the path alone.
    """
    path = "mxu" if (backend or jax.default_backend()) == "tpu" else "xla"
    if not isinstance(packed, bcnn.BCNNPacked):
        return ExecutionPlan(path=path, conv_strategy=())
    strategies = [None] * bcnn.N_LAYERS
    for idx in range(1, 6):
        fp = packed.convs[idx - 1]
        c = fp.k // (fp.fh * fp.fw)             # true input channel count
        strategies[idx] = bconv.resolve_strategy("auto", c, fp)
    return ExecutionPlan(path=path, conv_strategy=tuple(strategies))
