"""Bi-Real Net-18 on the binary engine: binary residual blocks around a
real-valued residual stream.

Liu et al., "Bi-Real Net: Enhancing the Performance of 1-bit CNNs with
Improved Representational Capability and Advanced Training Algorithm",
ECCV 2018 (arXiv:1808.00278), on the 18-layer ResNet topology (He et al.,
arXiv:1512.03385, Table 1). The geometry comes from a config dict
(``geometry``), so no width is a constant of this module:

* stem (real): a KxK stride-s conv on the pixels, BN, a 3x3 stride-2
  max-pool with pad 1 (no ReLU, as the authors' released code);
* blocks (binary): ``x' = BN(alpha * (sign(W) * sign(x))) + S(x)``, a 3x3
  conv with zero padding 1 on the +-1 map; ``S`` is the identity, or, where
  the block's first conv has stride 2 (or changes the width), the real
  downsample ``BN(conv1x1(avgpool(x)))``; the residual stream ``x`` is
  float32;
* head (real): global average pool, then an FC with bias.

Deployment (``fold`` → ``BiRealPacked``, a
`core/packed_model.py::PackedModel`): each binary conv runs through
``kernels/ops.py::xnor_conv2d`` on the plan's path, unfused, returning
agree-counts y. The kernels pad in the {1,0} bit domain, that is with -1;
Bi-Real pads with 0. The exact fix is an integer border correction: with
``T`` the filter taps that fall in the padding at an output pixel,
``dot_0 = dot_(-1) + sum over T of sum_c w[o, tap, c]``. ``fold`` tabulates
it per border class of rows and columns (``border_correction``) and folds
it, BN and alpha into one affine ``gain * y + bias[row class, col class]``.
The stem, the 1x1 downsamples, the pools and the FC are ordinary XLA ops in
float32 (products at HIGHEST precision) inside the same jitted forward.

Named scopes, which a device trace carries in each operation's metadata:
``bireal/stem``, ``bireal/stage<s>/block<b>`` (``down`` inside a block for
its downsample branch) and ``bireal/head``.

``reference_forward`` is the plain network in float32 ``jax.numpy``, with
no kernel, packing or folding.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.kernels import ops

FILTER = 3          # the binary convs' filter size (3x3, pad 1)
HIGHEST = jax.lax.Precision.HIGHEST
_DN = ("NHWC", "HWIO", "NHWC")


class Geometry(NamedTuple):
    """The network's shape, from the config dict (``geometry``)."""
    input_shape: tuple          # (H, W, C)
    input_dtype: str            # what pixels cross to the device in
    stem_channels: int
    stem_kernel: int
    stem_stride: int
    pool_kernel: int
    pool_stride: int
    pool_pad: int
    stages: tuple               # ((channels, blocks, first stride), ...)
    n_classes: int

    def block_strides(self) -> list[tuple[int, int, int]]:
        """(in channels, out channels, stride) of every block, in order."""
        out, c = [], self.stem_channels
        for o, n, stride in self.stages:
            for b in range(n):
                out.append((c, o, stride if b == 0 else 1))
                c = o
        return out

    def stem_out_hw(self) -> tuple[int, int]:
        """The map the first block sees: after the stem conv and pool."""
        def one(n):
            n = (n + 2 * (self.stem_kernel // 2) - self.stem_kernel) \
                // self.stem_stride + 1
            return (n + 2 * self.pool_pad - self.pool_kernel) \
                // self.pool_stride + 1
        return one(self.input_shape[0]), one(self.input_shape[1])


def geometry(cfg: dict) -> Geometry:
    """The ``Geometry`` a config dict states: ``input_shape``,
    ``input_dtype``, ``stem`` {``channels``, ``kernel``, ``stride``,
    ``pool`` {``kernel``, ``stride``, ``pad``}}, ``stages`` [[channels,
    blocks, first stride], ...] and ``n_classes``."""
    stem, pool = cfg["stem"], cfg["stem"]["pool"]
    return Geometry(
        input_shape=tuple(int(v) for v in cfg["input_shape"]),
        input_dtype=str(cfg.get("input_dtype", "uint8")),
        stem_channels=int(stem["channels"]), stem_kernel=int(stem["kernel"]),
        stem_stride=int(stem["stride"]), pool_kernel=int(pool["kernel"]),
        pool_stride=int(pool["stride"]), pool_pad=int(pool["pad"]),
        stages=tuple(tuple(int(v) for v in s) for s in cfg["stages"]),
        n_classes=int(cfg["n_classes"]))


# ---------------------------------------------------------------- parameters
class BN(NamedTuple):
    """Batch norm in its inference form: ``y * scale + shift`` per channel
    (scale = gamma / sqrt(var + eps), shift = beta - mean * scale)."""
    scale: jnp.ndarray
    shift: jnp.ndarray


class DownParams(NamedTuple):
    w: jnp.ndarray          # (C, O) 1x1 conv
    bn: BN


class BlockParams(NamedTuple):
    w: jnp.ndarray          # (O, 3, 3, C) latent filters; sign(w) is used
    alpha: jnp.ndarray      # (O,) positive per-channel scale
    bn: BN
    down: DownParams | None


class BiRealParams(NamedTuple):
    stem_w: jnp.ndarray     # (K, K, C_in, C0) HWIO, input normalisation in
    stem_bn: BN
    blocks: tuple           # BlockParams, in order
    fc_w: jnp.ndarray       # (C_last, n_classes)
    fc_b: jnp.ndarray       # (n_classes,)


def init(seed: int, geom: Geometry,
         grid: float = 2.0 ** -4) -> BiRealParams:
    """Random parameters from ``seed`` (numpy's generator: no compile),
    on dyadic grids, so that the float32 arithmetic of the packed forward
    and of ``reference_forward`` is exact up to the global pool, and the
    two agree bit for bit before the head.

    Integer stem weights on integer pixels; the stem's BN scale a multiple
    of ``grid`` and its shift an odd multiple of ``grid / 2`` (it centres
    the pixels' mid-grey and the max-pool's lift): the stream starts on
    (Z + 1/2) * ``grid``. Every block's alpha and BN scale and shift are multiples of ``grid``,
    so a block adds a multiple of ``grid``. A downsample's 2x2 average of
    the stream lies on ``s / 4`` * Z (``s`` the stream's grid); its 1x1
    weights are in {-1, 0, 1}, its BN scale an integer and its shift an
    odd multiple of ``s / 8``, so the stream goes on at (Z + 1/2) *
    ``s / 4``. No value of the stream is zero, and no sign decision can
    land on or near it.
    """
    rng = np.random.default_rng(seed)

    def ints(shape, lo, hi):
        return rng.integers(lo, hi, shape).astype(np.float32)

    def signed(shape, lo, hi):
        return ints(shape, lo, hi) * np.where(rng.random(shape) < 0.2,
                                              -1.0, 1.0).astype(np.float32)

    c_in, c0 = geom.input_shape[2], geom.stem_channels
    k = geom.stem_kernel
    stem_w = ints((k, k, c_in, c0), -1, 2)
    stem_scale = signed((c0,), 1, 2) * grid
    mid_grey = np.round(127.5 * stem_w.sum(axis=(0, 1, 2)))
    lift = np.round(110.0 * np.sqrt((stem_w ** 2).sum(axis=(0, 1, 2))))
    stem_bn = BN(stem_scale, (ints((c0,), -64, 64) + 0.5) * grid
                 - mid_grey * stem_scale - lift * np.abs(stem_scale))
    blocks, s = [], grid
    for c, o, stride in geom.block_strides():
        w = np.where(rng.random((o, 3, 3, c)) < 0.5, 1.0, -1.0)
        alpha = ints((o,), 1, 3)
        bn = BN(signed((o,), 1, 17) * grid, ints((o,), -64, 64) * grid)
        down = None
        if stride != 1 or c != o:
            s = s / (stride * stride)
            dw = ints((c, o), -1, 2) * (rng.random((c, o)) < 2.0 / c)
            down = DownParams(dw, BN(signed((o,), 1, 3),
                                     (ints((o,), -64, 64) + 0.5) * s))
        blocks.append(BlockParams(w, alpha, bn, down))
    c_last = geom.stages[-1][0]
    fc_w = rng.standard_normal((c_last, geom.n_classes)) \
        / np.sqrt(c_last) * (grid / 64.0)
    fc_b = rng.standard_normal((geom.n_classes,))
    params = BiRealParams(stem_w, stem_bn, tuple(blocks), fc_w, fc_b)
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)


# -------------------------------------------------------- zero-pad borders
def border_taps(n: int, stride: int, f: int = FILTER):
    """A stride-``stride`` conv's output rows (or columns) over an
    ``n``-wide map zero padded by ``f // 2``: the pattern id of each output
    row, and the patterns, (P, f) bool, where tap d of the row reads inside
    the map. A 3x3 conv has at most three patterns: top, inside, bottom."""
    pad = f // 2
    rows = [tuple(0 <= i * stride - pad + d < n for d in range(f))
            for i in range((n + 2 * pad - f) // stride + 1)]
    patterns = sorted(set(rows))
    return (np.array([patterns.index(r) for r in rows], np.int32),
            np.array(patterns, bool))


def border_correction(w_pm1: jnp.ndarray, h: int, w: int,
                      stride: int) -> jnp.ndarray:
    """(O, f, f, C) +-1 filters → the integer correction
    ``dot_0 - dot_(-1)`` by border class, (row patterns, column patterns,
    O): the sum over the padded taps of each filter's weights."""
    _, rp = border_taps(h, stride, w_pm1.shape[1])
    _, cp = border_taps(w, stride, w_pm1.shape[2])
    wsum = jnp.sum(w_pm1, axis=3)                         # (O, f, f)
    outside = 1.0 - (rp[:, None, :, None] & cp[None, :, None, :])
    return jnp.einsum("rcyx,oyx->rco", outside, wsum,
                      precision=HIGHEST)


def at_positions(table: jnp.ndarray, h: int, w: int,
                 stride: int) -> jnp.ndarray:
    """(row patterns, column patterns, O) per border class → (HO, WO, O)
    per output pixel."""
    rid, _ = border_taps(h, stride)
    cid, _ = border_taps(w, stride)
    return table[rid][:, cid]


# ---------------------------------------------------------------- deployment
class DownPacked(NamedTuple):
    w: jnp.ndarray          # (C, O)
    scale: jnp.ndarray
    shift: jnp.ndarray


class BlockPacked(NamedTuple):
    w_words: jnp.ndarray    # (O, 3*3*ceil(C/32)) per-position packed
    gain: jnp.ndarray       # (O,) 2 * bn scale * alpha
    bias: jnp.ndarray       # (row patterns, column patterns, O)
    down: DownPacked | None


class BiRealPacked(NamedTuple):
    """Packed Bi-Real Net; a `core/packed_model.py::PackedModel`."""
    stem_w: jnp.ndarray
    stem_scale: jnp.ndarray
    stem_shift: jnp.ndarray
    blocks: tuple           # BlockPacked, in order
    fc_w: jnp.ndarray
    fc_b: jnp.ndarray
    geom: Geometry          # statics

    @property
    def input_shape(self) -> tuple[int, int, int]:
        return self.geom.input_shape

    @property
    def input_dtype(self) -> np.dtype:
        return np.dtype(self.geom.input_dtype)

    @property
    def n_classes(self) -> int:
        return self.geom.n_classes

    def check_plan(self, plan) -> None:
        """Only the kernel path applies: there is no per-layer strategy
        and no fused pair."""
        if plan.conv_strategy or plan.conv_fusion:
            raise ValueError("Bi-Real Net takes a plan with no conv "
                             "strategies and no fusion (the path alone)")

    def forward(self, x: jnp.ndarray, plan) -> jnp.ndarray:
        return forward_packed(self, x, plan)


def fold(params: BiRealParams, geom: Geometry) -> BiRealPacked:
    """Parameters → the deployment form: packed filters, and BN, alpha and
    the zero-padding correction folded into a gain and a bias table per
    block (module docstring)."""
    from repro.kernels.xnor_conv import pack_conv_weights
    h, w = geom.stem_out_hw()
    blocks = []
    for p, (c, o, stride) in zip(params.blocks, geom.block_strides()):
        w_pm1 = jnp.where(p.w >= 0, 1.0, -1.0)
        a = p.bn.scale * p.alpha
        corr = border_correction(w_pm1, h, w, stride)
        k = FILTER * FILTER * c
        down = None
        if p.down is not None:
            down = DownPacked(p.down.w, p.down.bn.scale, p.down.bn.shift)
        blocks.append(BlockPacked(pack_conv_weights(w_pm1), 2.0 * a,
                                  a * (corr - k) + p.bn.shift, down))
        h = (h + 2 * (FILTER // 2) - FILTER) // stride + 1
        w = (w + 2 * (FILTER // 2) - FILTER) // stride + 1
    return BiRealPacked(params.stem_w, params.stem_bn.scale,
                        params.stem_bn.shift, tuple(blocks), params.fc_w,
                        params.fc_b, geom)


def _stem(w, scale, shift, x, geom: Geometry) -> jnp.ndarray:
    pad = geom.stem_kernel // 2
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w, (geom.stem_stride,) * 2,
        ((pad, pad), (pad, pad)), dimension_numbers=_DN, precision=HIGHEST)
    y = y * scale + shift
    k, s, p = geom.pool_kernel, geom.pool_stride, geom.pool_pad
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                 (1, s, s, 1),
                                 ((0, 0), (p, p), (p, p), (0, 0)))


def _avgpool(x: jnp.ndarray, s: int) -> jnp.ndarray:
    if s == 1:
        return x
    return jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, s, s, 1),
                                 (1, s, s, 1), "VALID") / (s * s)


def _downsample(w, scale, shift, x, stride: int) -> jnp.ndarray:
    y = jnp.einsum("nhwc,co->nhwo", _avgpool(x, stride), w,
                   precision=HIGHEST)
    return y * scale + shift


def _head(fc_w, fc_b, x) -> jnp.ndarray:
    return jnp.dot(jnp.mean(x, axis=(1, 2)), fc_w, precision=HIGHEST) + fc_b


def apply_block(bp: BlockPacked, x: jnp.ndarray, stride: int,
                path: str) -> jnp.ndarray:
    """One binary residual block on the float32 stream ``x``."""
    n, h, w, c = x.shape
    y = ops.xnor_conv2d(bitpack.encode_pm1(x), bp.w_words,
                        k=FILTER * FILTER * c, fh=FILTER, fw=FILTER,
                        stride=stride, pad=FILTER // 2, path=path)
    out = y.astype(jnp.float32) * bp.gain + at_positions(bp.bias, h, w,
                                                         stride)
    if bp.down is None:
        return out + x
    with jax.named_scope("down"):
        return out + _downsample(*bp.down, x, stride)


def forward_packed(packed: BiRealPacked, x: jnp.ndarray,
                   plan) -> jnp.ndarray:
    """Deployment forward: (N, H, W, 3) pixels → (N, n_classes) float32
    logits. ``plan`` (`core/execution_plan.py::ExecutionPlan`) gives the
    binary convs' kernel path."""
    geom = packed.geom
    with jax.named_scope("bireal/stem"):
        x = _stem(packed.stem_w, packed.stem_scale, packed.stem_shift, x,
                  geom)
    blocks = iter(zip(packed.blocks, geom.block_strides()))
    for s, (_, n, _) in enumerate(geom.stages):
        with jax.named_scope(f"bireal/stage{s + 1}"):
            for b in range(n):
                bp, (_, _, stride) = next(blocks)
                with jax.named_scope(f"block{b + 1}"):
                    x = apply_block(bp, x, stride, plan.path)
    with jax.named_scope("bireal/head"):
        return _head(packed.fc_w, packed.fc_b, x)


# ----------------------------------------------------------------- reference
def reference_forward(params: BiRealParams, geom: Geometry,
                      x: jnp.ndarray) -> jnp.ndarray:
    """The plain network in float32: (N, H, W, 3) pixels → logits.

    ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; the
    binary convs are ``lax`` convs of +-1 float maps with zero padding, as
    published. No kernel, packing or folding. Departures from the paper:

    * sign(0) is +1 (the released code's ``torch.sign`` gives 0 there);
    * BN is held in its inference form, a scale and a shift per channel;
    * the input normalisation is folded into the stem's weights and the
      BN shift, as an exported model holds it, so the input is raw pixels.
    """
    with jax.default_matmul_precision("highest"):
        x = _stem(params.stem_w, *params.stem_bn, x, geom)
        for p, (_, _, stride) in zip(params.blocks, geom.block_strides()):
            sign = jnp.where(x >= 0, 1.0, -1.0)
            w = jnp.transpose(jnp.where(p.w >= 0, 1.0, -1.0), (1, 2, 3, 0))
            y = jax.lax.conv_general_dilated(
                sign, w, (stride, stride), ((1, 1), (1, 1)),
                dimension_numbers=_DN)
            y = (y * p.alpha) * p.bn.scale + p.bn.shift
            if p.down is None:
                x = y + x
            else:
                x = y + _downsample(p.down.w, *p.down.bn, x, stride)
        return _head(params.fc_w, params.fc_b, x)
