"""Direct (im2col-free) binary 2-D convolution Pallas kernels (paper §3.1).

The paper's convolution unit (Fig. 5/6) streams reception fields straight
through XNOR + bit-count + NormBinarize logic: intermediate feature maps
never leave the chip. The im2col lowering in ``core/bconv.py`` instead
materializes an (N, H, W, FH·FW·Cw) patch tensor in HBM — FH·FW× the
activation traffic the paper's dataflow needs. These kernels remove that
buffer: the grid walks output tiles (N, H-tile, W-tile, O-tile), the full
channel-packed image stays resident in VMEM, and each program gathers its
FH×FW reception field with in-VMEM dynamic slices. Packed int32 words are
the only activation bytes that ever cross HBM.

Two variants, mirroring ``xnor_matmul.py``:

* ``xnor_conv2d_vpu`` — paper-faithful XNOR + popcount on the VPU (bit-exact
  integer agree-counts, eq. 5).
* ``xnor_conv2d_mxu`` — TPU-native: unpack the gathered patches to ±1 bf16
  inside VMEM and feed the MXU (exact for k ≤ 2²⁴).

Both optionally fuse the eq. (8) NormBinarize comparator as an epilogue.

Weight layout: *per-position* channel packing — ``(O, FH, FW, ceil(C/32))``
flattened to ``(O, FH·FW·Cw)`` (see ``pack_conv_weights``). When C is not a
multiple of 32 each filter position carries its own pad bits, so the pad
correction is the constant ``FH·FW·Cw·32 − k``. Note this differs from the
im2col layout, which packs the flat (FH·FW·C) reduction contiguously; the
two layouts coincide exactly when C % 32 == 0.

The public padded/jit'd wrapper is ``ops.xnor_conv2d``; the pure-jnp oracle
is ``ref.xnor_conv2d_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bitpack
from repro.core.bitpack import PACK
from repro.kernels.xnor_matmul import _agree_counts, _norm_binarize

# Default output tile sizes: 8×8 spatial pixels × 128 output channels gives a
# (64, 128) output tile — sublane/lane aligned on TPU.
TH = 8     # output rows per block
TW = 8     # output cols per block
BO = 128   # output channels per block


def tile_width(wo: int) -> int:
    """Output-tile width: ``TW``, or the whole map when it is narrower.
    Mosaic needs an output block's second-minor dimension to be a multiple
    of 8 or the whole array, so the width is not a free tiling knob."""
    return TW if wo >= TW else wo


def pack_conv_weights(w: jnp.ndarray) -> jnp.ndarray:
    """(O, FH, FW, C) real/±1 filters → (O, FH·FW·Cw) per-position packed words.

    Each (fh, fw) position's C channels are padded to a 32-bit boundary and
    packed independently (sign rule, eq. 4), matching the activation packing
    ``pack_bits(pad_to_pack(a_bits))`` the direct kernels consume.
    """
    o = w.shape[0]
    return bitpack.pack_pm1(w).reshape(o, -1)


def _gather_patches(a_ref, *, th: int, tw: int, fh: int, fw: int,
                    stride: int) -> jnp.ndarray:
    """Gather this program's reception fields from the VMEM-resident image.

    a_ref: (1, Hp, Wp, Cw) packed image block.
    Returns (th·tw, fh·fw·Cw) int32 patch words, ordered (dy, dx, cw) to
    match ``pack_conv_weights``.
    """
    i = pl.program_id(1)
    j = pl.program_id(2)
    kwc = a_ref.shape[3]
    span_h = (th - 1) * stride + fh
    span_w = (tw - 1) * stride + fw
    block = a_ref[0, pl.ds(i * th * stride, span_h),
                  pl.ds(j * tw * stride, span_w), :]
    cols = []
    for dy in range(fh):
        for dx in range(fw):
            cols.append(jax.lax.slice(
                block, (dy, dx, 0),
                (dy + (th - 1) * stride + 1, dx + (tw - 1) * stride + 1, kwc),
                (stride, stride, 1)))
    patches = jnp.concatenate(cols, axis=-1)        # (th, tw, fh·fw·Cw)
    return patches.reshape(th * tw, fh * fw * kwc)


def _xnor_conv_kernel(a_ref, w_ref, c_ref, f_ref, out_ref, *, variant: str,
                      fh: int, fw: int, stride: int, k: int, n_pad_bits: int,
                      fuse_nb: bool):
    """One (1, th, tw, bo) output tile.

    a_ref: (1, Hp, Wp, Cw) int32 packed image (full image resident in VMEM)
    w_ref: (bo, fh·fw·Cw) int32 per-position packed filters
    c_ref: (1, bo) float32 NormBinarize thresholds (if fuse_nb)
    f_ref: (1, bo) int32 {0,1} comparison-flip mask (if fuse_nb)
    ``variant``: "vpu" XNOR + popcount or "mxu" unpack-dot
    (`kernels/xnor_matmul.py::_agree_counts`).
    """
    th, tw, bo = out_ref.shape[1], out_ref.shape[2], out_ref.shape[3]
    pm = _gather_patches(a_ref, th=th, tw=tw, fh=fh, fw=fw, stride=stride)
    y_l = _agree_counts(pm, w_ref[...], variant=variant, k=k,
                        npad=n_pad_bits)                # (th·tw, bo)
    if fuse_nb:
        y_l = _norm_binarize(y_l, c_ref[...], f_ref[...])
    out_ref[...] = y_l.reshape(1, th, tw, bo)


def _conv_call(kernel, a_words, w_words, thr_c, thr_flip, *, ho: int, wo: int,
               th: int, tw: int, bo: int, interpret: bool):
    """Shared pallas_call plumbing for both conv variants."""
    n, hp, wp, kwc = a_words.shape
    o, ll = w_words.shape
    assert ho % th == 0 and wo % tw == 0 and o % bo == 0, (ho, wo, o)
    fuse = thr_c is not None
    if not fuse:
        thr_c = jnp.zeros((1, o), jnp.float32)
        thr_flip = jnp.zeros((1, o), jnp.int32)
    grid = (n, ho // th, wo // tw, o // bo)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, kwc), lambda b, i, j, ob: (b, 0, 0, 0)),
            pl.BlockSpec((bo, ll), lambda b, i, j, ob: (ob, 0)),
            pl.BlockSpec((1, bo), lambda b, i, j, ob: (0, ob)),
            pl.BlockSpec((1, bo), lambda b, i, j, ob: (0, ob)),
        ],
        out_specs=pl.BlockSpec((1, th, tw, bo),
                               lambda b, i, j, ob: (b, i, j, ob)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, o), jnp.int32),
        interpret=interpret,
    )(a_words, w_words, thr_c, thr_flip)


def _conv_variant(variant, a_words, w_words, *, k, fh, fw, stride, ho, wo,
                  thr_c, thr_flip, th, tw, bo, interpret):
    kern = functools.partial(_xnor_conv_kernel, variant=variant, fh=fh,
                             fw=fw, stride=stride, k=k,
                             n_pad_bits=w_words.shape[1] * PACK - k,
                             fuse_nb=thr_c is not None)
    return _conv_call(kern, a_words, w_words, thr_c, thr_flip, ho=ho, wo=wo,
                      th=th, tw=tw, bo=bo, interpret=interpret)


def xnor_conv2d_vpu(a_words, w_words, *, k: int, fh: int, fw: int,
                    stride: int = 1, ho: int, wo: int, thr_c=None,
                    thr_flip=None, th: int = TH, tw: int = TW, bo: int = BO,
                    interpret: bool = False):
    """Direct packed conv, VPU path. Shapes must be pre-padded (see ops.py).

    a_words (N, Hp, Wp, Cw) int32, w_words (O, FH·FW·Cw) int32 →
    (N, ho, wo, O) int32 agree-counts y_l (or {0,1} bits when fused).
    ``ho``/``wo`` are the padded output dims; the input must satisfy
    Hp ≥ (ho−1)·stride + fh (resp. W).
    """
    return _conv_variant("vpu", a_words, w_words, k=k, fh=fh, fw=fw,
                         stride=stride, ho=ho, wo=wo, thr_c=thr_c,
                         thr_flip=thr_flip, th=th, tw=tw, bo=bo,
                         interpret=interpret)


def xnor_conv2d_mxu(a_words, w_words, *, k: int, fh: int, fw: int,
                    stride: int = 1, ho: int, wo: int, thr_c=None,
                    thr_flip=None, th: int = TH, tw: int = TW, bo: int = BO,
                    interpret: bool = False):
    """Direct packed conv via in-VMEM unpack + MXU dot. Bit-exact for
    k ≤ 2²⁴ (f32 accumulation of ±1 products is exact in that range)."""
    return _conv_variant("mxu", a_words, w_words, k=k, fh=fh, fw=fw,
                         stride=stride, ho=ho, wo=wo, thr_c=thr_c,
                         thr_flip=thr_flip, th=th, tw=tw, bo=bo,
                         interpret=interpret)
