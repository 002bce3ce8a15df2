"""Direct (im2col-free) binary 2-D convolution Pallas kernels (paper §3.1).

The paper's convolution unit (Fig. 5/6) streams reception fields straight
through XNOR + bit-count + NormBinarize logic: intermediate feature maps
never leave the chip. The im2col lowering in ``core/bconv.py`` instead
materializes an (N, H, W, FH·FW·Cw) patch tensor in HBM — FH·FW× the
activation traffic the paper's dataflow needs. These kernels remove that
buffer: the grid walks output tiles (N, H-tile, W-tile and O-tile, in an
order that differs by variant, below), the full channel-packed image stays
resident in VMEM, and each program gathers its FH×FW reception field with
in-VMEM dynamic slices. Packed int32 words are the only activation bytes
that ever cross HBM.

Two variants, mirroring ``xnor_matmul.py``:

* ``xnor_conv2d_vpu`` — paper-faithful XNOR + popcount on the VPU (bit-exact
  integer agree-counts, eq. 5).
* ``xnor_conv2d_mxu`` — TPU-native: unpack the gathered patches to ±1 bf16
  inside VMEM and feed the MXU (exact for k ≤ 2²⁴).

Both optionally fuse the eq. (8) NormBinarize comparator as an epilogue.

The ``mxu`` contract. Its grid is ``(O/bo, N, H/th, W/tw)``: the
output-channel block is outermost, so the filter block changes only O/bo
times a call, and the three inner axes are declared sequential
(``"arbitrary"``). The filter block is unpacked to ±1 bf16 into a VMEM
scratch of ``(bo, 32·FH·FW·Cw)`` on the first program of each block
(``b = i = j = 0``); every program then unpacks only its own patch words
and dots them with the scratch, chunk by ``KCHUNK`` words through the same
``_unpack_pm1``, so both operands share one bit-plane order. The filters
stay packed in HBM. Scratch per Table 2 layer (bo = 128, 3×3): CONV-2 and
CONV-3 (C = 128) 288 KiB, CONV-4 and CONV-5 (C = 256) 576 KiB, CONV-6
(C = 512) 1.125 MiB. The packed image block is fetched O/bo times per
image. The ``vpu`` grid is ``(N, H/th, W/tw, O/bo)`` and unpacks nothing.

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
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitpack
from repro.core.bitpack import PACK
from repro.kernels.xnor_matmul import (_agree_counts, _kchunks,
                                       _mxu_agree_counts, _norm_binarize,
                                       _unpack_pm1)

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


def split_phases(a_words: jnp.ndarray, stride: int) -> jnp.ndarray:
    """(N, s·Hq, s·Wq, Cw) padded packed image → (N, s·s·Hq, Wq, Cw): its
    ``stride`` × ``stride`` phases, phase (p, q) = rows p::s, columns q::s,
    stacked along the rows in order p·s + q.

    A strided conv's tap (dy, dx) at output (oi, oj) reads image row
    s·oi + dy = s·(oi + dy//s) + dy%s: row oi + dy//s of phase row dy%s.
    So every tap is a stride-1 window of one phase, which Mosaic lowers;
    it refuses a strided slice of a vector.
    """
    n, hp, wp, kwc = a_words.shape
    hq, wq = hp // stride, wp // stride
    a = a_words.reshape(n, hq, stride, wq, stride, kwc)
    return a.transpose(0, 2, 4, 1, 3, 5).reshape(n, stride * stride * hq, wq,
                                                 kwc)


def _gather_patches(a_ref, i, j, *, th: int, tw: int, fh: int, fw: int,
                    stride: int) -> jnp.ndarray:
    """Gather output tile (i, j)'s reception fields from the VMEM-resident
    image.

    a_ref: (1, Hp, Wp, Cw) packed image block; for ``stride`` > 1 its
    phases (``split_phases``), (1, s·s·Hq, Wq, Cw).
    Returns (th·tw, fh·fw·Cw) int32 patch words, ordered (dy, dx, cw) to
    match ``pack_conv_weights``.
    """
    kwc = a_ref.shape[3]
    if stride > 1:
        hq = a_ref.shape[1] // (stride * stride)
        span_h, span_w = th + (fh - 1) // stride, tw + (fw - 1) // stride
        phases = {}
        cols = []
        for dy in range(fh):
            for dx in range(fw):
                ph = (dy % stride) * stride + dx % stride
                if ph not in phases:
                    phases[ph] = a_ref[0, pl.ds(ph * hq + i * th, span_h),
                                       pl.ds(j * tw, span_w), :]
                oy, ox = dy // stride, dx // stride
                cols.append(jax.lax.slice(phases[ph], (oy, ox, 0),
                                          (oy + th, ox + tw, kwc)))
        patches = jnp.concatenate(cols, axis=-1)
        return patches.reshape(th * tw, fh * fw * kwc)
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


def _write_tile(y_l, c_ref, f_ref, out_ref, fuse_nb: bool):
    """(th·tw, bo) y_l → the (1, th, tw, bo) output tile, through the fused
    eq. 8 NormBinarize when ``fuse_nb``."""
    if fuse_nb:
        y_l = _norm_binarize(y_l, c_ref[...], f_ref[...])
    out_ref[...] = y_l.reshape(out_ref.shape)


def _xnor_conv_vpu_kernel(a_ref, w_ref, c_ref, f_ref, out_ref, *, fh: int,
                          fw: int, stride: int, k: int, n_pad_bits: int,
                          fuse_nb: bool):
    """One (1, th, tw, bo) output tile, XNOR + popcount; grid (N, H/th,
    W/tw, O/bo).

    a_ref: (1, Hp, Wp, Cw) int32 packed image (full image resident in VMEM)
    w_ref: (bo, fh·fw·Cw) int32 per-position packed filters
    c_ref: (1, bo) float32 NormBinarize thresholds (if fuse_nb)
    f_ref: (1, bo) int32 {0,1} comparison-flip mask (if fuse_nb)
    """
    th, tw = out_ref.shape[1], out_ref.shape[2]
    pm = _gather_patches(a_ref, pl.program_id(1), pl.program_id(2), th=th,
                         tw=tw, fh=fh, fw=fw, stride=stride)
    y_l = _agree_counts(pm, w_ref[...], variant="vpu", k=k, npad=n_pad_bits)
    _write_tile(y_l, c_ref, f_ref, out_ref, fuse_nb)


def _xnor_conv_mxu_kernel(a_ref, w_ref, c_ref, f_ref, out_ref, wpm_ref, *,
                          fh: int, fw: int, stride: int, k: int,
                          n_pad_bits: int, fuse_nb: bool):
    """One (1, th, tw, bo) output tile, unpack + MXU dot; grid (O/bo, N,
    H/th, W/tw), refs as in ``_xnor_conv_vpu_kernel``.

    wpm_ref: (bo, 32·fh·fw·Cw) bf16 VMEM scratch, the filter block unpacked
    to ±1. Filled on the first program of each output-channel block, the
    only program where the block changes (the inner axes run in order);
    every tile then unpacks its own patch words alone.
    """
    th, tw, bo = out_ref.shape[1], out_ref.shape[2], out_ref.shape[3]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when((pl.program_id(1) == 0) & (i == 0) & (j == 0))
    def _unpack_filters():
        for k0, k1 in _kchunks(w_ref.shape[1]):
            wpm_ref[:, k0 * PACK:k1 * PACK] = _unpack_pm1(
                w_ref[:, k0:k1], jnp.bfloat16)

    pm = _gather_patches(a_ref, i, j, th=th, tw=tw, fh=fh, fw=fw,
                         stride=stride)
    y_l = _mxu_agree_counts(
        pm, lambda k0, k1, oc, oe: wpm_ref[oc:oe, k0 * PACK:k1 * PACK], bo,
        k=k, npad=n_pad_bits)
    _write_tile(y_l, c_ref, f_ref, out_ref, fuse_nb)


def _conv_variant(variant, a_words, w_words, *, k, fh, fw, stride, ho, wo,
                  thr_c, thr_flip, th, tw, bo, interpret):
    """pallas_call plumbing for both conv variants. The "mxu" grid puts the
    output-channel block outermost, so its filter block (and scratch)
    changes O/bo times a call; "vpu" walks (N, H/th, W/tw, O/bo)."""
    n, hp, wp, kwc = a_words.shape
    o, ll = w_words.shape
    assert ho % th == 0 and wo % tw == 0 and o % bo == 0, (ho, wo, o)
    fuse = thr_c is not None
    if not fuse:
        thr_c = jnp.zeros((1, o), jnp.float32)
        thr_flip = jnp.zeros((1, o), jnp.int32)
    statics = dict(fh=fh, fw=fw, stride=stride, k=k,
                   n_pad_bits=ll * PACK - k, fuse_nb=fuse)
    tiles = (n, ho // th, wo // tw)
    if variant == "mxu":
        kernel = functools.partial(_xnor_conv_mxu_kernel, **statics)
        grid = (o // bo,) + tiles

        def tile_of(ob, b, i, j):
            return b, i, j, ob
        extra = dict(
            scratch_shapes=[pltpu.VMEM((bo, ll * PACK), jnp.bfloat16)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "arbitrary", "arbitrary", "arbitrary")))
    else:
        kernel = functools.partial(_xnor_conv_vpu_kernel, **statics)
        grid = tiles + (o // bo,)

        def tile_of(b, i, j, ob):
            return b, i, j, ob
        extra = {}

    def spec(block, index):
        """A BlockSpec whose ``index`` takes tile coordinates (b, i, j, ob)."""
        return pl.BlockSpec(block, lambda *g: index(*tile_of(*g)))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            spec((1, hp, wp, kwc), lambda b, i, j, ob: (b, 0, 0, 0)),
            spec((bo, ll), lambda b, i, j, ob: (ob, 0)),
            spec((1, bo), lambda b, i, j, ob: (0, ob)),
            spec((1, bo), lambda b, i, j, ob: (0, ob)),
        ],
        out_specs=spec((1, th, tw, bo), lambda b, i, j, ob: (b, i, j, ob)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, o), jnp.int32),
        interpret=interpret,
        **extra,
    )(a_words, w_words, thr_c, thr_flip)


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
