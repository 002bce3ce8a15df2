"""Binary 2-D convolution (paper §3.1) — two dataflows: im2col and direct.

The paper's convolutional kernel computes each output pixel as an XNOR dot
product over an FW×FH×FD reception field (eq. 3/5). This module lowers it
two ways, selected by ``strategy``:

* ``"im2col"`` — materialize (N, H, W, FH·FW·C) patches, pack, and reuse the
  packed XNOR *matmul* kernels (the paper notes the FC kernel "is similar to
  Fig. 6"). Simple and fully general, but the patch tensor costs FH·FW× the
  activation bytes in HBM — exactly the off-chip traffic the paper's
  deep-pipelined design avoids.
* ``"direct"`` — the paper-faithful dataflow (Fig. 5/6): a fused Pallas
  kernel (``kernels/xnor_conv.py``) keeps the channel-packed image in VMEM,
  gathers each FH×FW reception field on-chip, and fuses XNOR + popcount +
  the eq. (8) NormBinarize comparator. No im2col buffer ever exists in HBM;
  packed words are the only activation traffic.
* ``"auto"`` (default) — ``direct`` when the channel count is 32-aligned
  (packed words identical in both layouts), else ``im2col``.

See ``kernels/README.md`` for the trade-off in bytes and how the direct
kernel maps onto the paper's pipeline stages.

Layout: NHWC feature maps; im2col packs HWIO→(O, FH·FW·I) flat, the direct
kernel packs per filter position →(O, FH·FW·ceil(I/32)) (both precomputed by
``fold``). First layer (eq. 7): FpDotProduct of 6-bit activations × 2-bit
weights — implemented as a regular conv in fp with quantized operands (TPU
has no sub-8-bit dtypes; DESIGN.md §2.2).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.core.binarize import binarize_ste, quantize_input_6bit, quantize_weight_2bit
from repro.core.normbinarize import (BNParams, NBThreshold, bn_affine_exact,
                                     bn_denom, fold_threshold)
from repro.kernels import ops


class BConvParams(NamedTuple):
    w: jnp.ndarray          # (O, FH, FW, I) latent fp filters
    bn_mean: jnp.ndarray    # (O,)
    bn_var: jnp.ndarray
    bn_gamma: jnp.ndarray
    bn_beta: jnp.ndarray


class BConvPacked(NamedTuple):
    w_words: jnp.ndarray    # (O, ceil(FH*FW*I/32)) int32 — im2col layout
    thr: NBThreshold
    k: int                  # FH*FW*I = the paper's cnum
    w_words_hw: jnp.ndarray | None = None  # (O, FH*FW*ceil(I/32)) — direct
    fh: int = 3
    fw: int = 3


def init(key, in_ch: int, out_ch: int, fh: int = 3, fw: int = 3,
         dtype=jnp.float32) -> BConvParams:
    w = jax.random.uniform(key, (out_ch, fh, fw, in_ch), dtype, -1.0, 1.0)
    return BConvParams(w=w,
                       bn_mean=jnp.zeros((out_ch,), dtype),
                       bn_var=jnp.ones((out_ch,), dtype),
                       bn_gamma=jnp.ones((out_ch,), dtype),
                       bn_beta=jnp.zeros((out_ch,), dtype))


def _im2col(x: jnp.ndarray, fh: int, fw: int,
            pad: int | tuple[int, int] = 1) -> jnp.ndarray:
    """NHWC → (N, H, W, FH*FW*C) patches (stride 1, zero padding `pad`,
    a scalar or per-dimension (pad_h, pad_w))."""
    n, h, w, c = x.shape
    ph, pw = (pad, pad) if isinstance(pad, int) else pad
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    cols = []
    for dy in range(fh):
        for dx in range(fw):
            cols.append(jax.lax.dynamic_slice(
                xp, (0, dy, dx, 0), (n, h, w, c)))
    return jnp.concatenate(cols, axis=-1)


def apply_train(p: BConvParams, a_pm1: jnp.ndarray, *,
                binarize_out: bool = True, maxpool: bool = False) -> jnp.ndarray:
    """Differentiable binary conv (±1 in / ±1 out), BN, optional 2×2 maxpool.

    Pool-before-binarize note: the paper pools the *pre-binarize* y_l
    (Fig. 3: MP then NormBinarize). max-pool commutes with the monotone
    NormBinarize threshold, so either order is bit-equivalent; we keep the
    paper's order.
    """
    wb = binarize_ste(p.w)
    # Pad with −1, not 0: the paper's "zero padding" is in the {1,0} bit
    # encoding where bit 0 *is* −1 (eq. 4). This keeps the train path
    # bit-identical to the packed XNOR path (whose pad bits are 0 = −1).
    fh, fw = p.w.shape[1], p.w.shape[2]
    ap = jnp.pad(a_pm1, ((0, 0), (fh // 2, fh // 2), (fw // 2, fw // 2),
                         (0, 0)), constant_values=-1.0)
    y = jax.lax.conv_general_dilated(
        ap, jnp.transpose(wb, (1, 2, 3, 0)),                  # HWIO
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if maxpool:
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    z = (y - p.bn_mean) / jnp.sqrt(p.bn_var + 1e-4) * p.bn_gamma + p.bn_beta
    return binarize_ste(z) if binarize_out else z


def fold(p: BConvParams) -> BConvPacked:
    from repro.kernels.xnor_conv import pack_conv_weights
    o, fh, fw, i = p.w.shape
    k = fh * fw * i
    w_flat = p.w.reshape(o, k)
    # im2col emits patches ordered (dy, dx, c) — (fh, fw, i) reshape matches.
    w_words = bitpack.pack_pm1(w_flat)
    bn = BNParams(p.bn_mean, p.bn_var, p.bn_gamma, p.bn_beta)
    return BConvPacked(w_words=w_words, thr=fold_threshold(bn, cnum=k), k=k,
                       w_words_hw=pack_conv_weights(p.w), fh=fh, fw=fw)


def resolve_strategy(strategy: str | None, c: int,
                     fp: BConvPacked | None = None) -> str:
    """Resolve "auto" (and None) to a concrete dataflow for channel count c.

    "auto" → "direct" when C is 32-aligned (packed activation words are
    identical in both layouts, so the direct kernel is a pure traffic win),
    else fall back to "im2col" (general, handles per-position pad raggedness
    without re-packing the feature map).
    """
    strategy = strategy or "auto"
    if strategy == "auto":
        have_hw = fp is None or fp.w_words_hw is not None
        strategy = ("direct" if c % bitpack.PACK == 0 and have_hw
                    else "im2col")
    if strategy not in ("direct", "im2col"):
        raise ValueError(f"unknown conv strategy: {strategy!r}")
    if strategy == "direct" and fp is not None and fp.w_words_hw is None:
        raise ValueError(
            "strategy='direct' needs the per-position weight layout; this "
            "BConvPacked predates it — re-fold() the params or use "
            "strategy='im2col'")
    return strategy


def apply_packed(fp: BConvPacked, a_bits: jnp.ndarray, *,
                 fh: int | None = None, fw: int | None = None,
                 maxpool: bool = False, path: str = "mxu",
                 fuse_nb: bool = True,
                 strategy: str | None = None) -> jnp.ndarray:
    """Packed inference conv on {0,1} int8 NHWC bit feature maps.

    a_bits: (N, H, W, C) {0,1}. fh/fw default to the filter size recorded at
    fold() time. ``strategy`` picks the dataflow (module docstring): "direct"
    streams the channel-packed image through the fused
    ``kernels/xnor_conv.py`` kernel; "im2col" packs FH·FW·C patches per pixel
    and reuses the XNOR matmul kernels; "auto"/None resolves per
    ``resolve_strategy``. Both are bit-identical.

    Max-pool (paper: on y_l before NormBinarize) commutes with the monotone
    eq. 8 threshold, so with fuse_nb we pool the output *bits*: max where the
    compare is y>=c, min where γ<0 flips it.
    """
    fh = fh if fh is not None else fp.fh
    fw = fw if fw is not None else fp.fw
    n, h, w, c = a_bits.shape
    strategy = resolve_strategy(strategy, c, fp)
    thr = dict(thr_c=fp.thr.c, thr_flip=fp.thr.flip) if fuse_nb else {}
    if strategy == "direct":
        out = ops.xnor_conv2d(a_bits, fp.w_words_hw, k=fp.k, fh=fh, fw=fw,
                              path=path, **thr)
    else:
        patches = _im2col(a_bits, fh, fw, pad=(fh // 2, fw // 2))  # (N,H,W,K)
        words = bitpack.pack_bits(bitpack.pad_to_pack(patches))  # (N,H,W,Kw)
        out = ops.xnor_matmul(words, fp.w_words, k=fp.k, path=path, **thr)
    if not fuse_nb:
        if maxpool:
            out = jax.lax.reduce_window(out, jnp.iinfo(jnp.int32).min,
                                        jax.lax.max,
                                        (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        return out
    if maxpool:
        mx = jax.lax.reduce_window(out, jnp.int8(0), jax.lax.max,
                                   (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        mn = jax.lax.reduce_window(out, jnp.int8(1), jax.lax.min,
                                   (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        out = jnp.where(fp.thr.flip[None, None, None, :], mn, mx)
    return out


def apply_packed_pair(fa: BConvPacked, fb: BConvPacked, a_bits: jnp.ndarray,
                      *, maxpool_b: bool = False,
                      path: str = "mxu",
                      tiles: tuple[int, int] | None = None) -> jnp.ndarray:
    """Fused pair of packed binary convs: conv A → NormBinarize → (VMEM
    re-pack) → conv B → NormBinarize → optional trailing 2×2 max-pool.

    Bit-exact with ``apply_packed(fa, ...) ; apply_packed(fb, ...,
    maxpool=maxpool_b)`` for EITHER conv strategy — the fused megakernel is
    its own (direct-style) dataflow, so the ``strategy`` knob does not apply
    inside a fused group; it keeps selecting the lowering of unfused layers.
    Requires the per-position weight layouts and 32-aligned channel counts
    (the same condition under which "auto" resolves to "direct").
    ``tiles``: static (th, tw) output-tile override from an
    `core/execution_plan.py::ExecutionPlan` (None → pick_tiles heuristic).
    """
    n, h, w, c = a_bits.shape
    if fa.w_words_hw is None or fb.w_words_hw is None:
        raise ValueError(
            "fused conv pair needs the per-position weight layout; these "
            "BConvPacked predate it — re-fold() the params")
    oa = fa.w_words_hw.shape[0]
    if c % bitpack.PACK or oa % bitpack.PACK:
        raise ValueError(
            f"fused conv pair needs 32-aligned channels, got C={c}, OA={oa}")
    return ops.xnor_conv2d_pair(
        a_bits, fa.w_words_hw, fb.w_words_hw, ka=fa.k, kb=fb.k,
        fha=fa.fh, fwa=fa.fw, fhb=fb.fh, fwb=fb.fw, pool_b=maxpool_b,
        thr_a_c=fa.thr.c, thr_a_flip=fa.thr.flip,
        thr_b_c=fb.thr.c, thr_b_flip=fb.thr.flip, path=path, tiles=tiles)


# ---------------------------------------------------------------------------
# First layer: FpDotProduct (paper eq. 7) — 6-bit activations × 2-bit weights
# ---------------------------------------------------------------------------

class FpConvParams(NamedTuple):
    w: jnp.ndarray          # (O, FH, FW, I) latent fp
    bn_mean: jnp.ndarray
    bn_var: jnp.ndarray
    bn_gamma: jnp.ndarray
    bn_beta: jnp.ndarray


def fpconv_init(key, in_ch: int, out_ch: int, fh: int = 3, fw: int = 3,
                dtype=jnp.float32) -> FpConvParams:
    w = jax.random.normal(key, (out_ch, fh, fw, in_ch), dtype) * 0.1
    return FpConvParams(w=w,
                        bn_mean=jnp.zeros((out_ch,), dtype),
                        bn_var=jnp.ones((out_ch,), dtype),
                        bn_gamma=jnp.ones((out_ch,), dtype),
                        bn_beta=jnp.zeros((out_ch,), dtype))


def fpconv_apply(p: FpConvParams, x01: jnp.ndarray, *,
                 binarize_out: bool = True) -> jnp.ndarray:
    """Paper eq. (7): 6-bit input (rescaled to [−31,31]) × 2-bit weights.

    x01: (N, H, W, C) raw image in [0, 1].
    """
    a0 = quantize_input_6bit(x01)
    w2 = quantize_weight_2bit(p.w)
    y = jax.lax.conv_general_dilated(
        a0, jnp.transpose(w2, (1, 2, 3, 0)),
        window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # bn_denom/bn_affine_exact: this BN runs inside the deployment jit with
    # hot-swappable (runtime-argument) stats — rounding must match the
    # eager oracle or a 1-ulp wobble at z == 0 flips the binarized bit
    z = bn_affine_exact((y - p.bn_mean) / bn_denom(p.bn_var, 1e-4),
                        p.bn_gamma, p.bn_beta)
    return binarize_ste(z) if binarize_out else z
