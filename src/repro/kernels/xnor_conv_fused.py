"""Cross-layer fused binary conv-pair Pallas megakernel (paper §4, Fig. 5/6).

The paper's pipeline streams activations between conv units *without touching
off-chip memory*. The direct kernel (``xnor_conv.py``) already achieves that
within a layer, but each layer boundary in ``core/bcnn.py::forward_packed``
still roundtrips the packed bit map through HBM. This kernel fuses a pair of
consecutive same-resolution binary conv layers into one program:

    XNOR+popcount (conv A) → eq. 8 NormBinarize → re-pack to int32 words
    → XNOR+popcount (conv B) → eq. 8 NormBinarize → optional 2×2 max-pool

The intermediate packed bit map lives only in VMEM/registers — it is never
written to HBM. The fusible pairs are planned by
``core/bcnn.py::plan_layer_groups`` from the Table 2 geometry: CONV-3/CONV-4
(16×16, eliminating the 16·16·256 boundary) and CONV-5/CONV-6 (8×8,
eliminating the 8·8·512 boundary). Max-pool (resolution-change) boundaries
are never fused across; when the *second* member pools (CONV-4, CONV-6), the
pool runs as the kernel epilogue, exactly where the unfused layer puts it.

Dataflow: the grid walks B's (pooled) output tiles ``(N, HO/th, WO/tw)``.
Each program gathers conv A's reception fields over a halo large enough to
produce the ``(pf·th + FHb − 1, pf·tw + FWb − 1)`` patch of A-output bits
that conv B's tile consumes (``pf`` = 2 when B pools — Halide-style
recompute-at-consumer: halo columns are recomputed by adjacent programs
instead of ever being stored). Halo positions outside the real A output map
are masked to bit 0 (= −1), reproducing the unfused SAME-padding semantics
bit-exactly.

Two variants, mirroring ``xnor_conv.py``: ``_vpu`` (paper-faithful XNOR +
popcount, chunked over output channels to bound the popcount scratch) and
``_mxu`` (unpack to ±1 bf16, matrix unit). Both take *pre-padded* inputs;
the public padded/jit'd wrapper is ``ops.xnor_conv2d_pair``, the oracle is
the two-call composition of ``ref.xnor_conv2d_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitpack import PACK
from repro.kernels.xnor_conv import tile_width
from repro.kernels.xnor_matmul import (OCHUNK, SCRATCH_BUDGET, _agree_counts,
                                       _norm_binarize)

# Default tile height (B's pooled output rows per program); shrunk by
# pick_tiles when the halo scratch would outgrow the VMEM budget. The tile
# width is `kernels/xnor_conv.py::tile_width` (8, or the whole map).
TH = 8
# The VPU popcount scratch is chunked over OCHUNK output channels and sized
# against SCRATCH_BUDGET (both from kernels/xnor_matmul.py).


def _gather_span(block: jnp.ndarray, *, hs: int, ws: int, fh: int,
                 fw: int) -> jnp.ndarray:
    """(hs+fh−1, ws+fw−1, Cw) words → (hs·ws, fh·fw·Cw) stride-1 patches,
    ordered (dy, dx, cw) to match ``xnor_conv.pack_conv_weights``."""
    cw = block.shape[-1]
    cols = []
    for dy in range(fh):
        for dx in range(fw):
            cols.append(jax.lax.slice(block, (dy, dx, 0),
                                      (dy + hs, dx + ws, cw)))
    return jnp.concatenate(cols, axis=-1).reshape(hs * ws, fh * fw * cw)


def _repack(bits: jnp.ndarray) -> jnp.ndarray:
    """(P, C) int32 {0,1} bits → (P, C/32) int32 words, LSB-first (the
    `core/bitpack.py::pack_bits` layout).

    Runs on the MXU: byte ``q`` of every word is one dot with a
    (C, C/32) matrix holding 2^b at (32·w + 8·q + b, w). Exact: bits and
    powers ≤ 2^7 are exact in bf16, byte sums ≤ 255 exact in f32. (A
    shift-and-sum would split the lane axis into (C/32, 32), which Mosaic
    cannot reshape.)
    """
    c = bits.shape[1]
    ch = jax.lax.broadcasted_iota(jnp.int32, (c, c // PACK), 0)
    wd = jax.lax.broadcasted_iota(jnp.int32, (c, c // PACK), 1)
    b16 = bits.astype(jnp.float32).astype(jnp.bfloat16)
    words = None
    for q in range(4):
        sel = ((ch >> 5) == wd) & (((ch & 31) >> 3) == q)
        mat = jnp.where(sel, jnp.left_shift(1, ch & 7), 0)
        byte = jax.lax.dot_general(
            b16, mat.astype(jnp.float32).astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        byte = byte.astype(jnp.int32) << (8 * q)
        words = byte if words is None else words | byte
    return words


def _fused_pair_kernel(a_ref, wa_ref, ca_ref, fa_ref, wb_ref, cb_ref, fb_ref,
                       out_ref, *, fha: int, fwa: int, fhb: int, fwb: int,
                       pf: int, ka: int, npad_a: int, kb: int, npad_b: int,
                       h_img: int, w_img: int, variant: str):
    """One (1, th, tw, OB) fused-pair output tile.

    a_ref:  (1, Hp, Wp, CwA) int32 packed input (full image in VMEM)
    wa_ref: (OA, FHa·FWa·CwA) int32 per-position packed A filters
    wb_ref: (OB, FHb·FWb·OA/32) int32 per-position packed B filters
    ca/fa, cb/fb: (1, O) float32 thresholds / int32 flip masks (eq. 8)
    ``pf`` = 2 when conv B's output is 2×2 max-pooled (epilogue), else 1.
    ``h_img``/``w_img``: the real (unpadded) A-output map extent, for the
    halo validity mask.
    """
    th, tw, ob = out_ref.shape[1], out_ref.shape[2], out_ref.shape[3]
    oa = wa_ref.shape[0]
    i = pl.program_id(1)
    j = pl.program_id(2)
    ha = pf * th + fhb - 1                  # A-output halo extent
    wa = pf * tw + fwb - 1
    block = a_ref[0, pl.ds(i * th * pf, ha + fha - 1),
                  pl.ds(j * tw * pf, wa + fwa - 1), :]
    pm_a = _gather_span(block, hs=ha, ws=wa, fh=fha, fw=fwa)
    y_a = _agree_counts(pm_a, wa_ref[...], variant=variant, k=ka,
                        npad=npad_a)
    # conv A epilogue: eq. 8 NormBinarize → {0,1} bits, re-packed along
    # the channel axis. This packed intermediate map is the tensor the
    # unfused path writes to and reads back from HBM; here it never leaves
    # VMEM.
    bits = _norm_binarize(y_a, ca_ref[...], fa_ref[...])
    words = _repack(bits).reshape(ha, wa, oa // PACK)
    # Halo positions outside the real A-output map must read as bit 0 (−1):
    # that is exactly the SAME-padding the unfused conv-B call would see.
    gr = (jax.lax.broadcasted_iota(jnp.int32, (ha, wa, 1), 0)
          + i * th * pf - (fhb // 2))
    gc = (jax.lax.broadcasted_iota(jnp.int32, (ha, wa, 1), 1)
          + j * tw * pf - (fwb // 2))
    valid = (gr >= 0) & (gr < h_img) & (gc >= 0) & (gc < w_img)
    words = jnp.where(valid, words, 0)
    pm_b = _gather_span(words, hs=pf * th, ws=pf * tw, fh=fhb, fw=fwb)
    y_b = _agree_counts(pm_b, wb_ref[...], variant=variant, k=kb,
                        npad=npad_b)
    # conv B epilogue: NormBinarize, then the optional trailing 2×2 max-pool
    bit = _norm_binarize(y_b, cb_ref[...], fb_ref[...])
    bit = bit.reshape(pf * th, pf * tw, ob)
    if pf == 2:
        # pool on bits commutes with the monotone threshold: max where the
        # compare is y>=c, min where γ<0 flipped it (see bconv.apply_packed)
        q = bit.reshape(th, 2, tw, 2, ob)
        mx = q.max(axis=(1, 3))
        mn = q.min(axis=(1, 3))
        bit = jnp.where(fb_ref[0][None, None, :] != 0, mn, mx)
    out_ref[...] = bit.reshape(1, th, tw, ob)


def _fused_call(kernel, a_words, wa, ca, fa, wb, cb, fb, *, ho: int, wo: int,
                th: int, tw: int, interpret: bool):
    """Shared pallas_call plumbing for both fused-pair variants."""
    n, hp, wp, cwa = a_words.shape
    oa, la = wa.shape
    ob, lb = wb.shape
    assert ho % th == 0 and wo % tw == 0, (ho, wo, th, tw)
    grid = (n, ho // th, wo // tw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, cwa), lambda b, i, j: (b, 0, 0, 0)),
            pl.BlockSpec((oa, la), lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, oa), lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, oa), lambda b, i, j: (0, 0)),
            pl.BlockSpec((ob, lb), lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, ob), lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, ob), lambda b, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, th, tw, ob), lambda b, i, j: (b, i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, ob), jnp.int32),
        interpret=interpret,
    )(a_words, wa, ca, fa, wb, cb, fb)


def halo_scratch(th: int, tw: int, *, pf: int, fhb: int, fwb: int, oa: int,
                 la: int) -> int:
    """VMEM cost (int32 elements) of a (th, tw) fused-pair output tile.

    The dominant temporary is conv A's XNOR scratch over the halo:
    (pf·th + FHb − 1)·(pf·tw + FWb − 1) · min(OA, OCHUNK) · La int32 words.
    Shared between ``pick_tiles`` (the heuristic) and
    `kernels/autotune.py::tile_candidates` (the measured enumeration), so
    both agree on which tiles are legal for the budget.
    """
    return ((pf * th + fhb - 1) * (pf * tw + fwb - 1)
            * min(oa, OCHUNK) * la)


def pick_tiles(ho: int, wo: int, *, pf: int, fhb: int, fwb: int, oa: int,
               la: int, budget: int = SCRATCH_BUDGET) -> tuple[int, int]:
    """The tallest power-of-two tile whose halo popcount scratch fits
    ``budget`` (``halo_scratch``); the width is ``tile_width(wo)``. Height 1
    is the floor even over budget (the scratch is chunked in-kernel)."""
    from repro.kernels.ops import _block_for
    th = _block_for(ho, TH, floor=1)
    tw = tile_width(wo)
    while th > 1 and halo_scratch(th, tw, pf=pf, fhb=fhb, fwb=fwb, oa=oa,
                                  la=la) > budget:
        th //= 2
    return th, tw


def _pair_variant(variant, a_words, wa_words, wb_words, *, ka, kb, fha, fwa,
                  fhb, fwb, pf, thr_a_c, thr_a_flip, thr_b_c, thr_b_flip,
                  h_img, w_img, ho, wo, th, tw, interpret):
    npad_a = wa_words.shape[1] * PACK - ka
    npad_b = wb_words.shape[1] * PACK - kb
    kern = functools.partial(
        _fused_pair_kernel, fha=fha, fwa=fwa, fhb=fhb, fwb=fwb, pf=pf, ka=ka,
        npad_a=npad_a, kb=kb, npad_b=npad_b, h_img=h_img, w_img=w_img,
        variant=variant)
    return _fused_call(kern, a_words, wa_words, thr_a_c, thr_a_flip,
                       wb_words, thr_b_c, thr_b_flip, ho=ho, wo=wo, th=th,
                       tw=tw, interpret=interpret)


def xnor_conv2d_pair_vpu(a_words, wa_words, wb_words, **kw):
    """Fused conv pair, paper-faithful XNOR + popcount on the VPU.

    a_words (N, Hp, Wp, CwA) int32 pre-padded packed input; wa_words
    (OA, FHa·FWa·CwA) / wb_words (OB, FHb·FWb·OA/32) per-position packed
    filters; thresholds pre-broadcast to (1, O). Returns (N, ho, wo, OB)
    int32 {0,1} bits. See ``ops.xnor_conv2d_pair`` for the padded wrapper.
    """
    return _pair_variant("vpu", a_words, wa_words, wb_words, **kw)


def xnor_conv2d_pair_mxu(a_words, wa_words, wb_words, **kw):
    """Fused conv pair via in-VMEM unpack + MXU dots (exact for k ≤ 2²⁴)."""
    return _pair_variant("mxu", a_words, wa_words, wb_words, **kw)
