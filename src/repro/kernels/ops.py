"""Public jit'd wrappers around the Pallas binary-matmul/conv kernels.

This is the layer every consumer calls (core/bconv.py, core/blinear.py,
models/layers.py — never the raw kernels). Each wrapper handles:

* leading-batch flattening (arbitrary ``(..., K)`` inputs),
* padding rows/reduction words up to TPU-aligned tile multiples and
  slicing the result back (threshold vectors are padded with +inf /
  identity so padded lanes can never flip a bit),
* ``path`` selection — "vpu" (paper-faithful XNOR + popcount on the
  vector unit), "mxu" (unpack to ±1 and use the matrix unit), "xla"
  (pure-jnp oracle from kernels/ref.py, no Pallas at all),
* automatic ``interpret=True`` on non-TPU backends so the same call sites
  work in tests (CPU) and production (TPU).

All wrappers are ``jax.jit`` with *static* reduction lengths/filter sizes;
they may be re-traced inside a larger jit (e.g. the serving engine jits
``core/bcnn.py::make_packed_forward`` around a whole stack of these) —
statics stay Python ints as long as they are closed over, not passed as
traced pytree leaves. See ``src/repro/kernels/README.md`` for the kernel
contracts and the direct-vs-im2col dataflow trade-off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.kernels import ref as kref
from repro.kernels import xnor_matmul as kern


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(x: jnp.ndarray, mult: int) -> tuple[jnp.ndarray, int]:
    m = x.shape[0]
    rem = (-m) % mult
    if rem:
        x = jnp.pad(x, ((0, rem),) + ((0, 0),) * (x.ndim - 1))
    return x, m


def _block_for(m: int, default: int, floor: int = 8) -> int:
    """Pick a block size <= default that keeps padding waste reasonable."""
    if m >= default:
        return default
    b = floor
    while b * 2 <= m:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("k", "path", "interpret"))
def xnor_matmul(a_words: jnp.ndarray, w_words: jnp.ndarray, *, k: int,
                thr_c: jnp.ndarray | None = None,
                thr_flip: jnp.ndarray | None = None,
                path: str = "mxu", interpret: bool | None = None) -> jnp.ndarray:
    """Paper eq. (5) XnorDotProduct: (..., Kw)ᵢₙₜ₃₂ × (N, Kw)ᵢₙₜ₃₂ → (..., N).

    a_words / w_words: activations and weights bit-packed along the
    reduction axis (``core/bitpack.pack_bits`` / ``pack_pm1``), Kw =
    ceil(k/32) int32 words. ``k`` is the true reduction length (the paper's
    cnum) — needed because pad bits beyond k must not count.

    Returns int32 agree-counts y_l, or {0,1} int8 bits when per-output
    thresholds are given (fused eq. 8 NormBinarize: ``thr_c`` the c_l
    comparison constants, ``thr_flip`` the γ<0 direction bits — from
    ``core/normbinarize.fold_threshold``). ``path``: "vpu" (paper-faithful
    XNOR+popcount), "mxu" (TPU-native unpack→MXU), or "xla" (pure-jnp, no
    Pallas).
    """
    if interpret is None:
        interpret = not _on_tpu()
    lead = a_words.shape[:-1]
    kw = a_words.shape[-1]
    if w_words.shape[-1] != kw:
        raise ValueError(
            f"packed word-count mismatch: activations carry {kw} int32 "
            f"words, weights {w_words.shape[-1]} — both operands must be "
            f"packed over the same reduction axis (32 bits/word)")
    if bitpack.packed_len(k) != kw:
        raise ValueError(
            f"in_features k={k} needs ceil(k/32)={bitpack.packed_len(k)} "
            f"packed int32 words, got {kw} — pack with core/bitpack.py "
            f"(pack_pm1 / pad_to_pack+pack_bits pad the last <32 bits; any "
            f"other word count silently mis-counts agreements)")
    a2 = a_words.reshape(-1, kw)
    n = w_words.shape[0]

    if path == "xla":
        y = kref.xnor_matmul_ref(a2, w_words, k)
        if thr_c is not None:
            y = kref.norm_binarize_ref(y, thr_c, thr_flip)
        return y.reshape(*lead, n)

    bm = _block_for(a2.shape[0], kern.BM)
    bn = kern.BN                        # lane blocks: always 128 wide
    a2, m_true = _pad_rows(a2, bm)
    w_p, n_true = _pad_rows(w_words, bn)
    c = f = None
    if thr_c is not None:
        c = jnp.pad(thr_c.astype(jnp.float32), (0, w_p.shape[0] - n_true),
                    constant_values=jnp.inf).reshape(1, -1)
        f = jnp.pad(thr_flip.astype(jnp.int32), (0, w_p.shape[0] - n_true)
                    ).reshape(1, -1)
    fn = kern.xnor_matmul_vpu if path == "vpu" else kern.xnor_matmul_mxu
    y = fn(a2, w_p, k=k, thr_c=c, thr_flip=f, bm=bm, bn=bn, interpret=interpret)
    y = y[:m_true, :n_true]
    if thr_c is not None:
        y = y.astype(jnp.int8)
    return y.reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("k", "fh", "fw", "stride", "pad",
                                             "path", "interpret"))
def xnor_conv2d(a_bits: jnp.ndarray, w_words: jnp.ndarray, *, k: int,
                fh: int, fw: int, stride: int = 1,
                pad: int | tuple[int, int] | None = None,
                thr_c: jnp.ndarray | None = None,
                thr_flip: jnp.ndarray | None = None,
                path: str = "mxu",
                interpret: bool | None = None) -> jnp.ndarray:
    """Direct (im2col-free) binary conv: (N, H, W, C) bits × packed filters.

    a_bits:  (N, H, W, C) {0,1} activation bits (int8)
    w_words: (O, FH·FW·Cw) int32 per-position packed filters
             (``xnor_conv.pack_conv_weights``)
    k:       true reduction length FH·FW·C (the paper's cnum)
    pad:     scalar or (pad_h, pad_w); default SAME-style (fh//2, fw//2)
    Returns (N, HO, WO, O) int32 agree-counts y_l, or {0,1} int8 bits when
    thresholds are given (fused eq. 8 NormBinarize). Spatial zero padding is
    in the {1,0} bit domain, i.e. pads with −1 — identical to the im2col and
    train paths. ``path``: "vpu" | "mxu" | "xla" (jnp oracle, no Pallas).
    """
    from repro.kernels import xnor_conv as kconv
    if interpret is None:
        interpret = not _on_tpu()
    if pad is None:
        pad = (fh // 2, fw // 2)
    ph, pw = (pad, pad) if isinstance(pad, int) else pad
    n, h, w, c = a_bits.shape
    o, ll = w_words.shape
    kwc = ll // (fh * fw)
    ho = (h + 2 * ph - fh) // stride + 1
    wo = (w + 2 * pw - fw) // stride + 1

    if path == "xla":
        w_bits = bitpack.unpack_bits(w_words.reshape(o, fh, fw, kwc))[..., :c]
        y = kref.xnor_conv2d_ref(a_bits, w_bits, stride=stride, pad=(ph, pw))
        if thr_c is not None:
            ge = y >= thr_c[None, None, None, :]
            y = jnp.where(thr_flip[None, None, None, :] != 0, ~ge,
                          ge).astype(jnp.int8)
        return y

    # pack activation channels: (N, H, W, C) bits → (N, H, W, Cw) words
    aw = bitpack.pack_bits(bitpack.pad_to_pack(a_bits))
    # tile the output grid; pad the packed image so every tile's reception
    # span exists (extra rows/cols are zero words = −1 bits, sliced away)
    th = _block_for(ho, kconv.TH, floor=1)
    tw = kconv.tile_width(wo)
    bo = kconv.BO                       # lane blocks: always 128 wide
    ho_p = -(-ho // th) * th
    wo_p = -(-wo // tw) * tw
    if stride == 1:
        hp_need = (ho_p - 1) * stride + fh
        wp_need = (wo_p - 1) * stride + fw
    else:
        # whole phases (kconv.split_phases): each holds the tile grid plus
        # the taps' reach, (f - 1) // stride rows and columns
        hp_need = stride * (ho_p + (fh - 1) // stride)
        wp_need = stride * (wo_p + (fw - 1) // stride)
    aw = jnp.pad(aw, ((0, 0),
                      (ph, max(0, hp_need - h - ph)),
                      (pw, max(0, wp_need - w - pw)),
                      (0, 0)))
    if stride > 1:
        aw = kconv.split_phases(aw[:, :hp_need, :wp_need], stride)
    w_p, o_true = _pad_rows(w_words, bo)
    cc = ff = None
    if thr_c is not None:
        cc = jnp.pad(thr_c.astype(jnp.float32), (0, w_p.shape[0] - o_true),
                     constant_values=jnp.inf).reshape(1, -1)
        ff = jnp.pad(thr_flip.astype(jnp.int32), (0, w_p.shape[0] - o_true)
                     ).reshape(1, -1)
    fn = kconv.xnor_conv2d_vpu if path == "vpu" else kconv.xnor_conv2d_mxu
    y = fn(aw, w_p, k=k, fh=fh, fw=fw, stride=stride, ho=ho_p, wo=wo_p,
           thr_c=cc, thr_flip=ff, th=th, tw=tw, bo=bo, interpret=interpret)
    y = y[:, :ho, :wo, :o_true]
    if thr_c is not None:
        y = y.astype(jnp.int8)
    return y


@functools.partial(jax.jit, static_argnames=("ka", "kb", "fha", "fwa", "fhb",
                                             "fwb", "pool_b", "path", "tiles",
                                             "interpret"))
def xnor_conv2d_pair(a_bits: jnp.ndarray, wa_words: jnp.ndarray,
                     wb_words: jnp.ndarray, *, ka: int, kb: int,
                     fha: int, fwa: int, fhb: int, fwb: int,
                     pool_b: bool = False,
                     thr_a_c: jnp.ndarray, thr_a_flip: jnp.ndarray,
                     thr_b_c: jnp.ndarray, thr_b_flip: jnp.ndarray,
                     path: str = "mxu",
                     tiles: tuple[int, int] | None = None,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Fused pair of same-resolution binary convs (kernels/xnor_conv_fused.py).

    Computes conv A → eq. 8 NormBinarize → conv B → NormBinarize (→ optional
    trailing 2×2 max-pool when ``pool_b``) in ONE Pallas kernel: the
    intermediate packed bit map stays in VMEM and never touches HBM. Both
    convs are stride-1 SAME with odd filters; padding is in the {1,0} bit
    domain (pad bit 0 = −1), identical to two ``xnor_conv2d`` calls.

    a_bits:   (N, H, W, C) {0,1} int8, C % 32 == 0
    wa_words: (OA, FHa·FWa·C/32) int32 per-position packed (OA % 32 == 0)
    wb_words: (OB, FHb·FWb·OA/32) int32 per-position packed
    ka/kb:    true reduction lengths (FH·FW·C — the paper's cnum)
    Thresholds/flips are the ``fold_threshold`` outputs for each layer;
    both epilogues always binarize (the planner only fuses interior binary
    conv layers). Returns (N, HO, WO, OB) {0,1} int8, HO = H//2 when
    ``pool_b`` else H. ``path``: "vpu" | "mxu" | "xla" (the two-call
    composition — bit-identical, no Pallas). ``tiles``: static (th, tw)
    spatial output-tile override (a measured `kernels/autotune.py` winner);
    None keeps the `kernels/xnor_conv_fused.py::pick_tiles` heuristic.
    Ignored on the "xla" path, which has no tile grid.
    """
    from repro.kernels import xnor_conv_fused as kfused
    if interpret is None:
        interpret = not _on_tpu()
    n, h, w, c = a_bits.shape
    oa, la = wa_words.shape
    ob, lb = wb_words.shape
    assert fha % 2 == 1 and fwa % 2 == 1 and fhb % 2 == 1 and fwb % 2 == 1, \
        "fused pair supports odd SAME filters only"

    if path == "xla":
        bits1 = xnor_conv2d(a_bits, wa_words, k=ka, fh=fha, fw=fwa,
                            thr_c=thr_a_c, thr_flip=thr_a_flip, path="xla")
        out = xnor_conv2d(bits1, wb_words, k=kb, fh=fhb, fw=fwb,
                          thr_c=thr_b_c, thr_flip=thr_b_flip, path="xla")
        if pool_b:
            mx = jax.lax.reduce_window(out, jnp.int8(0), jax.lax.max,
                                       (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            mn = jax.lax.reduce_window(out, jnp.int8(1), jax.lax.min,
                                       (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            out = jnp.where(thr_b_flip[None, None, None, :] != 0, mn, mx)
        return out

    assert c % bitpack.PACK == 0 and oa % bitpack.PACK == 0, (c, oa)
    pf = 2 if pool_b else 1
    assert h % pf == 0 and w % pf == 0, (h, w, pf)
    ho, wo = h // pf, w // pf           # pooled output extent
    if tiles is None:
        th, tw = kfused.pick_tiles(ho, wo, pf=pf, fhb=fhb, fwb=fwb, oa=oa,
                                   la=la)
    else:
        th, tw = tiles
    ho_p = -(-ho // th) * th
    wo_p = -(-wo // tw) * tw
    pha, pwa = fha // 2, fwa // 2
    phb, pwb = fhb // 2, fwb // 2
    # pack activation channels, then pad so every tile's gather span exists:
    # top/left by both convs' SAME pads, bottom/right up to the tile grid
    # (extra rows/cols are zero words = −1 bits; out-of-map halo positions
    # are re-masked inside the kernel before re-packing)
    aw = bitpack.pack_bits(a_bits)
    hp_need = pf * ho_p + fha + fhb - 2
    wp_need = pf * wo_p + fwa + fwb - 2
    aw = jnp.pad(aw, ((0, 0),
                      (pha + phb, max(0, hp_need - h - pha - phb)),
                      (pwa + pwb, max(0, wp_need - w - pwa - pwb)),
                      (0, 0)))
    ca = thr_a_c.astype(jnp.float32).reshape(1, -1)
    fa = thr_a_flip.astype(jnp.int32).reshape(1, -1)
    cb = thr_b_c.astype(jnp.float32).reshape(1, -1)
    fb = thr_b_flip.astype(jnp.int32).reshape(1, -1)
    fn = (kfused.xnor_conv2d_pair_vpu if path == "vpu"
          else kfused.xnor_conv2d_pair_mxu)
    y = fn(aw, wa_words, wb_words, ka=ka, kb=kb, fha=fha, fwa=fwa, fhb=fhb,
           fwb=fwb, pf=pf, thr_a_c=ca, thr_a_flip=fa, thr_b_c=cb,
           thr_b_flip=fb, h_img=h, w_img=w, ho=ho_p, wo=wo_p, th=th, tw=tw,
           interpret=interpret)
    return y[:, :ho, :wo, :].astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def binary_weight_matmul(a: jnp.ndarray, w_words: jnp.ndarray, *, k: int,
                         scale: jnp.ndarray | None = None,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Weight-only binary matmul: real (..., K) × packed (N, Kw) → (..., N).

    The decode-critical path for binary LMs ("binary_weights" quant mode):
    activations stay real (bf16/f32), weights stream HBM→VMEM packed (32×
    fewer bytes) and are unpacked to ±1 bf16 in VMEM for the MXU.
    ``scale``: optional per-output-channel dequant scale applied to the
    result (the binary-weight technique's α). Returns a's dtype.
    """
    if interpret is None:
        interpret = not _on_tpu()
    lead = a.shape[:-1]
    kk = a.shape[-1]
    n, kw = w_words.shape
    if k != kk:
        raise ValueError(
            f"k={k} disagrees with the activations' in_features {kk}; pass "
            f"k = a.shape[-1] (the true reduction length)")
    if bitpack.packed_len(kk) != kw:
        raise ValueError(
            f"in_features {kk} needs ceil({kk}/32)={bitpack.packed_len(kk)} "
            f"packed weight words, got {kw} — weights must be packed along "
            f"a 32-bit-aligned reduction axis (core/bitpack.py::pack_pm1; "
            f"a ragged K < kw*32 is fine: the activation zero-padding "
            f"neutralizes the pad weight bits)")
    a2 = a.reshape(-1, kk)
    # pad K to the packed length (activation zeros neutralize pad weight bits)
    if kk < kw * bitpack.PACK:
        a2 = jnp.pad(a2, ((0, 0), (0, kw * bitpack.PACK - kk)))
    bm = _block_for(a2.shape[0], kern.BM)
    bn = kern.BN                        # lane blocks: always 128 wide
    bkw = kw if kw <= 128 else 128      # 128-aligned in-kernel K chunks
    rem_kw = (-kw) % bkw
    w_p = w_words
    if rem_kw:
        w_p = jnp.pad(w_p, ((0, 0), (0, rem_kw)))
        a2 = jnp.pad(a2, ((0, 0), (0, rem_kw * bitpack.PACK)))
    # the kernel unpacks each bkw-word weight chunk in bit-plane order
    # (`kernels/xnor_matmul.py::_unpack_pm1`): lay the activations out alike
    a2 = a2.reshape(a2.shape[0], -1, bkw, bitpack.PACK).swapaxes(2, 3)
    a2 = a2.reshape(a2.shape[0], -1)
    a2, m_true = _pad_rows(a2, bm)
    w_p, n_true = _pad_rows(w_p, bn)
    s = None
    if scale is not None:
        s = jnp.pad(scale.reshape(-1), (0, w_p.shape[0] - n_true))
    y = kern.binary_weight_matmul(a2, w_p, k=kk, scale=s, bm=bm, bn=bn,
                                  bkw=bkw, interpret=interpret)
    return y[:m_true, :n_true].reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("causal", "q_block", "kv_block",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, q_block: int = 512,
                    kv_block: int = 512,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Blocked softmax attention, (B, Hq, S, hd) head-major → same shape.

    Pads S up to the block grid and slices back; the kv-head count may
    divide the q-head count (GQA — kv heads are broadcast over their query
    group). ``causal`` applies the standard lower-triangular mask. Oracle:
    ``kernels/ref.py::flash_attention_ref``."""
    from repro.kernels import flash_attention as fk
    if interpret is None:
        interpret = not _on_tpu()
    b, hq, s, hd = q.shape
    blk = max(q_block, kv_block)
    s_pad = -(-s // blk) * blk
    if s_pad != s:
        pad = ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    out = fk.flash_attention(q, k, v, causal=causal,
                             q_block=min(q_block, s_pad),
                             kv_block=min(kv_block, s_pad),
                             interpret=interpret)
    return out[:, :, :s]
