"""Pallas TPU kernels for binary (XNOR) matrix multiplication.

Three kernels, all operating on bit-packed weights (32 weights / int32 word,
packed along the reduction axis — see core/bitpack.py):

* ``xnor_matmul_vpu`` — the paper-faithful path: XNOR + popcount on the VPU
  (the TPU analogue of the paper's LUT-mapped XNOR gates + bit-count logic).
* ``xnor_matmul_mxu`` — the TPU-native adaptation: unpack bits to ±1 bf16
  *inside VMEM* and feed the MXU. Same contract; weights still move
  HBM→VMEM packed (32× bandwidth saving), which is the durable part of the
  paper's insight on TPU.
* ``binary_weight_matmul`` — weight-only binarization (real activations ×
  packed ±1 weights), the decode-critical kernel for binary LMs.

The XNOR kernels optionally fuse the paper's eq. (8) NormBinarize comparator
as an epilogue so normalization never materializes in HBM. ``_agree_counts``
and ``_norm_binarize`` are shared with the conv kernels (xnor_conv.py,
xnor_conv_fused.py).

Block sizes are TPU-aligned: lane (last) block dims are 128 or the whole
array, and in-kernel lane slices start at multiples of 128 — Mosaic refuses
anything else. The public jit'd wrappers with padding live in ops.py; the
pure-jnp oracles in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitpack import PACK

# Default VMEM tile sizes (TPU v5e: 128-lane VPU/MXU, ~16 MiB VMEM/core).
BM = 128   # output rows per block (sublane-aligned)
BN = 128   # output cols per block (lane-aligned; narrower outputs are padded)
# Output-column chunk of the VPU popcount: bounds the (P, OCHUNK, L) XNOR
# scratch while the filter words stay fully resident.
OCHUNK = 128
# VMEM scratch budget (int32 elements) of one XNOR scratch tensor — a
# conservative slice of the ~16 MiB/core VMEM, leaving room for the
# operands; shared with kernels/xnor_conv_fused.py::pick_tiles.
SCRATCH_BUDGET = 1 << 20
# Packed words unpacked per MXU dot (4096 bits): bounds the ±1 operands.
KCHUNK = 128


def _unpack_pm1(words: jnp.ndarray, dtype) -> jnp.ndarray:
    """(P, n_words) int32 → (P, 32·n_words) ±1 values of ``dtype`` (in-VMEM).

    Bit-plane order: column ``b·n_words + w`` holds bit b of word w. Every
    operand of a dot is unpacked by this one function, so the order of the
    reduction axis agrees on both sides and the dot is the same. Planes are
    concatenated along lanes (no lane-splitting reshape, which Mosaic
    lowers to lane shuffles), shifts stay in int32 (an arithmetic shift is
    harmless under ``& 1``), and the ±1 values reach ``dtype`` through
    float32: Mosaic has no uint32 → bfloat16 cast.
    """
    planes = [((words >> b) & 1) * 2 - 1 for b in range(PACK)]
    return jnp.concatenate(planes, axis=1).astype(jnp.float32).astype(dtype)


def _kchunks(ll: int) -> list[tuple[int, int]]:
    """Word ranges ``[k0, k1)`` of the MXU path's ``KCHUNK``-word unpack
    chunks. Both operands of each dot are unpacked over the same ranges, so
    their bit-plane orders (``_unpack_pm1``) agree chunk by chunk."""
    return [(k0, min(k0 + KCHUNK, ll)) for k0 in range(0, ll, KCHUNK)]


def _mxu_agree_counts(pm: jnp.ndarray, w_chunk, o: int, *, k: int,
                      npad: int) -> jnp.ndarray:
    """(P, L) packed rows × O filters → (P, O) int32 y_l on the matrix unit.

    Unpacks ``pm`` to ±1 bf16 one ``_kchunks`` chunk at a time and dots it
    with ``w_chunk(k0, k1, oc, oe)``: filters ``oc:oe`` over words
    ``k0:k1`` as ±1 bf16 in ``_unpack_pm1``'s order, unpacked on the spot
    (``_agree_counts``) or read from a scratch unpacked once
    (``kernels/xnor_conv.py``). y_l = (k + dot − npad) / 2, exact for
    k ≤ 2²⁴ (pad bits agree: (−1)·(−1)).
    """
    p = pm.shape[0]
    och = min(o, OCHUNK)
    dots = [None] * len(range(0, o, och))
    for k0, k1 in _kchunks(pm.shape[1]):
        a_pm1 = _unpack_pm1(jax.lax.slice(pm, (0, k0), (p, k1)),
                            jnp.bfloat16)
        for n, oc in enumerate(range(0, o, och)):
            d = jax.lax.dot_general(
                a_pm1, w_chunk(k0, k1, oc, min(oc + och, o)),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            dots[n] = d if dots[n] is None else dots[n] + d
    dot_p = dots[0] if len(dots) == 1 else jnp.concatenate(dots, 1)
    return (k + dot_p.astype(jnp.int32) - npad) // 2


def _agree_counts(pm: jnp.ndarray, w: jnp.ndarray, *, variant: str, k: int,
                  npad: int) -> jnp.ndarray:
    """(P, L) packed rows × (O, L) packed filters → (P, O) int32 y_l.

    "mxu": unpack both operands to ±1 bf16 and use the matrix unit, in
    chunks of ``KCHUNK`` words × ``OCHUNK`` filters (``_mxu_agree_counts``).
    "vpu": XNOR + popcount (eq. 5) over the full word axis, chunked over O
    (lane-aligned) and P (sublane multiples) so each (P, chunk, L) scratch —
    L padded to whole 128-lane vregs — fits ``SCRATCH_BUDGET``. Shared by
    every binary kernel in this package.
    """
    o = w.shape[0]
    if variant == "mxu":
        return _mxu_agree_counts(
            pm, lambda k0, k1, oc, oe: _unpack_pm1(
                jax.lax.slice(w, (oc, k0), (oe, k1)), jnp.bfloat16),
            o, k=k, npad=npad)
    p, ll = pm.shape
    och = min(o, OCHUNK)
    lanes = -(-ll // 128) * 128
    pch = max(8, SCRATCH_BUDGET // (och * lanes) // 8 * 8)
    rows = []
    for r in range(0, p, pch):
        pr = jax.lax.slice(pm, (r, 0), (min(r + pch, p), ll))
        cols = []
        for oc in range(0, o, och):
            wc = jax.lax.slice(w, (oc, 0), (min(oc + och, o), ll))
            x = jnp.bitwise_xor(pr[:, None, :], wc[None, :, :])
            agree = jax.lax.population_count(
                jnp.bitwise_not(x).astype(jnp.uint32)).astype(jnp.int32)
            cols.append(agree.sum(axis=-1) - npad)
        rows.append(cols[0] if len(cols) == 1 else jnp.concatenate(cols, 1))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, 0)


def _norm_binarize(y_l: jnp.ndarray, c: jnp.ndarray,
                   flip: jnp.ndarray) -> jnp.ndarray:
    """Fused eq. 8 epilogue: (P, O) y_l, (1, O) f32 thresholds and int32
    {0,1} flips → (P, O) int32 {0,1} bits. XOR with the flip keeps the
    select in int32 (Mosaic cannot truncate i8 masks to i1)."""
    return (y_l.astype(jnp.float32) >= c).astype(jnp.int32) ^ flip


# ---------------------------------------------------------------------------
# XNOR matmul: one (BM, BN) output tile per program, full packed K resident
# ---------------------------------------------------------------------------

def _xnor_kernel(a_ref, w_ref, c_ref, f_ref, out_ref, *, variant: str, k: int,
                 n_pad_bits: int, fuse_nb: bool):
    """a_ref (BM, Kw) / w_ref (BN, Kw) int32 packed; c_ref (1, BN) f32
    thresholds and f_ref (1, BN) int32 flips (if fuse_nb); out_ref (BM, BN)
    int32 agree-counts y_l, or {0,1} bits if fuse_nb."""
    y_l = _agree_counts(a_ref[...], w_ref[...], variant=variant, k=k,
                        npad=n_pad_bits)
    out_ref[...] = (_norm_binarize(y_l, c_ref[...], f_ref[...]) if fuse_nb
                    else y_l)


def _xnor_call(variant, a_words, w_words, *, k, thr_c, thr_flip, bm, bn,
               interpret):
    m, kw = a_words.shape
    n = w_words.shape[0]
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    fuse = thr_c is not None
    if not fuse:  # dummy operands keep one kernel signature
        thr_c = jnp.zeros((1, n), jnp.float32)
        thr_flip = jnp.zeros((1, n), jnp.int32)
    return pl.pallas_call(
        functools.partial(_xnor_kernel, variant=variant, k=k,
                          n_pad_bits=kw * PACK - k, fuse_nb=fuse),
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, kw), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, kw), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(a_words, w_words, thr_c, thr_flip)


def xnor_matmul_vpu(a_words, w_words, *, k: int, thr_c=None, thr_flip=None,
                    bm: int = BM, bn: int = BN, interpret: bool = False):
    """Packed XNOR matmul, VPU path (XNOR + popcount, paper eq. 5).

    a_words (M, Kw) int32, w_words (N, Kw) int32 → (M, N) int32; shapes
    pre-padded to (bm, bn). With thr_c/thr_flip ((1, N) f32 / int32 {0,1}):
    fused NormBinarize, output {0,1} int32 bits.
    """
    return _xnor_call("vpu", a_words, w_words, k=k, thr_c=thr_c,
                      thr_flip=thr_flip, bm=bm, bn=bn, interpret=interpret)


def xnor_matmul_mxu(a_words, w_words, *, k: int, thr_c=None, thr_flip=None,
                    bm: int = BM, bn: int = BN, interpret: bool = False):
    """Packed XNOR matmul via in-VMEM unpack + MXU dot. Bit-exact vs. the oracle
    for k <= 2**24 (f32 accumulation of ±1 products is exact in that range)."""
    return _xnor_call("mxu", a_words, w_words, k=k, thr_c=thr_c,
                      thr_flip=thr_flip, bm=bm, bn=bn, interpret=interpret)


# ---------------------------------------------------------------------------
# Weight-only binary matmul (real activations × packed ±1 weights)
# ---------------------------------------------------------------------------

def _bw_matmul_kernel(a_ref, w_ref, s_ref, out_ref, *, n_kw_steps: int,
                      bkw_words: int, use_scale: bool):
    """Tile: a (BM, K) real, w (BN, Kw) packed. K-chunked unpack+dot to bound
    VMEM; chunks start at multiples of ``bkw_words`` (128 words, or all of
    Kw in one static step), so every lane slice is 128-aligned.

    Accumulates in f32; per-output-channel scale (XNOR-Net α) fused at the end.
    """
    def chunk_dot(a, w_words):
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), _unpack_pm1(w_words, jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    if n_kw_steps == 1:
        acc = chunk_dot(a_ref[...], w_ref[...])
    else:
        def body(s, acc):
            w0 = pl.multiple_of(s * bkw_words, bkw_words)
            a0 = pl.multiple_of(s * bkw_words * PACK, bkw_words * PACK)
            return acc + chunk_dot(a_ref[:, pl.ds(a0, bkw_words * PACK)],
                                   w_ref[:, pl.ds(w0, bkw_words)])

        acc = jax.lax.fori_loop(
            0, n_kw_steps, body,
            jnp.zeros((a_ref.shape[0], w_ref.shape[0]), jnp.float32))
    if use_scale:
        acc = acc * s_ref[...]
    out_ref[...] = acc.astype(out_ref.dtype)


def binary_weight_matmul(a, w_words, *, k: int, scale=None,
                         bm: int = BM, bn: int = BN, bkw: int = 128,
                         interpret: bool = False):
    """Real (M, K) activations × packed (N, Kw) ±1 weights → (M, N).

    K must be a multiple of 32 and padded consistently in both operands
    (pad activations with zeros — zero activation kills the pad weight bit).
    bkw: packed words per inner unpack step (bkw*32 = K-chunk; 128 → 4096
    bits); Kw must be a multiple of it, and it must be 128-aligned or Kw.
    """
    m, kk = a.shape
    n, kw = w_words.shape
    assert kk == kw * PACK, (kk, kw)
    assert m % bm == 0 and n % bn == 0 and kw % bkw == 0, (m, n, kw, bkw)
    use_scale = scale is not None
    if not use_scale:
        scale = jnp.ones((1, n), jnp.float32)
    else:
        scale = scale.reshape(1, n).astype(jnp.float32)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_bw_matmul_kernel, n_kw_steps=kw // bkw,
                          bkw_words=bkw, use_scale=use_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kk), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, kw), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
    )(a, w_words, scale)
