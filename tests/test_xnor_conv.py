"""Direct (im2col-free) binary-conv kernel parity tests (interpret mode).

Four implementations must agree bit-for-bit on the integer agree-counts y_l
(and on the fused NormBinarize bits): direct-VPU, direct-MXU, the im2col →
XNOR-matmul lowering, and the pure-jnp oracle. Sweeps odd H/W, stride,
padding, non-multiple-of-32 channels, and fused/unfused epilogues.
"""
import functools
from collections import Counter

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bconv, bitpack
from repro.kernels import ops, ref
from repro.kernels import xnor_conv as kconv

# (h, w, c, o, f, stride, pad)
CONFIGS = [
    (8, 8, 32, 16, 3, 1, 1),     # aligned everything (BCNN-like)
    (7, 9, 32, 8, 3, 1, 1),      # odd H/W (ragged output tiles)
    (8, 8, 48, 8, 3, 1, 1),      # C not a multiple of 32 (per-position pad)
    (9, 9, 32, 8, 3, 2, 1),      # stride 2
    (8, 8, 32, 8, 3, 1, 0),      # no spatial padding
    (6, 6, 16, 8, 1, 1, 0),      # 1×1 conv, C < 32
    (10, 6, 64, 24, 5, 2, 2),    # 5×5, stride 2, multi-word channels
    (8, 8, 32, 256, 3, 1, 1),    # two output-channel blocks (mxu: refill)
    (8, 8, 512, 16, 3, 1, 1),    # 144 filter words: two KCHUNK chunks
]


def _case(h, w, c, o, f, seed=0, n=2):
    rng = np.random.default_rng(seed + h * 1000 + c)
    a_bits = jnp.asarray(rng.integers(0, 2, (n, h, w, c)).astype(np.int8))
    w_pm1 = jnp.asarray(rng.choice([-1.0, 1.0], (o, f, f, c))
                        .astype(np.float32))
    return rng, a_bits, w_pm1


@pytest.mark.parametrize("h,w,c,o,f,stride,pad", CONFIGS)
@pytest.mark.parametrize("path", ["vpu", "mxu", "xla"])
def test_direct_conv_matches_oracle(h, w, c, o, f, stride, pad, path):
    _, a_bits, w_pm1 = _case(h, w, c, o, f)
    w_words = kconv.pack_conv_weights(w_pm1)
    k = f * f * c
    y = ops.xnor_conv2d(a_bits, w_words, k=k, fh=f, fw=f, stride=stride,
                        pad=pad, path=path)
    y_ref = ref.xnor_conv2d_ref(a_bits, bitpack.encode_pm1(w_pm1),
                                stride=stride, pad=pad)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.parametrize("h,w,c,o,f,stride,pad", CONFIGS)
@pytest.mark.parametrize("path", ["vpu", "mxu"])
def test_direct_conv_fused_normbinarize(h, w, c, o, f, stride, pad, path):
    rng, a_bits, w_pm1 = _case(h, w, c, o, f, seed=7)
    w_words = kconv.pack_conv_weights(w_pm1)
    k = f * f * c
    c_thr = jnp.asarray(rng.integers(0, k + 1, (o,)).astype(np.float32))
    flip = jnp.asarray(rng.integers(0, 2, (o,)).astype(bool))
    bits = ops.xnor_conv2d(a_bits, w_words, k=k, fh=f, fw=f, stride=stride,
                           pad=pad, thr_c=c_thr, thr_flip=flip, path=path)
    y_ref = np.asarray(ref.xnor_conv2d_ref(a_bits, bitpack.encode_pm1(w_pm1),
                                           stride=stride, pad=pad))
    ge = y_ref >= np.asarray(c_thr)[None, None, None, :]
    want = np.where(np.asarray(flip)[None, None, None, :], ~ge, ge
                    ).astype(np.int8)
    assert bits.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(bits), want)


def _pallas_kernels(jaxpr):
    """Kernel bodies of the pallas_calls in ``jaxpr``, nested jits too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["jaxpr"]
        for v in eqn.params.values():
            if isinstance(v, jcore.ClosedJaxpr):
                yield from _pallas_kernels(v.jaxpr)


def _unpack_rows(jaxpr) -> Counter:
    """Row counts of the bit-plane shifts in ``jaxpr``'s own equations
    (nested jaxprs excluded): one per plane of each ``_unpack_pm1``."""
    return Counter(e.outvars[0].aval.shape[0] for e in jaxpr.eqns
                   if e.primitive.name == "shift_right_arithmetic")


def test_mxu_conv_unpacks_filters_once_per_block():
    """The mxu kernel unpacks its (128-row) filter block only under the
    first-program guard; each output tile's body unpacks its 64 patch rows
    alone. C = 512 gives two KCHUNK chunks, unpacked alike on both sides."""
    c, o = 512, 256
    jaxpr = jax.make_jaxpr(functools.partial(
        ops.xnor_conv2d, k=9 * c, fh=3, fw=3, path="mxu", interpret=True))(
        jax.ShapeDtypeStruct((2, 8, 8, c), jnp.int8),
        jax.ShapeDtypeStruct((o, 9 * c // 32), jnp.int32)).jaxpr
    [body] = _pallas_kernels(jaxpr)
    guards = [e for e in body.eqns if e.primitive.name == "cond"]
    assert len(guards) == 1
    guarded = sum((_unpack_rows(b.jaxpr) for b in guards[0].params["branches"]),
                  Counter())
    tile = _unpack_rows(body)
    assert set(tile) == {kconv.TH * kconv.TW}
    assert set(guarded) == {kconv.BO}
    assert tile[kconv.TH * kconv.TW] == guarded[kconv.BO] > 0


# ---------------------------------------------------------------------------
# direct vs im2col through the bconv layer API (stride-1 SAME, as the BCNN)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c,o,maxpool,fuse_nb", [
    (32, 16, False, True),
    (32, 16, True, True),
    (48, 8, False, True),     # ragged C: explicit direct still bit-exact
    (32, 8, False, False),
    (32, 8, True, False),
])
@pytest.mark.parametrize("path", ["vpu", "mxu"])
def test_apply_packed_direct_equals_im2col(c, o, maxpool, fuse_nb, path):
    rng = np.random.default_rng(c * 31 + o)
    p = bconv.init(jax.random.PRNGKey(3), c, o)
    p = p._replace(
        bn_mean=jnp.asarray(rng.standard_normal(o) * 2, jnp.float32),
        bn_var=jnp.asarray(rng.random(o) * 3 + 0.1, jnp.float32),
        bn_gamma=jnp.asarray(rng.standard_normal(o), jnp.float32),
        bn_beta=jnp.asarray(rng.standard_normal(o), jnp.float32))
    fp = bconv.fold(p)
    a = jnp.asarray(rng.integers(0, 2, (2, 8, 8, c)).astype(np.int8))
    y_i = bconv.apply_packed(fp, a, maxpool=maxpool, fuse_nb=fuse_nb,
                             path=path, strategy="im2col")
    y_d = bconv.apply_packed(fp, a, maxpool=maxpool, fuse_nb=fuse_nb,
                             path=path, strategy="direct")
    np.testing.assert_array_equal(np.asarray(y_i), np.asarray(y_d))


def test_auto_strategy_resolution():
    assert bconv.resolve_strategy("auto", 128) == "direct"
    assert bconv.resolve_strategy("auto", 48) == "im2col"
    assert bconv.resolve_strategy(None, 64) == "direct"
    assert bconv.resolve_strategy("im2col", 128) == "im2col"
    with pytest.raises(ValueError):
        bconv.resolve_strategy("bogus", 32)
    # packed artifacts without the direct layout fall back
    fp = bconv.fold(bconv.init(jax.random.PRNGKey(0), 32, 8))
    assert bconv.resolve_strategy("auto", 32, fp) == "direct"
    fp_old = fp._replace(w_words_hw=None)
    assert bconv.resolve_strategy("auto", 32, fp_old) == "im2col"
    # …but an explicit "direct" on such an artifact fails loudly, not in jit
    with pytest.raises(ValueError, match="re-fold"):
        bconv.resolve_strategy("direct", 32, fp_old)


def test_apply_packed_uses_folded_filter_size():
    """fold() records fh/fw; apply_packed must not assume 3×3."""
    rng = np.random.default_rng(9)
    p = bconv.init(jax.random.PRNGKey(1), 32, 8, fh=5, fw=5)
    fp = bconv.fold(p)
    assert (fp.fh, fp.fw) == (5, 5)
    a = jnp.asarray(rng.integers(0, 2, (1, 9, 9, 32)).astype(np.int8))
    y_d = bconv.apply_packed(fp, a, fuse_nb=False, strategy="direct")
    y_ref = ref.xnor_conv2d_ref(
        a, bitpack.encode_pm1(jnp.asarray(p.w)), stride=1, pad=2)
    np.testing.assert_array_equal(np.asarray(y_d), np.asarray(y_ref))


def test_apply_packed_non_square_filter():
    """fh != fw: per-dimension SAME padding — all paths agree in shape and
    value with the ±1 train forward."""
    rng = np.random.default_rng(13)
    p = bconv.init(jax.random.PRNGKey(2), 32, 8, fh=3, fw=5)
    fp = bconv.fold(p)
    a_bits = jnp.asarray(rng.integers(0, 2, (1, 8, 8, 32)).astype(np.int8))
    y_d = bconv.apply_packed(fp, a_bits, fuse_nb=False, strategy="direct")
    y_i = bconv.apply_packed(fp, a_bits, fuse_nb=False, strategy="im2col")
    assert y_d.shape == y_i.shape == (1, 8, 8, 8)
    np.testing.assert_array_equal(np.asarray(y_d), np.asarray(y_i))
    # against the differentiable ±1 path: y_train = 2·y_l − k (eq. 6)
    a_pm1 = bitpack.decode_pm1(a_bits)
    y_train = bconv.apply_train(p._replace(w=jnp.sign(p.w)), a_pm1,
                                binarize_out=False)
    # undo BN (init BN is identity up to eps) by comparing pre-BN dot sums
    want = (np.asarray(y_train) * np.sqrt(1 + 1e-4)).round().astype(np.int64)
    np.testing.assert_array_equal(2 * np.asarray(y_d) - fp.k, want)


def test_pack_conv_weights_matches_flat_when_aligned():
    """C % 32 == 0 ⇒ per-position packing == flat im2col packing."""
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.choice([-1.0, 1.0], (4, 3, 3, 64)).astype(np.float32))
    per_pos = kconv.pack_conv_weights(w)
    flat = bitpack.pack_pm1(w.reshape(4, -1))
    np.testing.assert_array_equal(np.asarray(per_pos), np.asarray(flat))


@pytest.mark.slow
def test_direct_conv_bcnn_layer_scale():
    """Benchmark-shaped sweep: a full CONV-2-sized layer, both variants."""
    _, a_bits, w_pm1 = _case(32, 32, 128, 128, 3, seed=11, n=1)
    w_words = kconv.pack_conv_weights(w_pm1)
    k = 3 * 3 * 128
    y_ref = ref.xnor_conv2d_ref(a_bits, bitpack.encode_pm1(w_pm1))
    for path in ("vpu", "mxu"):
        y = ops.xnor_conv2d(a_bits, w_words, k=k, fh=3, fw=3, path=path)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
