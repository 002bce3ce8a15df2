"""Per-kernel allclose tests vs. the pure-jnp oracles (interpret=True on CPU).

Sweeps shapes (aligned & ragged) and dtypes per the deliverable-(c) contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitpack
from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _rand_pm1(rng, shape):
    return rng.choice(np.array([-1.0, 1.0], np.float32), size=shape)


SHAPES = [
    (8, 64, 16),       # tiny, ragged K and N (padding path)
    (16, 256, 32),     # one packed step
    (128, 1024, 128),  # aligned to default blocks
    (130, 300, 70),    # ragged everything
    (1, 512, 256),     # single row (decode-like)
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("path", ["vpu", "mxu", "xla"])
def test_xnor_matmul_matches_oracle(m, k, n, path):
    rng = np.random.default_rng(hash((m, k, n)) % 2**31)
    a_pm1 = _rand_pm1(rng, (m, k))
    w_pm1 = _rand_pm1(rng, (n, k))
    a_words = bitpack.pack_pm1(jnp.asarray(a_pm1))
    w_words = bitpack.pack_pm1(jnp.asarray(w_pm1))

    y = ops.xnor_matmul(a_words, w_words, k=k, path=path)
    y_ref = ref.xnor_matmul_pm1_ref(jnp.asarray(a_pm1), jnp.asarray(w_pm1))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.parametrize("m,k,n", SHAPES[:3])
@pytest.mark.parametrize("path", ["vpu", "mxu"])
def test_xnor_matmul_fused_normbinarize(m, k, n, path):
    rng = np.random.default_rng(7)
    a_words = bitpack.pack_pm1(jnp.asarray(_rand_pm1(rng, (m, k))))
    w_words = bitpack.pack_pm1(jnp.asarray(_rand_pm1(rng, (n, k))))
    c = jnp.asarray(rng.integers(0, k, size=(n,)).astype(np.float32))
    flip = jnp.asarray(rng.integers(0, 2, size=(n,)).astype(bool))

    bits = ops.xnor_matmul(a_words, w_words, k=k, thr_c=c, thr_flip=flip,
                           path=path)
    y_ref = ref.xnor_matmul_ref(a_words, w_words, k)
    bits_ref = ref.norm_binarize_ref(y_ref, c, flip)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(bits_ref))


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_binary_weight_matmul(m, k, n, dtype):
    rng = np.random.default_rng(hash((m, k, n, str(dtype))) % 2**31)
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32), dtype=dtype)
    w_pm1 = _rand_pm1(rng, (n, k))
    w_words = bitpack.pack_pm1(jnp.asarray(w_pm1))
    scale = jnp.asarray(rng.uniform(0.5, 2.0, size=(n,)).astype(np.float32))

    y = ops.binary_weight_matmul(a, w_words, k=k, scale=scale)
    y_ref = ref.binary_weight_matmul_ref(a, w_words, k, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol * k)


def test_batched_leading_dims():
    rng = np.random.default_rng(3)
    a_pm1 = _rand_pm1(rng, (4, 6, 96))
    w_pm1 = _rand_pm1(rng, (24, 96))
    a_words = bitpack.pack_pm1(jnp.asarray(a_pm1))
    w_words = bitpack.pack_pm1(jnp.asarray(w_pm1))
    y = ops.xnor_matmul(a_words, w_words, k=96, path="mxu")
    assert y.shape == (4, 6, 24)
    y_ref = ref.xnor_matmul_pm1_ref(
        jnp.asarray(a_pm1.reshape(24, 96)), jnp.asarray(w_pm1)).reshape(4, 6, 24)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    for k in [32, 64, 70, 257]:
        bits = rng.integers(0, 2, size=(5, k)).astype(np.int8)
        words = bitpack.pack_bits(bitpack.pad_to_pack(jnp.asarray(bits)))
        back = bitpack.unpack_bits(words, k)
        np.testing.assert_array_equal(np.asarray(back), bits)


# ---------------------------------------------------------------------------
# flash attention kernel (interpret=True on CPU) vs dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,hd", [
    (1, 2, 2, 256, 64),     # MHA
    (2, 4, 2, 256, 64),     # GQA group=2
    (1, 8, 2, 512, 128),    # GQA group=4, MXU-aligned hd
    (1, 2, 1, 384, 64),     # S not a multiple of the block (wrapper pads)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(b, hq, hkv, s, hd, causal):
    rng = np.random.default_rng(hash((b, hq, s, causal)) % 2**31)
    q = jnp.asarray(rng.standard_normal((b, hq, s, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal,
                              q_block=128, kv_block=128)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, q_block=128, kv_block=128)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


# --------------------------------------------------- wrapper shape guardrails

def test_xnor_matmul_rejects_mispacked_weights():
    """A k that doesn't match the packed word count must fail loudly at
    trace time — a silent mismatch would read garbage pad bits."""
    a_words = bitpack.pack_pm1(jnp.ones((4, 64), jnp.float32))
    w_words = bitpack.pack_pm1(jnp.ones((8, 96), jnp.float32))
    with pytest.raises(ValueError, match="packed"):
        ops.xnor_matmul(a_words, w_words, k=64)
    with pytest.raises(ValueError, match="packed int32 words"):
        ops.xnor_matmul(a_words, bitpack.pack_pm1(
            jnp.ones((8, 64), jnp.float32)), k=96)


def test_binary_weight_matmul_rejects_mismatched_k():
    a = jnp.ones((4, 64), jnp.float32)
    w_words = bitpack.pack_pm1(jnp.ones((8, 64), jnp.float32))
    with pytest.raises(ValueError, match="disagrees with the activations"):
        ops.binary_weight_matmul(a, w_words, k=32)
    with pytest.raises(ValueError, match="packed weight words"):
        ops.binary_weight_matmul(jnp.ones((4, 128), jnp.float32),
                                 w_words, k=128)


@pytest.mark.parametrize("k", [40, 70, 97])
def test_binary_weight_matmul_padded_k(k):
    """Ragged K (< kw*32): zero-padded activations neutralize the pad
    weight bits, so the padded path stays oracle-exact."""
    rng = np.random.default_rng(k)
    a = jnp.asarray(_rand_pm1(rng, (5, k)))
    w_words = bitpack.pack_pm1(jnp.asarray(_rand_pm1(rng, (7, k))))
    y = ops.binary_weight_matmul(a, w_words, k=k)
    y_ref = ref.binary_weight_matmul_ref(a, w_words, k)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
