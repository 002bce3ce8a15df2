"""Golden-value regression tests for the Pallas binary-matmul kernels.

Unlike the oracle-parity tests (test_kernels.py), these pin the kernels to
*checked-in* expected int32 tiles computed from small, deterministic,
hand-computable fixtures — so a refactor that breaks both a kernel and its
oracle the same way is still caught, without needing a TPU.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitpack
from repro.kernels import ops

# ---------------------------------------------------------------------------
# Hand-computed micro case (K=4, one packed word, 28 pad bits).
#   a  = [+1, +1, −1, −1]          → bits 0b0011
#   w0 = [+1, −1, +1, −1] agree at positions 0,3          → y_l = 2
#   w1 = [+1, +1, −1, −1] agree everywhere                → y_l = 4
#   w2 = [−1, −1, +1, +1] agree nowhere                   → y_l = 0
# ---------------------------------------------------------------------------

A_HAND = [[+1, +1, -1, -1]]
W_HAND = [[+1, -1, +1, -1], [+1, +1, -1, -1], [-1, -1, +1, +1]]
Y_HAND = [[2, 4, 0]]

# ---------------------------------------------------------------------------
# Formulaic 4×4 tile over K=40 (ragged: 2 words, 24 pad bits).
#   a_bits[i, j] = (3i + 2j) mod 5 < 2
#   w_bits[n, j] = (n + j) mod 3 == 0
#   y_l = (K + (±a)·(±w)ᵀ) / 2, computed once and checked in below.
# ---------------------------------------------------------------------------

K_GOLD = 40
Y_GOLD = [[22, 23, 19, 22],
          [22, 19, 23, 22],
          [20, 23, 21, 20],
          [22, 21, 21, 22]]
# fused NormBinarize with c = [20, 21, 19, 22], flip = [0, 1, 0, 1]
C_GOLD = [20.0, 21.0, 19.0, 22.0]
FLIP_GOLD = [False, True, False, True]
BITS_GOLD = [[1, 0, 1, 0],
             [1, 1, 1, 0],
             [1, 0, 1, 1],
             [1, 0, 1, 0]]

# ---------------------------------------------------------------------------
# binary_weight_matmul: integer-valued activations (exact in bf16×±1 + f32
# accumulation), K=64, checked-in integer outputs.
#   a[i, j] = ((i + 2j) mod 7) − 3;  w_bits[n, j] = (5n + j) mod 4 < 2
# ---------------------------------------------------------------------------

K_BW = 64
BW_GOLD = [[-9, -7, 9],
           [-2, -14, 2]]


def _gold_operands():
    a_bits = np.fromfunction(lambda i, j: (3 * i + 2 * j) % 5 < 2,
                             (4, K_GOLD)).astype(np.int8)
    w_bits = np.fromfunction(lambda n, j: (n + j) % 3 == 0,
                             (4, K_GOLD)).astype(np.int8)
    a_words = bitpack.pack_bits(bitpack.pad_to_pack(jnp.asarray(a_bits)))
    w_words = bitpack.pack_bits(bitpack.pad_to_pack(jnp.asarray(w_bits)))
    return a_words, w_words


@pytest.mark.parametrize("path", ["vpu", "mxu", "xla"])
def test_xnor_matmul_hand_case(path):
    a_words = bitpack.pack_pm1(jnp.asarray(A_HAND, jnp.float32))
    w_words = bitpack.pack_pm1(jnp.asarray(W_HAND, jnp.float32))
    y = ops.xnor_matmul(a_words, w_words, k=4, path=path)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(Y_HAND))


@pytest.mark.parametrize("path", ["vpu", "mxu", "xla"])
def test_xnor_matmul_golden_tile(path):
    a_words, w_words = _gold_operands()
    y = ops.xnor_matmul(a_words, w_words, k=K_GOLD, path=path)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(Y_GOLD))


@pytest.mark.parametrize("path", ["vpu", "mxu"])
def test_xnor_matmul_golden_fused(path):
    a_words, w_words = _gold_operands()
    bits = ops.xnor_matmul(a_words, w_words, k=K_GOLD,
                           thr_c=jnp.asarray(C_GOLD, jnp.float32),
                           thr_flip=jnp.asarray(FLIP_GOLD), path=path)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(BITS_GOLD))


# ---------------------------------------------------------------------------
# Whole-network golden: forward_packed logits on a fixed-seed init() and a
# formulaic input tile, checked in below. Pins the END-TO-END deployment
# path (fold + pack + all 9 layers + fused comparators), so a refactor
# that breaks a kernel AND its oracle the same way — or perturbs the
# fold/threshold arithmetic — is still caught. The integer XNOR part is
# exact; the final FC-3 Norm is fp32, hence the small tolerance.
# ---------------------------------------------------------------------------

LOGITS_SEED = 0
LOGITS_GOLD = [[-37.9981, -1.9999, 9.9995, 0.0, 43.997803,
                -5.999701, 7.9996, 57.997105, 5.999701, -55.997204],
               [-39.998, 71.99641, -23.998802, -5.999701, 33.998302,
                -63.996803, -13.999301, 7.9996, 23.998802, -5.999701]]


def _golden_packed():
    """The packed net behind LOGITS_GOLD: ``init`` at LOGITS_SEED under the
    non-partitionable threefry PRNG (the default before JAX 0.5), in which
    the goldens were computed."""
    from repro.core import bcnn
    with jax.threefry_partitionable(False):
        params = bcnn.init(jax.random.PRNGKey(LOGITS_SEED))
    return bcnn.fold_model(params)


def _golden_input_tile():
    """Deterministic (2, 32, 32, 3) image tile in [0, 1] — a pure formula,
    so the fixture itself cannot drift with PRNG implementations."""
    return (np.fromfunction(
        lambda n, i, j, c: (3 * n + 5 * i + 7 * j + 11 * c) % 29,
        (2, 32, 32, 3)) / 28.0).astype(np.float32)


@pytest.mark.parametrize("conv_strategy", ["direct", "im2col"])
def test_forward_packed_golden_logits(conv_strategy):
    from repro.core import bcnn
    from repro.core.execution_plan import default_plan
    packed = _golden_packed()
    base = default_plan(packed)
    plan = dataclasses.replace(
        base, path="xla",
        conv_strategy=tuple(None if s is None else conv_strategy
                            for s in base.conv_strategy))
    logits = bcnn.forward_packed(packed, jnp.asarray(_golden_input_tile()),
                                 plan=plan)
    got = np.asarray(logits)
    want = np.asarray(LOGITS_GOLD, np.float32)
    np.testing.assert_array_equal(np.argmax(got, -1), np.argmax(want, -1))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("deployment", ["single", "pipelined", "sharded"])
def test_forward_golden_logits_fused(deployment):
    """Cross-layer conv fusion pinned to the SAME golden logits — fusion is
    bit-exact, so the checked-in tile needs no fused variant — on all three
    deployment forwards (single-device, stage-pipelined, data-parallel)."""
    from repro.core import bcnn
    from repro.core.execution_plan import default_plan
    packed = _golden_packed()
    plan = dataclasses.replace(default_plan(packed), path="xla",
                               conv_fusion=True)
    if deployment == "single":
        fwd = bcnn.make_packed_forward(packed, plan=plan)
    elif deployment == "pipelined":
        from repro.parallel.bcnn_pipeline import make_pipelined_forward
        fwd = make_pipelined_forward(packed, n_stages=2, micro_batch=1,
                                     plan=plan)
    else:
        from repro.parallel.bcnn_data_parallel import make_sharded_forward
        fwd = make_sharded_forward(packed, data_shards=1, micro_batch=2,
                                   plan=plan)
    got = np.asarray(fwd(jnp.asarray(_golden_input_tile())))
    want = np.asarray(LOGITS_GOLD, np.float32)
    np.testing.assert_array_equal(np.argmax(got, -1), np.argmax(want, -1))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_binary_weight_matmul_golden():
    a = np.fromfunction(lambda i, j: ((i + 2 * j) % 7) - 3,
                        (2, K_BW)).astype(np.float32)
    w_bits = np.fromfunction(lambda n, j: (5 * n + j) % 4 < 2,
                             (3, K_BW)).astype(np.int8)
    w_words = bitpack.pack_bits(jnp.asarray(w_bits))
    y = ops.binary_weight_matmul(jnp.asarray(a), w_words, k=K_BW)
    np.testing.assert_array_equal(np.asarray(y, np.int64),
                                  np.asarray(BW_GOLD))
