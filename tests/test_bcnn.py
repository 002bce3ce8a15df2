"""The paper's BCNN end to end: training graph ≡ eval graph ≡ packed
deployment graph (XNOR + fused eq. 8 comparators)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn
from repro.core.execution_plan import default_plan


@pytest.fixture(scope="module")
def trained():
    params = bcnn.init(jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3])
    # a couple of steps so BN stats move off their init
    step = jax.jit(lambda p, x, y: jax.value_and_grad(
        bcnn.loss_fn, has_aux=True)(p, x, y))
    for _ in range(2):
        (_, stats), grads = step(params, x, y)
        params = jax.tree.map(lambda p, g: p - 1e-2 * g, params, grads)
        params = bcnn.update_running_stats(params, stats)
    return params, x


def test_forward_train_shapes_and_grads(trained):
    params, x = trained
    logits, stats = bcnn.forward_train(params, x)
    assert logits.shape == (4, 10)
    assert len(stats) == 9                      # 6 conv + 3 fc norms
    (_, _), grads = jax.value_and_grad(bcnn.loss_fn, has_aux=True)(
        params, x, jnp.array([0, 1, 2, 3]))
    # STE: binary conv weights must receive nonzero gradient
    assert float(jnp.abs(grads.convs[0].w).sum()) > 0
    assert float(jnp.abs(grads.fcs[0].w).sum()) > 0


def test_eval_packed_agreement(trained):
    """Deployment (packed XNOR + comparators) ≡ fp eval forward, top-1."""
    params, x = trained
    packed = bcnn.fold_model(params)
    le = bcnn.forward_eval(params, x)
    lp = bcnn.forward_packed(
        packed, x, plan=dataclasses.replace(default_plan(packed), path="xla"))
    np.testing.assert_array_equal(np.argmax(np.asarray(le), -1),
                                  np.argmax(np.asarray(lp), -1))
    # logits agree to BN-arithmetic tolerance (integer y_l is exact; the
    # final Norm is fp)
    np.testing.assert_allclose(np.asarray(le), np.asarray(lp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("path", ["xla", "mxu", "vpu"])
def test_packed_paths_agree(trained, path):
    params, x = trained
    packed = bcnn.fold_model(params)
    plan = default_plan(packed)
    ref = bcnn.forward_packed(packed, x[:2],
                              plan=dataclasses.replace(plan, path="xla"))
    out = bcnn.forward_packed(packed, x[:2],
                              plan=dataclasses.replace(plan, path=path))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_bn_batch_stats_are_unbiased(trained):
    """Fidelity regression: ``update_running_stats`` must fold the
    *unbiased* (Bessel-corrected) batch variance into ``bn_var`` — the
    estimate standard inference BN (and the eq. 8 threshold fold consuming
    ``bn_var``) expects — not the biased moment ``_bn_train`` normalizes
    with."""
    from repro.core.binarize import (quantize_input_6bit,
                                     quantize_weight_2bit)
    params, x = trained
    _, stats = bcnn.forward_train(params, x)
    # replicate CONV-1's pre-activation exactly as forward_train computes it
    p = params.conv1
    y = jax.lax.conv_general_dilated(
        quantize_input_6bit(x),
        jnp.transpose(quantize_weight_2bit(p.w), (1, 2, 3, 0)), (1, 1),
        "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    m, v = stats[0]
    n = y.shape[0] * y.shape[1] * y.shape[2]
    np.testing.assert_allclose(np.asarray(m),
                               np.asarray(jnp.mean(y, axis=(0, 1, 2))),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(v),
        np.asarray(jnp.var(y, axis=(0, 1, 2)) * (n / (n - 1))),
        rtol=1e-4, atol=1e-4)
    # and the running average folds exactly these values with BN_MOMENTUM
    upd = bcnn.update_running_stats(params, stats)
    np.testing.assert_allclose(
        np.asarray(upd.conv1.bn_var),
        np.asarray(bcnn.BN_MOMENTUM * params.conv1.bn_var
                   + (1 - bcnn.BN_MOMENTUM) * v), rtol=1e-6)


def test_train_eval_bn_parity_on_converged_stats(trained):
    """Running stats repeatedly fed the same batch's statistics converge to
    exactly those statistics — so eval-mode BN sees the (unbiased) moments
    of the data it is normalizing, the train-vs-eval parity contract."""
    params, x = trained
    _, stats = bcnn.forward_train(params, x)
    p = params
    for _ in range(300):
        p = bcnn.update_running_stats(p, stats)
    for layer, st in zip([p.conv1, *p.convs, *p.fcs], stats):
        m, v = st
        np.testing.assert_allclose(np.asarray(layer.bn_mean), np.asarray(m),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(layer.bn_var), np.asarray(v),
                                   rtol=1e-4, atol=1e-4)


def test_binary_feature_maps_are_bits(trained):
    params, x = trained
    packed = bcnn.fold_model(params)
    from repro.core import bconv
    a_pm1 = bconv.fpconv_apply(packed.conv1, x)
    assert set(np.unique(np.asarray(a_pm1))) <= {-1.0, 1.0}
