"""Correctness of the paper's §3 reformulation: the packed bit path must agree
with the real-valued ±1 path bit-for-bit (eqs. 5/6/8 equivalences)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bconv, bcnn, bitpack, blinear
from repro.core.binarize import binarize_ste, clip_latent
from repro.core.execution_plan import default_plan
from repro.core.normbinarize import (BNParams, batchnorm_inference,
                                     fold_threshold, norm_binarize)


def test_eq6_compensation():
    """y_lo = 2·y_l − cnum: XNOR agree-count ↔ ±1 dot product."""
    rng = np.random.default_rng(0)
    k = 300
    a = rng.choice([-1.0, 1.0], size=(k,))
    w = rng.choice([-1.0, 1.0], size=(k,))
    y_lo = float(a @ w)
    aw = bitpack.pack_pm1(jnp.asarray(a))
    ww = bitpack.pack_pm1(jnp.asarray(w))
    y_l = int(bitpack.xnor_dot(aw, ww, k))
    assert 2 * y_l - k == y_lo


@pytest.mark.parametrize("gamma_sign", [+1.0, -1.0])
def test_eq8_normbinarize_equals_bn_sign(gamma_sign):
    """NormBinarize(y_l, c_l) ≡ Binarize(BN(2·y_l − cnum)) incl. γ<0 flip."""
    rng = np.random.default_rng(1)
    n, cnum = 64, 117
    y_l = jnp.asarray(rng.integers(0, cnum + 1, size=(256, n)), jnp.int32)
    bn = BNParams(
        mean=jnp.asarray(rng.normal(0, 10, n), jnp.float32),
        var=jnp.asarray(rng.uniform(0.5, 30, n), jnp.float32),
        gamma=jnp.asarray(gamma_sign * rng.uniform(0.2, 3, n), jnp.float32),
        beta=jnp.asarray(rng.normal(0, 2, n), jnp.float32))
    thr = fold_threshold(bn, cnum)
    bits = norm_binarize(y_l, thr)
    y_lo = 2 * y_l - cnum
    ref_bits = (batchnorm_inference(y_lo.astype(jnp.float32), bn) >= 0)
    np.testing.assert_array_equal(np.asarray(bits, bool), np.asarray(ref_bits))


def test_blinear_train_vs_packed_bitexact():
    """A trained-mode binary linear layer and its folded packed form agree."""
    key = jax.random.PRNGKey(2)
    p = blinear.init(key, 256, 96)
    p = p._replace(bn_mean=jax.random.normal(key, (96,)) * 5,
                   bn_var=jax.random.uniform(key, (96,), minval=0.5, maxval=9),
                   bn_gamma=jax.random.normal(key, (96,)),  # mixed signs
                   bn_beta=jax.random.normal(key, (96,)))
    a_pm1 = binarize_ste(jax.random.normal(jax.random.PRNGKey(3), (32, 256)))
    out_train = p and blinear.apply_train(p, a_pm1)              # ±1
    fp = blinear.fold(p)
    a_words = bitpack.pack_pm1(a_pm1)
    out_bits = blinear.apply_packed(fp, a_words)                 # {0,1}
    np.testing.assert_array_equal(
        np.asarray(bitpack.encode_pm1(out_train)), np.asarray(out_bits))


@pytest.mark.parametrize("maxpool", [False, True])
def test_bconv_train_vs_packed_bitexact(maxpool):
    key = jax.random.PRNGKey(4)
    p = bconv.init(key, 32, 16)
    k2 = jax.random.split(key, 4)
    p = p._replace(bn_mean=jax.random.normal(k2[0], (16,)) * 3,
                   bn_var=jax.random.uniform(k2[1], (16,), minval=0.5, maxval=4),
                   bn_gamma=jax.random.normal(k2[2], (16,)),
                   bn_beta=jax.random.normal(k2[3], (16,)))
    a_pm1 = binarize_ste(jax.random.normal(jax.random.PRNGKey(5), (2, 8, 8, 32)))
    out_train = bconv.apply_train(p, a_pm1, maxpool=maxpool)
    fp = bconv.fold(p)
    out_bits = bconv.apply_packed(fp, bitpack.encode_pm1(a_pm1), maxpool=maxpool)
    np.testing.assert_array_equal(
        np.asarray(bitpack.encode_pm1(out_train)), np.asarray(out_bits))


def test_bcnn_eval_vs_packed():
    """Full 9-layer model: eval-mode forward ≡ packed deployment forward."""
    key = jax.random.PRNGKey(6)
    params = bcnn.init(key)
    # randomize BN stats so thresholds are non-trivial
    def jitter(p, k):
        ks = jax.random.split(k, 2)
        return p._replace(
            bn_mean=jax.random.normal(ks[0], p.bn_mean.shape) * 3,
            bn_gamma=jnp.where(
                jax.random.bernoulli(ks[1], 0.2, p.bn_gamma.shape),
                -1.0, 1.0) * p.bn_gamma)
    keys = jax.random.split(jax.random.PRNGKey(7), 9)
    params = bcnn.BCNNParams(
        conv1=jitter(params.conv1, keys[0]),
        convs=tuple(jitter(p, keys[1 + i]) for i, p in enumerate(params.convs)),
        fcs=tuple(jitter(p, keys[6 + j]) for j, p in enumerate(params.fcs)))
    x = jax.random.uniform(jax.random.PRNGKey(8), (2, 32, 32, 3))
    logits_eval = bcnn.forward_eval(params, x)
    packed = bcnn.fold_model(params)
    logits_packed = bcnn.forward_packed(
        packed, x, plan=dataclasses.replace(default_plan(packed), path="mxu"))
    assert logits_eval.shape == (2, 10) and logits_packed.shape == (2, 10)
    assert not np.any(np.isnan(np.asarray(logits_packed)))
    np.testing.assert_allclose(np.asarray(logits_eval),
                               np.asarray(logits_packed), rtol=1e-4, atol=1e-3)


def test_bcnn_train_step_decreases_loss():
    key = jax.random.PRNGKey(9)
    params = bcnn.init(key)
    x = jax.random.uniform(jax.random.PRNGKey(10), (8, 32, 32, 3))
    y = jnp.arange(8) % 10

    @jax.jit
    def step(params, lr):
        (loss, stats), grads = jax.value_and_grad(bcnn.loss_fn, has_aux=True)(
            params, x, y)
        params = jax.tree.map(lambda p, g: clip_latent(p - lr * g),
                              params, grads)
        return params, loss

    losses = []
    for _ in range(8):
        params, loss = step(params, 0.02)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert not any(np.isnan(l) for l in losses)


def test_ste_gradient_window():
    g = jax.grad(lambda x: binarize_ste(x).sum())(jnp.array([-2.0, -0.5, 0.5, 2.0]))
    np.testing.assert_array_equal(np.asarray(g), [0.0, 1.0, 1.0, 0.0])


def test_rwkv_chunked_equals_token_scan():
    """§Perf iteration D: the chunk-parallel wkv must match the token
    scan (same recurrence, matmul-factorized) on random inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.models import rwkv6

    cfg = configs.get_config("rwkv6-3b", smoke=True)
    rng = np.random.default_rng(0)
    b, s, d = 2, 2 * rwkv6.CHUNK, cfg.d_model
    h = d // rwkv6.HEAD_SIZE
    r, k, v = (jnp.asarray(rng.standard_normal((b, s, h, rwkv6.HEAD_SIZE)),
                           jnp.float32) for _ in range(3))
    w = jnp.asarray(
        np.exp(-np.exp(rng.standard_normal((b, s, h, rwkv6.HEAD_SIZE)) - 2)),
        jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, rwkv6.HEAD_SIZE)), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal(
        (b, h, rwkv6.HEAD_SIZE, rwkv6.HEAD_SIZE)) * 0.1, jnp.float32)

    out_c, s_c = rwkv6._wkv_chunked(r, k, v, w, u, s0)

    def step(st, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        o = jnp.einsum("bhk,bhkv->bhv", rt, st + u[..., None] * kv)
        return wt[..., None] * st + kv, o

    xs = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, w))
    s_ref, out_ref = jax.lax.scan(step, s0, xs)
    out_ref = out_ref.transpose(1, 0, 2, 3)

    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_ref),
                               rtol=2e-4, atol=2e-4)


def test_mamba_ssd_chunked_equals_token_scan():
    """§Perf iteration F: blocked SSD ≡ token-scan SSD recurrence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import mamba2

    rng = np.random.default_rng(1)
    b, nh, p_dim, n = 2, 3, 8, 16
    s = 2 * mamba2.CHUNK
    xs = jnp.asarray(rng.standard_normal((b, s, nh, p_dim)), jnp.float32)
    bmat = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    cmat = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, nh))), jnp.float32)
    decay = jnp.asarray(np.exp(-np.abs(rng.standard_normal((b, s, nh)))),
                        jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((b, nh, p_dim, n)) * 0.1,
                     jnp.float32)

    y_c, h_c = mamba2._ssd_chunked(xs, bmat, cmat, dt, decay, h0)

    def step(h, inp):
        xt, bt, ct, dct, dtt = inp
        dbx = dtt[..., None, None] * xt[..., :, None] * bt[:, None, None, :]
        h_new = dct[..., None, None] * h + dbx
        return h_new, jnp.einsum("bhpn,bn->bhp", h_new, ct)

    xs_t = (xs.transpose(1, 0, 2, 3), bmat.transpose(1, 0, 2),
            cmat.transpose(1, 0, 2), decay.transpose(1, 0, 2),
            dt.transpose(1, 0, 2))
    h_ref, y_ref = jax.lax.scan(step, h0, xs_t)
    y_ref = y_ref.transpose(1, 0, 2, 3)

    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_ref),
                               rtol=3e-4, atol=3e-4)
