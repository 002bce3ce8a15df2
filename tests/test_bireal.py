"""Bi-Real Net on the binary engine (core/bireal.py), at a small size on the
CPU: the packed forward against the plain float32 reference, the
zero-padding border correction against a zero-padded conv, the engine's
slot and bulk paths with uint8 pixels, hot-swap, and the Table 2 forward
left as it was by the packed-model seam."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn, bireal, bitpack, execution_plan
from repro.kernels import ops
from repro.serve import BCNNEngine

# 32x32 pixels, widths (32, 64, 64, 128), two blocks a stage: both kinds of
# shortcut, strided convs down to a 1x1 map
CFG = {"input_shape": [32, 32, 3], "input_dtype": "uint8",
       "stem": {"channels": 32, "kernel": 7, "stride": 2,
                "pool": {"kernel": 3, "stride": 2, "pad": 1}},
       "stages": [[32, 2, 1], [64, 2, 2], [64, 2, 2], [128, 2, 2]],
       "n_classes": 10}
GEOM = bireal.geometry(CFG)


@pytest.fixture(scope="module")
def model():
    params = bireal.init(3, GEOM)
    return params, bireal.fold(params, GEOM)


@pytest.fixture(scope="module")
def table2():
    return bcnn.fold_model(jax.jit(bcnn.init)(jax.random.PRNGKey(0)))


def pixels(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (n, *GEOM.input_shape), dtype=np.uint8)


def reference(params, x):
    return np.asarray(jax.jit(
        lambda x: bireal.reference_forward(params, GEOM, x))(x))


@pytest.mark.parametrize("path", ["xla", "mxu"])
def test_packed_forward_matches_reference(model, path):
    """On the dyadic grid of ``bireal.init`` every value of the residual
    stream is exact in float32 in both, so the logits agree to the bit
    (the head runs the same float32 ops on the same features)."""
    params, packed = model
    plan = execution_plan.build_plan(packed, path=path)
    x = pixels(3)
    got = np.asarray(jax.jit(
        lambda x: bireal.forward_packed(packed, x, plan))(x))
    np.testing.assert_array_equal(got, reference(params, x))


@pytest.mark.parametrize("path", ["xla", "mxu"])
@pytest.mark.parametrize("h,w,stride", [(8, 8, 1), (8, 8, 2), (7, 5, 2),
                                        (1, 1, 1), (2, 3, 2)])
def test_border_correction_is_zero_padding(h, w, stride, path):
    """Counts from the kernel, which pads with -1, plus the border
    correction equal a conv of the +-1 map padded with zeros."""
    rng = np.random.default_rng(h * 10 + stride)
    c, o = 32, 16
    x = np.where(rng.random((2, h, w, c)) < 0.5, 1.0, -1.0).astype(np.float32)
    wt = np.where(rng.random((o, 3, 3, c)) < 0.5, 1.0, -1.0).astype(np.float32)
    from repro.kernels.xnor_conv import pack_conv_weights
    y = ops.xnor_conv2d(bitpack.encode_pm1(jnp.asarray(x)),
                        pack_conv_weights(jnp.asarray(wt)), k=9 * c, fh=3,
                        fw=3, stride=stride, pad=1, path=path)
    dot = 2 * np.asarray(y) - 9 * c + np.asarray(bireal.at_positions(
        bireal.border_correction(jnp.asarray(wt), h, w, stride), h, w,
        stride))
    want = jax.lax.conv_general_dilated(
        x, np.transpose(wt, (1, 2, 3, 0)), (stride, stride),
        ((1, 1), (1, 1)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_array_equal(dot, np.asarray(want))


@pytest.fixture(scope="module")
def engine(model):
    """One engine for the tests below, which leave it serving ``model``:
    its slot step and bulk forward compile once."""
    plan = execution_plan.build_plan(model[1], path="mxu")
    return BCNNEngine.from_packed(model[1], plan=plan, n_slots=4,
                                  data_shards=1, data_micro_batch=4)


def test_engine_serves_uint8_through_both_paths(model, engine):
    params, packed = model
    assert engine.input_shape == GEOM.input_shape
    assert engine.input_dtype == np.uint8
    x = pixels(6, seed=1)
    want = reference(params, x)
    rids = [engine.submit(img) for img in x[:5]]
    out = engine.run()
    np.testing.assert_array_equal(np.stack([out[r] for r in rids]), want[:5])
    # a ragged bulk batch: two chunks of four, the second padded
    np.testing.assert_array_equal(engine.classify_batch(x), want)
    assert engine.step_cache_size == 1 and engine.batch_cache_size == 1
    assert engine.classify_batch(x[:0]).shape == (0, GEOM.n_classes)


def test_swap_packed(model, engine):
    params, packed = model
    x = pixels(4, seed=2)
    new = bireal.init(4, GEOM)
    assert engine.swap_packed(bireal.fold(new, GEOM)) == {}
    want = reference(new, x)
    np.testing.assert_array_equal(engine.classify_batch(x), want)
    rid = engine.submit(x[0])
    np.testing.assert_array_equal(engine.run()[rid], want[0])
    wider = bireal.geometry(dict(CFG, n_classes=12))
    with pytest.raises(ValueError):
        engine.swap_packed(bireal.fold(bireal.init(4, wider), wider))
    engine.swap_packed(packed)
    np.testing.assert_array_equal(engine.classify_batch(x),
                                  reference(params, x))
    assert engine.step_cache_size == 1 and engine.batch_cache_size == 1


def test_each_model_checks_its_plan(model, table2):
    _, packed = model
    with pytest.raises(ValueError):
        packed.check_plan(execution_plan.default_plan(table2))
    with pytest.raises(ValueError):
        table2.check_plan(execution_plan.default_plan(packed))


# sha256 of the Table 2 forward's lowered text before the packed-model seam
# (the default plan on each backend; "tpu" lowers the mxu kernels in
# interpret mode here)
TABLE2_LOWERED = {
    "cpu": "bb399beb2073b3b91cef94a6161e22d817afeb61f942ba6e0fd9bdc2483e5646",
    "tpu": "66843a44141bd7675d298255e3a0ccdd0a9fa7e111c6e43f5300d56489552082",
}


@pytest.mark.parametrize("backend", sorted(TABLE2_LOWERED))
def test_table2_forward_lowers_as_before_the_seam(table2, backend):
    plan = execution_plan.default_plan(table2, backend=backend)
    fwd = bcnn.make_packed_forward(table2, plan=plan)
    text = fwd.lower(jnp.zeros((4, 32, 32, 3), jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        TABLE2_LOWERED[backend]
