"""The wire form a bulk batch crosses to the device in
(core/packed_model.py::to_wire, from_wire) and the bulk path that sends it
(parallel/bcnn_data_parallel.py::ShardedForward,
serve/bcnn_engine.py::BCNNEngine.classify_batch).

* the round trip is bit-exact, and on a C-contiguous host batch ``to_wire``
  is a view;
* the bulk path's logits equal ``PackedForward`` on the 4-D images, bit for
  bit, for Table 2 and a small Bi-Real Net, with a ragged batch;
* host images, device images and the wire form all hit the one compiled
  chunk unit, and ``input_counts`` says which route each call took.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn, bireal, execution_plan, packed_model
from repro.parallel.bcnn_data_parallel import make_sharded_forward
from repro.serve import BCNNEngine

# (input_shape, dtype) → the wire row: Table 2, Bi-Real Net at 224², and a
# row whose bytes are not a whole number of 32-bit words
WIRE = {"f32-32x32x3": ((32, 32, 3), np.float32, (3072, np.uint32)),
        "u8-224x224x3": ((224, 224, 3), np.uint8, (37632, np.uint32)),
        "u8-3x3x3": ((3, 3, 3), np.uint8, (27, np.uint8))}


def _plan(packed, path):
    """``default_plan(packed)`` on ``path``'s kernels."""
    return dataclasses.replace(execution_plan.default_plan(packed), path=path)


def batch(n, shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype).kind == "f":
        return rng.standard_normal((n, *shape)).astype(dtype)
    return rng.integers(0, 256, (n, *shape), dtype=dtype)


@pytest.mark.parametrize("n", [1, 5, 128])
@pytest.mark.parametrize("name", sorted(WIRE))
def test_round_trip_is_bit_exact(name, n):
    shape, dtype, (row, wire_dtype) = WIRE[name]
    x = batch(n, shape, dtype, seed=n)
    w = packed_model.to_wire(x)
    assert w.shape == (n, row) and w.dtype == wire_dtype
    back = np.asarray(jax.jit(lambda w: packed_model.from_wire(
        w, shape, dtype))(w))
    assert back.dtype == dtype
    # compare the bytes, so a NaN pattern or a -0.0 would count too
    assert back.tobytes() == x.tobytes()


@pytest.mark.parametrize("name", sorted(WIRE))
def test_to_wire_views_a_contiguous_batch(name):
    shape, dtype, _ = WIRE[name]
    x = batch(5, shape, dtype)
    w = packed_model.to_wire(x)
    assert np.shares_memory(w, x)
    # a batch that is not contiguous is copied into the same form
    y = batch(10, shape, dtype)[::2]
    assert packed_model.to_wire(y).tobytes() == np.ascontiguousarray(
        y).tobytes()


@pytest.mark.parametrize("name", sorted(WIRE))
def test_to_wire_on_the_device_matches_the_host(name):
    shape, dtype, _ = WIRE[name]
    x = batch(3, shape, dtype)
    on_device = jax.jit(packed_model.to_wire)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(on_device),
                                  packed_model.to_wire(x))


# ---------------------------------------------------------------- bulk path

# the small Bi-Real geometry of tests/test_bireal.py: 32x32 uint8 pixels,
# both kinds of shortcut, strided convs down to a 1x1 map
BIREAL_CFG = {"input_shape": [32, 32, 3], "input_dtype": "uint8",
              "stem": {"channels": 32, "kernel": 7, "stride": 2,
                       "pool": {"kernel": 3, "stride": 2, "pad": 1}},
              "stages": [[32, 2, 1], [64, 2, 2], [64, 2, 2], [128, 2, 2]],
              "n_classes": 10}


@pytest.fixture(scope="module", params=["table2", "bireal"])
def model(request):
    """(packed, plan, a ragged batch of host images: 5 at chunk 2, 6 at
    chunk 4)."""
    if request.param == "table2":
        packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
        x = batch(5, packed.input_shape, np.float32)
        chunk = 2
    else:
        geom = bireal.geometry(BIREAL_CFG)
        packed = bireal.fold(bireal.init(3, geom), geom)
        x = batch(6, packed.input_shape, np.uint8, seed=1)
        chunk = 4
    return packed, _plan(packed, "xla"), x, chunk


@pytest.fixture(scope="module")
def want(model):
    packed, plan, x, _ = model
    return np.asarray(packed_model.PackedForward(packed, plan)(x))


def test_classify_batch_matches_packed_forward(model, want):
    packed, plan, x, chunk = model
    eng = BCNNEngine.from_packed(packed, n_slots=2, plan=plan,
                                 data_shards=1, data_micro_batch=chunk)
    np.testing.assert_array_equal(eng.classify_batch(x), want)
    assert eng.batch_input_counts == {"wire": 1, "device_relayout": 0}
    assert eng.steps_executed == 0


def test_every_input_route_hits_one_compile(model, want):
    """Host images, device images, the wire form on the host and on the
    device: the same logits, one compilation of the chunk unit, and each
    call counted by its route."""
    packed, plan, x, chunk = model
    eng = BCNNEngine.from_packed(packed, n_slots=2, plan=plan,
                                 data_shards=1, data_micro_batch=chunk)
    fwd = eng.batch_forward
    wire = packed_model.to_wire(x)
    np.testing.assert_array_equal(eng.classify_batch(x), want)
    np.testing.assert_array_equal(np.asarray(fwd(x)), want)
    np.testing.assert_array_equal(np.asarray(fwd(jnp.asarray(x))), want)
    np.testing.assert_array_equal(np.asarray(fwd(wire)), want)
    np.testing.assert_array_equal(np.asarray(fwd(jnp.asarray(wire))), want)
    np.testing.assert_array_equal(np.asarray(fwd(x[:1])), want[:1])
    assert eng.batch_cache_size == 1
    assert fwd.input_counts == {"wire": 5, "device_relayout": 1}
    assert eng.batch_input_counts == fwd.input_counts
    assert eng.batch_input_counts is not fwd.input_counts


def test_input_counts_without_a_bulk_path():
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    eng = BCNNEngine.from_packed(packed, n_slots=2, plan=_plan(packed, "xla"))
    assert eng.batch_input_counts == {}


def test_stage_columns_take_the_wire_form():
    """The 2-D data × stage plan feeds its columns (n, *input_shape)
    images: a wire batch is restored first, with the same logits."""
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    x = batch(3, packed.input_shape, np.float32)
    want = np.asarray(bcnn.forward_packed(packed, jnp.asarray(x),
                                          plan=_plan(packed, "xla")))
    fwd = make_sharded_forward(packed, data_shards=1, micro_batch=2,
                               n_stages=2, plan=_plan(packed, "xla"))
    np.testing.assert_array_equal(np.asarray(fwd(x)), want)
    np.testing.assert_array_equal(
        np.asarray(fwd(jnp.asarray(packed_model.to_wire(x)))), want)
    assert fwd.cache_size() == 1
