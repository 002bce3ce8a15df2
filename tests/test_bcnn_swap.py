"""Zero-recompile weight hot-swap (``BCNNEngine.swap_packed``) on all
three deployment forwards — plain (``core/bcnn.py::PackedForward``),
stage-pipelined (``parallel/bcnn_pipeline.py::PipelinedForward``), and
data-parallel (``parallel/bcnn_data_parallel.py::ShardedForward``).

The contract under test:

* a live occupancy sweep before AND after the swap leaves every jit cache
  at exactly 1 compilation (``step_cache_size``/``batch_cache_size``);
* post-swap results are the new net's (checked against the eager
  ``forward_packed`` reference — bit-exact on these fold-of-init nets);
* queued requests at swap time are served with the NEW weights, occupied
  slots (none, outside ``step``) would drain on the old ones;
* shape/static-incompatible replacements and opaque forwards are
  rejected loudly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn
from repro.core.execution_plan import default_plan
from repro.serve import BCNNEngine

N_SLOTS = 3

VARIANTS = {
    "plain": {},
    "pipelined": {"pipeline_stages": 2, "pipeline_micro_batch": 1},
    "data-parallel": {"data_shards": 1, "data_micro_batch": 2},
}


def _plan(packed, path):
    """``default_plan(packed)`` on ``path``'s kernels."""
    return dataclasses.replace(default_plan(packed), path=path)


@pytest.fixture(scope="module")
def packed_a():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def packed_b():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(1)))


@pytest.fixture(scope="module")
def images():
    return np.random.default_rng(0).random(
        (N_SLOTS, 32, 32, 3)).astype(np.float32)


def _occupancy_sweep(eng, images):
    """Drive occupancies 1..n_slots; returns {rid: logits} of the last."""
    out = {}
    for k in range(1, eng.n_slots + 1):
        rids = [eng.submit(img) for img in images[:k]]
        res = eng.run()
        out = {r: res[r] for r in rids}
    return out


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_swap_under_live_occupancy_sweep(variant, packed_a, packed_b,
                                         images):
    ref_a = np.asarray(bcnn.forward_packed(packed_a, jnp.asarray(images),
                                           plan=_plan(packed_a, "xla")))
    ref_b = np.asarray(bcnn.forward_packed(packed_b, jnp.asarray(images),
                                           plan=_plan(packed_b, "xla")))
    eng = BCNNEngine.from_packed(packed_a, n_slots=N_SLOTS,
                                 plan=_plan(packed_a, "xla"),
                                 **VARIANTS[variant])
    out = _occupancy_sweep(eng, images)
    np.testing.assert_array_equal(
        np.stack([out[r] for r in sorted(out)]), ref_a)

    drained = eng.swap_packed(packed_b)
    assert drained == {}                 # no slot is occupied between steps

    out = _occupancy_sweep(eng, images)  # same shapes, new weights
    np.testing.assert_array_equal(
        np.stack([out[r] for r in sorted(out)]), ref_b)
    assert eng.step_cache_size == 1, (
        f"{variant}: hot-swap recompiled the step")

    if eng.batch_forward is not None:    # the bulk route swaps too
        np.testing.assert_array_equal(eng.classify_batch(images), ref_b)
        assert eng.batch_cache_size == 1


def test_queued_requests_get_new_weights(packed_a, packed_b, images):
    """A request submitted before the swap but not yet admitted is served
    with the post-swap net."""
    ref_b = np.asarray(bcnn.forward_packed(packed_b,
                                           jnp.asarray(images[:1]),
                                           plan=_plan(packed_b, "xla")))
    eng = BCNNEngine.from_packed(packed_a, n_slots=N_SLOTS,
                                 plan=_plan(packed_a, "xla"))
    rid = eng.submit(images[0])          # queued, not admitted (no step yet)
    drained = eng.swap_packed(packed_b)
    assert drained == {} and eng.sched.n_queued == 1
    out = eng.run()
    np.testing.assert_array_equal(out[rid], ref_b[0])


def test_incompatible_swap_rejected(packed_a, packed_b):
    eng = BCNNEngine.from_packed(packed_a, n_slots=2,
                                 plan=_plan(packed_a, "xla"))
    # a request pending across the FAILED swap attempts: rejection must
    # leave the engine fully untouched — nothing drained, nothing served
    rid = eng.submit(np.zeros((32, 32, 3), np.float32))
    with pytest.raises(ValueError, match="static"):
        eng.swap_packed(packed_b._replace(fc3_k=packed_b.fc3_k + 1))
    bad_shape = packed_b._replace(
        fc3_w_words=jnp.concatenate([packed_b.fc3_w_words,
                                     packed_b.fc3_w_words]))
    with pytest.raises(ValueError, match="shape"):
        eng.swap_packed(bad_shape)
    assert eng.sched.n_queued == 1 and eng.steps_executed == 0
    # and it still serves with the old net
    ref_a = np.asarray(bcnn.forward_packed(
        packed_a, jnp.zeros((1, 32, 32, 3), jnp.float32),
        plan=_plan(packed_a, "xla")))
    np.testing.assert_array_equal(eng.run()[rid], ref_a[0])


def test_opaque_forward_rejects_swap(packed_b):
    eng = BCNNEngine(lambda x: x.sum(axis=(1, 2, 3))[:, None],
                     n_slots=2, input_shape=(4, 4, 1))
    with pytest.raises(TypeError, match="hot-swap"):
        eng.swap_packed(packed_b)


def test_packed_forward_swap_direct(packed_a, packed_b):
    """The underlying PackedForward: swap updates ``.packed`` and reuses
    the compiled executable (cache stays 1 across swaps and calls)."""
    fwd = bcnn.make_packed_forward(packed_a, plan=_plan(packed_a, "xla"))
    x = jnp.asarray(np.random.default_rng(2).random(
        (2, 32, 32, 3)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(fwd(x)),
        np.asarray(bcnn.forward_packed(packed_a, x,
                                       plan=_plan(packed_a, "xla"))))
    fwd.swap(packed_b)
    assert fwd.packed is packed_b
    np.testing.assert_array_equal(
        np.asarray(fwd(x)),
        np.asarray(bcnn.forward_packed(packed_b, x,
                                       plan=_plan(packed_b, "xla"))))
    assert fwd.cache_size() == 1
