"""Fusion-parity tier for the cross-layer fused binary-conv megakernel
(`kernels/xnor_conv_fused.py`).

The contract under test: a fused `plan_layer_groups` pair is BIT-EXACT with
the sequential `core/bcnn.py::apply_packed_layer` fold — for every fusible
Table 2 pair, against both sequential conv strategies, across ragged batch
sizes, on the XLA reference and both Pallas kernels (interpret mode on
CPU) — while the planner partitions layers without ever fusing across a
max-pool resolution drop or a pipeline stage cut, and the fused forward
keeps the one-compile / zero-recompile-hot-swap contracts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn, bconv
from repro.core.execution_plan import default_plan
from repro.kernels import ops

# the two fusible same-resolution pairs (CONV-3/4 at 16x16, CONV-5/6 at 8x8)
PAIRS = [(2, 3), (4, 5)]
# input feature-map geometry of each pair's first layer (Table 2)
PAIR_INPUT = {2: (16, 16, 128), 4: (8, 8, 256)}

SINGLETONS = tuple((i,) for i in range(bcnn.N_LAYERS))


@pytest.fixture(scope="module")
def packed():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(3)))


def _plan(packed, path, **fields):
    """``default_plan(packed)`` on ``path``'s kernels, other fields
    replaced as given."""
    return dataclasses.replace(default_plan(packed), path=path, **fields)


def _bits(seed: int, n: int, first: int) -> jnp.ndarray:
    h, w, c = PAIR_INPUT[first]
    u = jax.random.uniform(jax.random.PRNGKey(seed), (n, h, w, c))
    return (u < 0.5).astype(jnp.int8)


def _sequential(packed, pair, a, *, strategy) -> np.ndarray:
    strategies = tuple(None if s is None else strategy
                       for s in default_plan(packed).conv_strategy)
    plan = _plan(packed, "xla", conv_strategy=strategies)
    h = a
    for idx in pair:
        h = bcnn.apply_packed_layer(packed, idx, h, plan)
    return np.asarray(h)


# ------------------------------------------------------------- the planner

def test_plan_layer_groups_exact(packed):
    assert bcnn.plan_layer_groups(conv_fusion=True) == \
        ((0,), (1,), (2, 3), (4, 5), (6,), (7,), (8,))
    assert bcnn.plan_layer_groups(conv_fusion=False) == SINGLETONS
    # the default plan leaves fusion off (opt-in)
    fusion = default_plan(packed).conv_fusion
    assert bcnn.plan_layer_groups(conv_fusion=fusion) == SINGLETONS


def test_plan_layer_groups_respects_stage_cuts():
    # a stage cut through a fusible pair splits it — a group never spans
    # the [start, stop) window of a pipeline stage
    assert bcnn.plan_layer_groups(3, 7, conv_fusion=True) == \
        ((3,), (4, 5), (6,))
    assert bcnn.plan_layer_groups(0, 3, conv_fusion=True) == \
        ((0,), (1,), (2,))
    assert bcnn.plan_layer_groups(4, 6, conv_fusion=True) == ((4, 5),)
    assert bcnn.plan_layer_groups(5, 9, conv_fusion=True) == \
        ((5,), (6,), (7,), (8,))


def test_plan_layer_groups_partition_every_window():
    """Every (start, stop) window: groups partition range(start, stop) in
    order; pairs are adjacent binary convs whose first member never pools
    (fusing across a pool would cross a resolution drop)."""
    for start in range(bcnn.N_LAYERS):
        for stop in range(start, bcnn.N_LAYERS + 1):
            for fusion in (False, True):
                groups = bcnn.plan_layer_groups(start, stop,
                                                conv_fusion=fusion)
                assert [i for g in groups for i in g] == \
                    list(range(start, stop))
                for g in groups:
                    assert len(g) in (1, 2)
                    if len(g) == 2:
                        i, j = g
                        assert j == i + 1 and 1 <= i <= 4
                        assert not bcnn.CONV_SPECS[i][2]


def test_apply_packed_group_rejects_bad_pairs(packed):
    a = _bits(0, 1, 2)
    for bad in [(2, 4), (0, 1), (5, 6), (3, 2)]:
        with pytest.raises(ValueError, match="fusible"):
            bcnn.apply_packed_group(packed, bad, a, _plan(packed, "xla"))


# ----------------------------------------------------------- pair parity

@pytest.mark.parametrize("n", [1, 3])
@pytest.mark.parametrize("strategy", ["direct", "im2col"])
@pytest.mark.parametrize("pair", PAIRS, ids=["conv3-4", "conv5-6"])
def test_fused_pair_parity_xla(packed, pair, strategy, n):
    """Fused group == sequential two-layer fold, bit-exact, for either
    sequential conv strategy and ragged batch sizes."""
    a = _bits(10 * pair[0] + n, n, pair[0])
    ref = _sequential(packed, pair, a, strategy=strategy)
    got = bcnn.apply_packed_group(packed, pair, a, _plan(packed, "xla"))
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.slow
@pytest.mark.parametrize("path", ["vpu", "mxu"])
@pytest.mark.parametrize("pair", PAIRS, ids=["conv3-4", "conv5-6"])
def test_fused_pair_parity_pallas_interpret(packed, pair, path):
    """The actual megakernel (both in-kernel conv variants), interpret
    mode on CPU, against the sequential fold."""
    a = _bits(pair[0], 1, pair[0])
    ref = _sequential(packed, pair, a, strategy="direct")
    got = bcnn.apply_packed_group(packed, pair, a, _plan(packed, path))
    np.testing.assert_array_equal(np.asarray(got), ref)


def test_pair_requires_hw_layout_and_alignment(packed):
    fa, fb = packed.convs[1], packed.convs[2]
    with pytest.raises(ValueError, match="per-position"):
        bconv.apply_packed_pair(fa._replace(w_words_hw=None), fb,
                                _bits(0, 1, 2))
    with pytest.raises(ValueError, match="32-aligned"):
        bconv.apply_packed_pair(fa, fb, _bits(0, 1, 2)[..., :31])


# ----------------------------------------------- compile + swap contracts

def test_pair_kernel_compiles_once(packed):
    """One jit per fused group: the second identically-shaped call is a
    cache hit on `ops.xnor_conv2d_pair`."""
    a = _bits(1, 2, 2)
    fa, fb = packed.convs[1], packed.convs[2]
    r1 = bconv.apply_packed_pair(fa, fb, a, maxpool_b=True, path="xla")
    size = ops.xnor_conv2d_pair._cache_size()
    r2 = bconv.apply_packed_pair(fa, fb, a, maxpool_b=True, path="xla")
    assert ops.xnor_conv2d_pair._cache_size() == size
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


def test_fused_forward_compile_once_and_hot_swap(packed):
    """make_packed_forward on a fused plan: parity with the unfused
    forward, exactly one compile across repeat calls AND a weight
    hot-swap (the `packed_model.split` statics are unchanged by fusion)."""
    x = np.random.default_rng(0).random((2, 32, 32, 3)).astype(np.float32)
    xla = _plan(packed, "xla")
    ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(x), plan=xla))
    fwd = bcnn.make_packed_forward(
        packed, plan=dataclasses.replace(xla, conv_fusion=True))
    np.testing.assert_array_equal(np.asarray(fwd(x)), ref)
    np.testing.assert_array_equal(np.asarray(fwd(x)), ref)
    assert fwd.cache_size() == 1
    packed2 = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(4)))
    fwd.swap(packed2)
    ref2 = np.asarray(bcnn.forward_packed(packed2, jnp.asarray(x), plan=xla))
    np.testing.assert_array_equal(np.asarray(fwd(x)), ref2)
    assert fwd.cache_size() == 1


@pytest.mark.slow
def test_engine_fused_zero_recompile_across_swap(packed):
    """The serving engine with fusion on: logits match the unfused engine
    path and `step_cache_size` stays 1 across a live `swap_packed`."""
    from repro.serve import BCNNEngine
    rng = np.random.default_rng(2)
    imgs = rng.random((3, 32, 32, 3)).astype(np.float32)
    xla = _plan(packed, "xla")
    ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(imgs),
                                         plan=xla))
    eng = BCNNEngine.from_packed(
        packed, n_slots=2, plan=dataclasses.replace(xla, conv_fusion=True))
    rids = [eng.submit(img) for img in imgs]
    out = eng.run()
    for rid, want in zip(rids, ref):
        np.testing.assert_array_equal(out[rid], want)
    packed2 = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(4)))
    eng.swap_packed(packed2)
    ref2 = np.asarray(bcnn.forward_packed(packed2, jnp.asarray(imgs[:1]),
                                          plan=xla))
    rid = eng.submit(imgs[0])
    np.testing.assert_array_equal(eng.run()[rid], ref2[0])
    assert eng.step_cache_size == 1
