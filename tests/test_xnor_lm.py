"""XNOR LM tier: binarized transformer parity, goldens, and slot serving.

Locks the `models/xnor_lm.py` contracts:

* **bitwise parity** — eager ``forward_train`` ≡ eager ``forward_packed``
  on every logit (not just binarize decisions), for both kernel modes
  (full-XNOR prefill and weight-only decode) — the same standard
  tests/test_xnor_conv_fused.py pins for the conv path;
* **golden tier** — checked-in fixed-seed goldens (prefill logits + 8
  greedy decode steps) pinned on the train-mode AND packed forwards, so a
  refactor that breaks both sides the same way is still caught;
* **serving** — the packed LM on `serve/engine.py::ServingEngine`:
  occupancy-independent outputs, ``step_cache_size == 1`` at any slot
  occupancy and across a weight hot-swap, typed rejection of
  incompatible swaps.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import xnor_lm
from repro.models.xnor_lm import XnorLMConfig

CFG = XnorLMConfig(vocab_size=32, d_model=32, n_layers=2, n_heads=2,
                   d_ff=32, max_len=32)

# ---------------------------------------------------------------------------
# Goldens for CFG at PRNGKey(0), PROMPT below — regenerate by running the
# forwards (they are pinned on BOTH forms; the parity test keeps them equal).
# Min argmax margin along the decode chain is 0.11, far above fp32 noise.
# ---------------------------------------------------------------------------
PROMPT = [3, 1, 4, 1, 5]
GOLD_ARGMAX = [28, 7, 7, 20, 20]             # per-position prefill argmax
GOLD_LOGITS8 = [0.167022, 0.170978, -1.563937, 0.57944,
                -2.232179, 0.588731, -0.885416, -0.444776]
GOLD_DECODE = [20, 4, 20, 20, 4, 12, 16, 7]  # 8 greedy steps


@functools.lru_cache(maxsize=2)
def _model(seed: int = 0):
    # The goldens were computed under the non-partitionable threefry PRNG
    # (the default before JAX 0.5); pin it so the weights stay the same.
    with jax.threefry_partitionable(False):
        params = xnor_lm.init(CFG, jax.random.PRNGKey(seed))
    return params, xnor_lm.fold(CFG, params)


# ------------------------------------------------------------------- config
def test_config_rejects_unpackable_dims():
    with pytest.raises(ValueError, match="d_model must be a multiple"):
        XnorLMConfig(d_model=48)
    with pytest.raises(ValueError, match="d_ff must be a multiple"):
        XnorLMConfig(d_ff=100)
    with pytest.raises(ValueError, match="n_heads"):
        XnorLMConfig(d_model=64, n_heads=3)


def test_param_count_matches_tree():
    params, _ = _model()
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(params))
    assert n == CFG.param_count()


# ------------------------------------------------------------------- parity
@pytest.mark.parametrize("mode", ["xnor", "bw"])
def test_train_vs_packed_bitwise(mode):
    """The central contract: eager train and packed forwards agree on every
    logit BITWISE — the ±1 f32 matmul is integer-exact, so it equals the
    packed agree-counts exactly, and the fp spine is the same graph."""
    params, packed = _model()
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (3, 11)), jnp.int32)
    ref = np.asarray(xnor_lm.forward_train(CFG, params, toks))
    out = np.asarray(xnor_lm.forward_packed(CFG, packed, toks, mode=mode))
    np.testing.assert_array_equal(ref, out)


def test_packed_modes_agree_bitwise():
    _, packed = _model()
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 9)), jnp.int32)
    a = np.asarray(xnor_lm.forward_packed(CFG, packed, toks, mode="xnor"))
    b = np.asarray(xnor_lm.forward_packed(CFG, packed, toks, mode="bw"))
    np.testing.assert_array_equal(a, b)


def test_decode_step_matches_prefill():
    """Cached decode ≡ full-sequence forward at every position (same math,
    different attention plumbing — allclose + exact argmax, since the
    masked-softmax reduction order differs from the tril prefill)."""
    _, packed = _model()
    toks = jnp.asarray([PROMPT], jnp.int32)
    ref = np.asarray(xnor_lm.forward_packed(CFG, packed, toks, mode="bw"))
    state = xnor_lm.init_serve_state(CFG, 1, CFG.max_len)
    for i, t in enumerate(PROMPT):
        logits, state = xnor_lm.decode_step(
            CFG, packed, state, jnp.asarray([[t]], jnp.int32), mode="bw")
        step = np.asarray(logits)[0, 0]
        np.testing.assert_allclose(step, ref[0, i], rtol=1e-5, atol=1e-4)
        assert int(np.argmax(step)) == int(np.argmax(ref[0, i]))


def test_loss_differentiable():
    params, _ = _model()
    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    tgt = jnp.asarray([[2, 3, 4, 5]], jnp.int32)
    loss, grads = jax.value_and_grad(
        lambda p: xnor_lm.loss_fn(CFG, p, toks, tgt))(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.square(g)))
                for g in jax.tree_util.tree_leaves(grads))
    assert gnorm > 0.0    # the STE passes gradient through the binary projs


# ------------------------------------------------------------------ goldens
def test_golden_prefill_train_and_packed():
    params, packed = _model()
    toks = jnp.asarray([PROMPT], jnp.int32)
    for logits in (xnor_lm.forward_train(CFG, params, toks),
                   xnor_lm.forward_packed(CFG, packed, toks, mode="xnor")):
        lg = np.asarray(logits)[0]
        assert list(np.argmax(lg, axis=-1)) == GOLD_ARGMAX
        np.testing.assert_allclose(lg[-1, :8], GOLD_LOGITS8,
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["xnor", "bw"])
def test_golden_greedy_decode_packed(mode):
    _, packed = _model()
    assert xnor_lm.greedy_decode(CFG, packed, PROMPT, 8,
                                 mode=mode) == GOLD_DECODE


def test_golden_greedy_decode_train_oracle():
    """The same 8 tokens out of the train-mode forward, re-running the full
    sequence per step — pins the decode cache path against an oracle that
    has no cache at all."""
    params, _ = _model()
    seq = list(PROMPT)
    out = []
    for _ in range(8):
        lg = np.asarray(xnor_lm.forward_train(
            CFG, params, jnp.asarray([seq], jnp.int32)))
        out.append(int(np.argmax(lg[0, -1])))
        seq.append(out[-1])
    assert out == GOLD_DECODE


# ------------------------------------------------------------------ serving
def test_engine_serves_occupancy_independent_one_compile():
    """Mixed-length prompts through the slot engine: every request's output
    equals the solo eager ``greedy_decode`` reference (occupancy is data),
    with exactly one decode-step compilation."""
    _, packed = _model()
    eng, model = xnor_lm.make_serving_engine(CFG, packed, n_slots=3)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, CFG.vocab_size, (n,)))
               for n in (3, 7, 5, 2, 6)]
    rids = [eng.submit([int(t) for t in p], max_new_tokens=6)
            for p in prompts]
    out = eng.run()
    assert eng.step_cache_size == 1
    for rid, p in zip(rids, prompts):
        ref = xnor_lm.greedy_decode(CFG, packed, [int(t) for t in p], 6,
                                    mode="bw")
        assert out[rid] == ref, f"slot output diverged for prompt {p}"


def test_engine_hot_swap_zero_recompile():
    params2 = xnor_lm.init(CFG, jax.random.PRNGKey(1))
    packed2 = xnor_lm.fold(CFG, params2)
    _, packed = _model()
    eng, model = xnor_lm.make_serving_engine(CFG, packed, n_slots=2)
    eng.submit(PROMPT, max_new_tokens=4)
    out1 = eng.run()
    assert eng.step_cache_size == 1
    eng.swap_params(model.swap_arrays(packed2))
    rid = eng.submit(PROMPT, max_new_tokens=4)
    out2 = eng.run()
    assert eng.step_cache_size == 1, "hot-swap must not recompile"
    assert out2[rid] == xnor_lm.greedy_decode(CFG, packed2, PROMPT, 4,
                                              mode="bw")
    assert out2[rid] != next(iter(out1.values())), \
        "post-swap output should reflect the new weights"


def test_swap_rejects_incompatible_packed():
    _, packed = _model()
    other_cfg = CFG.with_(d_ff=64)
    other = xnor_lm.fold(other_cfg,
                         xnor_lm.init(other_cfg, jax.random.PRNGKey(3)))
    with pytest.raises(ValueError):
        xnor_lm.assert_swap_compatible(packed, other)
    eng, model = xnor_lm.make_serving_engine(CFG, packed, n_slots=2)
    eng.submit(PROMPT, max_new_tokens=2)
    eng.run()
    with pytest.raises(ValueError):
        model.swap_arrays(other)
    # a raw mismatched tuple is caught by the engine itself too
    bad = tuple(jnp.zeros((2, 2), jnp.float32) for _ in model.arrays)
    with pytest.raises(ValueError, match="shape/dtype mismatch"):
        eng.swap_params(bad)


def test_engine_rejects_overlong_prompt():
    _, packed = _model()
    eng, _ = xnor_lm.make_serving_engine(CFG, packed, n_slots=2, max_len=16)
    with pytest.raises(ValueError, match="prompt length"):
        eng.submit(list(range(15)), max_new_tokens=2)
