"""Restartable BCNN training (train/bcnn_train.py) and the end-to-end
trained-artifact lifecycle:

* the jitted train step learns (loss decreases) and clips latents;
* the full ``BCNNTrainState`` (params + Adam moments + step counter)
  roundtrips through ``train/checkpoint.py`` exactly;
* a run killed mid-way and resumed from its checkpoint is BIT-IDENTICAL
  to an uninterrupted run (deterministic ``data/pipeline.py`` stream +
  exact state restore) — including a re-save of the restored step (the
  checkpoint double-save regression);
* the whole lifecycle: train → checkpoint → kill/resume → export artifact
  → the serving engine loads the artifact and its slot/batch results
  match the training-graph oracle's top-1 decisions.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn, bcnn_artifact
from repro.core.execution_plan import default_plan
from repro.serve import BCNNEngine
from repro.train import bcnn_train
from repro.train import checkpoint as ckpt_lib

STEPS, BATCH = 4, 16


def _leaves_equal(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def straight_run():
    """One uninterrupted training run — the oracle for resume parity."""
    return bcnn_train.train(steps=STEPS, batch=BATCH, verbose=False)


def test_loss_decreases(straight_run):
    _, info = straight_run
    losses = info["losses"]
    assert len(losses) == STEPS
    assert losses[STEPS - 1] < losses[0]


def test_latent_weights_stay_clipped(straight_run):
    state, _ = straight_run
    for p in (state.params.conv1, *state.params.convs, *state.params.fcs):
        w = np.asarray(p.w)
        assert w.min() >= -1.0 and w.max() <= 1.0


def test_train_state_checkpoint_roundtrip(tmp_path, straight_run):
    """The full params+optimizer tree survives save/restore exactly
    (fp32 weights and moments, int32 Adam step counter)."""
    state, _ = straight_run
    ckpt_lib.save(str(tmp_path), STEPS, state)
    got, step = ckpt_lib.restore(str(tmp_path),
                                 jax.eval_shape(lambda: state))
    assert step == STEPS
    assert int(got.opt.step) == int(state.opt.step) == STEPS
    _leaves_equal(state, got)


@pytest.fixture(scope="module")
def resumed_run(tmp_path_factory):
    """Kill at step 2 of 4 (checkpoint at step 2), resume to the end."""
    ckdir = str(tmp_path_factory.mktemp("bcnn_ck"))
    with pytest.raises(bcnn_train.SimulatedCrash):
        bcnn_train.train(steps=STEPS, batch=BATCH, ckpt_dir=ckdir,
                         ckpt_every=2, crash_at=2, verbose=False)
    assert ckpt_lib.latest_step(ckdir) == 2
    state, info = bcnn_train.train(steps=STEPS, batch=BATCH, ckpt_dir=ckdir,
                                   ckpt_every=2, resume=True, verbose=False)
    return state, info, ckdir


def test_resume_is_bit_exact(resumed_run, straight_run):
    """The crash+resume run's params, optimizer state, and per-step losses
    are identical to the uninterrupted run's."""
    ref_state, ref_info = straight_run
    state, info, ckdir = resumed_run
    assert info["start_step"] == 2
    _leaves_equal(ref_state, state)
    for s in range(2, STEPS):                       # overlapping steps
        assert info["losses"][s] == ref_info["losses"][s]
    # the resumed run already saved step 4; saving step 4 again exercises
    # the same-step re-save path (the checkpoint double-save regression)
    assert ckpt_lib.latest_step(ckdir) == 4
    ckpt_lib.save(ckdir, 4, state)
    got, _ = ckpt_lib.restore(ckdir, jax.eval_shape(lambda: state), step=4)
    _leaves_equal(state, got)


def test_lifecycle_end_to_end(tmp_path, resumed_run):
    """train → checkpoint → kill/resume → export artifact → engine serves
    the artifact: slot-path and batch-path top-1 match the training-graph
    oracle (``forward_eval``), per the paper's full life cycle."""
    artdir = str(tmp_path / "art")
    state, _, _ = resumed_run

    packed = bcnn.fold_model(state.params)
    bcnn_artifact.save_packed(artdir, packed, provenance={"steps": STEPS})
    loaded = bcnn_artifact.load_packed(artdir)

    x = np.random.default_rng(3).random((6, 32, 32, 3)).astype(np.float32)
    oracle = np.argmax(np.asarray(
        bcnn.forward_eval(state.params, jnp.asarray(x))), -1)

    eng = BCNNEngine.from_packed(
        loaded, n_slots=2,
        plan=dataclasses.replace(default_plan(loaded), path="xla"))
    rids = [eng.submit(img) for img in x]
    out = eng.run()
    slot_top1 = np.argmax(np.stack([out[r] for r in rids]), -1)
    np.testing.assert_array_equal(slot_top1, oracle)

    batch_top1 = np.argmax(eng.classify_batch(x), -1)
    np.testing.assert_array_equal(batch_top1, oracle)
    assert eng.step_cache_size == 1


def test_evaluate_agreement(straight_run):
    """The fold is faithful on trained weights: deployment vs training
    graph top-1 agreement on held-out batches."""
    state, _ = straight_run
    ev = bcnn_train.evaluate(state.params, batch=16, n_batches=2)
    assert ev["n"] == 32
    assert ev["agree"] >= 0.97
