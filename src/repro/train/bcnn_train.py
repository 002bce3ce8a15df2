"""Restartable training for the paper's 9-layer CIFAR-10 BCNN.

The training half of the paper's life cycle (Fig. 3, §2): learn fp latent
weights under binary constraints so that ``core/bcnn.py::fold_model`` can
fold them into the bit-packed deployment net the serving stack runs. One
jitted step implements the Courbariaux/Bengio recipe the paper trains
with:

* STE gradients through every binarization (``core/bcnn.py::loss_fn``);
* Adam on the fp latent ("master") weights — ``train/optimizer.py::AdamW``
  with ``weight_decay=0`` (BN statistics live in the same pytree and must
  not decay) and the [−1, 1] latent clip applied to the *weight* leaves
  only (``clip_latent_weights``; without it the STE's zero-gradient region
  freezes saturated weights forever);
* BN running-stat updates folded in after the optimizer step
  (``core/bcnn.py::update_running_stats`` — unbiased batch variance, the
  estimate the eq. 8 threshold fold expects).

Restartability is the contract, not an afterthought: the whole
``BCNNTrainState`` (params + Adam moments + step counter) checkpoints
step-atomically via ``train/checkpoint.py``, and the data stream
(``data/pipeline.py::SyntheticImages``) is a pure function of
``(seed, step)`` — so a run killed at any step and resumed from its last
checkpoint produces *bit-identical* parameters and losses to one that
never died (tests/test_bcnn_train.py asserts this, and the
``--crash-at``/``--resume`` path of ``launch/train_bcnn.py`` exercises it
from the CLI). The trained result exports through
``core/bcnn_artifact.py`` into ``launch/serve_bcnn.py --artifact`` and
``serve/bcnn_engine.py::BCNNEngine.swap_packed``.

Recipe + operator guide: ``docs/TRAINING.md``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bcnn, execution_plan
from repro.data import SyntheticImages
from repro.train import checkpoint as ckpt_lib
from repro.train import optimizer as opt_lib


class BCNNTrainState(NamedTuple):
    """Everything a restart needs: parameters + optimizer moments (the
    Adam step counter lives inside ``opt.step``)."""
    params: bcnn.BCNNParams
    opt: opt_lib.AdamWState


def make_adamw(lr: float = 2e-3) -> opt_lib.AdamW:
    """The BCNN training optimizer: plain Adam on the latent weights.

    Every deviation from ``AdamW``'s defaults keeps the recipe identical
    to the proven hand-rolled loop this subsystem replaced:

    * ``weight_decay=0`` — the optimizer updates the *whole* params
      pytree and BN means/variances must not decay toward zero;
    * ``clip_latent_unit=False`` — the unit clip belongs on the latent
      weight leaves only (``clip_latent_weights``), not on BN affines;
    * ``grad_clip=inf`` — no global-norm clipping: early BCNN gradients
      routinely have norm ≫ 1, and AdamW's default clip of 1.0 would
      silently change the training trajectory;
    * ``b2=0.999`` — the classic Adam second-moment horizon.
    """
    return opt_lib.AdamW(lr=lr, b2=0.999, weight_decay=0.0,
                         clip_latent_unit=False,
                         grad_clip=float("inf"))


def clip_latent_weights(params: bcnn.BCNNParams) -> bcnn.BCNNParams:
    """Clip every latent weight leaf to [−1, 1], leaving BN leaves alone."""
    def clip_w(p):
        return p._replace(w=jnp.clip(p.w, -1.0, 1.0))
    return bcnn.BCNNParams(conv1=clip_w(params.conv1),
                           convs=tuple(clip_w(p) for p in params.convs),
                           fcs=tuple(clip_w(p) for p in params.fcs))


def init_state(key, adamw: opt_lib.AdamW) -> BCNNTrainState:
    params = bcnn.init(key)
    return BCNNTrainState(params=params, opt=adamw.init(params))


def make_train_step(adamw: opt_lib.AdamW) -> Callable:
    """Jitted ``(state, x01, labels) → (state, metrics)`` train step."""
    def train_step(state: BCNNTrainState, x01, labels):
        (loss, stats), grads = jax.value_and_grad(
            bcnn.loss_fn, has_aux=True)(state.params, x01, labels)
        params, opt, gnorm = adamw.update(grads, state.opt, state.params)
        params = clip_latent_weights(params)
        params = bcnn.update_running_stats(params, stats)
        return (BCNNTrainState(params=params, opt=opt),
                {"loss": loss, "grad_norm": gnorm})
    return jax.jit(train_step)


class SimulatedCrash(RuntimeError):
    """Raised by ``train(crash_at=N)`` after step N (restart testing)."""


def train(*, steps: int, batch: int = 64, lr: float = 2e-3, seed: int = 0,
          ckpt_dir: str | None = None, ckpt_every: int = 0,
          resume: bool = False, crash_at: int | None = None,
          log_every: int = 50, verbose: bool = True
          ) -> tuple[BCNNTrainState, dict]:
    """Run (or resume) a restartable BCNN training loop.

    * ``ckpt_dir``/``ckpt_every`` — save the full ``BCNNTrainState``
      step-atomically every ``ckpt_every`` steps (0 = never).
    * ``resume`` — restore the newest checkpoint under ``ckpt_dir`` (if
      any) and continue from its step; the deterministic data stream
      regenerates exactly the remaining batches, so the resumed run is
      bit-identical to an uninterrupted one.
    * ``crash_at`` — raise ``SimulatedCrash`` once ``crash_at`` steps have
      completed (after any due checkpoint), for restart testing.

    Returns ``(final_state, info)`` with ``info["losses"]`` = per-step
    losses of THIS run (absolute step → loss) and ``info["start_step"]``.
    """
    adamw = make_adamw(lr)
    step_fn = make_train_step(adamw)
    state = init_state(jax.random.PRNGKey(seed), adamw)
    start = 0
    if resume and ckpt_dir and ckpt_lib.latest_step(ckpt_dir) is not None:
        state, start = ckpt_lib.restore(
            ckpt_dir, jax.eval_shape(lambda: state))
        if verbose:
            print(f"[resume] restored step {start} from {ckpt_dir}")
    data = SyntheticImages(global_batch=batch, seed=seed)

    losses: dict[int, float] = {}
    for s in range(start, steps):
        x, y = data.batch(s)
        state, metrics = step_fn(state, jnp.asarray(x), jnp.asarray(y))
        losses[s] = float(metrics["loss"])
        if verbose and ((s + 1) % log_every == 0 or s == start):
            print(f"step {s + 1:5d}  loss={losses[s]:.4f}  "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
        if ckpt_dir and ckpt_every and (s + 1) % ckpt_every == 0:
            path = ckpt_lib.save(ckpt_dir, s + 1, state)
            if verbose:
                print(f"[ckpt] {path}")
        if crash_at is not None and s + 1 >= crash_at:
            raise SimulatedCrash(f"simulated fault after step {s + 1}")
    return state, {"losses": losses, "start_step": start}


def evaluate(params: bcnn.BCNNParams, *, batch: int = 64, seed: int = 0,
             n_batches: int = 4) -> dict:
    """Held-out agreement check of the paper's full life cycle: fold the
    trained params and compare the deployment forward
    (``core/bcnn.py::forward_packed``, on the default plan's kernels in
    their "xla" form) against the training-graph oracle
    (``core/bcnn.py::forward_eval``) on fresh synthetic batches.

    Returns ``{"acc_eval", "acc_packed", "agree", "n"}`` (fractions).
    Eval batches are drawn from the 10_000+ step range so they never
    overlap the training stream.
    """
    data = SyntheticImages(global_batch=batch, seed=seed)
    packed = bcnn.fold_model(params)
    plan = dataclasses.replace(execution_plan.default_plan(packed),
                               path="xla")
    n = correct_eval = correct_packed = agree = 0
    for b in range(n_batches):
        x, y = data.batch(10_000 + b)
        le = bcnn.forward_eval(params, jnp.asarray(x))
        lp = bcnn.forward_packed(packed, jnp.asarray(x), plan=plan)
        pe = np.asarray(jnp.argmax(le, -1))
        pp = np.asarray(jnp.argmax(lp, -1))
        correct_eval += int((pe == y).sum())
        correct_packed += int((pp == y).sum())
        agree += int((pe == pp).sum())
        n += len(y)
    return {"acc_eval": correct_eval / n, "acc_packed": correct_packed / n,
            "agree": agree / n, "n": n}


MIN_FOLD_AGREEMENT = 0.97   # deployment-vs-training top-1 divergence gate


def report_eval(ev: dict) -> None:
    """Print the ``evaluate`` summary and enforce the fold-fidelity gate
    (shared by ``launch/train_bcnn.py`` and the training example)."""
    print(f"eval accuracy   : {ev['acc_eval']:6.1%} (training graph)")
    print(f"packed accuracy : {ev['acc_packed']:6.1%} "
          f"(deployment graph: XNOR + eq.8 comparators)")
    print(f"top-1 agreement : {ev['agree']:6.1%}")
    assert ev["agree"] >= MIN_FOLD_AGREEMENT, \
        "deployment path diverged from training"
