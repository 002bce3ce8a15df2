"""Comparator-based normalization — the paper's eq. (8) reformulation.

Inference-time batch norm + the ±1↔{1,0} compensation (eq. 6) + sign binarization
(eq. 4) fold into a single integer threshold compare:

    NormBinarize(y_l, c_l) = 1  if y_l >= c_l else 0,

where ``y_l`` is the raw XNOR agree-count (eq. 5) and ``c_l`` is one precomputed
constant per output channel.

Derivation (kept explicit because the paper's printed formula has a typo —
it omits a parenthesis; we re-derive from eqs. 2/4/6):

    BN(y_lo) >= 0
    ⇔ γ · (y_lo − µ)/sqrt(σ²+ε) + β >= 0
    ⇔ sign(γ) · (y_lo − µ + β·sqrt(σ²+ε)/γ) >= 0        (divide by |γ|)
    with y_lo = 2·y_l − cnum (eq. 6):
    γ>0:  y_l >= (cnum + µ − β·sqrt(σ²+ε)/γ) / 2  =: c_l   (paper's formula)
    γ<0:  y_l <= c_l  (comparison flips; the paper assumes γ>0 — we keep the
          general form with a per-channel ``flip`` bit so folding is lossless).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class BNParams(NamedTuple):
    """Inference-time batch-norm statistics/affine parameters (per channel)."""
    mean: jnp.ndarray     # µ
    var: jnp.ndarray      # σ²
    gamma: jnp.ndarray    # γ
    beta: jnp.ndarray     # β
    eps: float = 1e-4


class NBThreshold(NamedTuple):
    """Folded comparator parameters: one integer threshold (+flip) per channel."""
    c: jnp.ndarray        # threshold on the XNOR agree-count y_l (float; round opt.)
    flip: jnp.ndarray     # bool: True where γ<0 (comparison direction flips)


def fold_threshold(bn: BNParams, cnum: int, rounded: bool = True) -> NBThreshold:
    """Fold BN params + eq. 6 compensation into the eq. 8 threshold c_l.

    Folding is an offline deployment-build step (``bconv.fold`` /
    ``blinear.fold`` run eagerly, never under jit), so the fold happens in
    host float64: the exact c_l can sit within a float32 ulp of an integer
    (ulp(1152) ≈ 6e-5), and a float32 fold then snaps it *onto* the integer,
    making the ceil/floor below a no-op and shifting the threshold by one.
    Every y_l landing exactly on that boundary flips vs. the BN oracle.
    """
    mean = np.asarray(bn.mean, np.float64)
    var = np.asarray(bn.var, np.float64)
    gamma = np.asarray(bn.gamma, np.float64)
    beta = np.asarray(bn.beta, np.float64)
    denom = np.where(np.abs(gamma) < 1e-12, 1e-12, gamma)
    c = (cnum + mean - beta * np.sqrt(var + float(bn.eps)) / denom) * 0.5
    if rounded:
        # paper: "rounded to the nearest integer for hardware implementation".
        # We round so the integer compare stays *bit-exact* vs. the real BN:
        #   γ>0:  y_l >= c      ⇔ y_l >= ceil(c)        (y_l integer)
        #   γ<0:  y_l <= c      ⇔ y_l <  floor(c)+1 = ~(y_l >= floor(c)+1)
        # (norm_binarize implements the flip as ~(y_l >= c)).
        c = np.where(gamma >= 0, np.ceil(c), np.floor(c) + 1.0)
    # rounded thresholds are integers well below 2**24 → exact in float32
    return NBThreshold(c=jnp.asarray(c, jnp.float32),
                       flip=jnp.asarray(gamma < 0))


def bn_denom(var: jnp.ndarray, eps: float) -> jnp.ndarray:
    """``sqrt(var + eps)`` behind an optimization barrier.

    Part of the deployment path's bit-exactness contract (jit'd engine
    forward ≡ eager ``core/bcnn.py::forward_packed``, asserted by the
    serving tests and benchmark harnesses): the BN arithmetic must round
    identically in and out of jit, for ANY weights — whether they ride as
    constants (closure) or runtime arguments (the
    ``core/packed_model.py::split`` hot-swap path). XLA otherwise rewrites
    ``x / sqrt(v)`` into ``x * rsqrt(v)`` / a division by a constant into
    a reciprocal multiply — 1-ulp differences the eager reference never
    sees. The barrier makes the divisor opaque, pinning the division as
    written. ``bn_affine_exact`` handles the multiply-add half.
    """
    return jax.lax.optimization_barrier(jnp.sqrt(var + eps))


def bn_affine_exact(normalized: jnp.ndarray, gamma: jnp.ndarray,
                    beta: jnp.ndarray) -> jnp.ndarray:
    """``normalized * gamma + beta`` with the multiply barriered so jit
    cannot contract it into an FMA — the other 1-ulp divergence between
    the fused and eager computations (see ``bn_denom``)."""
    return jax.lax.optimization_barrier(normalized * gamma) + beta


def norm_binarize(y_l: jnp.ndarray, thr: NBThreshold) -> jnp.ndarray:
    """Paper eq. (8): the fused comparator. Returns {0,1} bits (int8)."""
    ge = y_l >= thr.c
    bits = jnp.where(thr.flip, ~ge, ge)
    return bits.astype(jnp.int8)


def batchnorm_inference(y_lo: jnp.ndarray, bn: BNParams) -> jnp.ndarray:
    """Reference eq. (2) batch norm on the ±1-domain pre-activation (oracle)."""
    return bn_affine_exact((y_lo - bn.mean) / bn_denom(bn.var, bn.eps),
                           bn.gamma, bn.beta)


def norm_only(y_l: jnp.ndarray, bn: BNParams, cnum: int) -> jnp.ndarray:
    """Final layer (paper Fig. 3 step 3): Norm without binarize, on agree-counts."""
    y_lo = 2 * y_l - cnum
    return batchnorm_inference(y_lo, bn)
