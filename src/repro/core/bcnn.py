"""The paper's 9-layer CIFAR-10 BCNN (Table 2), faithful end to end.

Layer stack (paper Table 2, §2.5):

    CONV-1  3→128   3×3  out 128×32×32   (FpDotProduct, eq. 7: 6-bit × 2-bit)
    CONV-2  128→128 3×3  +MP             out 128×16×16
    CONV-3  128→256 3×3                  out 256×16×16
    CONV-4  256→256 3×3  +MP             out 256×8×8
    CONV-5  256→512 3×3                  out 512×8×8
    CONV-6  512→512 3×3  +MP             out 512×4×4
    FC-1    8192→1024
    FC-2    1024→1024
    FC-3    1024→10  (Norm only, no binarize — paper Fig. 3 step 3)

Two forwards:
* ``forward_train``  — differentiable (STE), batch-stat BN, updates running
  stats; used by examples/train_bcnn_cifar10.py.
* ``forward_packed`` — deployment path: packed int32 weights + fused eq. 8
  comparators via the Pallas XNOR kernels. tests/test_bcnn.py asserts the two
  paths agree bit-for-bit on the binary feature maps.
"""
from __future__ import annotations


from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bconv, bitpack, blinear, packed_model
from repro.core.binarize import binarize_ste, quantize_input_6bit, quantize_weight_2bit
from repro.core.normbinarize import BNParams, norm_only

CONV_SPECS = [  # (in_ch, out_ch, maxpool) — paper Table 2
    (3, 128, False),    # CONV-1 (fp)
    (128, 128, True),   # CONV-2
    (128, 256, False),  # CONV-3
    (256, 256, True),   # CONV-4
    (256, 512, False),  # CONV-5
    (512, 512, True),   # CONV-6
]
FC_SPECS = [(8192, 1024), (1024, 1024), (1024, 10)]  # FC-1..3
BN_EPS = 1e-4
BN_MOMENTUM = 0.9


class BCNNParams(NamedTuple):
    conv1: bconv.FpConvParams
    convs: tuple          # BConvParams × 5 (CONV-2..6)
    fcs: tuple            # BLinearParams × 3


def init(key) -> BCNNParams:
    keys = jax.random.split(key, 9)
    conv1 = bconv.fpconv_init(keys[0], *CONV_SPECS[0][:2])
    convs = tuple(bconv.init(keys[i], CONV_SPECS[i][0], CONV_SPECS[i][1])
                  for i in range(1, 6))
    fcs = tuple(blinear.init(keys[6 + j], *FC_SPECS[j]) for j in range(3))
    return BCNNParams(conv1=conv1, convs=convs, fcs=fcs)


# ---------------------------------------------------------------------------
# Training forward (STE) with batch-stat BN
# ---------------------------------------------------------------------------

def _bn_train(y, gamma, beta, axes):
    """Batch-stat BN: normalize with the biased batch variance (standard
    training semantics), but report the *unbiased* (Bessel-corrected)
    variance for the running-stat side channel — inference BN (and the
    eq. 8 threshold fold consuming ``bn_var``) expects the population
    estimate, not the biased batch moment."""
    mean = jnp.mean(y, axis=axes)
    var = jnp.var(y, axis=axes)
    z = (y - mean) / jnp.sqrt(var + BN_EPS) * gamma + beta
    n = 1
    for a in axes:
        n *= y.shape[a]
    var_u = var * (n / (n - 1)) if n > 1 else var
    return z, mean, var_u


def forward_train(params: BCNNParams, x01: jnp.ndarray):
    """x01: (N,32,32,3) in [0,1]. Returns (logits, batch_stats).

    batch_stats is a list of (mean, var) per normalized layer, in layer
    order, for the trainer's running-average update (BN_MOMENTUM); ``var``
    is the unbiased estimate (see ``_bn_train``).
    """
    stats = []
    # CONV-1 (fp path, eq. 7)
    p = params.conv1
    a0 = quantize_input_6bit(x01)
    w2 = quantize_weight_2bit(p.w)
    y = jax.lax.conv_general_dilated(
        a0, jnp.transpose(w2, (1, 2, 3, 0)), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    z, m, v = _bn_train(y, p.bn_gamma, p.bn_beta, (0, 1, 2))
    stats.append((m, v))
    a = binarize_ste(z)

    # CONV-2..6 (binary)
    for i, p in enumerate(params.convs):
        mp = CONV_SPECS[i + 1][2]
        fh, fw = p.w.shape[1], p.w.shape[2]
        ap = jnp.pad(a, ((0, 0), (fh // 2, fh // 2), (fw // 2, fw // 2),
                         (0, 0)), constant_values=-1.0)
        y = jax.lax.conv_general_dilated(
            ap, jnp.transpose(binarize_ste(p.w), (1, 2, 3, 0)), (1, 1),
            "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if mp:
            y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        z, m, v = _bn_train(y, p.bn_gamma, p.bn_beta, (0, 1, 2))
        stats.append((m, v))
        a = binarize_ste(z)

    # FC-1..3
    a = a.reshape(a.shape[0], -1)                             # (N, 8192) hwc
    for j, p in enumerate(params.fcs):
        y = a @ binarize_ste(p.w).T
        z, m, v = _bn_train(y, p.bn_gamma, p.bn_beta, (0,))
        stats.append((m, v))
        a = binarize_ste(z) if j < 2 else z                   # FC-3: Norm only
    return a, stats


def update_running_stats(params: BCNNParams, stats) -> BCNNParams:
    """Fold fresh batch statistics into the stored running BN stats."""
    def upd(p, st):
        m, v = st
        return p._replace(
            bn_mean=BN_MOMENTUM * p.bn_mean + (1 - BN_MOMENTUM) * m,
            bn_var=BN_MOMENTUM * p.bn_var + (1 - BN_MOMENTUM) * v)
    conv1 = upd(params.conv1, stats[0])
    convs = tuple(upd(p, stats[1 + i]) for i, p in enumerate(params.convs))
    fcs = tuple(upd(p, stats[6 + j]) for j, p in enumerate(params.fcs))
    return BCNNParams(conv1=conv1, convs=convs, fcs=fcs)


# ---------------------------------------------------------------------------
# Inference forward with *stored* BN stats, fp ±1 domain (oracle for packed)
# ---------------------------------------------------------------------------

def forward_eval(params: BCNNParams, x01: jnp.ndarray) -> jnp.ndarray:
    """Inference logits using running BN stats (the packed path's oracle)."""
    p = params.conv1
    a = bconv.fpconv_apply(p, x01)
    for i, p in enumerate(params.convs):
        a = bconv.apply_train(p, a, maxpool=CONV_SPECS[i + 1][2])
    a = a.reshape(a.shape[0], -1)
    for j, p in enumerate(params.fcs):
        a = blinear.apply_train(p, a, binarize_out=(j < 2))
    return a


# ---------------------------------------------------------------------------
# Deployment: fold + packed forward (Pallas XNOR kernels, eq. 5/8)
# ---------------------------------------------------------------------------

class BCNNPacked(NamedTuple):
    """The packed Table 2 network; a `core/packed_model.py::PackedModel`."""
    conv1: bconv.FpConvParams          # first layer stays fixed-point (eq. 7)
    convs: tuple                       # BConvPacked × 5
    fcs: tuple                         # BLinearPacked × 2 (FC-1, FC-2)
    fc3_w_words: jnp.ndarray           # packed FC-3 weights
    fc3_bn: BNParams                   # FC-3 ends with Norm (no binarize)
    fc3_k: int

    @property
    def input_shape(self) -> tuple[int, int, int]:
        return (32, 32, 3)

    @property
    def input_dtype(self) -> np.dtype:
        return np.dtype(np.float32)

    @property
    def n_classes(self) -> int:
        return self.fc3_w_words.shape[0]

    def check_plan(self, plan) -> None:
        """A plan carries one conv strategy per layer of the nine."""
        if len(plan.conv_strategy) != N_LAYERS:
            raise ValueError(
                f"conv_strategy must have {N_LAYERS} entries, got "
                f"{len(plan.conv_strategy)}")

    def forward(self, x01: jnp.ndarray, plan) -> jnp.ndarray:
        return forward_packed(self, x01, plan=plan)


def fold_model(params: BCNNParams) -> BCNNPacked:
    convs = tuple(bconv.fold(p) for p in params.convs)
    fcs = tuple(blinear.fold(p) for p in params.fcs[:2])
    p3 = params.fcs[2]
    return BCNNPacked(
        conv1=params.conv1, convs=convs, fcs=fcs,
        fc3_w_words=bitpack.pack_pm1(p3.w),
        fc3_bn=BNParams(p3.bn_mean, p3.bn_var, p3.bn_gamma, p3.bn_beta,
                        BN_EPS),
        fc3_k=p3.w.shape[1])


N_LAYERS = 9  # CONV-1..6 (indices 0..5) + FC-1..3 (indices 6..8)


def apply_packed_layer(packed: BCNNPacked, idx: int, h: jnp.ndarray, *,
                       path: str = "mxu",
                       conv_strategy: str | None = None,
                       plan=None) -> jnp.ndarray:
    """Apply ONE layer of the packed deployment forward (paper Fig. 3).

    ``h`` is the layer's input in its *natural* inter-layer form, and the
    return value is the next layer's natural input:

    * idx 0 (CONV-1):   (N, 32, 32, 3) float image in [0, 1]
                        → (N, 32, 32, 128) {0,1} int8 bit feature map
    * idx 1..5 (CONV-2..6): {0,1} int8 NHWC bit maps in / out (spatial dims
                        halve after the max-pool layers, Table 2)
    * idx 6 (FC-1):     (N, 4, 4, 512) bit map in — flattened and packed to
                        (N, 256) int32 words on entry — → (N, 32) words out
    * idx 7 (FC-2):     (N, 32) int32 packed words in / out
    * idx 8 (FC-3):     (N, 32) words → (N, 10) float32 logits (Norm only)

    This is the unit the stage-pipelined deployment forward
    (``parallel/bcnn_pipeline.py``) partitions; ``forward_packed`` is the
    sequential fold of all ``N_LAYERS`` of them.

    ``plan`` — an `core/execution_plan.py::ExecutionPlan`; when given it
    supplies the kernel path and the per-layer resolved conv strategy, and
    the bare ``path=``/``conv_strategy=`` kwargs are ignored (they remain
    as deprecated shims for one release).
    """
    from repro.kernels import ops
    if plan is not None:
        path = plan.path
        conv_strategy = plan.strategy_for(idx)
    if idx == 0:
        # layer 1: fp conv (eq. 7) → NormBinarize → {0,1} bits
        return bitpack.encode_pm1(bconv.fpconv_apply(packed.conv1, h))
    if 1 <= idx <= 5:
        return bconv.apply_packed(packed.convs[idx - 1], h,
                                  maxpool=CONV_SPECS[idx][2], path=path,
                                  strategy=conv_strategy)
    if idx in (6, 7):
        if idx == 6:                                    # conv→fc flatten+pack
            h = bitpack.pack_bits(h.reshape(h.shape[0], -1))      # (N, 256)
        bits = blinear.apply_packed(packed.fcs[idx - 6], h, path=path)
        return bitpack.pack_bits(bits)
    if idx == 8:
        # FC-3: XnorDotProduct then Norm (no binarize)
        y_l = ops.xnor_matmul(h, packed.fc3_w_words, k=packed.fc3_k,
                              path=path)
        return norm_only(y_l, packed.fc3_bn, packed.fc3_k)
    raise ValueError(f"layer index {idx} out of range 0..{N_LAYERS - 1}")


def plan_layer_groups(start: int = 0, stop: int = N_LAYERS, *,
                      conv_fusion: bool | None = None
                      ) -> tuple[tuple[int, ...], ...]:
    """Partition layers [start, stop) into fused execution groups.

    With ``conv_fusion`` off (None → ``bconv.DEFAULT_CONV_FUSION``) every
    group is a singleton — the classic one-layer-at-a-time fold. With it on,
    consecutive binary conv layers running at the SAME spatial resolution —
    the first member has no trailing max-pool — pair into one fused
    megakernel call (``kernels/xnor_conv_fused.py``). Table 2 yields exactly
    the boundary-dominated pairs: CONV-3/CONV-4 (16×16 maps, eliminating the
    16·16·256 bit-map boundary) and CONV-5/CONV-6 (8×8 maps, eliminating
    8·8·512). Max-pool boundaries — where the resolution drops — are never
    fused across (a pooling layer can only *end* a group, its pool running
    as the kernel epilogue), and a group never crosses [start, stop): the
    stage-cut contract of ``parallel/bcnn_pipeline.py::PipelinedForward``.

    Returns a tuple of index tuples that partitions ``range(start, stop)``
    in order; every group is a singleton or a fusible (i, i+1) pair.
    """
    fusion = (bconv.DEFAULT_CONV_FUSION if conv_fusion is None
              else bool(conv_fusion))
    groups = []
    i = start
    while i < stop:
        if (fusion and 1 <= i < 5 and i + 1 < stop
                and not CONV_SPECS[i][2]):
            groups.append((i, i + 1))
            i += 2
        else:
            groups.append((i,))
            i += 1
    return tuple(groups)


def group_scope(group: tuple[int, ...]) -> str:
    """The named scope of a layer group: ``conv1``…``conv6``, ``fc1``…
    ``fc3``, and ``conv3_4``/``conv5_6`` for a fused pair."""
    def one(i):
        return f"conv{i + 1}" if i < 6 else f"fc{i - 5}"
    return one(group[0]) + "".join(f"_{g + 1}" for g in group[1:])


def apply_packed_group(packed: BCNNPacked, group: tuple[int, ...],
                       h: jnp.ndarray, *, path: str = "mxu",
                       conv_strategy: str | None = None,
                       plan=None) -> jnp.ndarray:
    """Apply ONE ``plan_layer_groups`` group of the packed forward.

    Singleton groups defer to ``apply_packed_layer``; (i, i+1) pairs run the
    fused megakernel via ``bconv.apply_packed_pair`` — bit-exact with the
    two-layer sequential fold, but the intermediate bit map never leaves
    VMEM. ``conv_strategy`` only shapes unfused layers (the fused kernel is
    its own dataflow). With a ``plan``
    (`core/execution_plan.py::ExecutionPlan`) the path, per-layer strategy,
    and the fused pair's (th, tw) output tile all come from the plan.

    The group's operations run under the named scope ``group_scope(group)``,
    which a device trace carries in each operation's metadata; the
    operations' own names (the kernels' custom calls) are unchanged.
    """
    with jax.named_scope(group_scope(group)):
        if len(group) == 1:
            return apply_packed_layer(packed, group[0], h, path=path,
                                      conv_strategy=conv_strategy, plan=plan)
        i, j = group
        if j != i + 1 or not 1 <= i < j <= 5:
            raise ValueError(f"not a fusible binary-conv pair: {group}")
        tiles = None
        if plan is not None:
            path = plan.path
            tiles = plan.tiles_for(i)
        return bconv.apply_packed_pair(packed.convs[i - 1],
                                       packed.convs[j - 1], h,
                                       maxpool_b=CONV_SPECS[j][2], path=path,
                                       tiles=tiles)


def forward_packed(packed: BCNNPacked, x01: jnp.ndarray,
                   path: str = "mxu",
                   conv_strategy: str | None = None,
                   conv_fusion: bool | None = None,
                   plan=None) -> jnp.ndarray:
    """Deployment forward: bit feature maps all the way (paper Fig. 3).

    All kernel choices live in ONE ``plan``
    (`core/execution_plan.py::ExecutionPlan`); when None, the deprecated
    ``path``/``conv_strategy``/``conv_fusion`` kwargs are resolved into a
    plan via `core/execution_plan.py::build_plan` — the historical rules,
    applied once up front, so legacy call sites compute bit-exactly what
    they always did. Not jit'd at the top level: the packed artifacts carry
    static ints (k) that must stay Python values; each XNOR kernel call is
    jit'd internally.
    """
    if plan is None:
        from repro.core import execution_plan
        plan = execution_plan.build_plan(
            packed, path=path, conv_strategy=conv_strategy,
            conv_fusion=conv_fusion, input_hw=x01.shape[1:3])
    h = x01
    for group in plan_layer_groups(conv_fusion=plan.conv_fusion):
        h = apply_packed_group(packed, group, h, plan=plan)
    return h


# ---------------------------------------------------------------------------
# Weight hot-swap plumbing: arrays ride as jit ARGUMENTS, statics stay closed
# (shared by every packed model: core/packed_model.py)
# ---------------------------------------------------------------------------

split_packed = packed_model.split
assert_swap_compatible = packed_model.check_swap


def make_packed_forward(packed: BCNNPacked, *, path: str = "mxu",
                        conv_strategy: str | None = None,
                        conv_fusion: bool | None = None,
                        plan=None) -> packed_model.PackedForward:
    """Close the packed statics over ``forward_packed`` → a
    `core/packed_model.py::PackedForward`.

    The returned object is a plain ``x01 → logits`` callable with a
    shape-only jit signature — it compiles exactly once per input shape,
    which is the zero-recompile contract the streaming engine
    (``serve/bcnn_engine.py``) relies on — and additionally supports
    ``swap(new_packed)``: zero-recompile weight hot-swap (see
    ``PackedForward``). ``conv_fusion`` turns on the cross-layer fused
    megakernel for the planner's same-resolution pairs; the hot-swap and
    zero-recompile contracts are unchanged (``split_packed`` statics are
    identical — the fused kernel consumes the same packed arrays).
    ``plan`` — an `core/execution_plan.py::ExecutionPlan` carrying every
    kernel choice at once; the other kwargs become no-ops when it is given.
    """
    if plan is None:
        from repro.core import execution_plan
        plan = execution_plan.build_plan(packed, path=path,
                                         conv_strategy=conv_strategy,
                                         conv_fusion=conv_fusion)
    return packed_model.PackedForward(packed, plan)


def loss_fn(params: BCNNParams, x01: jnp.ndarray, labels: jnp.ndarray):
    """Softmax cross-entropy over the Norm output + BN stat side-channel."""
    logits, stats = forward_train(params, x01)
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, stats
