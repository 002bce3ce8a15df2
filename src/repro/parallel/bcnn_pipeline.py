"""Stage-pipelined multi-device deployment forward for the paper's BCNN.

The paper's accelerator is *batch-insensitive* because the 9-layer network
is laid out as deep pipeline stages (§4, Fig. 5/6): every conv/FC unit
processes a different image at the same instant, and eq. 12 —
throughput = freq / max(C_1..C_k) — is the steady-state law of that
spatial layout. This module is the software analogue over a JAX device
list: the packed deployment forward (``core/bcnn.py::forward_packed``) is
cut into N contiguous stages, each stage is jit'd once and pinned to its
own device, and micro-batches of images stream through the stages with
purely asynchronous dispatch — while stage s works on micro-batch t, stage
s−1 is already working on micro-batch t+1.

Three pieces, mirroring the LM pipeline (``parallel/pipeline.py``) but for
a *heterogeneous* layer stack (conv stages with max-pool and shrinking
spatial dims, then FC stages):

* **Stage-cost model** — per-layer binary-op counts from the paper's
  Table 2 (``layer_costs``: eq. 9 ``cycle_conv`` for CONV-1..6, i·o MACs
  for FC-1..3), fed to the same exact DP the Table 3 reproduction uses
  (``core.throughput.balance_stages``) → ``plan_bcnn_stages``.
* **Boundary repacking** — stage boundaries carry *bit-packed* activations
  (``pack_boundary``/``unpack_boundary``): conv/conv boundaries pack the
  {0,1} int8 NHWC feature map 32×-dense along channels into int32 words
  (every BCNN conv width is 32-aligned), FC boundaries are already packed
  words, so inter-device traffic is the paper's one-bit-per-activation
  wire format. Packing runs inside the producing stage's jit; unpacking
  inside the consumer's.
* **``make_pipelined_forward``** — returns a ``PipelinedForward`` closure
  with the same shape-only signature as ``core/bcnn.py::make_packed_forward``,
  so ``serve/bcnn_engine.py::BCNNEngine`` can ride it unchanged: occupancy
  stays host-side data, every stage compiles exactly once
  (``PipelinedForward.cache_size`` — the zero-recompile contract,
  guarded by tests/test_bcnn_pipeline.py).

The schedule is the inference-only fill/drain pipeline: with S stages and
M micro-batches a forward takes M+S−1 ticks, modeled analytically by
``parallel.pipeline.schedule_1f1b(..., fwd_bwd_mult=1.0)``. Docs:
``docs/PIPELINE.md``.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import bcnn, bitpack, execution_plan as xplan, packed_model
from repro.core.throughput import (BCNN_CONV_LAYERS, BCNN_FC_SPECS,
                                   balance_stages, cycle_conv)
from repro.parallel.pipeline import schedule_1f1b, stage_costs_from_bounds

LAYER_NAMES = tuple(d.name for d in BCNN_CONV_LAYERS) + ("FC 1", "FC 2",
                                                         "FC 3")

# Natural inter-layer activation forms of the packed forward (the input of
# layer i lives at boundary i; boundary 9 is the logits). Spatial dims from
# Table 2: pools after CONV-2/4/6 halve H×W. Forms (for batch B):
#   boundary 0:    (B, 32, 32, 3)  float32 image
#   boundary 1..6: (B, H, W, C)    {0,1} int8 bit map    (see _CONV_BOUNDS)
#   boundary 7..8: (B, 32)         int32 packed words
#   boundary 9:    (B, 10)         float32 logits
_CONV_BOUNDS = {1: (32, 32, 128), 2: (16, 16, 128), 3: (16, 16, 256),
                4: (8, 8, 256), 5: (8, 8, 512), 6: (4, 4, 512)}


def layer_costs() -> list[float]:
    """Per-layer op counts of the 9-layer BCNN (the C_l of eq. 12).

    CONV-1..6 use the paper's eq. 9 serial cycle count
    (WID·HEI·DEP·FW·FH·FD, exactly Table 2/3's ``Cycle_conv``); FC-1..3
    use in·out MACs. One XNOR+accumulate per position in both, so the
    units agree and ``balance_stages`` can cut across the conv/FC border.
    """
    return ([float(cycle_conv(d)) for d in BCNN_CONV_LAYERS]
            + [float(i * o) for i, o in BCNN_FC_SPECS])


class StagePlan(NamedTuple):
    """A cost-balanced partition of the 9 layers into pipeline stages."""
    bounds: tuple          # n_stages+1 layer boundaries (bounds[0]=0, [-1]=9)
    costs: tuple           # per-layer op counts (len 9)
    stage_costs: tuple     # per-stage summed cost (len n_stages)

    @property
    def n_stages(self) -> int:
        return len(self.bounds) - 1

    @property
    def bottleneck(self) -> float:
        """max stage cost — the eq. 12 throughput limiter C_max."""
        return max(self.stage_costs)

    @property
    def balance(self) -> float:
        """mean/max stage cost; 1.0 ⇔ perfectly equalized (§4.3 optimum)."""
        return (sum(self.stage_costs)
                / (self.n_stages * self.bottleneck))

    def stage_layers(self, s: int) -> tuple:
        """Layer names of stage ``s`` (for logs/benchmark tables)."""
        return LAYER_NAMES[self.bounds[s]:self.bounds[s + 1]]


def plan_bcnn_stages(n_stages: int) -> StagePlan:
    """Cut the BCNN's 9 layers into ``n_stages`` bottleneck-minimal stages.

    Same exact DP as the paper's Table 3 parallelism allocation
    (``core.throughput.balance_stages``), applied to the Table 2 op counts.
    """
    if not 1 <= n_stages <= bcnn.N_LAYERS:
        raise ValueError(f"n_stages must be in 1..{bcnn.N_LAYERS}, "
                         f"got {n_stages}")
    costs = layer_costs()
    bounds = balance_stages(costs, n_stages)
    return StagePlan(bounds=tuple(bounds), costs=tuple(costs),
                     stage_costs=tuple(stage_costs_from_bounds(costs,
                                                               bounds)))


def schedule_stream(plan: StagePlan, n_micro: int) -> dict:
    """Analytic fill/drain model of the inference pipeline (fwd-only 1F1B).

    ``parallel.pipeline.schedule_1f1b`` with ``fwd_bwd_mult=1``: every tick
    is one forward, M micro-batches drain in M+S−1 ticks, and the
    n_micro→∞ steady rate is eq. 12's 1/C_max.
    """
    return schedule_1f1b(list(plan.stage_costs), n_micro, fwd_bwd_mult=1.0)


# ---------------------------------------------------------------------------
# stage-boundary repacking: bit maps cross devices as packed words
# ---------------------------------------------------------------------------

def pack_boundary(i: int, h: jnp.ndarray) -> jnp.ndarray:
    """Wire format of boundary ``i``: bit-pack what isn't packed already.

    Conv boundaries (1..6) pack the {0,1} int8 NHWC map along its
    32-aligned channel axis → (B, H, W, C//32) int32, an 8× byte shrink of
    the inter-device transfer (and 32× vs a hypothetical fp32 map) — the
    paper's one-bit activation wires between pipeline stages. Boundaries
    0 (image), 7/8 (already words), and 9 (logits) pass through.
    """
    if i in _CONV_BOUNDS:
        return bitpack.pack_bits(h)
    return h


def unpack_boundary(i: int, h: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``pack_boundary``: restore the natural per-layer form."""
    if i in _CONV_BOUNDS:
        return bitpack.unpack_bits(h, k=_CONV_BOUNDS[i][2])
    return h


def pad_rows(x: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    """Zero-pad dim 0 of ``x`` up to ``n_rows`` (no-op when already there).

    The shared ragged-tail contract of the streaming forwards: batches are
    padded to the fixed jit'd granule with zero rows and the results
    sliced back, so rows never mix and no new shape is ever compiled.
    Used by ``PipelinedForward`` and the data-parallel
    ``parallel/bcnn_data_parallel.py::ShardedForward``.
    """
    if x.shape[0] == n_rows:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((n_rows - x.shape[0], *x.shape[1:]), x.dtype)])


def _make_stage_fn(rebuild: Callable, a: int, b: int, *,
                   plan: "xplan.ExecutionPlan") -> Callable:
    """Closure applying layers [a, b): unpack → layers → pack, jit-ready.

    Statics (layer indices, packed k's, filter sizes, and every kernel
    choice in the ``core/execution_plan.py::ExecutionPlan``) are closed
    over while the weight arrays arrive as the first jit argument (the
    ``core/packed_model.py::split`` hot-swap contract), so the returned
    function has a shape-only jit signature — the same contract as
    ``core/bcnn.py::make_packed_forward``, per stage — and a weight swap
    with identical shapes reuses the compiled executable.

    ``plan.conv_fusion`` plans fused conv pairs WITHIN [a, b) only
    (``core/bcnn.py::plan_layer_groups(a, b, ...)``): a stage cut is a
    device boundary, so a group never spans one — fusion within a stage,
    never across it.
    """
    groups = bcnn.plan_layer_groups(a, b, conv_fusion=plan.conv_fusion)

    def stage(arrays, h: jnp.ndarray) -> jnp.ndarray:
        packed = rebuild(arrays)
        h = unpack_boundary(a, h)
        for group in groups:
            h = bcnn.apply_packed_group(packed, group, h, plan=plan)
        return pack_boundary(b, h)
    return stage


# ---------------------------------------------------------------------------
# the pipelined forward
# ---------------------------------------------------------------------------

class PipelinedForward:
    """Callable: (N, 32, 32, 3) images → (N, 10) logits, stage-pipelined.

    Built by ``make_pipelined_forward``. The input batch is split into
    fixed-size micro-batches (the last one zero-padded if ragged — results
    are sliced back, rows never mix); micro-batch m enters stage s at tick
    m+s, so all stages work concurrently once the pipeline fills. Stage
    handoffs are async ``jax.device_put`` transfers of the bit-packed
    boundary forms; nothing blocks until the caller consumes the logits.

    Shape discipline: every stage sees only ``(micro_batch, …)`` shapes,
    so each of the S stage functions compiles exactly once — for ANY total
    batch size N and any occupancy pattern. ``cache_size`` (the max
    per-stage jit-cache size) is the engine's zero-recompile guard and
    must stay 1.
    """

    def __init__(self, packed: bcnn.BCNNPacked, stage_plan: StagePlan,
                 devices: Sequence, micro_batch: int, *,
                 plan: "xplan.ExecutionPlan | None" = None):
        if micro_batch < 1:
            raise ValueError(f"micro_batch must be >= 1, got {micro_batch}")
        if plan is None:
            plan = xplan.default_plan(packed)
        self.plan = stage_plan          # the StagePlan (stage cut points)
        self.exec_plan = plan           # the ExecutionPlan (kernel choices)
        self.micro_batch = micro_batch
        self._packed = packed
        self._n_classes = packed.fc3_w_words.shape[0]
        # stage s runs on devices[s % len(devices)]: fewer devices than
        # stages degrades gracefully (stages co-resident, still correct)
        self.devices = tuple(devices[s % len(devices)]
                             for s in range(stage_plan.n_stages))
        arrays, rebuild = packed_model.split(packed)
        self._stage_arrays = self._place_arrays(arrays)
        self._stage_fns = [
            jax.jit(_make_stage_fn(rebuild, stage_plan.bounds[s],
                                   stage_plan.bounds[s + 1], plan=plan))
            for s in range(stage_plan.n_stages)]

    def _place_arrays(self, arrays) -> list:
        """One device-resident copy of the weight arrays per stage (the
        whole packed net is ~1.7 MB — replication beats a per-call host
        transfer). Mixed-device jit arguments would be rejected, so each
        stage call pairs its committed weights with its committed input."""
        return [jax.device_put(arrays, d) for d in self.devices]

    @property
    def n_stages(self) -> int:
        return self.plan.n_stages

    @property
    def packed(self) -> bcnn.BCNNPacked:
        """The packed net currently being served (all stages)."""
        return self._packed

    def __call__(self, x01: jnp.ndarray) -> jnp.ndarray:
        n = x01.shape[0]
        if n == 0:          # drop-in contract: empty batch → empty logits
            return jnp.zeros((0, self._n_classes), jnp.float32)
        mb = self.micro_batch
        n_micro = -(-n // mb)
        x = pad_rows(jnp.asarray(x01), n_micro * mb)    # ragged tail
        s_n = self.n_stages
        # classic software pipeline: at tick t, stage s holds micro-batch
        # t−s. bufs[s] = stage s's output from the previous tick; iterating
        # stages back-to-front makes each consume last tick's predecessor
        # output. All calls dispatch async — concurrency across devices
        # comes from XLA's non-blocking execution, not host threads.
        bufs: list = [None] * s_n
        outs = []
        for t in range(n_micro + s_n - 1):
            nxt: list = [None] * s_n
            for s in reversed(range(s_n)):
                m = t - s
                if 0 <= m < n_micro:
                    h = x[m * mb:(m + 1) * mb] if s == 0 else bufs[s - 1]
                    nxt[s] = self._stage_fns[s](
                        self._stage_arrays[s],
                        jax.device_put(h, self.devices[s]))
            if nxt[-1] is not None:
                outs.append(nxt[-1])
            bufs = nxt
        logits = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
        return logits[:n]

    # ------------------------------------------------------------ contracts
    def swap(self, new_packed: bcnn.BCNNPacked) -> None:
        """Hot-swap the served weights across every stage; zero recompiles
        (identical shapes → each stage's jit executable is reused; checked
        by ``core/packed_model.py::check_swap``)."""
        arrays = packed_model.check_swap(self._packed, new_packed)
        self._packed = new_packed
        self._stage_arrays = self._place_arrays(arrays)

    def cache_size(self) -> int:
        """Max per-stage jit-cache size — the zero-recompile contract says
        this stays 1 across every batch size, occupancy pattern, and weight
        swap (each stage only ever sees the fixed micro-batch shapes)."""
        return max(int(f._cache_size()) for f in self._stage_fns)


def make_pipelined_forward(packed: bcnn.BCNNPacked, *, n_stages: int,
                           micro_batch: int = 1, devices=None,
                           plan: "xplan.ExecutionPlan | None" = None
                           ) -> PipelinedForward:
    """Close packed artifacts over an N-stage pipelined deployment forward.

    The multi-device counterpart of ``core/bcnn.py::make_packed_forward``:
    stages are planned by ``plan_bcnn_stages`` (Table 2 cost balance),
    jit'd once each, and pinned round-robin onto ``devices`` (default: all
    of ``jax.devices()``; pass an explicit list to choose placement).
    ``micro_batch`` is the streaming granule — smaller means more overlap
    (and more dispatch overhead); the engine default of 1 mirrors the
    paper's one-image-per-stage pipeline. ``plan`` — the
    `core/execution_plan.py::ExecutionPlan` every stage runs; None means
    `core/execution_plan.py::default_plan`.

    The returned ``PipelinedForward`` accepts any batch size N (including
    N < micro_batch) with zero recompiles, so ``BCNNEngine`` can use it as
    a drop-in ``forward_fn``.
    """
    stage_plan = plan_bcnn_stages(n_stages)
    if devices is None:
        devices = jax.devices()
    return PipelinedForward(packed, stage_plan, devices, micro_batch,
                            plan=plan)
