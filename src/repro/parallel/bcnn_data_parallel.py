"""Data-parallel multi-device batch serving for the paper's BCNN.

The paper's second Fig. 7 claim (§6.3) is the *large-batch* scenario: for
"static data in large batch sizes" the accelerator sustains peak
throughput, matching a Titan X. The per-stream axis is already covered —
the streaming engine (``serve/bcnn_engine.py``) and the deep per-layer
stage pipeline (``parallel/bcnn_pipeline.py``) reproduce the online side —
but one pipeline only ever processes one image per tick. The natural
second scaling axis (FINN; the FPGA-CNN survey's standard throughput
lever) is *data parallelism*: replicate the whole packed network per
device and split the batch.

This module provides that axis, and its composition with the stage
pipeline into a 2-D **data × stage** deployment plan:

* ``make_sharded_forward(packed, mesh, micro_batch=...)`` — a
  ``shard_map``-based batch-sharded packed forward: the device mesh's
  data axes (``parallel/sharding.py::batch_spec`` over
  ``launch/mesh.py::dp_axes``) split the batch dimension, every shard runs
  the full ``core/bcnn.py::forward_packed`` locally (weights replicated —
  the whole packed model is ~1.7 MB of int32 words, replication is free),
  and no collective ever crosses shards: per-image results are
  independent, so the sharded forward is bit-exact with the sequential
  one by construction — and asserted by tests/test_bcnn_data_parallel.py.
* ``n_stages > 1`` — the 2-D plan: each data shard owns a *column* of
  stage devices running the existing cost-balanced stage pipeline
  (``parallel/bcnn_pipeline.py::make_pipelined_forward``, planned by
  ``plan_bcnn_stages``). Shard columns advance concurrently (dispatch is
  async), stages within a column overlap as before.

**The one-compilation contract.** The jit'd unit only ever sees one
shape: the *chunk* — ``data_shards × micro_batch`` images. Any batch N is
cut into ceil(N / chunk) chunks, the ragged tail zero-padded and the
results sliced back to N (rows never mix). So for a fixed
(shards, stages, micro_batch) plan there is exactly ONE compilation —
``ShardedForward.cache_size()`` — across every batch size, mirroring the
zero-recompile contract of the engine and the stage pipeline.

Measured curves: ``benchmarks/fig7.py --offline`` (throughput vs batch
size × device count). Served through
``serve/bcnn_engine.py::BCNNEngine.classify_batch`` when the engine is
built with ``from_packed(data_shards=...)``. Operator guide:
``docs/SERVING.md``.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.core import bcnn, execution_plan as xplan, packed_model
from repro.core.packed_model import PackedModel
from repro.launch.mesh import dp_axes, make_data_mesh
from repro.parallel import sharding
from repro.parallel.bcnn_pipeline import (PipelinedForward, StagePlan,
                                          pad_rows, plan_bcnn_stages)

class DeploymentPlan(NamedTuple):
    """The 2-D (data × stage) deployment layout of a sharded forward.

    ``chunk = data_shards × micro_batch`` is the one jit'd global shape;
    ``stage_plan`` is the Table-2 cost-balanced layer partition of each
    shard column (``plan_bcnn_stages``; trivial single-stage plan when
    ``n_stages == 1``).
    """
    data_shards: int
    n_stages: int
    micro_batch: int
    chunk: int
    stage_plan: StagePlan | None    # None: a network other than Table 2
    conv_fusion: bool = False
    fused_groups: tuple = ()   # per stage: the plan_layer_groups partition

    def describe(self) -> dict:
        """JSON-ready plan metadata — embedded in every
        ``benchmarks/fig7.py`` dump so a curve is reproducible from the
        artifact alone. ``conv_fusion``/``fused_groups`` record the
        cross-layer fusion plan (one layer-group partition per stage)."""
        return {"data_shards": self.data_shards,
                "n_stages": self.n_stages,
                "micro_batch": self.micro_batch,
                "chunk": self.chunk,
                "stage_bounds": (None if self.stage_plan is None
                                 else list(self.stage_plan.bounds)),
                "conv_fusion": bool(self.conv_fusion),
                "fused_groups": [[list(g) for g in stage]
                                 for stage in self.fused_groups]}


class ShardedForward:
    """Callable: (N, *input_shape) inputs, or their (N, row) wire form
    (``core/packed_model.py::to_wire``) → (N, n_classes) logits,
    batch-sharded, for any `core/packed_model.py::PackedModel`.

    Built by ``make_sharded_forward``. Accepts ANY batch size N (including
    0 and N < chunk) with zero recompiles: batches are processed in
    fixed-shape chunks of ``plan.chunk`` images, the ragged tail padded
    with zero images whose rows are sliced away again. Per-image results
    are independent (pure data parallelism — no cross-shard collective),
    so output rows are bit-identical to the network's own forward
    (``core/bcnn.py::forward_packed``, ``core/bireal.py::forward_packed``).

    With ``n_stages == 1`` the chunk function is one jit'd ``shard_map``
    over the mesh's data axes (``chunk_unit``), and the batch crosses to
    the device in its wire form, told apart by rank: a 2-D array is the
    wire form and passes straight on; host images are viewed as it on the
    host, for free; device-resident images are relaid by one small jit on
    the device. ``input_counts`` counts the calls by route (``"wire"``,
    ``"device_relayout"``). With ``n_stages > 1`` (the Table 2 network
    only) each shard column is a
    ``parallel/bcnn_pipeline.py::PipelinedForward`` over its own stage
    devices, fed (n, *input_shape) images (a wire batch is restored on the
    device first); the chunk is split host-side and the columns run
    concurrently via async dispatch.

    ``cache_size()`` is the one-compilation-per-plan contract (the chunk
    jit, or the max per-stage jit cache across shard pipelines) and must
    stay 1 — guarded by tests/test_bcnn_data_parallel.py and asserted
    inside ``benchmarks/fig7.py --offline``.
    """

    def __init__(self, packed: PackedModel, mesh, micro_batch: int, *,
                 n_stages: int = 1, devices: Sequence | None = None,
                 path: str = "mxu", conv_strategy: str | None = None,
                 conv_fusion: bool | None = None,
                 plan: "xplan.ExecutionPlan | None" = None):
        if micro_batch < 1:
            raise ValueError(f"micro_batch must be >= 1, got {micro_batch}")
        if plan is None:    # deprecated per-knob kwargs → a shim plan
            plan = xplan.build_plan(packed, path=path,
                                    conv_strategy=conv_strategy,
                                    conv_fusion=conv_fusion)
        packed.check_plan(plan)
        self.exec_plan = plan           # the ExecutionPlan (kernel choices)
        self.mesh = mesh
        shards = 1
        for a in dp_axes(mesh):
            shards *= mesh.shape[a]
        table2 = isinstance(packed, bcnn.BCNNPacked)
        if not table2 and n_stages != 1:
            raise ValueError("stage pipelining cuts the Table 2 network's "
                             "nine layers; other networks take n_stages=1")
        stage_plan = plan_bcnn_stages(n_stages) if table2 else None
        self.plan = DeploymentPlan(
            data_shards=shards, n_stages=n_stages, micro_batch=micro_batch,
            chunk=shards * micro_batch, stage_plan=stage_plan,
            conv_fusion=plan.conv_fusion,
            fused_groups=tuple(
                bcnn.plan_layer_groups(stage_plan.bounds[s],
                                       stage_plan.bounds[s + 1],
                                       conv_fusion=plan.conv_fusion)
                for s in range(n_stages)) if table2 else ())
        self._n_classes = packed.n_classes
        if devices is None:
            devices = list(mesh.devices.flat)
        self.devices = tuple(devices)
        self._packed = packed
        if n_stages == 1:
            # pure data parallelism: ONE shard_map'd jit of the whole
            # packed forward, fed the wire form
            arrays, self._chunk_fn = chunk_unit(packed, mesh, plan,
                                                self.plan.chunk)
            self._arrays = self._replicate(arrays)
            # to_wire on the device, for a device-resident image batch
            self._relayout = jax.jit(lambda x: packed_model.to_wire(
                x.astype(packed.input_dtype)))
            self._columns = None
        else:
            # 2-D plan: shard column s pipelines the 9 layers over its own
            # stage devices (round-robin when the grid is larger than the
            # device list — same graceful degradation as PipelinedForward)
            self._chunk_fn = None
            self._columns = tuple(
                PipelinedForward(
                    packed, self.plan.stage_plan,
                    [self.devices[(s * n_stages + j) % len(self.devices)]
                     for j in range(n_stages)],
                    micro_batch, plan=plan)
                for s in range(shards))
            self._unwire = jax.jit(lambda w: packed_model.from_wire(
                w, packed.input_shape, packed.input_dtype))
        self.input_counts = {"wire": 0, "device_relayout": 0}

    @property
    def data_shards(self) -> int:
        return self.plan.data_shards

    @property
    def packed(self) -> PackedModel:
        """The packed net currently being served (all shards/columns)."""
        return self._packed

    def __call__(self, x) -> jnp.ndarray:
        n = x.shape[0]
        if n == 0:          # drop-in contract: empty batch → empty logits
            return jnp.zeros((0, self._n_classes), jnp.float32)
        if self._columns is None:
            x = self._to_wire(x)
        elif x.ndim == 2:   # the stage columns take (n, *input_shape)
            x = self._unwire(x)
        chunk = self.plan.chunk
        n_chunks = -(-n // chunk)
        x = pad_rows(jnp.asarray(x), n_chunks * chunk)      # ragged tail
        outs = []
        for c in range(n_chunks):
            xc = x[c * chunk:(c + 1) * chunk]
            if self._columns is None:
                outs.append(self._chunk_fn(self._arrays, xc))
            else:
                mb = self.plan.micro_batch
                # host-side split; every column call dispatches async, so
                # the shard pipelines genuinely overlap across devices.
                # Each column's logits land on its last stage device —
                # gather them onto one device before concatenating.
                tgt = self.devices[0]
                outs.append(jnp.concatenate(
                    [jax.device_put(col(xc[s * mb:(s + 1) * mb]), tgt)
                     for s, col in enumerate(self._columns)]))
        logits = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
        return logits[:n]

    def _to_wire(self, x):
        """The batch in the chunk unit's wire form, counted in
        ``input_counts`` by the route it took (the class docstring)."""
        if x.ndim == 2:
            self.input_counts["wire"] += 1
            return x
        if isinstance(x, jax.Array):
            self.input_counts["device_relayout"] += 1
            return self._relayout(x)
        self.input_counts["wire"] += 1
        return packed_model.to_wire(np.asarray(x, self._packed.input_dtype))

    def _replicate(self, arrays) -> tuple:
        """Replicate the weight arrays onto the whole mesh once (they ride
        as jit arguments now, not baked-in constants — without this every
        chunk call would re-transfer ~1.7 MB of words per device)."""
        from jax.sharding import NamedSharding
        return jax.device_put(arrays, NamedSharding(self.mesh, P()))

    # ------------------------------------------------------------ contracts
    def swap(self, new_packed: PackedModel) -> None:
        """Hot-swap the served weights (every shard / shard-column); zero
        recompiles — identical shapes reuse the compiled chunk unit
        (checked by ``core/packed_model.py::check_swap``)."""
        if self._columns is None:
            self._arrays = self._replicate(
                packed_model.check_swap(self._packed, new_packed))
        else:
            for col in self._columns:
                col.swap(new_packed)
        self._packed = new_packed

    def cache_size(self) -> int:
        """Compilations of the jit'd chunk unit (max across shard-column
        stages for the 2-D plan). The contract is exactly 1 per
        (shards, stages, micro_batch) plan, for every batch size and
        across any number of ``swap``s."""
        if self._columns is None:
            return int(self._chunk_fn._cache_size())
        return max(col.cache_size() for col in self._columns)


def chunk_unit(packed: PackedModel, mesh, plan, chunk: int):
    """The jit'd unit of a data-parallel forward: ``(weight arrays, wire
    chunk) → (chunk, n_classes)`` logits, one ``shard_map`` over the mesh's
    data axes. The chunk arrives in its wire form
    (``core/packed_model.py::to_wire``), which the unit restores to
    ``(n, *input_shape)`` images as its first operation, on each shard.
    Returns ``(weight arrays, unit)``; the arrays ride as a replicated
    (``P()``) argument, not closed-over constants, so a swap re-binds them
    with zero recompiles."""
    spec = sharding.batch_spec(mesh, chunk)
    arrays, rebuild = packed_model.split(packed)
    shape, dtype = packed.input_shape, packed.input_dtype

    def fwd(arrs, w):
        return rebuild(arrs).forward(
            packed_model.from_wire(w, shape, dtype), plan)

    # check_vma=False: a pallas_call's output carries no varying-axes type,
    # and the per-shard body has no collective that would need one
    return arrays, jax.jit(jax.shard_map(
        fwd, mesh=mesh, in_specs=(P(), spec), out_specs=spec,
        check_vma=False))


def make_sharded_forward(packed: PackedModel, mesh=None, *,
                         data_shards: int | None = None,
                         micro_batch: int = 8, n_stages: int = 1,
                         devices=None, path: str = "mxu",
                         conv_strategy: str | None = None,
                         conv_fusion: bool | None = None,
                         plan: "xplan.ExecutionPlan | None" = None
                         ) -> ShardedForward:
    """Close packed artifacts over a batch-sharded deployment forward.

    The data-parallel counterpart of ``core/bcnn.py::make_packed_forward``
    (and, via ``n_stages``, the 2-D composition with
    ``parallel/bcnn_pipeline.py::make_pipelined_forward``):

    * ``mesh`` — a mesh whose DP axes (``launch/mesh.py::dp_axes``) carry
      the batch split; built with ``launch/mesh.py::make_data_mesh`` from
      ``data_shards`` (default: one shard per local device) when omitted.
    * ``micro_batch`` — per-shard images per chunk; the jit'd global
      shape is ``data_shards × micro_batch`` and never changes.
    * ``n_stages`` — stages per shard column (1 = whole network per
      device). The stage axis reuses ``plan_bcnn_stages`` (Table 2 cost
      balance); ``data_shards × n_stages`` is the device grid.
    * ``devices`` — explicit placement for the 2-D grid (flattened
      row-major: shard-major, stage-minor); defaults to the mesh's
      devices cycled as needed.

    The returned ``ShardedForward`` is bit-exact with ``forward_packed``
    for any batch size and compiles exactly once per plan.
    """
    if not 1 <= n_stages <= bcnn.N_LAYERS:
        raise ValueError(f"n_stages must be in 1..{bcnn.N_LAYERS}, "
                         f"got {n_stages}")
    if data_shards is not None and data_shards < 1:
        raise ValueError(f"data_shards must be >= 1, got {data_shards}")
    if devices is None and n_stages > 1:
        devices = jax.devices()     # the full grid, not just the data axis
    if mesh is None:
        if data_shards is None:
            pool = jax.devices() if devices is None else list(devices)
            data_shards = max(1, len(pool) // n_stages)
        mesh = make_data_mesh(
            data_shards,
            devices=None if devices is None else list(devices)[:data_shards])
    elif data_shards is not None:
        have = 1
        for a in dp_axes(mesh):
            have *= mesh.shape[a]
        if have != data_shards:
            raise ValueError(f"mesh has {have} data shards, "
                             f"data_shards={data_shards} requested")
    return ShardedForward(packed, mesh, micro_batch, n_stages=n_stages,
                          devices=devices, path=path,
                          conv_strategy=conv_strategy,
                          conv_fusion=conv_fusion, plan=plan)
