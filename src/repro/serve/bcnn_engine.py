"""Streaming BCNN inference service — the paper's online-request scenario.

The paper's headline result (§6.3, Fig. 7) is *batch-size-insensitive
throughput for online individual requests*: the FPGA wins 8.3× at batch 16
because its streaming pipeline never waits to fill a batch. This engine is
the TPU/Pallas analogue of that serving discipline over the deployment-path
BCNN (``core/bcnn.forward_packed`` — packed bits + XNOR kernels + fused
eq. 8 comparators):

* a fixed set of ``n_slots`` image slots stepped continuously;
* FIFO admission (shared ``serve/slots.py`` scheduler) the moment a slot
  frees — a request never waits for co-arrivals, only for a free slot;
* ONE shape-stable jit'd step: the slot buffer is always
  ``(n_slots, 32, 32, 3)``; occupancy is host-side data, not array shape,
  so the step compiles exactly once however occupancy fluctuates
  (guarded by tests/test_bcnn_engine.py via ``step_cache_size``);
* greedy per-request completion: a BCNN request is a single forward, so
  every occupied slot completes at the end of its step and frees
  immediately for the next queued request;
* per-request latency (submit → done) and aggregate throughput accounting
  (``serve/slots.latency_stats``: p50/p95/p99) — the measured curve behind
  ``benchmarks/fig7.py --online``.

The step's forward can be the single-device packed closure
(``core/bcnn.py::make_packed_forward``) or — with
``from_packed(pipeline_stages=N)`` — the stage-pipelined multi-device
forward (``parallel/bcnn_pipeline.py``), the software analogue of the
paper's per-layer spatial pipeline; the serving contracts above hold for
both.

The paper's *other* Fig. 7 scenario — "static data in large batch sizes"
(§6.3) — is served by ``classify_batch``: with
``from_packed(data_shards=N)`` the engine also owns a batch-sharded
data-parallel forward (``parallel/bcnn_data_parallel.py``), and a bulk
batch at or above ``batch_threshold`` bypasses the slots entirely while
smaller ones stream through them unchanged.

Trained weights come from the artifact lifecycle
(``launch/train_bcnn.py`` → ``core/bcnn_artifact.py`` →
``launch/serve_bcnn.py --artifact``; see ``docs/TRAINING.md``) and can be
replaced under live traffic with ``BCNNEngine.swap_packed`` — a
zero-recompile weight hot-swap on all three forward variants (plain,
stage-pipelined, data-parallel).

Entry points: ``launch/serve_bcnn.py`` (CLI service loop),
``examples/serve_bcnn_cifar10.py`` (Poisson arrival demo).
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packed_model
from repro.core.packed_model import PackedModel
from repro.serve.slots import SlotScheduler, SpanLog, latency_stats


class BCNNEngine:
    """Continuous streaming engine over a one-shot image classifier.

    ``forward_fn``: ``(n_slots, *input_shape) input_dtype → (n_slots,
    n_classes)``; ``input_dtype`` is the dtype images cross to the device
    in (float32 for the Table 2 network, uint8 pixels for Bi-Real Net).
    Two kinds are accepted:

    * a plain shape-only function (no per-call statics) — jit'd here, once;
      use ``BCNNEngine.from_packed`` for a packed network
      (`core/packed_model.py::PackedModel`);
    * a *self-jitting* forward that manages its own compilation and exposes
      a ``cache_size()`` method — e.g. the stage-pipelined
      ``parallel/bcnn_pipeline.py::PipelinedForward``, whose per-stage jits
      must not be re-wrapped in an outer jit (the host-side micro-batch
      streaming loop IS the schedule). It is used as-is and its
      ``cache_size()`` backs ``step_cache_size``.
    """

    def __init__(self, forward_fn: Callable, *, n_slots: int = 8,
                 input_shape: tuple[int, int, int] = (32, 32, 3),
                 input_dtype=np.float32,
                 clock: Callable[[], float] = time.perf_counter,
                 history: int = 4096):
        self.n_slots = n_slots
        self.input_shape = tuple(input_shape)
        self.input_dtype = np.dtype(input_dtype)
        self.sched = SlotScheduler(n_slots, clock=clock, history=history)
        # off until ``spans.enable()``; see "Tracing the engine" in
        # docs/SERVING.md for what each span covers
        self.spans = SpanLog(self.sched.clock)
        self._x = np.zeros((n_slots, *self.input_shape), self.input_dtype)
        self._self_jitting = hasattr(forward_fn, "cache_size")
        if self._self_jitting:
            # e.g. PipelinedForward: owns one jit per pipeline stage (do
            # NOT share one instance across engines — same cache-pollution
            # rule as below)
            self._step_fn = forward_fn
        else:
            # wrap in a per-engine lambda: jax keys its compilation cache
            # on the function object, so two engines sharing one
            # forward_fn would also share (and cross-pollute) the
            # step_cache_size compile counter
            self._step_fn = jax.jit(lambda x: forward_fn(x))
        self._steps = 0
        self._images = 0
        self._occupancy = [0] * (n_slots + 1)   # steps by occupied slots
        self._batch_fn = None           # set by from_packed(data_shards=N)
        self._batch_threshold = 0
        self._n_classes = None          # known for from_packed engines
        self._plan = None               # ExecutionPlan, for from_packed

    @classmethod
    def from_packed(cls, packed: PackedModel, *, n_slots: int = 8,
                    path: str = "auto", conv_strategy: str | None = None,
                    conv_fusion: bool | None = None,
                    plan=None, autotune: bool = False,
                    pipeline_stages: int = 1,
                    pipeline_micro_batch: int = 1,
                    pipeline_devices=None,
                    data_shards: int = 0,
                    data_micro_batch: int = 8,
                    batch_threshold: int | None = None,
                    **kw) -> "BCNNEngine":
        """Engine over a packed network's deployment forward (paper Fig. 3
        path): the Table 2 BCNN or Bi-Real Net, through their common seam
        (`core/packed_model.py::PackedModel`), which also gives the engine
        its input shape and dtype and the logits' width.

        ``pipeline_stages > 1`` (Table 2 only) serves through the
        stage-pipelined multi-device forward (``parallel/bcnn_pipeline.py``)
        instead of the single-device
        ``core/packed_model.py::PackedForward``: the 9 layers
        are cost-balanced onto ``pipeline_devices`` (default all local
        devices) and slot images stream through in
        ``pipeline_micro_batch``-sized granules. The serving contracts are
        unchanged — occupancy stays data, ``step_cache_size`` stays 1.

        ``data_shards >= 1`` additionally equips the engine for the
        paper's *large-batch* Fig. 7 scenario: a batch-sharded
        data-parallel forward
        (``parallel/bcnn_data_parallel.py::make_sharded_forward``, with
        ``n_stages=pipeline_stages`` — the 2-D data × stage plan when both
        are set) that ``classify_batch`` routes to whenever a bulk batch
        reaches ``batch_threshold`` images (default: one full chunk,
        ``data_shards × data_micro_batch``). Slot streaming for individual
        requests is untouched. ``data_shards=0`` (default) disables the
        bulk path.

        ``conv_fusion`` (None → the ``core/bconv.py`` default) turns on the
        cross-layer fused conv megakernel inside whichever forward is built
        — bit-exact, and the ``step_cache_size``/hot-swap contracts are
        unchanged (the fused kernel consumes the same packed arrays).

        ``plan`` — a ``core/execution_plan.py::ExecutionPlan`` carrying
        EVERY kernel choice at once (path, per-layer conv strategy, fusion
        + tiles, LM mode). When given, the per-knob kwargs above are
        ignored; when omitted they build the equivalent plan (deprecated
        shims — new code should pass a plan). ``autotune=True`` measures
        one (``kernels/autotune.py::autotune_packed``) on this device
        first; serving contracts are identical either way (a plan is
        static — trace-time only).
        """
        from repro.core import execution_plan as _xp
        if autotune and plan is None:
            from repro.kernels.autotune import autotune_packed
            plan = autotune_packed(packed)
        if plan is None:    # deprecated per-knob kwargs → a shim plan
            plan = _xp.build_plan(packed, path=path,
                                  conv_strategy=conv_strategy,
                                  conv_fusion=conv_fusion)
        if pipeline_stages > 1:
            from repro.parallel.bcnn_pipeline import make_pipelined_forward
            fwd = make_pipelined_forward(
                packed, n_stages=pipeline_stages,
                micro_batch=pipeline_micro_batch, devices=pipeline_devices,
                plan=plan)
        else:
            fwd = packed_model.PackedForward(packed, plan)
        kw.setdefault("input_shape", packed.input_shape)
        kw.setdefault("input_dtype", packed.input_dtype)
        eng = cls(fwd, n_slots=n_slots, **kw)
        eng._n_classes = packed.n_classes
        eng._plan = plan
        if data_shards >= 1:
            from repro.parallel.bcnn_data_parallel import make_sharded_forward
            eng._batch_fn = make_sharded_forward(
                packed, data_shards=data_shards,
                micro_batch=data_micro_batch, n_stages=pipeline_stages,
                plan=plan)
            eng._batch_threshold = (eng._batch_fn.plan.chunk
                                    if batch_threshold is None
                                    else batch_threshold)
        return eng

    @property
    def clock(self) -> Callable[[], float]:
        """The engine's time source (the one its latency stamps use).
        ``drive_poisson`` times arrivals with it so an injected
        deterministic clock governs the WHOLE drive, not just the stamps."""
        return self.sched.clock

    @property
    def plan(self):
        """The ``core/execution_plan.py::ExecutionPlan`` every forward of
        this engine was built with (slot step, pipeline stages, bulk
        data-parallel path share ONE plan), or None for an opaque
        user ``forward_fn``."""
        return self._plan

    @property
    def forward(self) -> Callable:
        """The step's forward (the jit-wrapped closure, or the self-jitting
        ``PipelinedForward`` — whose ``plan``/``devices`` callers may
        inspect for logging)."""
        return self._step_fn

    # ------------------------------------------------------------------ api
    def submit(self, image: np.ndarray) -> int:
        """Enqueue one image (``input_shape``, cast to ``input_dtype``);
        returns the request id."""
        img = np.asarray(image, self.input_dtype)
        if img.shape != self.input_shape:
            raise ValueError(f"image shape {img.shape} != engine input "
                             f"shape {self.input_shape}")
        return self.sched.submit(img)

    def warmup(self) -> None:
        """Compile the step before timing-sensitive driving (one trace)."""
        jax.block_until_ready(self._step_fn(jnp.asarray(self._x)))

    def step(self) -> dict[int, np.ndarray]:
        """One engine tick: admit from the queue, run the fixed-shape
        forward, complete every occupied slot. Returns {rid: logits}."""
        spans = self.spans
        with spans("engine.step") as step_id:
            with spans("engine.admit"):
                for i, req in self.sched.admit():
                    self._x[i] = req.payload
            return self._flush(step_id)

    def _flush(self, step_id: int | None = None) -> dict[int, np.ndarray]:
        """Run the forward over the slot buffer and complete every occupied
        slot (no admission — ``swap_packed`` uses this to drain in-flight
        requests on the pre-swap weights). ``step_id`` is the enclosing
        ``engine.step`` span's id, stamped on each request served."""
        n = self.sched.n_occupied
        if n == 0:
            return {}
        spans = self.spans
        with spans("engine.put"):
            x = jnp.asarray(self._x)
        with spans("engine.dispatch"):
            y = self._step_fn(x)
        with spans("engine.wait"):
            y = jax.block_until_ready(y)
        with spans("engine.readback"):
            logits = np.asarray(y)
        self._steps += 1
        self._images += n
        self._occupancy[n] += 1
        results = {}
        with spans("engine.complete"):
            for i, req in self.sched.occupied():
                self.sched.complete(i)
                req.step = step_id
                results[req.rid] = logits[i]
        return results

    def swap_packed(self, new_packed: PackedModel
                    ) -> dict[int, np.ndarray]:
        """Hot-swap the served weights under live traffic, zero recompiles.

        The swap contract (tests/test_bcnn_swap.py):

        * the replacement must be shape/static-identical to the current
          packed net (``core/packed_model.py::check_swap``) — so every
          jit'd unit (slot step, pipeline stages, data-parallel chunk) hits
          its existing executable: ``step_cache_size``/``batch_cache_size``
          stay exactly where they were;
        * slots occupied at swap time are drained first — their logits are
          computed with the PRE-swap weights and returned to the caller
          ({} in the usual case: slots only stay occupied inside ``step``);
        * queued (not yet admitted) requests are untouched and will be
          served with the new weights.

        Only engines whose forward supports ``swap`` qualify — i.e. any
        ``from_packed`` engine (plain, pipelined, or data-parallel);
        an opaque user ``forward_fn`` raises TypeError.
        """
        if not hasattr(self._step_fn, "swap"):
            raise TypeError(
                "this engine's forward does not support weight hot-swap; "
                "build it with BCNNEngine.from_packed (core/packed_model.py"
                "::PackedForward / the pipelined or data-parallel forwards)")
        # validate BEFORE draining: a rejected swap must leave the engine
        # untouched (and not silently discard the drained results)
        packed_model.check_swap(self._step_fn.packed, new_packed)
        if self._batch_fn is not None:
            packed_model.check_swap(self._batch_fn.packed, new_packed)
        drained = self._flush()         # pre-swap weights, consistently
        self._step_fn.swap(new_packed)
        if self._batch_fn is not None:
            self._batch_fn.swap(new_packed)
        self._n_classes = new_packed.n_classes
        return drained

    def run(self, max_steps: int = 100_000) -> dict[int, np.ndarray]:
        """Drive until every submitted request completes. {rid: logits}."""
        results: dict[int, np.ndarray] = {}
        for _ in range(max_steps):
            if not self.sched.any_active:
                break
            results.update(self.step())
        return results

    def classify_batch(self, images: np.ndarray) -> np.ndarray:
        """Bulk batch → (N, n_classes) logits, in input order.

        The paper's large-batch Fig. 7 scenario: a batch of at least
        ``batch_threshold`` images (and an engine built with
        ``from_packed(data_shards=...)``) bypasses the slots and runs
        through the batch-sharded data-parallel forward
        (``parallel/bcnn_data_parallel.py``) — one compile per plan, any
        batch size; the batch crosses to the device in its row-major wire
        form (``core/packed_model.py::to_wire``), which the forward restores
        on the device. Smaller batches stream through the slot scheduler
        exactly like individually submitted requests. Both routes produce
        bit-identical logits.

        Single-driver contract (same as ``run``/``drive_poisson``): the
        slot route drives the engine loop until its own requests finish,
        so requests already queued by another caller are served alongside
        but their logits are delivered to THIS loop and dropped (the
        scheduler retains latency stamps, not results). Route concurrent
        traffic through one driving loop rather than interleaving
        ``classify_batch`` with pending ``submit``s.
        """
        spans = self.spans
        with spans("engine.classify_batch"):
            images = np.asarray(images, self.input_dtype)
            if images.ndim != 1 + len(self.input_shape) or \
                    images.shape[1:] != self.input_shape:
                raise ValueError(f"batch shape {images.shape} != (N, "
                                 f"{', '.join(map(str, self.input_shape))})")
            if len(images) == 0:
                # zero images carry zero information: answer host-side
                # before either route (the bulk path used to pay a full
                # padded-chunk device round-trip here). Width is known for
                # from_packed engines; 0 for opaque forwards.
                # ``batch_cache_size`` is untouched — the bulk forward
                # neither compiles nor runs.
                return np.zeros((0, self._n_classes or 0), np.float32)
            if self._batch_fn is not None and \
                    len(images) >= self._batch_threshold:
                with spans("bulk.put"):     # row-major: no host transpose
                    x = jnp.asarray(packed_model.to_wire(images))
                with spans("bulk.dispatch"):
                    y = self._batch_fn(x)
                with spans("bulk.wait"):
                    y = jax.block_until_ready(y)
                with spans("bulk.readback"):
                    return np.asarray(y)
            rids = [self.submit(img) for img in images]
            out = self.run()
            return np.stack([out[r] for r in rids])

    # ------------------------------------------------------------ accounting
    @property
    def steps_executed(self) -> int:
        return self._steps

    @property
    def images_served(self) -> int:
        """Images answered by the slot steps (the bulk path not counted)."""
        return self._images

    @property
    def occupancy(self) -> list[int]:
        """Slot steps by occupied slots: ``occupancy[k]`` steps ran with
        ``k`` of the ``n_slots`` slots live."""
        return list(self._occupancy)

    @property
    def batch_forward(self):
        """The data-parallel bulk forward
        (``parallel/bcnn_data_parallel.py::ShardedForward`` — its ``plan``
        carries the shards/stages/micro-batch metadata), or None when the
        engine was built without ``data_shards``."""
        return self._batch_fn

    @property
    def batch_threshold(self) -> int:
        """Minimum batch size ``classify_batch`` routes to the bulk
        data-parallel forward (0 when the bulk path is disabled)."""
        return self._batch_threshold

    @property
    def batch_cache_size(self) -> int:
        """Compilations of the bulk data-parallel forward: 0 before its
        first use, then exactly 1 per (shards, stages, micro-batch) plan
        whatever batch sizes ``classify_batch`` has seen."""
        return 0 if self._batch_fn is None else self._batch_fn.cache_size()

    @property
    def batch_input_counts(self) -> dict:
        """Calls of the bulk forward by the route their batch took
        (``ShardedForward.input_counts``: ``"wire"``, ``"device_relayout"``);
        ``classify_batch`` sends the wire form, so its calls count as
        ``"wire"``. Empty when the bulk path is disabled."""
        return {} if self._batch_fn is None else dict(
            self._batch_fn.input_counts)

    @property
    def step_cache_size(self) -> int:
        """Number of distinct compilations of the jit'd step (for a
        pipelined forward: of its most-recompiled stage). The streaming
        contract is that this stays 1 across any occupancy pattern."""
        if self._self_jitting:
            return int(self._step_fn.cache_size())
        return int(self._step_fn._cache_size())

    def stats(self, last_n: int | None = None) -> dict:
        """p50/p95/p99 latency + throughput over (the last_n) retained
        finished requests — see ``serve/slots.latency_stats``."""
        reqs = list(self.sched.finished)
        if last_n is not None:
            reqs = reqs[-last_n:]
        return latency_stats(reqs)


def drive_poisson(engine: BCNNEngine, images: np.ndarray, rate_hz: float,
                  *, seed: int = 0, warmup: bool = True) -> dict:
    """Offer ``images`` to the engine as a Poisson arrival process.

    Real wall-clock simulation of the paper's online individual-request
    regime: inter-arrival gaps are drawn i.i.d. exponential with mean
    ``1/rate_hz``; the loop submits every request whose arrival time has
    passed, steps the engine while anything is live, and sleeps to the next
    arrival otherwise. Returns ``{"results", "stats", "offered_hz"}`` where
    ``results`` and ``stats`` cover exactly this drive's requests
    (p50/p95/p99 end-to-end latency and achieved throughput) — requests
    already queued on the engine are served alongside but excluded.

    Arrival timing uses the ENGINE's clock (``BCNNEngine.clock``), not raw
    ``time.perf_counter`` — so an engine built with an injected
    deterministic clock keeps arrivals and latency stamps on one timeline
    (they desynchronized before). An injected clock must advance on its own
    (each call returns a later value), since the idle-wait path can only
    ``sleep`` real wall-clock time.
    """
    if rate_hz <= 0:
        raise ValueError(f"rate_hz must be > 0, got {rate_hz}")
    rng = np.random.default_rng(seed)
    n = len(images)
    if n > engine.sched.finished.maxlen:
        # stats are computed from the retained-history window; a drive
        # larger than it would silently report a recent-biased subset
        raise ValueError(
            f"drive of {n} requests exceeds the engine's finished-request "
            f"history ({engine.sched.finished.maxlen}); construct the "
            f"engine with history >= {n}")
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, size=n))
    if warmup:
        engine.warmup()
    clock = engine.clock
    real_time = clock is time.perf_counter   # sleeping only advances THIS
    my_rids: set[int] = set()
    results: dict[int, np.ndarray] = {}
    t0 = clock()
    nxt = 0
    while len(results) < n:
        now = clock() - t0
        while nxt < n and arrivals[nxt] <= now:
            my_rids.add(engine.submit(images[nxt]))
            nxt += 1
        if engine.sched.any_active:
            results.update((rid, logits)
                           for rid, logits in engine.step().items()
                           if rid in my_rids)
        elif nxt < n and real_time:
            time.sleep(max(0.0, min(arrivals[nxt] - now, 0.05)))
    mine = [r for r in engine.sched.finished if r.rid in my_rids]
    return {"results": results, "stats": latency_stats(mine),
            "offered_hz": float(rate_hz)}
