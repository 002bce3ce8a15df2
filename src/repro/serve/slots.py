"""Shared slot scheduler for the serving engines (LM and BCNN).

Both engines implement the paper's online-request scenario (§6.3, Fig. 7):
a fixed set of slots stepped continuously, with FIFO admission the moment a
slot frees — a request never waits for a batch to fill, only for a free
slot. What differs per engine is the step itself (autoregressive decode in
``serve/engine.py`` vs the one-shot packed BCNN forward in
``serve/bcnn_engine.py``); what is shared — and tested once, in
``tests/test_slots.py`` — is the request bookkeeping:

* monotone request-id assignment and a FIFO admission queue,
* slot occupancy and reuse (a freed slot is immediately re-admittable),
* per-request latency stamps (submit → admit → done) feeding the
  p50/p95/p99 accounting of ``latency_stats`` (engine and router stats),
* the span log (``SpanLog``) that times the parts of an engine's step on
  the same clock as those stamps.

Slot occupancy is host-side *data*, never array *shape*: engines keep their
device buffers at a fixed ``(n_slots, …)`` shape so the jit'd step compiles
exactly once regardless of how many slots are live. The scheduler itself is
pure host Python — no jax dependency — which keeps it trivially unit-testable.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, NamedTuple

import numpy as np

# the prefix of the span log's names in a profiler trace
TRACE_PREFIX = "repro."


@dataclass
class Request:
    """One queued / in-flight / finished request plus its latency stamps.

    Engine-agnostic: ``payload`` is the prompt token list for the LM engine
    and an image array for the BCNN engine; ``out`` accumulates whatever the
    engine produces (generated tokens; the BCNN engine returns logits out of
    band and leaves it empty). ``payload`` and ``frontend`` are dropped at
    completion, and the scheduler only retains the most recent ``history``
    finished requests, so a long-running service's memory stays bounded.
    """
    rid: int
    payload: Any
    max_new: int = 1
    frontend: Any = None            # e.g. audio frames / patch embeds
    out: list = field(default_factory=list)
    done: bool = False
    t_submit: float | None = None
    t_admit: float | None = None
    t_done: float | None = None
    step: int | None = None         # id of the step span that served it

    @property
    def latency(self) -> float | None:
        """End-to-end seconds: submission to completion (queue + service).
        ``None`` until both stamps exist — a queued or in-flight request has
        no latency yet (the stamps used to default to 0.0, so an unfinished
        request silently reported a negative wall-clock offset)."""
        if self.t_done is None or self.t_submit is None:
            return None
        return self.t_done - self.t_submit

    @property
    def queue_wait(self) -> float | None:
        """Seconds spent waiting for a free slot before admission, or
        ``None`` while the request is still queued (not yet admitted)."""
        if self.t_admit is None or self.t_submit is None:
            return None
        return self.t_admit - self.t_submit


class Span(NamedTuple):
    """One closed span of a ``SpanLog``, its times on the log's clock."""
    name: str
    t0: float
    t1: float
    id: int
    parent: int             # the enclosing span's id, -1 at the top level


_OFF = contextlib.nullcontext()


class SpanLog:
    """Named, nested spans on one clock, kept in a bounded ring.

    An engine owns one, beside its ``SlotScheduler``, on the scheduler's
    clock, so spans and request stamps share one timeline. The engine
    marks its parts as ``with log("engine.put"): ...``. Off (the default)
    that costs one attribute check and allocates nothing: the call returns
    a shared no-op context, and ``as`` binds None.

    ``enable`` allocates the ring and turns the log on. Each span then
    records its name, start, end, id (0, 1, 2, … in the order spans open)
    and its parent's id, and ``as`` binds the id. It also opens a
    ``jax.profiler.TraceAnnotation`` named ``TRACE_PREFIX + name``, so a
    running profiler puts the span on the device trace's clock. Past
    ``capacity`` spans the newest overwrite the oldest (``dropped`` counts
    them). ``read`` returns the closed spans still held, oldest first.
    Like the engine, it is driven by one thread.
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.on = False
        self._clock = clock
        self._cap = 0
        self._n = 0

    def enable(self, capacity: int = 1 << 18) -> None:
        """Start recording into a fresh ring of ``capacity`` spans."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self._names: list[str] = []
        self._codes: dict[str, int] = {}
        self._code = np.zeros(capacity, np.int32)
        self._t0 = np.zeros(capacity, np.float64)
        self._t1 = np.full(capacity, np.nan)
        self._parent = np.zeros(capacity, np.int64)
        self._stack: list[tuple[int, Any]] = []
        self._pending = ""
        self._cap, self._n = capacity, 0
        self.on = True

    def __call__(self, name: str):
        if not self.on:
            return _OFF
        self._pending = name
        return self

    def __enter__(self) -> int:
        name = self._pending
        code = self._codes.get(name)
        if code is None:
            code = self._codes[name] = len(self._names)
            self._names.append(name)
        ann = self._annotation(TRACE_PREFIX + name)
        ann.__enter__()
        sid = self._n
        k = sid % self._cap
        self._code[k] = code
        self._parent[k] = self._stack[-1][0] if self._stack else -1
        self._t1[k] = np.nan
        self._stack.append((sid, ann))
        self._n += 1
        self._t0[k] = self._clock()
        return sid

    def __exit__(self, *exc) -> bool:
        t1 = self._clock()
        sid, ann = self._stack.pop()
        self._t1[sid % self._cap] = t1
        ann.__exit__(None, None, None)
        return False

    @property
    def dropped(self) -> int:
        """Spans overwritten because the ring was full."""
        return max(0, self._n - self._cap)

    def read(self) -> list[Span]:
        """The closed spans the ring still holds, oldest first."""
        if not self._cap:
            return []
        ids = np.arange(self.dropped, self._n)
        ids = ids[~np.isnan(self._t1[ids % self._cap])]
        k = ids % self._cap
        return [Span(self._names[c], t0, t1, i, p) for c, t0, t1, i, p
                in zip(self._code[k].tolist(), self._t0[k].tolist(),
                       self._t1[k].tolist(), ids.tolist(),
                       self._parent[k].tolist())]


class SlotScheduler:
    """FIFO admission over a fixed set of slots.

    The scheduler owns the queue, the slot table, and the timing stamps; the
    engine owns the device state keyed by slot index (KV caches, image
    buffer) and calls back in three places:

        for i, req in sched.admit():   # fill engine state for slot i
        for i, req in sched.occupied():# step over live slots
        sched.complete(i)              # free slot i, stamp t_done

    ``clock`` is injectable for deterministic tests (defaults to
    ``time.perf_counter``). ``history`` bounds how many finished requests
    are retained for latency accounting — older ones are evicted FIFO so a
    long-running service does not grow without bound.
    """

    def __init__(self, n_slots: int, *,
                 clock: Callable[[], float] = time.perf_counter,
                 history: int = 4096):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.n_slots = n_slots
        self.slots: list[Request | None] = [None] * n_slots
        self.finished: deque[Request] = deque(maxlen=history)
        # deque, not list: admission pops from the head, and the deep
        # backlogs a fleet router builds up made list.pop(0) O(n²)
        self._queue: deque[Request] = deque()
        self._next_rid = 0
        self._clock = clock

    @property
    def clock(self) -> Callable[[], float]:
        """The scheduler's time source — drive loops must stamp arrivals
        with the SAME clock the latency stamps use (``drive_poisson``
        desynchronized from deterministic-clock tests before it did)."""
        return self._clock

    # ------------------------------------------------------------------ api
    def submit(self, payload, *, max_new: int = 1, frontend=None) -> int:
        """Enqueue a request; returns its rid. Admission happens at the next
        ``admit()`` call (the engine's step boundary), FIFO."""
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid, payload, max_new=max_new,
                                   frontend=frontend,
                                   t_submit=self._clock()))
        return rid

    def admit(self) -> list[tuple[int, Request]]:
        """Move queued requests into free slots (FIFO) and stamp t_admit.
        Returns the newly admitted (slot_index, request) pairs so the engine
        can initialize per-slot device state."""
        admitted: list[tuple[int, Request]] = []
        for i, slot in enumerate(self.slots):
            if slot is None and self._queue:
                req = self._queue.popleft()
                req.t_admit = self._clock()
                self.slots[i] = req
                admitted.append((i, req))
        return admitted

    def occupied(self) -> list[tuple[int, Request]]:
        """The live (slot_index, request) pairs, in slot order."""
        return [(i, r) for i, r in enumerate(self.slots) if r is not None]

    def complete(self, slot: int) -> Request:
        """Finish the request in ``slot``: stamp t_done, free the slot (it is
        admittable again immediately), retain the request in ``finished``
        (bounded by ``history``; inputs are dropped, only stamps + out
        stay)."""
        req = self.slots[slot]
        if req is None:
            raise ValueError(f"slot {slot} is not occupied")
        req.t_done = self._clock()
        req.done = True
        req.payload = None
        req.frontend = None
        self.slots[slot] = None
        self.finished.append(req)
        return req

    # ------------------------------------------------------------ introspect
    @property
    def n_occupied(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def any_active(self) -> bool:
        """True while there is anything left to do (queued or in-flight)."""
        return bool(self._queue) or self.n_occupied > 0


def latency_stats(requests: Iterable[Request],
                  percentiles: tuple[int, ...] = (50, 95, 99)) -> dict:
    """Aggregate per-request latency + throughput over finished requests.

    Returns seconds-valued fields: ``p50``/``p95``/``p99`` (end-to-end
    latency percentiles), ``mean``/``max``, ``queue_p50`` (admission wait),
    and ``throughput`` = completed requests / wall span from first
    submission to last completion. A zero-length span (e.g. a single
    completed request: its submission IS the span's start and end to clock
    resolution) carries no rate information, so ``throughput`` is ``None``
    there — never ``inf``/``nan``, which are not JSON. Empty input →
    ``{"n": 0}``.

    Only fully stamped requests contribute: an unfinished request's
    ``latency``/``queue_wait`` are ``None`` (not a number), so queued or
    in-flight entries are filtered out rather than skewing the percentiles.
    """
    reqs = [r for r in requests
            if r.done and r.latency is not None and r.queue_wait is not None]
    if not reqs:
        return {"n": 0}
    lat = np.array([r.latency for r in reqs], np.float64)
    wait = np.array([r.queue_wait for r in reqs], np.float64)
    span = max(r.t_done for r in reqs) - min(r.t_submit for r in reqs)
    out = {"n": len(reqs),
           "mean": float(lat.mean()), "max": float(lat.max()),
           "queue_p50": float(np.percentile(wait, 50)),
           "throughput": float(len(reqs) / span) if span > 0 else None}
    for p in percentiles:
        out[f"p{p}"] = float(np.percentile(lat, p))
    return out
