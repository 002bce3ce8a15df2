"""Shared slot scheduler (serve/slots.py): the bookkeeping both serving
engines (LM continuous batching + streaming BCNN) rely on — FIFO admission
order, slot reuse after completion, timing stamps, latency aggregation.

Pure host-side: no jax required."""
import itertools

import pytest

from repro.serve.slots import Request, SlotScheduler, latency_stats


def make_clock(start: float = 0.0, step: float = 1.0):
    """Deterministic monotone clock: 0, 1, 2, ... seconds."""
    counter = itertools.count()
    return lambda: start + step * next(counter)


def test_fifo_admission_order():
    s = SlotScheduler(2, clock=make_clock())
    rids = [s.submit(f"p{i}") for i in range(5)]
    assert rids == [0, 1, 2, 3, 4]          # monotone rid assignment
    adm = s.admit()
    assert [(i, r.rid) for i, r in adm] == [(0, 0), (1, 1)]
    assert s.n_queued == 3 and s.n_occupied == 2
    # no free slot → nothing admitted, queue order preserved
    assert s.admit() == []
    s.complete(1)
    adm = s.admit()
    assert [(i, r.rid) for i, r in adm] == [(1, 2)]   # next-in-FIFO, not rid 3


def test_slot_reuse_after_completion():
    s = SlotScheduler(1, clock=make_clock())
    for i in range(4):
        s.submit(i)
    served = []
    while s.any_active:
        s.admit()
        (slot, req), = s.occupied()
        assert slot == 0                     # single slot reused every time
        served.append(req.rid)
        s.complete(slot)
    assert served == [0, 1, 2, 3]
    assert len(s.finished) == 4 and all(r.done for r in s.finished)


def test_complete_unoccupied_slot_raises():
    s = SlotScheduler(2)
    with pytest.raises(ValueError, match="not occupied"):
        s.complete(0)


def test_timing_stamps_monotone_and_payload_dropped():
    s = SlotScheduler(1, clock=make_clock())
    s.submit("a")
    s.submit("b")
    s.admit()
    ra = s.complete(0)
    s.admit()
    rb = s.complete(0)
    for r in (ra, rb):
        assert r.t_submit <= r.t_admit <= r.t_done
        assert r.payload is None             # dropped at completion
    # b queued while a held the slot → nonzero queue wait
    assert rb.queue_wait > 0
    assert ra.latency > 0 and rb.latency > ra.latency


def test_latency_stats_percentiles():
    reqs = [Request(rid=i, payload=None, done=True,
                    t_submit=0.0, t_admit=0.5, t_done=float(i + 1))
            for i in range(10)]              # latencies 1..10 s
    st = latency_stats(reqs)
    assert st["n"] == 10
    assert st["p50"] <= st["p95"] <= st["p99"] <= st["max"] == 10.0
    assert st["p50"] == pytest.approx(5.5)
    assert st["throughput"] == pytest.approx(1.0)    # 10 requests / 10 s span
    assert latency_stats([]) == {"n": 0}
    # undone requests are excluded
    assert latency_stats(reqs + [Request(rid=99, payload=None)])["n"] == 10


def test_latency_stats_zero_span_is_finite_and_json():
    """Regression: a zero wall span (e.g. a single completed request under
    a coarse clock) must yield a well-defined, JSON-valid throughput —
    not ``float("inf")``, which ``json.dump`` emits as bare ``Infinity``
    and breaks downstream JSON parsers."""
    import json
    import math
    req = Request(rid=0, payload=None, done=True,
                  t_submit=1.0, t_admit=1.0, t_done=1.0)   # span == 0
    st = latency_stats([req])
    assert st["n"] == 1
    assert st["throughput"] is None                         # undefined, not inf
    for v in st.values():
        if isinstance(v, float):
            assert math.isfinite(v)
    json.loads(json.dumps(st))                              # valid JSON


def test_any_active_lifecycle():
    s = SlotScheduler(2, clock=make_clock())
    assert not s.any_active
    s.submit("x")
    assert s.any_active                      # queued counts as active
    s.admit()
    assert s.any_active                      # in-flight counts as active
    s.complete(0)
    assert not s.any_active


def test_invalid_n_slots():
    with pytest.raises(ValueError, match="n_slots"):
        SlotScheduler(0)


def test_unfinished_request_latency_is_none():
    """Regression: ``latency``/``queue_wait`` on a not-yet-stamped request
    used to return negative nonsense (stamps defaulted to 0.0); they are
    ``None`` now, and ``latency_stats`` filters such requests out."""
    s = SlotScheduler(1, clock=make_clock(start=100.0))
    s.submit("a")
    (queued,) = s._queue
    assert queued.latency is None and queued.queue_wait is None
    s.admit()
    (slot, inflight), = s.occupied()
    assert inflight.latency is None            # admitted, not done
    assert inflight.queue_wait is not None     # admission IS stamped
    # an unstamped request mixed into stats must not skew the percentiles
    done = Request(rid=9, payload=None, done=True,
                   t_submit=0.0, t_admit=1.0, t_done=2.0)
    st = latency_stats([done, inflight, Request(rid=10, payload=None)])
    assert st["n"] == 1 and st["p50"] == pytest.approx(2.0)


def test_backlog_scale_admission():
    """Regression: the admission queue was a plain list drained with
    ``pop(0)`` — O(n²) under the deep backlogs a fleet router builds.
    30k queued requests through one slot must drain in linear-ish time
    (the quadratic version shifts ~450M list elements here)."""
    import time as _time
    n = 30_000
    s = SlotScheduler(1, clock=make_clock())
    t0 = _time.perf_counter()
    for i in range(n):
        s.submit(i)
    while s.any_active:
        s.admit()
        s.complete(0)
    assert _time.perf_counter() - t0 < 5.0
    assert s.n_queued == 0 and s.n_occupied == 0


def test_finished_history_is_bounded():
    """A long-running service must not retain every request ever served."""
    s = SlotScheduler(1, clock=make_clock(), history=3)
    for i in range(10):
        s.submit(payload=[i], frontend=object())
        s.admit()
        s.complete(0)
    assert len(s.finished) == 3
    assert [r.rid for r in s.finished] == [7, 8, 9]      # most recent kept
    # inputs are dropped at completion, only stamps/outputs retained
    assert all(r.payload is None and r.frontend is None for r in s.finished)
