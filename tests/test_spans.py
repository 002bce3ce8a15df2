"""The serving layer's span log (serve/slots.py SpanLog) and the spans the
BCNN engine records with it (serve/bcnn_engine.py step / classify_batch).

Off, a span is a shared no-op and the engine records nothing; on, the
spans nest as documented in docs/SERVING.md, share the scheduler's clock
with the request stamps, and stay within the ring's capacity."""
import itertools
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn
from repro.serve import BCNNEngine, SpanLog, slots

ONLINE_CHILDREN = ["engine.admit", "engine.put", "engine.dispatch",
                   "engine.wait", "engine.readback", "engine.complete"]
BULK_CHILDREN = ["bulk.put", "bulk.dispatch", "bulk.wait", "bulk.readback"]


def make_clock(step: float = 1.0):
    counter = itertools.count()
    return lambda: step * next(counter)


def toy_forward(x):
    s = x.sum(axis=(1, 2, 3))
    return jnp.stack([s, -s], axis=-1)


def toy_engine(n_slots=2, clock=None):
    kw = {} if clock is None else {"clock": clock}
    return BCNNEngine(toy_forward, n_slots=n_slots, input_shape=(4, 4, 1),
                      **kw)


def children(spans, parent):
    return [s for s in spans if s.parent == parent.id]


# ------------------------------------------------------------------ the log
def test_off_span_is_a_shared_noop_that_allocates_nothing():
    log = SpanLog()
    assert log("a") is log("b")
    with log("a") as sid:
        assert sid is None
    assert log.read() == [] and log.dropped == 0

    def spans(n):
        for _ in range(n):
            with log("engine.step"):
                with log("engine.put"):
                    pass

    spans(10)                       # warm the code path
    tracemalloc.start()
    try:
        spans(1000)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    # what the log's code or this loop allocated and still holds (other
    # threads of the test process may allocate meanwhile)
    here = snap.filter_traces([tracemalloc.Filter(True, slots.__file__),
                               tracemalloc.Filter(True, __file__)])
    assert sum(st.size for st in here.statistics("filename")) == 0


def test_spans_nest_with_ids_and_parents_on_the_given_clock():
    log = SpanLog(make_clock())
    log.enable(capacity=16)
    with log("outer") as a:               # t0 = 0
        with log("inner") as b:           # 1 .. 2
            pass
        with log("inner") as c:           # 3 .. 4
            pass
    with log("next") as d:                # 6 .. 7
        pass
    assert (a, b, c, d) == (0, 1, 2, 3)
    got = log.read()
    assert [(s.name, s.t0, s.t1, s.id, s.parent) for s in got] == [
        ("outer", 0.0, 5.0, 0, -1), ("inner", 1.0, 2.0, 1, 0),
        ("inner", 3.0, 4.0, 2, 0), ("next", 6.0, 7.0, 3, -1)]


def test_the_ring_is_bounded_and_keeps_the_newest():
    log = SpanLog(make_clock())
    log.enable(capacity=4)
    for i in range(10):
        with log(f"s{i}"):
            pass
    got = log.read()
    assert [s.name for s in got] == ["s6", "s7", "s8", "s9"]
    assert [s.id for s in got] == [6, 7, 8, 9]
    assert log.dropped == 6
    assert log._t0.shape == (4,)


def test_an_open_span_is_not_read():
    log = SpanLog(make_clock())
    log.enable(capacity=8)
    with log("outer"):
        with log("done"):
            pass
        assert [s.name for s in log.read()] == ["done"]
    assert [s.name for s in log.read()] == ["outer", "done"]


def test_enable_starts_a_fresh_log_and_rejects_no_room():
    log = SpanLog(make_clock())
    log.enable(capacity=4)
    with log("old"):
        pass
    log.enable(capacity=4)
    assert log.read() == []
    with pytest.raises(ValueError):
        log.enable(capacity=0)


def test_spans_reach_the_profiler_under_the_program_prefix(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    log = SpanLog()
    log.enable(capacity=4)
    with log("engine.step"):
        with log("engine.wait"):
            pass
    assert opened == [("enter", "repro.engine.step"),
                      ("enter", "repro.engine.wait"),
                      ("exit", "repro.engine.wait"),
                      ("exit", "repro.engine.step")]


# ------------------------------------------------------------- online step
def test_engine_off_records_nothing_and_counts_its_steps():
    eng = toy_engine(n_slots=2)
    for i in range(3):
        eng.submit(np.full((4, 4, 1), i, np.float32))
    out = eng.run()
    assert len(out) == 3 and not eng.spans.on
    assert eng.spans.read() == []
    assert all(r.step is None for r in eng.sched.finished)
    assert eng.steps_executed == 2 and eng.images_served == 3
    assert eng.occupancy == [0, 1, 1]


def test_engine_step_spans_nest_and_join_requests_to_their_step():
    eng = toy_engine(n_slots=2, clock=make_clock(0.001))
    eng.spans.enable(capacity=64)
    for i in range(3):
        eng.submit(np.full((4, 4, 1), i, np.float32))
    eng.run()
    spans = eng.spans.read()
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == 2 and all(s.parent == -1 for s in steps)
    for st in steps:
        kids = children(spans, st)
        assert [k.name for k in kids] == ONLINE_CHILDREN
        assert all(st.t0 <= k.t0 <= k.t1 <= st.t1 for k in kids)
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
    by_id = {s.id: s for s in spans}
    for r in eng.sched.finished:
        st = by_id[r.step]
        kids = {k.name: k for k in children(spans, st)}
        # the request stamps lie in their step's spans, on one clock
        assert kids["engine.admit"].t0 <= r.t_admit <= kids["engine.admit"].t1
        assert kids["engine.complete"].t0 <= r.t_done \
            <= kids["engine.complete"].t1
    assert sorted({r.step for r in eng.sched.finished}) == \
        [s.id for s in steps]


def test_an_idle_step_is_one_span_with_its_admission():
    eng = toy_engine(clock=make_clock(0.001))
    eng.spans.enable(capacity=8)
    assert eng.step() == {}
    assert [s.name for s in eng.spans.read()] == ["engine.step",
                                                  "engine.admit"]


def test_engine_stays_within_the_ring():
    eng = toy_engine(n_slots=1)
    eng.spans.enable(capacity=10)
    for _ in range(5):
        eng.submit(np.zeros((4, 4, 1), np.float32))
    eng.run()
    assert len(eng.spans.read()) <= 10
    assert eng.spans.dropped == 5 * 7 - 10


# ---------------------------------------------------------- the packed net
@pytest.fixture(scope="module")
def packed():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def images():
    return np.random.default_rng(3).random((4, 32, 32, 3)).astype(np.float32)


def test_recorder_leaves_the_logits_unchanged(packed, images):
    eng = BCNNEngine.from_packed(packed, n_slots=4, data_shards=1,
                                 data_micro_batch=2)
    rids = [eng.submit(im) for im in images]
    off = eng.run()
    bulk_off = eng.classify_batch(images)
    assert eng.spans.read() == []
    eng.spans.enable(capacity=64)
    rids_on = [eng.submit(im) for im in images]
    on = eng.run()
    bulk_on = eng.classify_batch(images)
    for a, b in zip(rids, rids_on):
        np.testing.assert_array_equal(off[a], on[b])
    np.testing.assert_array_equal(bulk_off, bulk_on)
    assert eng.step_cache_size == 1 and eng.batch_cache_size == 1


def test_bulk_spans_nest_inside_classify_batch(packed, images):
    eng = BCNNEngine.from_packed(packed, n_slots=4, data_shards=1,
                                 data_micro_batch=2)
    eng.classify_batch(images)              # compile outside the log
    eng.spans.enable(capacity=64)
    eng.classify_batch(images)
    spans = eng.spans.read()
    assert spans[0].name == "engine.classify_batch"
    assert [k.name for k in children(spans, spans[0])] == BULK_CHILDREN
    assert len(spans) == 1 + len(BULK_CHILDREN)
    # a batch below the threshold streams through the slots: its steps
    # nest inside the call
    eng.spans.enable(capacity=64)
    eng.classify_batch(images[:1])
    spans = eng.spans.read()
    assert [k.name for k in children(spans, spans[0])] == ["engine.step"]
