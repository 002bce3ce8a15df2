"""Fleet router (serve/router.py + serve/replica.py) unit tier.

Deterministic scheduling tests run the router in pump mode
(``threaded=False``: no worker threads, injected clocks) — FIFO-within-
class fairness, strict priority, EDF within a rank, least-loaded dispatch,
typed backpressure, and the rolling-swap walk. The acceptance-criterion
test drives a mixed online+bulk Poisson load over >= 2 packed-BCNN
replicas with a mid-drive rolling ``swap_packed``: every submitted request
completes (zero drops), logits are bit-exact for the weight epoch that
served them, and ``step_cache_size == 1`` on every replica. A small
threaded smoke exercises the real worker-thread machinery end to end.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn
from repro.core.execution_plan import default_plan
from repro.serve import (BCNNEngine, RequestClass, Router, RouterOverload,
                         RouterShutdown, drive_mixed_poisson)
from repro.serve.router import BULK, ONLINE


def _plan(packed, path):
    """``default_plan(packed)`` on ``path``'s kernels."""
    return dataclasses.replace(default_plan(packed), path=path)


class StepClock:
    """Deterministic clock: advances ``dt`` seconds per call."""

    def __init__(self, dt: float = 1e-3):
        self.t = 0.0
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


def toy_forward(x):
    """(N, H, W, C) → (N, 2), row-separable so routing errors show up."""
    s = x.sum(axis=(1, 2, 3))
    return jnp.stack([s, -s], axis=-1)


def toy_router(n_replicas=2, n_slots=2, clock=None, **kw):
    clock = clock or StepClock()
    engines = [BCNNEngine(toy_forward, n_slots=n_slots,
                          input_shape=(4, 4, 1), clock=clock)
               for _ in range(n_replicas)]
    return Router(engines, threaded=False, clock=clock, **kw)


def img(v, shape=(4, 4, 1)):
    return np.full(shape, v, np.float32)


@pytest.fixture(scope="module")
def packed_a():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def packed_b():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(1)))


# --------------------------------------------------------------- scheduling
def test_fifo_within_class_and_priority_across_classes():
    """Online (priority 0) overtakes queued bulk (priority 1); arrival
    order is preserved within each class."""
    r = toy_router(n_replicas=1, n_slots=1, dispatch_depth=1)
    bulk = [r.submit(img(i), cls="bulk") for i in range(4)]
    online = [r.submit(img(10 + i), cls="online") for i in range(3)]
    r.run_until_idle()
    # every request completed, each with its own image's logits
    for i, q in enumerate(bulk):
        np.testing.assert_array_equal(q.logits, [16.0 * i, -16.0 * i])
    # bulk[0] was already dispatched (depth 1) before online arrived; the
    # rest of the backlog serves online first, then the remaining bulk
    order = sorted(bulk + online, key=lambda q: q.t_dispatch)
    assert [q.rid for q in order] == [bulk[0].rid] + \
        [q.rid for q in online] + [q.rid for q in bulk[1:]]
    # FIFO within each class
    for group in (bulk, online):
        ts = [q.t_dispatch for q in group]
        assert ts == sorted(ts)


def test_edf_within_priority_rank():
    """Two classes at the SAME priority: the tighter deadline wins."""
    tight = RequestClass("tight", priority=0, deadline_s=0.1)
    loose = RequestClass("loose", priority=0, deadline_s=10.0)
    r = toy_router(n_replicas=1, n_slots=1, dispatch_depth=1,
                   classes=(tight, loose))
    r.submit(img(0), cls="loose")        # dispatched immediately (depth 1)
    q_loose = [r.submit(img(i), cls="loose") for i in range(1, 3)]
    q_tight = [r.submit(img(9), cls="tight")]
    r.run_until_idle()
    order = sorted(q_loose + q_tight, key=lambda q: q.t_dispatch)
    assert order[0] is q_tight[0]        # later arrival, earlier deadline


def test_least_loaded_dispatch_spreads_replicas():
    r = toy_router(n_replicas=2, n_slots=2)
    reqs = [r.submit(img(i)) for i in range(4)]
    assert [q.replica_id for q in reqs] == [0, 1, 0, 1]
    r.run_until_idle()
    assert all(q.done for q in reqs)
    assert all(rep.served == 2 for rep in r.replicas)


def test_backpressure_typed_rejection_and_atomic_batch():
    # dispatch_depth=0 freezes dispatch so the admission queue alone fills
    r = toy_router(n_replicas=1, n_slots=1, max_queue=4, dispatch_depth=0)
    for i in range(4):
        r.submit(img(i), cls="online")
    with pytest.raises(RouterOverload) as ei:
        r.submit(img(9), cls="online")
    assert ei.value.queue_depth == 4 and ei.value.max_queue == 4
    assert ei.value.cls_name == "online" and ei.value.n_requested == 1

    # a batch that does not fit is shed WHOLE (atomic admission) ...
    r2 = toy_router(n_replicas=1, n_slots=1, max_queue=4, dispatch_depth=0)
    r2.submit(img(0), cls="online")
    with pytest.raises(RouterOverload) as ei:
        r2.submit_batch([img(i) for i in range(4)], cls="bulk")
    assert ei.value.n_requested == 4
    assert r2.n_queued == 1              # nothing partially admitted
    # ... while one that fits is admitted in full
    assert len(r2.submit_batch([img(i) for i in range(3)], cls="bulk")) == 3
    c = r2.counters()
    assert c["bulk"] == {"submitted": 3, "rejected": 4, "completed": 0,
                         "shed": 0}


def test_unknown_class_rejected():
    r = toy_router(n_replicas=1)
    with pytest.raises(ValueError, match="unknown request class"):
        r.submit(img(0), cls="no-such-class")


def test_counters_ledger_zero_drop():
    """submitted == completed + pending, rejected tracked separately."""
    r = toy_router(n_replicas=2, n_slots=2, max_queue=8, dispatch_depth=1)
    for i in range(10):
        try:
            r.submit(img(i), cls="online")
        except RouterOverload:
            pass
    c = r.counters()["online"]
    assert c["submitted"] == c["completed"] + r.pending
    r.run_until_idle()
    c = r.counters()["online"]
    assert c["completed"] == c["submitted"] and r.pending == 0


def test_stats_per_class_with_deadline_accounting():
    clock = StepClock(dt=1e-3)
    r = toy_router(n_replicas=1, n_slots=2, clock=clock,
                   classes=(RequestClass("online", 0, deadline_s=1e-6),
                            BULK))
    for i in range(3):
        r.submit(img(i), cls="online")
    r.submit(img(9), cls="bulk")
    r.run_until_idle()
    st = r.stats()
    assert st["online"]["n"] == 3 and st["bulk"]["n"] == 1
    # the 1 µs deadline is unmeetable under a 1 ms-per-tick clock
    assert st["online"]["deadline_miss_frac"] == 1.0
    assert "deadline_miss_frac" not in st["bulk"]   # no deadline: no SLO
    assert st["online"]["rejected"] == 0


def test_classify_batch_no_threshold_cliff():
    """Bulk work rides the scheduler (any size, no batch_threshold): a
    3-image batch and a 1-image batch both serve, bit-identically to the
    per-image toy forward."""
    r = toy_router(n_replicas=2, n_slots=2)
    for n in (3, 1):
        xs = np.stack([img(i + 1) for i in range(n)])
        out = r.classify_batch(xs, cls="bulk")
        assert out.shape == (n, 2)
        for i in range(n):
            np.testing.assert_array_equal(out[i],
                                          [16.0 * (i + 1), -16.0 * (i + 1)])


# ------------------------------------------------------------- rolling swap
def test_rolling_swap_mixed_poisson_zero_drops_bit_exact(packed_a,
                                                         packed_b):
    """THE acceptance criterion: a mixed online+bulk Poisson load over 2
    packed-BCNN replicas with a mid-drive rolling ``swap_packed`` —
    every submitted request completes, logits are bit-exact for the weight
    epoch that served them, and ``step_cache_size == 1`` per replica."""
    clock = StepClock(dt=2e-3)
    router = Router.from_packed(packed_a, n_replicas=2, n_slots=2,
                                plan=_plan(packed_a, "xla"), threaded=False,
                                clock=clock)
    n = 20
    images = np.random.default_rng(0).random((n, 32, 32, 3)).astype(
        np.float32)
    ref_a = np.asarray(bcnn.forward_packed(packed_a, jnp.asarray(images),
                                           plan=_plan(packed_a, "xla")))
    ref_b = np.asarray(bcnn.forward_packed(packed_b, jnp.asarray(images),
                                           plan=_plan(packed_b, "xla")))
    d = drive_mixed_poisson(router, images, rate_hz=100.0,
                            mix={"online": 3.0, "bulk": 1.0}, seed=1,
                            swap_to=packed_b, swap_at_frac=0.5)
    # zero drops: everything offered was accepted and served
    assert d["n_accepted"] == n and d["n_rejected"] == 0
    assert len(d["results"]) == n and router.pending == 0
    # traffic really spanned the weight update
    assert set(d["epochs"]) == {0, 1}, d["epochs"]
    assert d["epochs"][0] > 0 and d["epochs"][1] > 0
    # bit-exact logits per weight epoch (rid == arrival index here: all
    # offered requests were accepted in order)
    for q in d["requests"]:
        ref = ref_a if q.epoch == 0 else ref_b
        np.testing.assert_array_equal(q.logits, ref[q.rid])
    # zero recompiles on every replica, every replica actually served
    for rep in router.replicas:
        assert rep.step_cache_size == 1, f"replica {rep.id} recompiled"
        assert rep.served > 0
        assert rep.epoch == 1


def test_rolling_swap_incompatible_leaves_fleet_serving(packed_a, packed_b):
    clock = StepClock()
    router = Router.from_packed(packed_a, n_replicas=2, n_slots=2,
                                plan=_plan(packed_a, "xla"), threaded=False,
                                clock=clock)
    images = np.random.default_rng(2).random((4, 32, 32, 3)).astype(
        np.float32)
    ref_a = np.asarray(bcnn.forward_packed(packed_a, jnp.asarray(images),
                                           plan=_plan(packed_a, "xla")))
    reqs = [router.submit(im) for im in images]
    bad = packed_b._replace(fc3_k=packed_b.fc3_k + 1)
    with pytest.raises(ValueError, match="static"):
        router.rolling_swap(bad)
    # nothing swapped, nothing dropped: the fleet serves on epoch 0
    router.run_until_idle()
    for i, q in enumerate(reqs):
        assert q.done and q.epoch == 0
        np.testing.assert_array_equal(q.logits, ref_a[i])
    assert all(rep.epoch == 0 for rep in router.replicas)
    assert not router._paused                 # pause rolled back on failure


def test_rolling_swap_while_idle(packed_a, packed_b):
    router = Router.from_packed(packed_a, n_replicas=2, n_slots=2,
                                plan=_plan(packed_a, "xla"), threaded=False,
                                clock=StepClock())
    assert router.rolling_swap(packed_b) == 2
    assert all(rep.epoch == 1 for rep in router.replicas)
    x = np.random.default_rng(3).random((2, 32, 32, 3)).astype(np.float32)
    ref_b = np.asarray(bcnn.forward_packed(packed_b, jnp.asarray(x),
                                           plan=_plan(packed_b, "xla")))
    np.testing.assert_array_equal(router.classify_batch(x), ref_b)
    assert all(rep.step_cache_size == 1 for rep in router.replicas)


# ---------------------------------------------------------- shutdown/drain
def test_shutdown_drain_timeout_sheds_typed_threaded():
    """Regression (ISSUE 8): ``shutdown(drain=True)`` with a backlog that
    CANNOT drain (dispatch frozen — the stand-in for a wedged replica)
    must terminate within its timeout and shed the remainder with typed
    ``RouterShutdown`` errors raised from each victim's ``wait()`` —
    never hang, never raise out of shutdown, never drop silently."""
    engines = [BCNNEngine(toy_forward, n_slots=1, input_shape=(4, 4, 1))]
    r = Router(engines, threaded=True, max_queue=8, dispatch_depth=0)
    reqs = [r.submit(img(i)) for i in range(4)]
    t0 = time.monotonic()
    r.shutdown(drain=True, timeout=0.3)
    assert time.monotonic() - t0 < 10.0
    for q in reqs:
        assert q.done and q.error is not None
        with pytest.raises(RouterShutdown):
            q.wait(timeout=1.0)
    c = r.counters()["online"]
    assert c == {"submitted": 4, "rejected": 0, "completed": 0, "shed": 4}
    assert r.pending == 0                  # the ledger closed: none vanish
    with pytest.raises(RouterShutdown):    # post-shutdown admits are typed
        r.submit(img(9))


def test_shutdown_wedged_pump_mode_sheds_not_hangs():
    r = toy_router(n_replicas=1, n_slots=1, max_queue=8, dispatch_depth=0)
    reqs = [r.submit(img(i)) for i in range(3)]
    r.shutdown(drain=True, timeout=1.0)    # wedged drain: no 100k-pump spin
    assert all(q.done for q in reqs)
    assert r.counters()["online"]["shed"] == 3 and r.pending == 0


def test_shutdown_no_drain_sheds_queue_completes_inflight():
    r = toy_router(n_replicas=1, n_slots=1, dispatch_depth=1)
    reqs = [r.submit(img(i)) for i in range(3)]
    assert reqs[0].replica_id is not None  # dispatched (depth 1)
    r.shutdown(drain=False)
    # dispatched work finished on stop; queued work shed with typed errors
    assert reqs[0].done and reqs[0].error is None
    np.testing.assert_array_equal(reqs[0].logits, [0.0, 0.0])
    for q in reqs[1:]:
        assert q.done and isinstance(q.error, RouterShutdown)
    c = r.counters()["online"]
    assert c["completed"] == 1 and c["shed"] == 2 and r.pending == 0


# ----------------------------------------------------------- threaded smoke
def test_threaded_router_end_to_end(packed_a, packed_b):
    """Real worker threads: mixed Poisson wall-clock drive with a
    concurrent rolling swap; zero drops, zero recompiles."""
    router = Router.from_packed(packed_a, n_replicas=2, n_slots=2,
                                plan=_plan(packed_a, "xla"), threaded=True)
    try:
        images = np.random.default_rng(4).random((12, 32, 32, 3)).astype(
            np.float32)
        d = drive_mixed_poisson(router, images, rate_hz=300.0,
                                mix={"online": 1.0, "bulk": 1.0}, seed=5,
                                swap_to=packed_b, swap_at_frac=0.5)
        assert d["n_accepted"] == 12 and d["n_rejected"] == 0
        assert len(d["results"]) == 12
        assert sum(d["epochs"].values()) == 12
        for rep in router.replicas:
            assert rep.step_cache_size == 1
            assert rep.epoch == 1
    finally:
        router.shutdown()
