"""Elastic fleet tier (serve/autoscale.py + the router's scale surface).

Everything here is deterministic pump mode (``threaded=False``, injected
``StepClock``): the autoscaler samples once per ``Router.pump()``, so a
load step is replayed tick by tick. The acceptance-criterion test drives a
low → burst → idle mixed load over a packed-BCNN fleet with a mid-run
rolling swap: the fleet scales 1→N→1 with zero drops, logits bit-exact per
weight epoch across scale AND swap boundaries, and ``step_cache_size == 1``
on every replica that EVER existed (retired included). The co-scheduling
tests pin the ``online_reserve`` contract: a reserve-blocked bulk chunk
parks aside and lets online traffic queued behind it dispatch.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn
from repro.core.execution_plan import default_plan
from repro.serve import (AutoscaleConfig, BCNNEngine, RequestClass, Router,
                         RouterShutdown)


class StepClock:
    def __init__(self, dt: float = 1e-3):
        self.t = 0.0
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


def toy_forward(x):
    s = x.sum(axis=(1, 2, 3))
    return jnp.stack([s, -s], axis=-1)


def toy_router(n_replicas=1, n_slots=2, clock=None, **kw):
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


def xla_plan(packed):
    return dataclasses.replace(default_plan(packed), path="xla")


def packed_router(packed, clock, *, n_replicas=1, autoscale=None, **kw):
    return Router.from_packed(packed, n_replicas=n_replicas, n_slots=2,
                              plan=xla_plan(packed), threaded=False,
                              clock=clock,
                              autoscale=autoscale, **kw)


# --------------------------------------------------------------- the config
def test_config_validates_hysteresis_and_bounds():
    AutoscaleConfig()                                    # defaults are legal
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscaleConfig(min_replicas=0)
    with pytest.raises(ValueError, match="max_replicas"):
        AutoscaleConfig(min_replicas=3, max_replicas=2)
    # the anti-oscillation invariant: down < up/2, strictly
    with pytest.raises(ValueError, match="hysteresis"):
        AutoscaleConfig(up_watermark=2.0, down_watermark=1.0)
    with pytest.raises(ValueError, match="hysteresis"):
        AutoscaleConfig(up_watermark=2.0, down_watermark=0.0)
    with pytest.raises(ValueError, match="window_s"):
        AutoscaleConfig(window_s=0.0)
    with pytest.raises(ValueError, match="miss_frac_hi"):
        AutoscaleConfig(miss_frac_hi=1.5)


def test_autoscale_requires_engine_factory():
    with pytest.raises(ValueError, match="factory"):
        toy_router(autoscale=AutoscaleConfig())


# ------------------------------------------------------------- scale up/down
def test_scale_up_spawns_warm_identical_replica(packed_a):
    clock = StepClock()
    router = packed_router(packed_a, clock)
    assert router.n_replicas == 1
    rep = router.scale_up()
    assert router.n_replicas == 2 and rep.id == 1
    assert rep.step_cache_size == 1          # warmed before taking traffic
    x = np.random.default_rng(0).random((4, 32, 32, 3)).astype(np.float32)
    ref = np.asarray(bcnn.forward_packed(packed_a, jnp.asarray(x),
                                         plan=xla_plan(packed_a)))
    np.testing.assert_array_equal(router.classify_batch(x), ref)
    assert all(r.step_cache_size == 1 for r in router.replicas_ever)


def test_scale_down_drains_never_drops(packed_a):
    clock = StepClock()
    router = packed_router(packed_a, clock, n_replicas=2)
    x = np.random.default_rng(1).random((6, 32, 32, 3)).astype(np.float32)
    reqs = [router.submit(im) for im in x]    # spread over both replicas
    rid = router.scale_down()
    assert router.n_replicas == 1
    router.run_until_idle()
    assert all(q.done and q.error is None for q in reqs)
    # the retired replica stays auditable: it drained, served, compiled once
    retired = [r for r in router.replicas_ever if r.id == rid]
    assert len(retired) == 1 and retired[0].load == 0
    assert retired[0].step_cache_size == 1
    with pytest.raises(RuntimeError, match="below 1"):
        router.scale_down()


def test_autoscaler_scales_up_under_pressure_and_back_down(packed_a):
    clock = StepClock(dt=1e-3)
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=2, up_watermark=2.0,
                          down_watermark=0.25, window_s=0.004,
                          cooldown_s=0.05, interval_s=0.001)
    router = packed_router(packed_a, clock, autoscale=cfg, max_queue=512)
    x = np.random.default_rng(2).random((16, 32, 32, 3)).astype(np.float32)
    reqs = [router.submit(im) for im in x]    # pressure 16/2 = 8 > up
    router.run_until_idle()
    assert router.autoscaler.n_scale_ups == 1     # capped by max_replicas
    assert router.n_replicas == 2
    for _ in range(400):                          # idle: window drains to 0
        router.pump()
    assert router.autoscaler.n_scale_downs == 1
    assert router.n_replicas == 1                 # floored by min_replicas
    assert all(q.done and q.error is None for q in reqs)
    assert all(r.step_cache_size == 1 for r in router.replicas_ever)
    tl = router.autoscaler.timeline(1)
    assert [n for _, n in tl] == [1, 2, 1]


# --------------------------------------------------------- swap ↔ scale race
def test_scale_up_racing_rolling_swap_lands_on_post_swap_epoch(
        packed_a, packed_b, monkeypatch):
    """A scale-up that fires WHILE the rolling swap walks the fleet must
    come up on the post-swap artifact and epoch — and the walk must skip
    it (it never serves stale weights, and is not double-swapped)."""
    clock = StepClock()
    router = packed_router(packed_a, clock, n_replicas=2)
    spawned = []
    orig = router._drain_replica

    def drain_then_spawn(rep, timeout):
        orig(rep, timeout)
        if not spawned:                   # re-entrant _scale_lock: same
            spawned.append(router.scale_up())   # thread as the swap walk
    monkeypatch.setattr(router, "_drain_replica", drain_then_spawn)
    assert router.rolling_swap(packed_b) == 2   # only the two originals
    new = spawned[0]
    assert router.fleet_epoch == 1
    assert new.epoch == 1                 # spawned ON the post-swap epoch
    assert all(r.epoch == 1 for r in router.replicas)
    x = np.random.default_rng(3).random((3, 32, 32, 3)).astype(np.float32)
    ref_b = np.asarray(bcnn.forward_packed(packed_b, jnp.asarray(x),
                                           plan=xla_plan(packed_b)))
    # every replica (the spawned one included) serves the NEW weights
    for im, ref in zip(x, ref_b):
        for rep_id in range(3):
            q = router.submit(im)
            router.run_until_idle()
            np.testing.assert_array_equal(q.logits, ref)
    assert all(r.step_cache_size == 1 for r in router.replicas_ever)


def test_swap_after_scale_up_swaps_everyone(packed_a, packed_b):
    clock = StepClock()
    router = packed_router(packed_a, clock)
    router.scale_up()
    assert router.rolling_swap(packed_b) == 2
    assert all(r.epoch == 1 for r in router.replicas)


# ------------------------------------------------------------- co-scheduling
def test_reserve_blocked_bulk_parks_and_online_flows():
    """Same-priority bulk ahead of online in the queue: when the bulk
    chunk is blocked by the online reserve, the online request behind it
    must still dispatch (no head-of-line blocking through the reserve)."""
    on = RequestClass("on", priority=0)
    bk = RequestClass("bk", priority=0, bulk=True)
    r = toy_router(n_slots=2, dispatch_depth=2, online_reserve=1,
                   classes=(on, bk))
    b = r.submit_batch([img(1), img(2)], cls="bk")   # 2 single-image chunks
    assert b[0].replica_id is not None               # budget = 2 - 1 = 1
    assert b[1].replica_id is None                   # reserve-blocked: parks
    o = r.submit(img(3), cls="on")
    assert o.replica_id is not None                  # flowed past parked bulk
    r.run_until_idle()
    assert all(q.done for q in b) and o.done
    np.testing.assert_array_equal(o.logits, [48.0, -48.0])


def test_bulk_chunking_splits_and_reassembles_bit_exact():
    r = toy_router(n_slots=2, dispatch_depth=4, bulk_chunk=2)
    xs = np.stack([img(i + 1) for i in range(5)])
    reqs = r.submit_batch(xs, cls="bulk")
    assert [q.image.shape[0] for q in reqs] == [2, 2, 1]   # 2+2+tail
    out = r.classify_batch(xs, cls="bulk")
    assert out.shape == (5, 2)
    for i in range(5):
        np.testing.assert_array_equal(out[i], [16.0 * (i + 1),
                                               -16.0 * (i + 1)])
    # ledger counts images, not chunks
    c = r.counters()["bulk"]
    assert c["submitted"] == 10 and c["completed"] == 10


def test_chunk_clamps_to_bulk_budget_under_reserve():
    r = toy_router(n_slots=2, dispatch_depth=4, online_reserve=1)
    reqs = r.submit_batch(np.stack([img(i) for i in range(6)]),
                          cls="bulk", chunk=64)
    # 64 clamps to depth - reserve = 3, else the chunk could never dispatch
    assert [q.image.shape[0] for q in reqs] == [3, 3]
    r.run_until_idle()
    assert all(q.done for q in reqs)


def test_monopoly_chunk_without_reserve_still_serves():
    """reserve=0 keeps the pre-elastic behavior: one whole-batch chunk is
    legal (the bulk-monopoly baseline)."""
    r = toy_router(n_slots=2, dispatch_depth=4, online_reserve=0)
    out = r.classify_batch(np.stack([img(i + 1) for i in range(8)]),
                           cls="bulk", chunk=8)
    assert out.shape == (8, 2)
    np.testing.assert_array_equal(out[:, 0], 16.0 * np.arange(1, 9))


# ---------------------------------------------------- the acceptance criterion
def test_load_step_acceptance_one_to_n_to_one(packed_a, packed_b):
    """ISSUE 8 acceptance: pump-mode load step (low → burst → idle) on
    mixed online+bulk traffic scales the fleet 1→N→1 with zero drops,
    bit-exact per-epoch logits across scale AND swap events, and one
    compile on every replica that ever existed."""
    clock = StepClock(dt=1e-3)
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=3, up_watermark=2.0,
                          down_watermark=0.25, window_s=0.004,
                          cooldown_s=0.03, interval_s=0.001)
    router = packed_router(packed_a, clock, autoscale=cfg, max_queue=512,
                           online_reserve=1, bulk_chunk=2)
    n = 28
    images = np.random.default_rng(7).random((n, 32, 32, 3)).astype(
        np.float32)
    ref = {0: np.asarray(bcnn.forward_packed(packed_a, jnp.asarray(images),
                                             plan=xla_plan(packed_a))),
           1: np.asarray(bcnn.forward_packed(packed_b, jnp.asarray(images),
                                             plan=xla_plan(packed_b)))}
    online, bulk_idx = [], []
    # low phase: a trickle the lone replica absorbs
    for i in range(4):
        online.append((i, router.submit(images[i], cls="online")))
        router.pump()
    assert router.n_replicas == 1
    # burst phase: online flood + a chunked bulk batch, then a mid-burst
    # rolling swap racing the scale decisions
    for i in range(4, 20):
        online.append((i, router.submit(images[i], cls="online")))
    bulk_idx = list(range(20, n))
    bulk_reqs = router.submit_batch(images[20:], cls="bulk")
    router.rolling_swap(packed_b)
    router.run_until_idle()
    assert router.autoscaler.n_scale_ups >= 1
    peak = max(e.n_replicas for e in router.autoscaler.events)
    assert peak >= 2
    # idle phase: scale back to the floor
    for _ in range(600):
        router.pump()
    assert router.n_replicas == 1
    assert router.autoscaler.n_scale_downs == router.autoscaler.n_scale_ups
    # zero drops + bit-exact per weight epoch, online and bulk alike
    for i, q in online:
        assert q.done and q.error is None
        np.testing.assert_array_equal(q.logits, ref[q.epoch][i])
    off = 0
    for q in bulk_reqs:
        assert q.done and q.error is None
        k = 1 if q.logits.ndim == 1 else q.logits.shape[0]
        rows = q.logits if q.logits.ndim == 2 else q.logits[None]
        for j in range(k):
            np.testing.assert_array_equal(rows[j],
                                          ref[q.epoch][bulk_idx[off + j]])
        off += k
    c = router.counters()
    assert sum(v["submitted"] for v in c.values()) == n
    assert sum(v["completed"] for v in c.values()) == n
    assert sum(v["shed"] for v in c.values()) == 0
    # one compile per replica, EVER — retired replicas included
    assert len(router.replicas_ever) >= 3     # 1 seed + >=1 up + >=1 retired
    for rep in router.replicas_ever:
        assert rep.step_cache_size == 1, f"replica {rep.id} recompiled"
    # every live replica converged to the post-swap epoch
    assert all(r.epoch == router.fleet_epoch for r in router.replicas)
