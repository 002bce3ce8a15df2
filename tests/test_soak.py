"""Soak-lite tier: the elastic fleet under sustained chaos, deterministically.

ROADMAP item 4's long-running soak harness, compressed to a CI-tractable
(~1-2 min, ``slow``-marked) pump-mode run: tens of iterations of bursty
mixed online+bulk traffic over a packed-BCNN fleet with an active
autoscaler, periodic alternating rolling weight swaps, and co-scheduled
bulk chunks under an online reserve. Everything is ``threaded=False`` with
an injected ``StepClock`` — scale events, swap walks, and scheduling are
replayed tick by tick, so a failure reproduces exactly.

What a full run must hold FLAT or CLOSED, every iteration:

* **jit caches** — ``step_cache_size == 1`` and ``batch_cache_size``
  unchanged on every replica that ever existed (elasticity must not leak
  compiles: a spawned replica compiles once at warmup, a retired one
  never again);
* **RSS-delta-per-iteration** — the memory-leak-check discipline of the
  CNTK soak suite: after a warmup prefix (compiles, allocator
  high-water), the per-iteration resident-set growth must average near
  zero and stay under a hard per-iteration bound;
* **request ledger** — submitted == completed + shed (+ 0 pending) per
  class at every iteration boundary; every request either carries logits
  that are bit-exact for its stamped weight epoch or a typed
  ``RouterOverload``-family error. None vanish.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn
from repro.core.execution_plan import default_plan
from repro.serve import AutoscaleConfig, Router, RouterOverload

psutil = pytest.importorskip(
    "psutil", reason="RSS discipline needs psutil")


class StepClock:
    def __init__(self, dt: float = 1e-3):
        self.t = 0.0
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


N_ITERS = 24
WARMUP_ITERS = 8            # compiles + allocator high-water settle here
SWAP_EVERY = 5
BURST = 32                  # images per iteration
POOL = 16                   # distinct images (requests cycle the pool)
RSS_MEAN_PER_ITER = 4 << 20          # bytes; post-warmup average bound
RSS_TOTAL = 192 << 20                # absolute post-warmup growth ceiling


@pytest.mark.slow
def test_soak_elastic_fleet_flat_caches_bounded_rss_closed_ledger():
    clock = StepClock(dt=1e-3)
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=2, up_watermark=2.0,
                          down_watermark=0.25, window_s=0.02,
                          cooldown_s=0.5, interval_s=0.001)
    packed = [bcnn.fold_model(bcnn.init(jax.random.PRNGKey(k)))
              for k in (0, 1)]
    plan = dataclasses.replace(default_plan(packed[0]), path="xla")
    router = Router.from_packed(packed[0], n_replicas=2, n_slots=2,
                                plan=plan, threaded=False, clock=clock,
                                autoscale=cfg, max_queue=256,
                                online_reserve=1, bulk_chunk=2)
    rng = np.random.default_rng(11)
    pool = rng.random((POOL, 32, 32, 3)).astype(np.float32)
    # per-weight-set reference logits: epoch e serves packed[e % 2]
    refs = [np.asarray(bcnn.forward_packed(p, jnp.asarray(pool), plan=plan))
            for p in packed]
    base_batch_cache = [(r.id, r.engine.batch_cache_size)
                        for r in router.replicas]

    proc = psutil.Process()
    rss = []
    n_swaps = 0
    ledger_checked = 0
    for it in range(N_ITERS):
        # --- offered load: a mixed burst, indices cycling the pool
        reqs = []                     # (pool_idx, request)
        for j in range(BURST):
            idx = (it * BURST + j) % POOL
            cls = "online" if (it + j) % 3 else "bulk"
            try:
                reqs.append((idx, router.submit(pool[idx], cls=cls)))
            except RouterOverload:
                pass                  # typed reject IS a closed outcome
        if it and it % SWAP_EVERY == 0:
            n_swaps += 1              # alternate a→b→a→… mid-backlog
            router.rolling_swap(packed[n_swaps % 2])
        router.run_until_idle()
        for _ in range(25):           # idle tail: lets the window drain so
            router.pump()             # scale-downs actually happen
        # --- bit-exact per stamped epoch
        for idx, q in reqs:
            assert q.done and q.error is None
            np.testing.assert_array_equal(q.logits, refs[q.epoch % 2][idx])
        # --- ledger closed at every iteration boundary
        assert router.pending == 0
        for name, c in router.counters().items():
            assert c["submitted"] == c["completed"] + c["shed"], (it, name, c)
        ledger_checked += 1
        # --- caches flat on every replica that ever existed
        for rep in router.replicas_ever:
            assert rep.step_cache_size == 1, \
                f"iter {it}: replica {rep.id} recompiled"
        for rid, base in base_batch_cache:
            rep = next(r for r in router.replicas_ever if r.id == rid)
            assert rep.engine.batch_cache_size == base, \
                f"iter {it}: replica {rid} grew its batch cache"
        rss.append(proc.memory_info().rss)

    # the chaos actually happened: swaps + scale events in both directions
    assert n_swaps >= 3 and router.fleet_epoch == n_swaps
    assert router.autoscaler.n_scale_ups >= 1
    assert router.autoscaler.n_scale_downs >= 1
    assert ledger_checked == N_ITERS
    # --- RSS discipline over the post-warmup window
    steady = rss[WARMUP_ITERS - 1:]
    deltas = np.diff(steady)
    mean_delta = float(deltas.mean()) if len(deltas) else 0.0
    assert mean_delta < RSS_MEAN_PER_ITER, \
        f"leaking {mean_delta / 1e6:.1f} MB/iteration (post-warmup)"
    assert steady[-1] - steady[0] < RSS_TOTAL, \
        f"grew {(steady[-1] - steady[0]) / 1e6:.1f} MB post-warmup"
    router.shutdown()


def test_fault_injection_replica_death_respawn_closed_ledger():
    """Kill a replica worker mid-traffic (`serve/replica.py::
    EngineReplica.inject_fault`): its orphaned requests requeue at their
    original priority/deadline, the autoscaler respawns the fleet to
    ``min_replicas`` on its next tick (cooldown-exempt floor), the ledger
    closes exactly, and every request — orphans included — finishes with
    logits bit-exact to the packed reference. No request is silently lost."""
    clock = StepClock(dt=1e-3)
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    plan = dataclasses.replace(default_plan(packed), path="xla")
    router = Router.from_packed(
        packed, n_replicas=2, n_slots=2, plan=plan, threaded=False,
        clock=clock,
        # huge cooldown: only the min_replicas floor can explain a respawn
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=3,
                                  up_watermark=50.0, down_watermark=1.0,
                                  window_s=0.02, cooldown_s=1e9,
                                  interval_s=0.001))
    rng = np.random.default_rng(3)
    pool = rng.random((8, 32, 32, 3)).astype(np.float32)
    ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(pool),
                                         plan=plan))

    reqs = [router.submit(im) for im in pool[:4]]
    router.pump()                       # first wave served, slots warm
    reqs += [router.submit(im) for im in pool[4:]]
    victim = router.replicas[0]
    victim.inject_fault()
    router.pump()                       # worker dies mid-traffic here
    assert router.replica_deaths == 1
    assert not victim.alive
    assert isinstance(victim.death_error, RuntimeError)
    assert router.n_replicas == 1       # corpse retired, not yet respawned
    with pytest.raises(RuntimeError, match="dead"):
        victim.enqueue(reqs[0])         # a corpse rejects new work loudly

    router.run_until_idle()             # survivor absorbs the orphans
    router.pump()                       # next autoscaler tick: floor respawn
    assert router.n_replicas == 2, "autoscaler must respawn to min_replicas"
    assert router.autoscaler.n_scale_ups == 1

    # the respawned replica takes traffic too
    reqs += [router.submit(im) for im in pool]
    router.run_until_idle()

    assert all(r.done and r.error is None for r in reqs), \
        "a replica death must never silently lose a request"
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.logits), ref[i % len(pool)])
    c = router.counters()["online"]
    assert c["submitted"] == c["completed"] + c["shed"] == 16
    assert c["shed"] == 0 and router.pending == 0
    # the dead replica stays in the compile-contract audit set, still at
    # exactly one compile — dying must not cost or leak an executable
    ever = router.replicas_ever
    assert victim in ever and len(ever) == 3
    assert all(rep.step_cache_size == 1 for rep in ever)
    router.shutdown()


def test_fault_injection_threaded_replica_death():
    """The same death path with real worker threads: the victim's thread
    exits, the router requeues its orphans, the controller thread respawns
    capacity, and every submitted request completes."""
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    plan = dataclasses.replace(default_plan(packed), path="xla")
    router = Router.from_packed(
        packed, n_replicas=2, n_slots=2, plan=plan, threaded=True,
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=3,
                                  up_watermark=50.0, down_watermark=1.0,
                                  window_s=0.02, cooldown_s=1e9,
                                  interval_s=0.002))
    try:
        rng = np.random.default_rng(4)
        pool = rng.random((6, 32, 32, 3)).astype(np.float32)
        ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(pool),
                                             plan=plan))
        first = [router.submit(im) for im in pool]
        for r in first:
            r.wait(timeout=60.0)
        victim = router.replicas[0]
        victim.inject_fault()
        deadline = time.monotonic() + 30.0
        while router.replica_deaths < 1:
            assert time.monotonic() < deadline, "death never detected"
            time.sleep(0.002)
        while router.n_replicas < 2:
            assert time.monotonic() < deadline, "no respawn"
            time.sleep(0.002)
        reqs = [router.submit(im) for im in pool]
        for i, r in enumerate(reqs):
            np.testing.assert_array_equal(np.asarray(r.wait(timeout=60.0)),
                                          ref[i])
        assert not victim.alive
        c = router.counters()["online"]
        assert c["submitted"] == c["completed"] + c["shed"] == 12
        assert c["shed"] == 0
    finally:
        router.shutdown()
