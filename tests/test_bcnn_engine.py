"""Streaming BCNN engine (serve/bcnn_engine.py) invariants.

The two hard ones, per the paper's online-serving scenario:
* co-tenant isolation — a request's logits are bit-identical whether it is
  served alone or sharing the step with arbitrary other requests (slot
  occupancy is data, and rows never mix);
* zero-recompile — the jit'd step compiles exactly once across every
  occupancy 1..n_slots (occupancy is never shape).

Cheap scheduler-level behavior is tested through a toy forward; the packed
9-layer BCNN itself backs the isolation test (module-scoped fold)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn
from repro.core.execution_plan import default_plan
from repro.serve import BCNNEngine, drive_poisson

N_SLOTS = 4


def _plan(packed, path):
    """``default_plan(packed)`` on ``path``'s kernels."""
    return dataclasses.replace(default_plan(packed), path=path)


def toy_forward(x):
    """(N, H, W, C) → (N, 2), row-separable so routing errors are visible."""
    s = x.sum(axis=(1, 2, 3))
    return jnp.stack([s, -s], axis=-1)


def toy_engine(n_slots=N_SLOTS):
    return BCNNEngine(toy_forward, n_slots=n_slots, input_shape=(4, 4, 1))


@pytest.fixture(scope="module")
def packed():
    params = bcnn.init(jax.random.PRNGKey(0))
    return bcnn.fold_model(params)


@pytest.fixture(scope="module")
def images():
    return np.random.default_rng(0).random((N_SLOTS, 32, 32, 3)).astype(
        np.float32)


def test_all_requests_complete_in_submit_order():
    eng = toy_engine(n_slots=2)
    imgs = [np.full((4, 4, 1), i, np.float32) for i in range(5)]
    rids = [eng.submit(im) for im in imgs]
    out = eng.run()
    assert sorted(out) == sorted(rids)
    for i, r in enumerate(rids):            # rid → its own image's logits
        np.testing.assert_array_equal(out[r], [16.0 * i, -16.0 * i])
    # 5 requests over 2 slots, each completing in one step → 3 steps
    assert eng.steps_executed == 3


def test_wrong_image_shape_rejected():
    eng = toy_engine()
    with pytest.raises(ValueError, match="shape"):
        eng.submit(np.zeros((8, 8, 1), np.float32))


def test_zero_recompile_across_occupancies():
    """Jit cache size stays 1 while occupancy varies over 1..n_slots."""
    eng = toy_engine()
    for k in range(1, N_SLOTS + 1):
        for _ in range(k):
            eng.submit(np.zeros((4, 4, 1), np.float32))
        eng.run()
    assert eng.steps_executed == N_SLOTS
    assert eng.step_cache_size == 1


def test_latency_accounting():
    eng = toy_engine()
    for _ in range(6):
        eng.submit(np.zeros((4, 4, 1), np.float32))
    eng.run()
    st = eng.stats()
    assert st["n"] == 6
    assert 0 <= st["p50"] <= st["p95"] <= st["p99"] <= st["max"]
    assert st["throughput"] > 0


def test_drive_poisson_serves_everything():
    eng = toy_engine(n_slots=2)
    imgs = np.random.default_rng(1).random((9, 4, 4, 1)).astype(np.float32)
    d = drive_poisson(eng, imgs, rate_hz=400.0, seed=2)
    assert len(d["results"]) == 9
    assert d["stats"]["n"] == 9
    assert eng.step_cache_size == 1


def test_drive_poisson_excludes_preexisting_requests():
    """A request already queued on the engine is served alongside the drive
    but must not count toward (or pollute) the drive's results/stats."""
    eng = toy_engine(n_slots=2)
    foreign = eng.submit(np.full((4, 4, 1), 99.0, np.float32))
    imgs = np.random.default_rng(3).random((5, 4, 4, 1)).astype(np.float32)
    d = drive_poisson(eng, imgs, rate_hz=400.0, seed=4)
    assert foreign not in d["results"]
    assert len(d["results"]) == 5 and d["stats"]["n"] == 5
    assert not eng.sched.any_active          # the foreign one was served too
    assert any(r.rid == foreign for r in eng.sched.finished)


class TickClock:
    """Deterministic clock: advances a fixed dt per call."""

    def __init__(self, dt=1e-3):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def test_drive_poisson_uses_engine_clock():
    """Regression: ``drive_poisson`` timed arrivals with raw
    ``time.perf_counter`` even when the engine carried an injected clock,
    desynchronizing arrival timing from the latency stamps. With the clock
    threaded through, a deterministic-clock drive is bit-reproducible."""
    def one_drive():
        eng = BCNNEngine(toy_forward, n_slots=2, input_shape=(4, 4, 1),
                         clock=TickClock(dt=1e-3))
        assert eng.clock is eng.sched.clock          # one timeline
        imgs = np.random.default_rng(7).random((8, 4, 4, 1)).astype(
            np.float32)
        d = drive_poisson(eng, imgs, rate_hz=100.0, seed=8)
        return d["stats"]
    a, b = one_drive(), one_drive()
    assert a == b                       # identical timeline, identical stats
    assert a["n"] == 8 and a["p99"] > 0


def test_classify_batch_empty_skips_device(packed):
    """Regression: an empty batch used to route through the bulk forward,
    paying a full padded-chunk device round-trip (and a compile) for zero
    images. It must early-return host-side on both kinds of engine."""
    eng = BCNNEngine.from_packed(packed, n_slots=2, plan=_plan(packed, "xla"),
                                 data_shards=1, data_micro_batch=2)
    out = eng.classify_batch(np.zeros((0, 32, 32, 3), np.float32))
    assert out.shape == (0, 10) and out.dtype == np.float32
    assert eng.batch_cache_size == 0    # bulk forward never compiled or ran
    assert eng.steps_executed == 0      # slot path untouched too
    # a real bulk batch afterwards still works (and compiles exactly once)
    got = eng.classify_batch(np.zeros((2, 32, 32, 3), np.float32))
    assert got.shape == (2, 10) and eng.batch_cache_size == 1


def test_cotenant_isolation_packed_bcnn(packed, images):
    """Paper BCNN, deployment path: logits for image 0 are bit-identical
    served alone vs sharing the step with 3 co-tenants."""
    eng_alone = BCNNEngine.from_packed(packed, n_slots=N_SLOTS,
                                       plan=_plan(packed, "xla"))
    r = eng_alone.submit(images[0])
    alone = eng_alone.run()[r]

    eng_shared = BCNNEngine.from_packed(packed, n_slots=N_SLOTS,
                                        plan=_plan(packed, "xla"))
    rids = [eng_shared.submit(im) for im in images]
    shared = eng_shared.run()
    np.testing.assert_array_equal(alone, shared[rids[0]])


def test_packed_engine_matches_forward_packed(packed, images):
    """Engine logits ≡ a direct forward_packed call on the same batch."""
    eng = BCNNEngine.from_packed(packed, n_slots=N_SLOTS,
                                 plan=_plan(packed, "xla"))
    rids = [eng.submit(im) for im in images]
    out = eng.run()
    ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(images),
                                         plan=_plan(packed, "xla")))
    got = np.stack([out[r] for r in rids])
    np.testing.assert_array_equal(got, ref)
    assert eng.step_cache_size == 1
