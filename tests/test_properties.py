"""Hypothesis property tests on the system's core invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

import dataclasses
import functools
import types

from repro.core import bcnn, bconv, blinear, bitpack
from repro.core.execution_plan import default_plan
from repro.core.normbinarize import BNParams, fold_threshold, norm_binarize
from repro.core.throughput import balance_stages, pipeline_throughput
from repro.serve import (AutoscaleConfig, BCNNEngine, FleetAutoscaler,
                         RequestClass, Router)
from repro.train import optimizer as opt_lib

SET = settings(max_examples=40, deadline=None)
# the deployment-path properties run the full 9-layer network both ways
# per example — keep the example count commensurate
SET_DEPLOY = settings(max_examples=6, deadline=None)
# fleet properties build jitted toy engines per example
SET_FLEET = settings(max_examples=15, deadline=None)


# --------------------------------------------------------------------- bitpack

@SET
@given(st.integers(1, 300), st.integers(1, 7), st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_roundtrip(k, rows, seed):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (rows, k)).astype(np.int8)
    words = bitpack.pack_bits(bitpack.pad_to_pack(jnp.asarray(bits)))
    back = bitpack.unpack_bits(words, k)
    np.testing.assert_array_equal(np.asarray(back), bits)


@SET
@given(st.integers(1, 3), st.integers(1, 6), st.integers(1, 6),
       st.integers(1, 80), st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_roundtrip_nhwc(n, h, w, c, seed):
    """The deployment wire format: NHWC bit feature maps packed along the
    channel axis (how stage/shard boundaries travel between devices —
    parallel/bcnn_pipeline.py::pack_boundary) round-trip exactly for any
    spatial shape and any (unaligned) channel count."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (n, h, w, c)).astype(np.int8)
    words = bitpack.pack_bits(bitpack.pad_to_pack(jnp.asarray(bits)))
    assert words.shape == (n, h, w, bitpack.packed_len(c))
    back = bitpack.unpack_bits(words, c)
    np.testing.assert_array_equal(np.asarray(back), bits)


@SET
@given(st.integers(1, 200), st.integers(0, 2 ** 31 - 1))
def test_xnor_dot_equals_pm1_dot(k, seed):
    """Eq. 5/6: XNOR agree-count ↔ ±1 dot product, any (unaligned) K."""
    rng = np.random.default_rng(seed)
    a = np.sign(rng.standard_normal((3, k)) + 1e-9)
    w = np.sign(rng.standard_normal((5, k)) + 1e-9)
    aw = bitpack.pack_pm1(jnp.asarray(a))
    ww = bitpack.pack_pm1(jnp.asarray(w))
    y_l = bitpack.xnor_dot(aw[:, None, :], ww[None, :, :], k)
    y = bitpack.pm1_from_xnor(y_l, k)
    np.testing.assert_array_equal(np.asarray(y), (a @ w.T).astype(np.int64))


# ------------------------------------------------------------ deployment path

@functools.lru_cache(maxsize=2)
def _bcnn_model(model_seed: int):
    """init + fold once per model seed (the expensive part of an example)."""
    params = bcnn.init(jax.random.PRNGKey(model_seed))
    return params, bcnn.fold_model(params)


@SET_DEPLOY
@given(st.integers(0, 1), st.integers(0, 2 ** 31 - 1), st.integers(1, 3))
def test_apply_packed_layer_matches_eval_layerwise(model_seed, input_seed,
                                                   batch):
    """Layer-wise parity of the deployment path: every
    ``apply_packed_layer`` output (bit maps, packed FC words, final Norm
    logits) equals the fp ``forward_eval`` layer sequence, for randomized
    model/input seeds and batch sizes. Stronger than the end-to-end logits
    check in tests/test_bcnn.py: a bug that cancels across layers (or only
    corrupts an intermediate bit map) is pinned to the exact layer."""
    params, packed = _bcnn_model(model_seed)
    x = jnp.asarray(np.random.default_rng(input_seed)
                    .random((batch, 32, 32, 3)).astype(np.float32))
    plan = dataclasses.replace(default_plan(packed), path="xla")
    h = x
    a = bconv.fpconv_apply(params.conv1, x)                     # oracle, ±1
    for idx in range(bcnn.N_LAYERS):
        h = bcnn.apply_packed_layer(packed, idx, h, plan)
        if idx >= 1 and idx <= 5:
            a = bconv.apply_train(params.convs[idx - 1], a,
                                  maxpool=bcnn.CONV_SPECS[idx][2])
        elif idx == 6:
            a = blinear.apply_train(params.fcs[0],
                                    a.reshape(a.shape[0], -1))
        elif idx == 7:
            a = blinear.apply_train(params.fcs[1], a)
        elif idx == 8:
            a = blinear.apply_train(params.fcs[2], a, binarize_out=False)
        if idx <= 5:            # {0,1} bit feature maps: exact
            np.testing.assert_array_equal(
                np.asarray(h), np.asarray(bitpack.encode_pm1(a)),
                err_msg=f"layer {idx}")
        elif idx <= 7:          # packed FC words: exact
            want = bitpack.pack_bits(bitpack.encode_pm1(a))
            np.testing.assert_array_equal(np.asarray(h), np.asarray(want),
                                          err_msg=f"layer {idx}")
        else:                   # FC-3 Norm logits: fp to BN tolerance
            np.testing.assert_allclose(np.asarray(h), np.asarray(a),
                                       rtol=1e-4, atol=1e-4)


@SET_DEPLOY
@given(st.integers(0, 1), st.integers(0, 2 ** 31 - 1), st.integers(1, 3))
def test_conv_fusion_parity(model_seed, input_seed, batch):
    """Cross-layer conv fusion (kernels/xnor_conv_fused.py) is bit-exact:
    the fused forward equals the unfused fold for randomized model seeds,
    inputs, and batch sizes — the fusion-parity invariant the megakernel's
    test tier pins on fixtures, here over the whole sampled space."""
    _, packed = _bcnn_model(model_seed)
    x = jnp.asarray(np.random.default_rng(input_seed)
                    .random((batch, 32, 32, 3)).astype(np.float32))
    plan = dataclasses.replace(default_plan(packed), path="xla")
    ref = bcnn.forward_packed(packed, x, plan=plan)
    got = bcnn.forward_packed(
        packed, x, plan=dataclasses.replace(plan, conv_fusion=True))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@SET
@given(st.integers(0, bcnn.N_LAYERS), st.integers(0, bcnn.N_LAYERS),
       st.booleans())
def test_plan_layer_groups_partitions(start, stop, fusion):
    """The fusion planner partitions any [start, stop) layer window in
    order; every group is a singleton or an adjacent binary-conv pair whose
    first member has no max-pool (a pool only ever ends a group), so a
    group never spans a resolution drop or a stage cut."""
    start, stop = min(start, stop), max(start, stop)
    groups = bcnn.plan_layer_groups(start, stop, conv_fusion=fusion)
    assert [i for g in groups for i in g] == list(range(start, stop))
    for g in groups:
        assert len(g) in (1, 2)
        if len(g) == 2:
            i, j = g
            assert j == i + 1 and 1 <= i <= 4
            assert not bcnn.CONV_SPECS[i][2]
    if not fusion:
        assert all(len(g) == 1 for g in groups)


# ---------------------------------------------------------------- normbinarize

@SET
@given(st.integers(4, 256), st.integers(0, 2 ** 31 - 1),
       st.booleans())
def test_fold_threshold_equals_bn_sign(cnum, seed, neg_gamma):
    """Eq. 8 ≡ Binarize(BN(2y−cnum)) for ANY γ sign (incl. the paper's
    unstated γ>0 assumption — we handle γ<0 with the flip bit)."""
    rng = np.random.default_rng(seed)
    n = 8
    bn = BNParams(
        mean=jnp.asarray(rng.standard_normal(n) * 3),
        var=jnp.asarray(rng.random(n) * 4 + 0.1),
        gamma=jnp.asarray((-1 if neg_gamma else 1)
                          * (rng.random(n) * 2 + 0.05)),
        beta=jnp.asarray(rng.standard_normal(n)), eps=1e-4)
    thr = fold_threshold(bn, cnum, rounded=False)
    y_l = jnp.asarray(rng.integers(0, cnum + 1, (16, n)))
    got = norm_binarize(y_l, thr)
    y_lo = 2 * y_l - cnum
    z = ((y_lo - bn.mean) / jnp.sqrt(bn.var + bn.eps)) * bn.gamma + bn.beta
    want = (z >= 0).astype(jnp.int8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------------ throughput

@SET
@given(st.lists(st.floats(0.1, 100), min_size=1, max_size=12),
       st.integers(1, 6))
def test_balance_stages_optimal(costs, n_stages):
    """The DP returns the true min-bottleneck contiguous partition."""
    n_stages = min(n_stages, len(costs))
    bounds = balance_stages(costs, n_stages)
    assert bounds[0] == 0 and bounds[-1] == len(costs)
    assert all(b2 > b1 for b1, b2 in zip(bounds, bounds[1:]))
    got = 1.0 / pipeline_throughput(costs, bounds)

    # brute force all partitions for small n
    import itertools
    best = float("inf")
    for cuts in itertools.combinations(range(1, len(costs)), n_stages - 1):
        bb = [0, *cuts, len(costs)]
        best = min(best, max(sum(costs[bb[i]:bb[i + 1]])
                             for i in range(n_stages)))
    assert got <= best * (1 + 1e-9)


# ------------------------------------------------------- gradient compression

@SET
@given(st.integers(0, 2 ** 31 - 1))
def test_ef_compression_unbiased_accumulation(seed):
    """Error feedback: quantization error is carried, not lost — the sum of
    transmitted values tracks the sum of true gradients."""
    rng = np.random.default_rng(seed)
    g_true = [jnp.asarray(rng.standard_normal((4, 4)) * (i + 1))
              for i in range(3)]
    params = {"a": jnp.zeros((4, 4))}
    ef = opt_lib.ef_init(params)
    sent = jnp.zeros((4, 4))
    for g in g_true:
        q, ef = opt_lib.compress_decompress({"a": g}, ef)
        sent = sent + q["a"]
        # wire format really is 1 bit + scale:
        vals = np.unique(np.abs(np.asarray(q["a"])))
        assert len(vals) == 1
    total = sum(np.asarray(g) for g in g_true)
    resid = np.asarray(ef.residual["a"])
    np.testing.assert_allclose(np.asarray(sent) + resid, total,
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ fleet scheduler

class _TickClock:
    def __init__(self, dt=1e-3):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _toy_fleet(n_slots=1, **kw):
    clock = _TickClock()
    eng = BCNNEngine(lambda x: jnp.stack([x.sum(axis=(1, 2, 3))] * 2,
                                         axis=-1),
                     n_slots=n_slots, input_shape=(2, 2, 1), clock=clock)
    return Router([eng], threaded=False, clock=clock, **kw)


@st.composite
def _sched_cases(draw):
    n_classes = draw(st.integers(2, 4))
    classes = tuple(
        RequestClass(f"c{i}", priority=draw(st.integers(0, 2)),
                     deadline_s=draw(st.one_of(st.none(),
                                               st.floats(0.01, 10.0))))
        for i in range(n_classes))
    arrivals = draw(st.lists(st.integers(0, n_classes - 1),
                             min_size=1, max_size=12))
    return classes, arrivals


@SET_FLEET
@given(_sched_cases())
def test_dispatch_order_priority_then_edf_then_fifo(case):
    """Over random class sets and arrival sequences, dispatching a frozen
    backlog follows exactly (strict priority, EDF within a rank, FIFO
    within a class) — the documented key, observed from the outside via
    ``t_dispatch`` stamps, not read off the heap."""
    classes, arrivals = case
    r = _toy_fleet(n_slots=1, dispatch_depth=1, classes=classes,
                   max_queue=64)
    with r._lock:
        r._paused.add(0)                 # freeze dispatch while admitting
    reqs = [r.submit(np.full((2, 2, 1), i, np.float32),
                     cls=classes[ci].name)
            for i, ci in enumerate(arrivals)]
    with r._lock:
        r._paused.discard(0)
    r.run_until_idle()
    assert all(q.done for q in reqs)
    # depth 1 serializes dispatch: observed order is the t_dispatch order
    observed = sorted(range(len(reqs)), key=lambda i: reqs[i].t_dispatch)
    expected = sorted(
        range(len(reqs)),
        key=lambda i: (reqs[i].cls.priority,
                       reqs[i].t_submit + reqs[i].cls.deadline_s
                       if reqs[i].cls.deadline_s is not None
                       else float("inf"),
                       i))
    assert observed == expected


@st.composite
def _coschedule_cases(draw):
    depth = draw(st.integers(2, 5))
    reserve = draw(st.integers(1, depth - 1))
    ops = draw(st.lists(
        st.tuples(st.booleans(), st.integers(1, 4)), min_size=1,
        max_size=8))
    return depth, reserve, ops


@SET_FLEET
@given(_coschedule_cases())
def test_bulk_never_enters_the_online_reserve(case):
    """Under any interleaving of online singles and chunked bulk batches,
    the images of dispatched-but-unfinished bulk on a replica never exceed
    ``dispatch_depth - online_reserve`` — and everything still completes
    (the reserve protects online without starving bulk forever)."""
    depth, reserve, ops = case
    budget = depth - reserve
    bk = RequestClass("bk", priority=1, bulk=True)
    on = RequestClass("on", priority=0)
    r = _toy_fleet(n_slots=2, dispatch_depth=depth, online_reserve=reserve,
                   classes=(on, bk), max_queue=512)
    every = []

    def bulk_in_flight():
        per = {}
        for q in every:
            if q.cls.bulk and q.t_dispatch is not None and not q.done:
                k = 1 if q.image.ndim == 3 else q.image.shape[0]
                per[q.replica_id] = per.get(q.replica_id, 0) + k
        return per

    for is_bulk, k in ops:
        if is_bulk:
            xs = np.zeros((k, 2, 2, 1), np.float32)
            every.extend(r.submit_batch(xs, cls="bk", chunk=k))
        else:
            every.append(r.submit(np.zeros((2, 2, 1), np.float32),
                                  cls="on"))
        for rid, n in bulk_in_flight().items():
            assert n <= budget, (rid, n, budget)
        r.pump()
        for rid, n in bulk_in_flight().items():
            assert n <= budget, (rid, n, budget)
    r.run_until_idle()
    assert all(q.done and q.error is None for q in every)


class _FakeFleet:
    """Constant-load fleet stub for the autoscaler: ``outstanding`` images
    never change; scale calls just move the replica count."""

    def __init__(self, outstanding, slots_per, n0):
        self.outstanding = float(outstanding)
        self.slots_per = slots_per
        self.n = n0
        self._next = n0

    def load_snapshot(self):
        return {"queued": 0, "inflight": self.outstanding,
                "outstanding": self.outstanding, "n_replicas": self.n,
                "total_slots": self.n * self.slots_per,
                "deadline_missed": 0, "deadline_total": 0}

    @property
    def n_replicas(self):
        return self.n

    def scale_up(self):
        self.n += 1
        self._next += 1
        return types.SimpleNamespace(id=self._next)

    def scale_down(self):
        self.n -= 1
        return self.n


@SET
@given(st.floats(0.0, 200.0), st.integers(1, 8), st.integers(1, 6),
       st.floats(0.5, 8.0), st.floats(0.05, 0.95))
def test_autoscaler_never_oscillates_on_constant_load(load, slots_per, n0,
                                                      up, down_frac):
    """Hysteresis property: with a CONSTANT offered load, every valid
    config (down < up/2 is enforced) produces scale events in at most ONE
    direction — the fleet walks monotonically to its steady size and
    stays there. Oscillation (an up after a down, or vice versa) is a
    config-independent impossibility, not a tuning accident."""
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=8, up_watermark=up,
                          down_watermark=up / 2 * down_frac,
                          window_s=0.5, cooldown_s=2.0, interval_s=1.0)
    fleet = _FakeFleet(load, slots_per, n0)
    auto = FleetAutoscaler(fleet, cfg, clock=lambda: 0.0)
    for step in range(200):
        auto.step(now=float(step))
    directions = {e.direction for e in auto.events}
    assert len(directions) <= 1, auto.events
    ns = [e.n_replicas for e in auto.events]
    assert ns == sorted(ns) or ns == sorted(ns, reverse=True)
    assert 1 <= fleet.n <= 8
    # and it converged: the tail of the run is event-free
    assert all(e.t < 150.0 for e in auto.events)


# ------------------------------------------------------------------- xnor lm

from repro.kernels import ops as kops, ref as kref  # noqa: E402


@SET
@given(st.integers(1, 96), st.integers(1, 8), st.integers(1, 5),
       st.integers(0, 2 ** 31 - 1))
def test_blinear_train_vs_packed_parity(in_f, out_f, batch, seed):
    """`core/blinear.py::apply_train` ≡ fold + ``apply_packed`` on every
    binarize decision, for any (in, out, batch) shape — including ragged
    in_f (the packed pad bits cancel). BN stats are constructed
    sign-exact (integer means, beta=0, ±gamma) so the f32 train-side sign
    is the same mathematical integer compare the folded eq. 8 threshold
    makes — no boundary flakes, the same standard the LM parity tier pins
    end to end (tests/test_xnor_lm.py)."""
    rng = np.random.default_rng(seed)
    a = rng.choice(np.array([-1.0, 1.0], np.float32), size=(batch, in_f))
    p = blinear.BLinearParams(
        w=jnp.asarray(rng.uniform(-1, 1, (out_f, in_f)), jnp.float32),
        bn_mean=jnp.asarray(
            rng.integers(-in_f, in_f + 1, (out_f,)), jnp.float32),
        bn_var=jnp.asarray(rng.choice([0.25, 1.0, 4.0], (out_f,)),
                           jnp.float32),
        bn_gamma=jnp.asarray(rng.choice([-1.0, 1.0], (out_f,))
                             * rng.uniform(0.5, 2.0, (out_f,)), jnp.float32),
        bn_beta=jnp.zeros((out_f,), jnp.float32))
    train = blinear.apply_train(p, jnp.asarray(a), binarize_out=True)
    bits = blinear.apply_packed(blinear.fold(p),
                                bitpack.pack_pm1(jnp.asarray(a)))
    packed = bitpack.decode_pm1(bits)
    np.testing.assert_array_equal(np.asarray(train), np.asarray(packed))


@SET
@given(st.integers(1, 6), st.integers(1, 130), st.integers(1, 9),
       st.booleans(), st.integers(0, 2 ** 31 - 1))
def test_binary_weight_matmul_matches_oracle(m, k, n, scaled, seed):
    """The weight-only decode kernel vs its `kernels/ref.py` oracle over
    arbitrary shapes — K deliberately spans ragged/padded reduction
    lengths (k % 32 ≠ 0 exercises the zero-pad path)."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(-3, 4, (m, k)), jnp.float32)
    w_words = bitpack.pack_pm1(jnp.asarray(
        rng.choice(np.array([-1.0, 1.0], np.float32), size=(n, k))))
    scale = (jnp.asarray(rng.uniform(0.5, 2.0, (n,)), jnp.float32)
             if scaled else None)
    y = kops.binary_weight_matmul(a, w_words, k=k, scale=scale)
    y_ref = kref.binary_weight_matmul_ref(a, w_words, k, scale)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6)
