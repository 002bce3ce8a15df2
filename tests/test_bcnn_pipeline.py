"""Stage-pipelined BCNN deployment forward (parallel/bcnn_pipeline.py).

The hard invariants:

* bit-exact parity — the pipelined forward must equal ``forward_packed``
  exactly, for every stage count, including ragged micro-batches (padded
  tail) and batch sizes smaller than one micro-batch;
* stage-plan balance — the Table 2 cost partition obeys the eq. 12
  bottleneck properties (monotone non-increasing in stage count, full
  cover, exact-DP optimality vs any naive split);
* zero recompiles — each stage jits once across every batch size and,
  through the engine, every occupancy pattern;
* multi-device — the same parity holds when stages actually live on
  different (simulated host) devices; subprocess-isolated like
  tests/test_pipeline.py so THIS process keeps seeing 1 device.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn, bitpack
from repro.core.execution_plan import default_plan
from repro.parallel import bcnn_pipeline as bp
from repro.serve import BCNNEngine


def _plan(packed, path):
    """``default_plan(packed)`` on ``path``'s kernels."""
    return dataclasses.replace(default_plan(packed), path=path)


@pytest.fixture(scope="module")
def packed():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def images():
    return np.random.default_rng(0).random((5, 32, 32, 3)).astype(np.float32)


@pytest.fixture(scope="module")
def ref_logits(packed, images):
    return np.asarray(bcnn.forward_packed(packed, jnp.asarray(images),
                                          plan=_plan(packed, "xla")))


# ---------------------------------------------------------------- stage plan

def test_layer_costs_match_table2():
    costs = bp.layer_costs()
    assert len(costs) == bcnn.N_LAYERS
    # spot-check against the paper's Cycle_conv column (Table 3) + FC MACs
    assert costs[0] == 3538944.0          # Conv 1
    assert costs[5] == 150994944.0        # Conv 6
    assert costs[6] == 8192 * 1024        # FC 1
    assert costs[8] == 1024 * 10          # FC 3


def test_plan_properties():
    total = sum(bp.layer_costs())
    prev_bottleneck = float("inf")
    for s in range(1, bcnn.N_LAYERS + 1):
        plan = bp.plan_bcnn_stages(s)
        assert plan.n_stages == s
        assert plan.bounds[0] == 0 and plan.bounds[-1] == bcnn.N_LAYERS
        assert all(a < b for a, b in zip(plan.bounds, plan.bounds[1:]))
        assert sum(plan.stage_costs) == total
        assert 0 < plan.balance <= 1.0
        # more stages never worsen the eq. 12 bottleneck (exact DP)
        assert plan.bottleneck <= prev_bottleneck
        prev_bottleneck = plan.bottleneck
    assert bp.plan_bcnn_stages(1).bounds == (0, bcnn.N_LAYERS)


def test_plan_beats_naive_even_split():
    costs = bp.layer_costs()
    plan = bp.plan_bcnn_stages(3)
    naive = max(sum(costs[0:3]), sum(costs[3:6]), sum(costs[6:9]))
    assert plan.bottleneck <= naive


def test_plan_rejects_bad_stage_counts():
    for s in (0, bcnn.N_LAYERS + 1):
        with pytest.raises(ValueError, match="n_stages"):
            bp.plan_bcnn_stages(s)


def test_schedule_stream_limits():
    plan = bp.plan_bcnn_stages(3)
    few = bp.schedule_stream(plan, n_micro=3)
    many = bp.schedule_stream(plan, n_micro=4096)
    assert 0 < few["bubble_fraction"] < 1
    assert many["bubble_fraction"] < 0.01          # eq. 12 limit
    # forward-only: steady rate is 1/C_max, not 1/(3 C_max)
    assert many["steady_rate"] == pytest.approx(1.0 / plan.bottleneck)


# ------------------------------------------------------- boundary repacking

def test_boundary_roundtrip_exact():
    rng = np.random.default_rng(1)
    for i, (h, w, c) in bp._CONV_BOUNDS.items():
        bits = jnp.asarray(rng.integers(0, 2, (2, h, w, c)), jnp.int8)
        words = bp.pack_boundary(i, bits)
        assert words.shape == (2, h, w, c // bitpack.PACK)
        assert words.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(bp.unpack_boundary(i, words)),
                                      np.asarray(bits))
    # non-conv boundaries pass through untouched
    img = jnp.ones((2, 32, 32, 3), jnp.float32)
    assert bp.pack_boundary(0, img) is img
    assert bp.unpack_boundary(9, img) is img


# ----------------------------------------------------------------- parity

@pytest.mark.parametrize("n_stages", [1, 2, 3])
def test_parity_with_forward_packed(packed, images, ref_logits, n_stages):
    """Bit-exact across stage counts, with a ragged tail (5 imgs, mb=2)."""
    fwd = bp.make_pipelined_forward(packed, n_stages=n_stages,
                                    micro_batch=2, plan=_plan(packed, "xla"))
    np.testing.assert_array_equal(np.asarray(fwd(images)), ref_logits)
    # ragged the other way: batch smaller than one micro-batch
    np.testing.assert_array_equal(np.asarray(fwd(images[:1])), ref_logits[:1])
    # zero recompiles across both batch sizes: stages only ever saw the
    # fixed micro-batch shape
    assert fwd.cache_size() == 1


def test_single_device_stage_cycling(packed, images, ref_logits):
    """More stages than devices: placement cycles, results unchanged."""
    dev = jax.devices()[0]
    fwd = bp.make_pipelined_forward(packed, n_stages=3, micro_batch=2,
                                    devices=[dev], plan=_plan(packed, "xla"))
    assert fwd.devices == (dev, dev, dev)
    np.testing.assert_array_equal(np.asarray(fwd(images)), ref_logits)


# ----------------------------------------------------------------- engine

def test_engine_on_pipeline_zero_recompile(packed, images, ref_logits):
    """BCNNEngine riding the pipelined forward: occupancy sweep 1..n_slots
    keeps every per-stage jit cache at exactly 1, and logits match the
    single-device deployment path bit-for-bit."""
    eng = BCNNEngine.from_packed(packed, n_slots=4, plan=_plan(packed, "xla"),
                                 pipeline_stages=2, pipeline_micro_batch=1)
    for k in range(1, 5):
        rids = [eng.submit(images[i % len(images)]) for i in range(k)]
        out = eng.run()
        assert sorted(out) == sorted(rids)
    assert eng.step_cache_size == 1
    # last sweep round had all 4 slots live: check a row against the oracle
    np.testing.assert_array_equal(out[rids[0]], ref_logits[0])


# ------------------------------------------------------------- multi-device

_SUBPROC = textwrap.dedent("""
    import dataclasses
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import bcnn
    from repro.core.execution_plan import default_plan
    from repro.parallel import bcnn_pipeline as bp

    assert len(jax.devices()) == 2, jax.devices()
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    plan = dataclasses.replace(default_plan(packed), path="xla")
    x = np.random.default_rng(0).random((4, 32, 32, 3)).astype(np.float32)
    ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(x), plan=plan))
    fwd = bp.make_pipelined_forward(packed, n_stages=2, micro_batch=1,
                                    plan=plan)
    assert len(set(fwd.devices)) == 2, fwd.devices
    np.testing.assert_array_equal(np.asarray(fwd(x)), ref)
    assert fwd.cache_size() == 1
    print("BCNN_PIPELINE_OK")
""")


def test_pipelined_forward_two_devices():
    """Stages on two (simulated host) devices: parity + one compile per
    stage. Subprocess-isolated so this process keeps its 1-device view."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    # forward the backend pin (same rule as tests/test_pipeline.py); the
    # child re-sets XLA_FLAGS itself before importing jax
    if "JAX_PLATFORMS" in os.environ:
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=600, env=env)
    assert "BCNN_PIPELINE_OK" in r.stdout, r.stdout + r.stderr
