"""Batch-sharded data-parallel BCNN forward (parallel/bcnn_data_parallel.py).

The hard invariants, per the paper's large-batch §6.3/Fig. 7 scenario:

* bit-exact parity — the sharded forward must equal ``forward_packed``
  exactly for every (batch, shards, stages) combination, including ragged
  batches (padded tail sliced back) and batches smaller than one chunk;
* one compile per plan — the chunk shape is the ONLY jit'd shape, so the
  compile count stays 1 across every batch size;
* engine routing — ``BCNNEngine.classify_batch`` sends bulk batches at or
  above the threshold through the sharded forward and everything smaller
  through the untouched slot path, with bit-identical logits either way;
* multi-device — the same parity holds when shards actually live on
  different (simulated host) devices; subprocess-isolated like
  tests/test_bcnn_pipeline.py so THIS process keeps seeing 1 device.
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

from repro.core import bcnn
from repro.core.execution_plan import default_plan
from repro.launch.mesh import make_data_mesh
from repro.parallel.bcnn_data_parallel import make_sharded_forward
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


# ----------------------------------------------------------------- parity

def test_parity_with_forward_packed(packed, images, ref_logits):
    """Bit-exact at 1 shard across ragged batch sizes, ONE compile total
    (5 imgs vs chunk 2: 3 chunks with a padded tail; 1 img: padded)."""
    fwd = make_sharded_forward(packed, data_shards=1, micro_batch=2,
                               plan=_plan(packed, "xla"))
    assert fwd.plan.chunk == 2
    np.testing.assert_array_equal(np.asarray(fwd(images)), ref_logits)
    np.testing.assert_array_equal(np.asarray(fwd(images[:1])), ref_logits[:1])
    np.testing.assert_array_equal(np.asarray(fwd(images[:4])), ref_logits[:4])
    assert fwd.cache_size() == 1


def test_empty_batch(packed):
    fwd = make_sharded_forward(packed, data_shards=1, micro_batch=2,
                               plan=_plan(packed, "xla"))
    out = fwd(np.zeros((0, 32, 32, 3), np.float32))
    assert out.shape == (0, 10)


def test_two_d_plan_single_device(packed, images, ref_logits):
    """data × stage composition with more grid cells than devices: the
    stage columns cycle placement, results unchanged, still one compile
    per stage."""
    fwd = make_sharded_forward(packed, data_shards=1, micro_batch=2,
                               n_stages=3, plan=_plan(packed, "xla"))
    assert fwd.plan.n_stages == 3
    assert fwd.plan.stage_plan.n_stages == 3
    np.testing.assert_array_equal(np.asarray(fwd(images)), ref_logits)
    assert fwd.cache_size() == 1


def test_plan_metadata_roundtrips():
    import json
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    fwd = make_sharded_forward(packed, data_shards=1, micro_batch=4,
                               n_stages=2, plan=_plan(packed, "xla"))
    meta = fwd.plan.describe()
    assert meta == json.loads(json.dumps(meta))       # JSON-clean
    assert meta["data_shards"] == 1 and meta["n_stages"] == 2
    assert meta["micro_batch"] == 4 and meta["chunk"] == 4
    assert meta["stage_bounds"][0] == 0
    assert meta["stage_bounds"][-1] == bcnn.N_LAYERS


def test_rejects_bad_arguments(packed):
    with pytest.raises(ValueError, match="micro_batch"):
        make_sharded_forward(packed, data_shards=1, micro_batch=0)
    with pytest.raises(ValueError, match="n_stages"):
        make_sharded_forward(packed, data_shards=1, n_stages=0)
    with pytest.raises(ValueError, match="data_shards"):
        make_sharded_forward(packed, data_shards=0)
    with pytest.raises(ValueError, match="devices"):
        make_data_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError, match="data shards"):
        make_sharded_forward(packed, mesh=make_data_mesh(1), data_shards=2)


# ----------------------------------------------------------------- engine

def test_engine_routes_large_batches_to_sharded_forward(packed, images,
                                                        ref_logits):
    eng = BCNNEngine.from_packed(packed, n_slots=2, plan=_plan(packed, "xla"),
                                 data_shards=1, data_micro_batch=2)
    assert eng.batch_forward is not None
    assert eng.batch_cache_size == 0                  # not yet used
    got = eng.classify_batch(images)                  # 5 >= threshold 2
    np.testing.assert_array_equal(got, ref_logits)
    assert eng.batch_cache_size == 1                  # sharded path ran
    assert eng.steps_executed == 0                    # slots untouched


def test_engine_routes_small_batches_through_slots(packed, images,
                                                   ref_logits):
    eng = BCNNEngine.from_packed(packed, n_slots=2, plan=_plan(packed, "xla"),
                                 data_shards=1, data_micro_batch=2,
                                 batch_threshold=4)
    got = eng.classify_batch(images[:3])              # 3 < threshold 4
    np.testing.assert_array_equal(got, ref_logits[:3])
    assert eng.steps_executed > 0                     # streamed via slots
    assert eng.batch_cache_size == 0                  # bulk path not used
    assert eng.step_cache_size == 1
    # ...and the same engine still serves bulk through the sharded path
    got = eng.classify_batch(images)
    np.testing.assert_array_equal(got, ref_logits)
    assert eng.batch_cache_size == 1


def test_engine_without_data_shards_still_classifies(packed, images,
                                                     ref_logits):
    """data_shards=0 (default): classify_batch falls back to the slot
    path for any size — behavior identical to submitting individually."""
    eng = BCNNEngine.from_packed(packed, n_slots=2, plan=_plan(packed, "xla"))
    assert eng.batch_forward is None
    got = eng.classify_batch(images)
    np.testing.assert_array_equal(got, ref_logits)
    assert eng.step_cache_size == 1


def test_engine_classify_batch_rejects_bad_shape(packed):
    eng = BCNNEngine.from_packed(packed, n_slots=2, plan=_plan(packed, "xla"))
    with pytest.raises(ValueError, match="batch shape"):
        eng.classify_batch(np.zeros((2, 16, 16, 3), np.float32))


# ------------------------------------------------------------- multi-device

_SUBPROC = textwrap.dedent("""
    import dataclasses
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import bcnn
    from repro.core.execution_plan import default_plan
    from repro.parallel.bcnn_data_parallel import make_sharded_forward

    assert len(jax.devices()) == 4, jax.devices()
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    plan = dataclasses.replace(default_plan(packed), path="xla")
    x = np.random.default_rng(0).random((6, 32, 32, 3)).astype(np.float32)
    ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(x), plan=plan))
    for shards in (2, 4):
        fwd = make_sharded_forward(packed, data_shards=shards,
                                   micro_batch=1, plan=plan)
        assert len(set(fwd.mesh.devices.flat)) == shards
        np.testing.assert_array_equal(np.asarray(fwd(x)), ref)   # ragged @4
        np.testing.assert_array_equal(np.asarray(fwd(x[:3])), ref[:3])
        assert fwd.cache_size() == 1, (shards, fwd.cache_size())
    # 2-D: 2 data shards x 2 pipeline stages over all 4 devices
    fwd = make_sharded_forward(packed, data_shards=2, micro_batch=2,
                               n_stages=2, plan=plan)
    cols = {d for col in fwd._columns for d in col.devices}
    assert len(cols) == 4, cols
    np.testing.assert_array_equal(np.asarray(fwd(x)), ref)
    assert fwd.cache_size() == 1
    # explicit placement on a device subset: shard count inferred from the
    # devices actually passed, not from the host total (construction only
    # -- placement logic, no compile)
    sub = make_sharded_forward(packed, devices=jax.devices()[:2],
                               micro_batch=1, plan=plan)
    assert sub.data_shards == 2, sub.plan
    assert set(sub.mesh.devices.flat) == set(jax.devices()[:2])
    print("BCNN_DATA_PARALLEL_OK")
""")


def test_sharded_forward_multi_device():
    """Shards on 2/4 (simulated host) devices + the 2×2 data × stage grid:
    parity + one compile per plan. Subprocess-isolated so this process
    keeps its 1-device view (same rule as tests/test_bcnn_pipeline.py)."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    if "JAX_PLATFORMS" in os.environ:
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=600, env=env)
    assert "BCNN_DATA_PARALLEL_OK" in r.stdout, r.stdout + r.stderr
