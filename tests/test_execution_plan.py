"""One plan per deployment, resolved in one place
(`core/execution_plan.py::default_plan`).

* ``default_plan`` pins the heuristics: path from the backend, the
  ``bconv.resolve_strategy`` rule on Table 2's binary convs, fusion off with
  no tiles, a path-only plan for Bi-Real Net;
* every serving constructor that may be given no plan builds exactly
  ``default_plan(packed)``;
* none of them takes a per-knob kernel argument (``path``,
  ``conv_strategy``, ``conv_fusion``) any more;
* one engine serves one plan object: its slot step and its bulk forward
  close over the plan the engine reports.
"""
import jax
import numpy as np
import pytest

from repro.core import bcnn, bireal
from repro.core.execution_plan import default_plan
from repro.parallel.bcnn_data_parallel import make_sharded_forward
from repro.parallel.bcnn_pipeline import make_pipelined_forward
from repro.serve import BCNNEngine, Router

# the small Bi-Real geometry of tests/test_bireal.py: 32x32 uint8 pixels
BIREAL_CFG = {"input_shape": [32, 32, 3], "input_dtype": "uint8",
              "stem": {"channels": 32, "kernel": 7, "stride": 2,
                       "pool": {"kernel": 3, "stride": 2, "pad": 1}},
              "stages": [[32, 2, 1], [64, 2, 2], [64, 2, 2], [128, 2, 2]],
              "n_classes": 10}


@pytest.fixture(scope="module")
def models():
    geom = bireal.geometry(BIREAL_CFG)
    return {"table2": bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0))),
            "bireal": bireal.fold(bireal.init(3, geom), geom)}


# ------------------------------------------------- the default heuristics

@pytest.mark.parametrize("backend,path", [("cpu", "xla"), ("tpu", "mxu")])
@pytest.mark.parametrize("model", ["table2", "bireal"])
def test_default_plan_pins_the_heuristics(models, model, backend, path):
    plan = default_plan(models[model], backend)
    assert plan.path == path
    if model == "table2":
        # CONV-2..6 have 32-aligned channels and per-position weights
        assert plan.conv_strategy == (None,) + ("direct",) * 5 + (None,) * 3
    else:
        assert plan.conv_strategy == ()
    assert plan.conv_fusion is False
    assert plan.group_tiles == ()
    assert plan.tuned is False


# ------------------------------------ plan=None means default_plan(packed)

def _router_plan(packed, **kw):
    router = Router.from_packed(packed, n_replicas=1, n_slots=2,
                                warmup=False, threaded=False, **kw)
    try:
        return router.replicas[0].engine.plan
    finally:
        router.shutdown()


# constructor → (the plan it serves, given the packed net and extra kwargs)
CONSTRUCTORS = {
    "make_packed_forward":
        lambda p, **kw: bcnn.make_packed_forward(p, **kw).plan,
    "BCNNEngine.from_packed":
        lambda p, **kw: BCNNEngine.from_packed(p, n_slots=2, **kw).plan,
    "make_sharded_forward":
        lambda p, **kw: make_sharded_forward(
            p, data_shards=1, micro_batch=2, **kw).exec_plan,
    "make_pipelined_forward":
        lambda p, **kw: make_pipelined_forward(
            p, n_stages=2, micro_batch=1, **kw).exec_plan,
    "Router.from_packed": _router_plan,
}
# the pipeline cuts Table 2's nine layers; every other constructor serves
# any packed model
SERVES = [(c, m) for c in CONSTRUCTORS for m in ("table2", "bireal")
          if not (c == "make_pipelined_forward" and m == "bireal")]


@pytest.mark.parametrize("constructor,model", SERVES)
def test_omitted_plan_is_the_default_plan(models, constructor, model):
    packed = models[model]
    assert CONSTRUCTORS[constructor](packed) == default_plan(packed)


@pytest.mark.parametrize("knob,value", [("path", "mxu"),
                                        ("conv_strategy", "direct"),
                                        ("conv_fusion", True)])
@pytest.mark.parametrize("constructor", sorted(CONSTRUCTORS))
def test_per_knob_kernel_arguments_are_gone(models, constructor, knob,
                                            value):
    with pytest.raises(TypeError):
        CONSTRUCTORS[constructor](models["table2"], **{knob: value})


# ------------------------------------------------- one engine, one plan

@pytest.mark.parametrize("model", ["table2", "bireal"])
def test_one_engine_serves_one_plan(models, model):
    packed = models[model]
    eng = BCNNEngine.from_packed(packed, n_slots=2, data_shards=1,
                                 data_micro_batch=2)
    assert eng.forward.plan is eng.plan
    assert eng.batch_forward.exec_plan is eng.plan
    assert eng.plan == default_plan(packed)
    # and both routes answer: the bulk forward and the slot step agree
    x = np.random.default_rng(0).integers(
        0, 256, (2, *packed.input_shape)).astype(packed.input_dtype)
    if packed.input_dtype == np.float32:
        x = x / np.float32(255)
    bulk = eng.classify_batch(x)
    rids = [eng.submit(img) for img in x]
    out = eng.run()
    np.testing.assert_array_equal(np.stack([out[r] for r in rids]), bulk)
