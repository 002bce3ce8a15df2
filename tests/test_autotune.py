"""Measure-and-cache kernel autotuner (kernels/autotune.py) + the
ExecutionPlan layer it feeds (core/execution_plan.py).

The hard invariants:

* legality — the enumerated candidate space matches the rules the
  heuristics use (`core/bconv.py::resolve_strategy` for conv dataflows,
  `kernels/xnor_conv_fused.py::halo_scratch` VMEM budgeting for fused
  tiles, backend-conditional Pallas paths);
* determinism — under an injected fake timer the tuner picks the same
  plan every run (ties broken first-candidate);
* bit-exactness — a tuned plan produces logits identical to the default
  plan on ALL THREE deployment forwards (packed / pipelined / sharded);
* persistence — the tuned plan roundtrips through the artifact's
  ``tuning`` manifest section, CRC/version tampering is rejected, and a
  stale or foreign-device cache entry falls back to ``default_plan``
  silently, never an error;
* zero-recompile — a tuned engine keeps ``step_cache_size == 1`` across
  the occupancy sweep AND a weight hot-swap.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn, bconv, bcnn_artifact, execution_plan as xplan
from repro.kernels import autotune as at
from repro.kernels import xnor_conv_fused as kfused
from repro.parallel import bcnn_data_parallel as bdp
from repro.parallel import bcnn_pipeline as bp
from repro.serve import BCNNEngine


def _plan(packed, path):
    """``default_plan(packed)`` on ``path``'s kernels."""
    return dataclasses.replace(xplan.default_plan(packed), path=path)


class FakeTimer:
    """Monotone counter clock: every measured interval is identical, so
    races are decided purely by candidate order — deterministic."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(scope="module")
def packed():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def tuned_plan(packed):
    """One (fake-timer) tuning run shared by the bit-exactness and
    persistence tests — the real measurement protocol, deterministic."""
    return at.autotune_packed(packed, timer=FakeTimer(), reps=1, warmup=0)


@pytest.fixture(scope="module")
def images():
    return np.random.default_rng(0).random((5, 32, 32, 3)).astype(np.float32)


@pytest.fixture(scope="module")
def ref_logits(packed, images):
    return np.asarray(bcnn.forward_packed(packed, jnp.asarray(images),
                                          plan=_plan(packed, "xla")))


# ----------------------------------------------------------- candidate space

def test_path_candidates_are_backend_conditional():
    """Pallas variants only race on TPU (interpret mode must never win a
    timing race); the XLA reference is always a candidate."""
    assert at.backend_paths("tpu") == ("vpu", "mxu", "xla")
    assert at.backend_paths("cpu") == ("xla",)
    assert at.backend_paths("gpu") == ("xla",)


def test_strategy_candidates_match_resolve_strategy(packed):
    """Per conv layer, "direct" is a candidate exactly when the resolver
    would accept an explicit request for it; "im2col" always is."""
    for idx in range(1, 6):
        fp = packed.convs[idx - 1]
        c = fp.k // (fp.fh * fp.fw)
        cands = at.strategy_candidates(fp, c)
        assert cands[-1] == "im2col"
        direct_legal = fp.w_words_hw is not None and c % 32 == 0
        assert ("direct" in cands) == direct_legal
        for s in cands:                      # every candidate must resolve
            assert bconv.resolve_strategy(s, c, fp) == s


def test_tile_candidates_fit_budget_and_cover_heuristic(packed):
    """Every enumerated fused tile fits the halo-scratch VMEM budget, and
    the ``pick_tiles`` heuristic winner is always in the candidate set."""
    space = at.enumerate_candidates(packed)
    assert set(space["pairs"]) == {2, 4}     # Table 2 same-resolution pairs
    for i, pair in space["pairs"].items():
        j = i + 1
        fa, fb = packed.convs[i - 1], packed.convs[j - 1]
        h, w = xplan._conv_resolution(i, (32, 32))
        pf = 2 if bcnn.CONV_SPECS[j][2] else 1
        oa, la = fa.w_words_hw.shape
        assert pair["pool_b"] == (pf == 2)
        assert len(pair["tiles"]) >= 1
        for th, tw in pair["tiles"]:
            assert kfused.halo_scratch(th, tw, pf=pf, fhb=fb.fh, fwb=fb.fw,
                                       oa=oa, la=la) <= kfused.SCRATCH_BUDGET
        heuristic = kfused.pick_tiles(h // pf, w // pf, pf=pf, fhb=fb.fh,
                                      fwb=fb.fw, oa=oa, la=la)
        assert heuristic in pair["tiles"]


def test_enumerate_covers_all_binary_convs(packed):
    space = at.enumerate_candidates(packed)
    assert set(space["convs"]) == {1, 2, 3, 4, 5}
    for info in space["convs"].values():
        assert len(info["strategies"]) >= 1


# ------------------------------------------------------------- determinism

def test_fake_timer_tuning_is_deterministic(packed, tuned_plan):
    """Same candidate order + identical fake intervals → identical plan,
    run to run. (The fixture ran once; this repeats the run.)"""
    report = {}
    again = at.autotune_packed(packed, timer=FakeTimer(), reps=1, warmup=0,
                               report=report)
    assert again == tuned_plan
    assert again.tuned is True
    assert report["n_candidates"] >= report["n_eligible"] >= 1
    # off-TPU every candidate is an xla lowering of the same math — all
    # must pass the bit-exact eligibility gate
    if jax.default_backend() != "tpu":
        assert report["n_eligible"] == report["n_candidates"]


def test_default_plan_matches_legacy_resolution(packed):
    """``default_plan`` reproduces the historical per-site heuristics:
    resolver strategies, fusion off (so no tiles)."""
    plan = xplan.default_plan(packed, "cpu")
    assert plan.tuned is False
    assert plan.path == "xla"                # "auto" off-TPU
    assert plan.conv_fusion is False and plan.group_tiles == ()
    for idx in range(1, 6):
        fp = packed.convs[idx - 1]
        c = fp.k // (fp.fh * fp.fw)
        assert plan.strategy_for(idx) == bconv.resolve_strategy(None, c, fp)
    for idx in (0, 6, 7, 8):
        assert plan.strategy_for(idx) is None


# ----------------------------------------------- bit-exact on all 3 forwards

def test_tuned_plan_bit_exact_packed(packed, images, ref_logits, tuned_plan):
    got = bcnn.forward_packed(packed, jnp.asarray(images), plan=tuned_plan)
    np.testing.assert_array_equal(np.asarray(got), ref_logits)


def test_tuned_plan_bit_exact_pipelined(packed, images, ref_logits,
                                        tuned_plan):
    fwd = bp.make_pipelined_forward(packed, n_stages=3, micro_batch=2,
                                    plan=tuned_plan)
    np.testing.assert_array_equal(np.asarray(fwd(images)), ref_logits)
    assert fwd.cache_size() == 1


def test_tuned_plan_bit_exact_sharded(packed, images, ref_logits,
                                      tuned_plan):
    fwd = bdp.make_sharded_forward(packed, data_shards=1, micro_batch=2,
                                   plan=tuned_plan)
    np.testing.assert_array_equal(np.asarray(fwd(images)), ref_logits)
    assert fwd.cache_size() == 1


# --------------------------------------------------- artifact tuning section

def test_tuning_section_roundtrip(tmp_path, packed, tuned_plan):
    """save_packed(tuning=...) → load_tuning → plan_from_dict gives back
    the exact plan; plan_for_host on the SAME host reuses it."""
    d = str(tmp_path / "art")
    tuning = at.tuning_section(packed, tuned_plan)
    bcnn_artifact.save_packed(d, packed, tuning=tuning)
    loaded = bcnn_artifact.load_tuning(d)
    assert loaded == tuning
    plan, source = at.plan_for_host(packed, loaded)
    assert source == "cached"
    assert plan == tuned_plan


def test_tuning_crc_tamper_rejected(tmp_path, packed, tuned_plan):
    import json
    import os
    d = str(tmp_path / "art")
    bcnn_artifact.save_packed(d, packed,
                              tuning=at.tuning_section(packed, tuned_plan))
    mpath = os.path.join(d, bcnn_artifact.MANIFEST)
    man = json.load(open(mpath))
    man["tuning"]["plan"]["path"] = "vpu"    # silently edited plan
    json.dump(man, open(mpath, "w"))
    with pytest.raises(bcnn_artifact.ArtifactError, match="CRC"):
        bcnn_artifact.load_tuning(d)
    # the weights themselves are untouched — the model still loads
    bcnn_artifact.load_packed(d)


def test_newer_tuning_version_ignored_not_fatal(tmp_path, packed,
                                                tuned_plan):
    """A tuning section written by a FUTURE tuner is skipped (None), not an
    error — the artifact stays loadable and serving falls back to the
    heuristics."""
    import json
    import os
    d = str(tmp_path / "art")
    bcnn_artifact.save_packed(d, packed,
                              tuning=at.tuning_section(packed, tuned_plan))
    mpath = os.path.join(d, bcnn_artifact.MANIFEST)
    man = json.load(open(mpath))
    man["tuning"]["tuning_version"] = bcnn_artifact.TUNING_VERSION + 1
    json.dump(man, open(mpath, "w"))
    assert bcnn_artifact.load_tuning(d) is None
    bcnn_artifact.load_packed(d)


def test_stale_device_falls_back_to_default(packed, tuned_plan):
    """A cache entry measured on a foreign device/backend/geometry must
    fall back to ``default_plan`` silently — never error, never reuse."""
    tuning = at.tuning_section(packed, tuned_plan)
    for field, value in (("backend", "tpu-of-someone-else"),
                         ("device_kind", "TPU v9"),
                         ("geometry", "deadbeef")):
        stale = {"key": dict(tuning["key"], **{field: value}),
                 "plan": tuning["plan"]}
        plan, source = at.plan_for_host(packed, stale)
        assert source == "default"
        assert plan == xplan.default_plan(packed)
    # no tuning at all → default too
    plan, source = at.plan_for_host(packed, None)
    assert source == "default"
    # malformed plan payload under a MATCHING key → default, not a raise
    bad = {"key": tuning["key"], "plan": {"path": "xla"}}
    plan, source = at.plan_for_host(packed, bad)
    assert source == "default"


# --------------------------------------------------------- zero-recompile

def test_tuned_engine_one_compile_across_swap(packed, images, tuned_plan):
    """The tuned plan is a trace-time static: occupancy sweep + weight
    hot-swap on a tuned engine keep the step cache at exactly 1, and the
    swapped weights' logits match their own xla reference."""
    eng = BCNNEngine.from_packed(packed, n_slots=4, plan=tuned_plan)
    assert eng.plan == tuned_plan
    for k in range(1, 5):
        for i in range(k):
            eng.submit(images[i % len(images)])
        eng.run()
    assert eng.step_cache_size == 1
    packed_b = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(7)))
    eng.swap_packed(packed_b)
    rid = eng.submit(images[0])
    out = eng.run()
    assert eng.step_cache_size == 1, "hot-swap must not recompile"
    ref_b = np.asarray(bcnn.forward_packed(
        packed_b, jnp.asarray(images[:1]), plan=_plan(packed_b, "xla")))
    np.testing.assert_array_equal(np.asarray(out[rid]), ref_b[0])
