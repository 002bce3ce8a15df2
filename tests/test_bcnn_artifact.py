"""Deployment-artifact lifecycle (core/bcnn_artifact.py): bit-exact
save→load roundtrip of the packed BCNN (including the int32 XNOR weight
words in both conv layouts and the static Python leaves), golden-logit
parity of the loaded net, CRC/version/format integrity rejection, and
fold provenance in the manifest."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcnn, bcnn_artifact, packed_model
from repro.core.execution_plan import default_plan


@pytest.fixture(scope="module")
def packed():
    return bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))


@pytest.fixture()
def saved(tmp_path, packed):
    d = str(tmp_path / "art")
    bcnn_artifact.save_packed(d, packed, provenance={"steps": 12,
                                                     "seed": 0})
    return d


def test_roundtrip_bit_exact(saved, packed):
    """Every leaf — arrays (fp, int32 words, bool flips) AND statics —
    comes back identical, so the loaded net is a valid zero-recompile
    ``swap_packed`` payload for an engine built from the original."""
    loaded = bcnn_artifact.load_packed(saved)
    la, _ = jax.tree_util.tree_flatten(loaded, is_leaf=lambda x: x is None)
    pa, _ = jax.tree_util.tree_flatten(packed, is_leaf=lambda x: x is None)
    assert len(la) == len(pa)
    for got, want in zip(la, pa):
        if hasattr(want, "shape"):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            assert got == want and type(got) is type(want)
    # statics must be plain Python values (jit static_argnames contract)
    assert type(loaded.fc3_k) is int
    assert type(loaded.convs[0].k) is int
    # swap-compatibility is the machine-checked version of the same claim
    packed_model.check_swap(packed, loaded)


def test_golden_logit_parity(saved, packed):
    """save → load → forward_packed reproduces the original's logits
    bit-for-bit (identical arrays through the identical eager graph)."""
    x = jnp.asarray(np.random.default_rng(1).random(
        (3, 32, 32, 3)).astype(np.float32))
    loaded = bcnn_artifact.load_packed(saved)
    plan = dataclasses.replace(default_plan(packed), path="xla")
    np.testing.assert_array_equal(
        np.asarray(bcnn.forward_packed(loaded, x, plan=plan)),
        np.asarray(bcnn.forward_packed(packed, x, plan=plan)))


def test_provenance_recorded(saved):
    man = bcnn_artifact.load_manifest(saved)
    prov = man["provenance"]
    assert prov["steps"] == 12 and prov["seed"] == 0    # caller fields
    assert prov["fold"] == "core/bcnn.py::fold_model"   # auto fields
    assert "jax" in prov and "created_unix" in prov


def test_resave_is_lose_nothing(saved, packed):
    """Re-exporting over a live artifact keeps it loadable throughout:
    the new weights land under a fresh name, the manifest rename is the
    commit point, the immediately previous generation survives (for
    readers holding the old manifest), and older ones are GC'd."""
    def weights_files():
        return sorted(f for f in os.listdir(saved)
                      if f.startswith(bcnn_artifact.WEIGHTS_PREFIX))

    gen0 = weights_files()
    bcnn_artifact.save_packed(saved, packed, provenance={"steps": 24})
    assert bcnn_artifact.load_manifest(saved)["provenance"]["steps"] == 24
    bcnn_artifact.load_packed(saved)                  # still fully valid
    assert set(gen0) <= set(weights_files())          # previous gen kept
    assert len(weights_files()) == 2
    bcnn_artifact.save_packed(saved, packed, provenance={"steps": 25})
    assert len(weights_files()) == 2                  # oldest collected
    assert not set(gen0) & set(weights_files())
    bcnn_artifact.load_packed(saved)


def test_crc_detects_corruption(saved):
    """A silently altered weight array must be caught before serving."""
    wpath = os.path.join(
        saved, bcnn_artifact.load_manifest(saved)["weights_file"])
    with np.load(wpath) as npz:
        arrays = dict(npz)
    key = "fc3_w_words"
    arrays[key] = arrays[key].copy()
    arrays[key].flat[0] ^= 1                    # one flipped bit
    with open(wpath, "wb") as f:
        np.savez(f, **arrays)
    with pytest.raises(bcnn_artifact.ArtifactError, match="CRC"):
        bcnn_artifact.load_packed(saved)


def test_version_and_format_rejected(saved):
    mpath = os.path.join(saved, bcnn_artifact.MANIFEST)
    man = json.load(open(mpath))
    man["version"] = bcnn_artifact.VERSION + 1
    json.dump(man, open(mpath, "w"))
    with pytest.raises(bcnn_artifact.ArtifactError, match="version"):
        bcnn_artifact.load_packed(saved)
    man["version"] = bcnn_artifact.VERSION
    man["format"] = "something-else"
    json.dump(man, open(mpath, "w"))
    with pytest.raises(bcnn_artifact.ArtifactError, match="format"):
        bcnn_artifact.load_packed(saved)


def test_pre_tuning_version1_artifact_loads_bit_exact(saved, packed):
    """Backward compat across the tuning-section version bump: an artifact
    written by the version-1 reader (no ``tuning`` section, ``version: 1``
    manifest — pinned here by rewriting the manifest to exactly that
    shape) still loads and serves bit-exact, and ``load_tuning`` reports
    "no tuning" rather than erroring."""
    mpath = os.path.join(saved, bcnn_artifact.MANIFEST)
    man = json.load(open(mpath))
    assert man["version"] == bcnn_artifact.VERSION == 2  # current writer
    man["version"] = 1                       # pin the pre-bump manifest
    man.pop("tuning", None)                  # version 1 never carried one
    json.dump(man, open(mpath, "w"))
    loaded = bcnn_artifact.load_packed(saved)
    x = jnp.asarray(np.random.default_rng(2).random(
        (2, 32, 32, 3)).astype(np.float32))
    plan = dataclasses.replace(default_plan(packed), path="xla")
    np.testing.assert_array_equal(
        np.asarray(bcnn.forward_packed(loaded, x, plan=plan)),
        np.asarray(bcnn.forward_packed(packed, x, plan=plan)))
    assert bcnn_artifact.load_tuning(saved) is None
    # and the version floor still holds below the compat window
    man["version"] = bcnn_artifact.MIN_VERSION - 1
    json.dump(man, open(mpath, "w"))
    with pytest.raises(bcnn_artifact.ArtifactError, match="version"):
        bcnn_artifact.load_packed(saved)


def test_missing_manifest_is_aborted_save(tmp_path):
    d = str(tmp_path / "empty")
    os.makedirs(d)
    with pytest.raises(bcnn_artifact.ArtifactError, match="manifest"):
        bcnn_artifact.load_packed(d)


def test_truncated_manifest_rejected_cleanly(saved):
    """A manifest torn mid-write must raise ArtifactError, not leak a raw
    JSONDecodeError; save_packed's tmp+rename commit makes this state
    unreachable from its own crashes, but disk corruption still happens."""
    mpath = os.path.join(saved, bcnn_artifact.MANIFEST)
    raw = open(mpath).read()
    open(mpath, "w").write(raw[:len(raw) // 2])
    with pytest.raises(bcnn_artifact.ArtifactError, match="manifest"):
        bcnn_artifact.load_packed(saved)
    # and no .tmp litter from the committed save
    assert not [f for f in os.listdir(saved) if f.endswith(".tmp")]
