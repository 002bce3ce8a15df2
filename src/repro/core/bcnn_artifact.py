"""Versioned on-disk deployment artifact for the packed BCNN.

The paper's life cycle (Fig. 3) is train-with-binary-constraints → fold BN
into eq. 8 thresholds (``core/bcnn.py::fold_model``) → deploy the
bit-packed network. This module is the hand-off point between the two
halves: ``save_packed`` freezes a ``core/bcnn.py::BCNNPacked`` to disk and
``load_packed`` restores it *bit-exactly*, so the serving stack
(``launch/serve_bcnn.py --artifact`` → ``serve/bcnn_engine.py``) runs the
exact net the trainer produced — identical logits, identical eq. 8
comparator decisions.

Artifact layout (one directory):

* a weights npz    — every array leaf of the packed tree (fp conv-1
  weights + BN, int32 XNOR weight words in both conv layouts, float32
  thresholds, bool flip bits), keyed by tree path; a FRESH file name per
  save so re-exports never clobber the live copy.
* a JSON manifest  — atomically renamed into place LAST (the single
  commit point; it records which weights file is live): format name +
  version, per-leaf shape/dtype/CRC32 for arrays, the static Python
  leaves (k / fh / fw / fc3_k / BN eps) by value, the tree structure
  counts, a provenance block (who folded it: train step, seed, jax
  version, caller-supplied fields), and — since version 2 — an optional
  ``tuning`` section: the measured kernel plan from
  ``kernels/autotune.py``, itself versioned and CRC'd, keyed by
  (backend, device kind, model geometry) so a foreign host ignores it.
  Single-writer: concurrent saves into one directory are not coordinated.

Integrity: every array carries a CRC32 verified on load before anything
reaches the engine; version/format mismatches and missing leaves raise
``ArtifactError`` rather than serving garbage. Round-tripping is exact —
``load_packed(save_packed(p)) == p`` leaf-for-leaf including the statics —
so a loaded artifact is also a valid ``BCNNEngine.swap_packed`` payload
for any engine built from the same architecture (zero-recompile hot-swap:
the shapes are the architecture).

Tested by tests/test_bcnn_artifact.py; operator docs in
``docs/TRAINING.md``.
"""
from __future__ import annotations

import json
import os
import time
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bconv, blinear
# _is_weight_array: the SAME leaf predicate the hot-swap path uses
# (split_packed / assert_swap_compatible) — a loaded artifact is documented
# as a valid swap payload, so the two must never diverge
from repro.core.bcnn import BCNNPacked
from repro.core.packed_model import _is_weight_array
from repro.core.crc import crc32_array as _crc
from repro.core.normbinarize import BNParams, NBThreshold

FORMAT = "bcnn-packed"
VERSION = 2                      # 2: optional "tuning" section (autotuner)
MIN_VERSION = 1                  # oldest artifact this reader still loads
TUNING_VERSION = 1               # schema of the "tuning" section itself
MANIFEST = "manifest.json"
WEIGHTS_PREFIX = "weights-"      # one uniquely-named npz per save


class ArtifactError(RuntimeError):
    """Unreadable / corrupt / incompatible deployment artifact."""


def _npz_key(key: str) -> str:
    # '/'-separated tree paths become nested zip members inside an npz;
    # dots keep the archive flat and the mapping obvious
    return key.replace("/", ".")


def _walk(packed: BCNNPacked):
    """Yield (key, leaf) for every leaf of the packed tree, arrays and
    statics alike, in a stable documented order (the manifest schema)."""
    for f in bconv.FpConvParams._fields:
        yield f"conv1/{f}", getattr(packed.conv1, f)
    for i, c in enumerate(packed.convs):
        yield f"convs/{i}/w_words", c.w_words
        yield f"convs/{i}/thr/c", c.thr.c
        yield f"convs/{i}/thr/flip", c.thr.flip
        yield f"convs/{i}/k", c.k
        yield f"convs/{i}/w_words_hw", c.w_words_hw
        yield f"convs/{i}/fh", c.fh
        yield f"convs/{i}/fw", c.fw
    for j, fc in enumerate(packed.fcs):
        yield f"fcs/{j}/w_words", fc.w_words
        yield f"fcs/{j}/thr/c", fc.thr.c
        yield f"fcs/{j}/thr/flip", fc.thr.flip
        yield f"fcs/{j}/k", fc.k
    yield "fc3_w_words", packed.fc3_w_words
    for f in BNParams._fields:
        yield f"fc3_bn/{f}", getattr(packed.fc3_bn, f)
    yield "fc3_k", packed.fc3_k


def _tuning_crc(tuning: dict) -> int:
    """CRC32 over the canonical JSON of the tuning payload — the manifest
    stores it next to the payload so a hand-edited or bit-rotted plan is
    rejected rather than silently steering kernel choices."""
    blob = json.dumps({"key": tuning["key"], "plan": tuning["plan"]},
                      sort_keys=True, separators=(",", ":"))
    return zlib.crc32(blob.encode("utf-8"))


def save_packed(path: str, packed: BCNNPacked, *,
                provenance: dict | None = None,
                tuning: dict | None = None) -> str:
    """Write ``packed`` as a versioned artifact directory at ``path``.

    ``provenance`` — caller-supplied fold provenance (train steps, seed,
    final loss, …) recorded verbatim in the manifest next to the
    auto-collected fields (fold entry point, jax version, creation time).
    ``tuning`` — optional measured kernel plan from
    ``kernels/autotune.py::tuning_section`` (``{"key": ..., "plan": ...}``);
    persisted as a versioned, CRC'd manifest section so the next load on
    the same device kind reuses it without re-measuring
    (``kernels/autotune.py::plan_for_host``).
    Returns the manifest path.

    Commit protocol (lose-nothing, including re-export over a live
    artifact): the arrays land in a *new* uniquely-named npz first; the
    atomic rename of the manifest — which records that npz's name — is
    the single commit point. At every instant the committed manifest
    references a complete weights file, so a crash anywhere leaves either
    the old artifact or the new one, never a torn mix. The immediately
    preceding generation's weights file is retained (a reader holding the
    old manifest can finish loading it); anything older — and aborted
    saves — is garbage-collected by the next successful save.
    """
    os.makedirs(path, exist_ok=True)
    arrays: dict[str, np.ndarray] = {}
    leaves: dict[str, Any] = {}
    for key, leaf in _walk(packed):
        if leaf is None:
            leaves[key] = {"kind": "none"}
        elif _is_weight_array(leaf):
            arr = np.asarray(jax.device_get(leaf))
            arrays[_npz_key(key)] = arr
            leaves[key] = {"kind": "array", "npz": _npz_key(key),
                           "shape": list(arr.shape),
                           "dtype": str(arr.dtype), "crc": _crc(arr)}
        else:
            leaves[key] = {"kind": "static", "value": leaf,
                           "type": type(leaf).__name__}
    weights_file = f"{WEIGHTS_PREFIX}{time.time_ns():016x}.npz"
    manifest = {
        "format": FORMAT, "version": VERSION,
        "weights_file": weights_file,
        "structure": {"n_convs": len(packed.convs),
                      "n_fcs": len(packed.fcs)},
        "leaves": leaves,
        "provenance": {"fold": "core/bcnn.py::fold_model",
                       "jax": jax.__version__,
                       "created_unix": time.time(),
                       **(provenance or {})},
    }
    if tuning is not None:
        manifest["tuning"] = {"tuning_version": TUNING_VERSION,
                              "key": tuning["key"],
                              "plan": tuning["plan"],
                              "crc": _tuning_crc(tuning)}
    # commit protocol (docstring): fresh weights file, then the manifest
    # rename as the single atomic commit point
    mpath = os.path.join(path, MANIFEST)
    prev_weights = None                 # keep one generation back: a
    try:                                # reader that already fetched the
        with open(mpath) as f:          # old manifest can still load it
            prev_weights = json.load(f).get("weights_file")
    except (OSError, json.JSONDecodeError):
        pass
    wpath = os.path.join(path, weights_file)
    with open(wpath, "wb") as f:
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    with open(mpath + ".tmp", "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(mpath + ".tmp", mpath)
    # GC weights files neither the committed manifest nor its predecessor
    # references (older generations, aborted saves)
    for fname in os.listdir(path):
        if fname.startswith(WEIGHTS_PREFIX) and \
                fname not in (weights_file, prev_weights):
            try:
                os.remove(os.path.join(path, fname))
            except OSError:
                pass
    return mpath


def load_manifest(path: str) -> dict:
    """Read + format/version-check the artifact manifest at ``path``."""
    mpath = os.path.join(path, MANIFEST)
    if not os.path.isfile(mpath):
        raise ArtifactError(f"no {MANIFEST} under {path!r} — not an "
                            f"artifact directory (or an aborted save)")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except json.JSONDecodeError as e:
        raise ArtifactError(f"unparseable manifest at {path!r}: {e}")
    if manifest.get("format") != FORMAT:
        raise ArtifactError(f"format {manifest.get('format')!r} != "
                            f"{FORMAT!r} at {path!r}")
    version = manifest.get("version")
    if not isinstance(version, int) or not \
            MIN_VERSION <= version <= VERSION:
        raise ArtifactError(f"unsupported artifact version {version!r} "
                            f"(reader supports {MIN_VERSION}..{VERSION}) "
                            f"at {path!r}")
    return manifest


def load_tuning(path_or_manifest) -> dict | None:
    """Extract the tuning payload ``{"key", "plan"}`` from an artifact.

    Accepts an artifact directory path or an already-loaded manifest dict.
    Returns ``None`` when the artifact predates version 2, carries no
    tuning section, or the section's schema version is newer than this
    reader — absence is normal (the caller falls back to
    ``kernels/autotune.py::plan_for_host`` heuristics). A CRC mismatch,
    by contrast, is corruption and raises ``ArtifactError``.
    """
    manifest = (path_or_manifest if isinstance(path_or_manifest, dict)
                else load_manifest(path_or_manifest))
    tuning = manifest.get("tuning")
    if tuning is None:
        return None
    if tuning.get("tuning_version") != TUNING_VERSION:
        return None                      # newer schema: ignore, don't error
    payload = {"key": tuning.get("key"), "plan": tuning.get("plan")}
    if _tuning_crc(payload) != tuning.get("crc"):
        raise ArtifactError("tuning section CRC mismatch — corrupt or "
                            "hand-edited plan; refusing to use it")
    return payload


def load_packed(path: str) -> BCNNPacked:
    """Restore a ``BCNNPacked`` bit-exactly from an artifact directory.

    Every array leaf's CRC is verified against the manifest before the net
    is assembled; static leaves (k, filter sizes, eps) come back as plain
    Python values so the loaded net jit-compiles identically to the
    freshly-folded one (``core/bcnn.py::make_packed_forward`` contract).
    """
    manifest = load_manifest(path)
    wpath = os.path.join(path, manifest["weights_file"])
    if not os.path.isfile(wpath):
        raise ArtifactError(f"weights file {manifest['weights_file']!r} "
                            f"referenced by the manifest is missing "
                            f"at {path!r}")
    with np.load(wpath) as npz:
        npz_arrays = dict(npz)

    leaves = manifest["leaves"]

    def get(key: str):
        meta = leaves.get(key)
        if meta is None:
            raise ArtifactError(f"leaf {key!r} missing from manifest "
                                f"at {path!r}")
        if meta["kind"] == "none":
            return None
        if meta["kind"] == "static":
            return meta["value"]
        arr = npz_arrays.get(meta["npz"])
        if arr is None:
            raise ArtifactError(
                f"array {key!r} missing from "
                f"{manifest['weights_file']!r} at {path!r}")
        if list(arr.shape) != meta["shape"] or str(arr.dtype) != \
                meta["dtype"]:
            raise ArtifactError(f"array {key!r}: stored "
                                f"{arr.shape}/{arr.dtype} != manifest "
                                f"{meta['shape']}/{meta['dtype']}")
        if _crc(arr) != meta["crc"]:
            raise ArtifactError(f"CRC mismatch for {key!r} at {path!r}")
        return jnp.asarray(arr)

    structure = manifest["structure"]
    conv1 = bconv.FpConvParams(
        **{f: get(f"conv1/{f}") for f in bconv.FpConvParams._fields})
    convs = tuple(
        bconv.BConvPacked(
            w_words=get(f"convs/{i}/w_words"),
            thr=NBThreshold(c=get(f"convs/{i}/thr/c"),
                            flip=get(f"convs/{i}/thr/flip")),
            k=get(f"convs/{i}/k"),
            w_words_hw=get(f"convs/{i}/w_words_hw"),
            fh=get(f"convs/{i}/fh"), fw=get(f"convs/{i}/fw"))
        for i in range(structure["n_convs"]))
    fcs = tuple(
        blinear.BLinearPacked(
            w_words=get(f"fcs/{j}/w_words"),
            thr=NBThreshold(c=get(f"fcs/{j}/thr/c"),
                            flip=get(f"fcs/{j}/thr/flip")),
            k=get(f"fcs/{j}/k"))
        for j in range(structure["n_fcs"]))
    return BCNNPacked(
        conv1=conv1, convs=convs, fcs=fcs,
        fc3_w_words=get("fc3_w_words"),
        fc3_bn=BNParams(**{f: get(f"fc3_bn/{f}")
                           for f in BNParams._fields}),
        fc3_k=get("fc3_k"))
