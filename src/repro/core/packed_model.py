"""The seam between a packed network and the paths that serve it.

A packed network is a NamedTuple pytree of weight arrays and static Python
values (reduction lengths, filter sizes, strides). The serving paths —
``PackedForward`` below, the data-parallel
``parallel/bcnn_data_parallel.py::ShardedForward`` and
``serve/bcnn_engine.py::BCNNEngine`` (slot ``step`` and bulk
``classify_batch``) — need of it only what ``PackedModel`` states, plus the
two functions that every packed pytree shares: ``split`` (arrays out as jit
arguments, statics closed over) and ``check_swap`` (may ``new`` replace
``old`` under a compiled forward). ``to_wire`` and ``from_wire`` give the
form a bulk batch crosses to the device in (``ShardedForward``). The
Table 2 BCNN (``core/bcnn.py::BCNNPacked``) and Bi-Real Net
(``core/bireal.py::BiRealPacked``) implement it.
"""
from __future__ import annotations

from typing import Protocol

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class PackedModel(Protocol):
    """What a serving path needs of a packed network."""

    @property
    def input_shape(self) -> tuple[int, ...]:
        """One input's shape, (H, W, C)."""

    @property
    def input_dtype(self) -> np.dtype:
        """The dtype inputs cross to the device in."""

    @property
    def n_classes(self) -> int:
        """Width of the logits."""

    def check_plan(self, plan) -> None:
        """Raise ValueError unless ``plan`` (an
        ``core/execution_plan.py::ExecutionPlan``) fits this network."""

    def forward(self, x: jnp.ndarray, plan) -> jnp.ndarray:
        """(N, *input_shape) inputs → (N, n_classes) float32 logits, traced
        under the caller's jit."""


def _is_weight_array(x) -> bool:
    """Array-like packed leaf (vs the static Python ints/floats/None the
    packed NamedTuples also carry: k, filter sizes, strides, BN eps)."""
    return hasattr(x, "shape") and hasattr(x, "dtype")


def split(packed):
    """Split a packed net into (array leaves, rebuild closure).

    A packed net cannot be one jit argument: it mixes arrays with static
    Python ints (k, filter sizes) that jit would trace into abstract values,
    breaking the kernels' ``static_argnames``. This split is the hot-swap
    contract: the *arrays* ride as a flat tuple of jit arguments (so two
    packed nets with identical shapes/dtypes hit the same compiled
    executable — zero recompiles on ``BCNNEngine.swap_packed``), while
    ``rebuild(arrays)`` re-threads them through the static skeleton inside
    the trace.
    """
    leaves, treedef = jax.tree_util.tree_flatten(
        packed, is_leaf=lambda x: x is None)
    mask = tuple(_is_weight_array(l) for l in leaves)
    arrays = tuple(l for l, m in zip(leaves, mask) if m)
    statics = tuple(None if m else l for l, m in zip(leaves, mask))

    def rebuild(arrs):
        it = iter(arrs)
        return jax.tree_util.tree_unflatten(
            treedef, [next(it) if m else s for m, s in zip(mask, statics)])

    return arrays, rebuild


def check_swap(old, new) -> tuple:
    """Validate that ``new`` can hot-swap into a forward built from ``old``
    with ZERO recompiles: identical tree structure, identical statics
    (k/fh/fw/eps), identical array shapes and dtypes. Returns the new
    array-leaf tuple (``split`` order) on success; raises ValueError with
    the first mismatch otherwise."""
    lo, to = jax.tree_util.tree_flatten(old, is_leaf=lambda x: x is None)
    ln, tn = jax.tree_util.tree_flatten(new, is_leaf=lambda x: x is None)
    if to != tn:
        raise ValueError(f"packed tree structure differs: {to} != {tn}")
    for i, (a, b) in enumerate(zip(lo, ln)):
        if _is_weight_array(a) != _is_weight_array(b):
            raise ValueError(f"leaf {i}: array/static kind mismatch "
                             f"({type(a).__name__} vs {type(b).__name__})")
        if _is_weight_array(a):
            if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                raise ValueError(
                    f"leaf {i}: shape/dtype mismatch {a.shape}/{a.dtype} vs "
                    f"{b.shape}/{b.dtype} — a swap must come from the same "
                    f"architecture (fold_model of identically-shaped params)")
        elif a != b:
            raise ValueError(f"leaf {i}: static mismatch {a!r} != {b!r} "
                             f"(k/filter-size/eps must be identical)")
    return tuple(l for l in ln if _is_weight_array(l))


def to_wire(images):
    """(N, *input_shape) images → their wire form, the 2-D array a bulk
    batch crosses to the device in: each image one row of 32-bit words
    (``images.reshape(N, -1)`` viewed as uint32), or, where a row's bytes
    are not a whole number of words, one row in the images' own dtype.

    A 4-D image batch is given a batch-minor layout on the device, which
    the host fills one element at a time; a 2-D array of 32-bit words is
    laid out row-major in tiles of whole rows, which the host fills by tile
    copies. On a C-contiguous NumPy batch the result is a view, no copy;
    on a device array (traced or not) it is the same relayout, done there.
    """
    flat = images.reshape(images.shape[0], -1)
    if flat.dtype.itemsize * flat.shape[1] % 4:
        return flat
    return flat.view(np.uint32)


def from_wire(x: jnp.ndarray, input_shape, input_dtype) -> jnp.ndarray:
    """The wire form back to (n, *input_shape) ``input_dtype`` images, the
    same bytes; traced, as the first operation of a forward."""
    return lax.bitcast_convert_type(x, input_dtype).reshape(
        x.shape[0], *input_shape)


class PackedForward:
    """Self-jitting, hot-swappable single-device packed forward.

    Callable ``(N, *input_shape) → (N, n_classes) float32`` with a
    shape-only jit signature: the weight arrays are passed as jit
    *arguments* (statics closed over via ``split``), so

    * the jit compiles exactly once per input shape (``cache_size()`` — the
      zero-recompile contract ``serve/bcnn_engine.py`` relies on), and
    * ``swap(new_packed)`` replaces the weights under live traffic with no
      recompilation at all: identical shapes/dtypes → same executable.
    """

    def __init__(self, packed: PackedModel, plan):
        packed.check_plan(plan)
        self._packed = packed
        self._plan = plan
        arrays, rebuild = split(packed)
        self._arrays = arrays

        def fwd(arrs, x01: jnp.ndarray) -> jnp.ndarray:
            return rebuild(arrs).forward(x01, plan)

        self._jit = jax.jit(fwd)

    @property
    def packed(self) -> PackedModel:
        """The packed net currently being served."""
        return self._packed

    @property
    def plan(self):
        """The `core/execution_plan.py::ExecutionPlan` closed over the jit."""
        return self._plan

    def __call__(self, x01: jnp.ndarray) -> jnp.ndarray:
        return self._jit(self._arrays, x01)

    def lower(self, x01: jnp.ndarray):
        """The step lowered for ``x01``'s shape, without running it —
        ``.compile().as_text()`` is the HLO the device executes."""
        return self._jit.lower(self._arrays, x01)

    def swap(self, new_packed: PackedModel) -> None:
        """Replace the served weights; zero recompiles (shapes must match,
        checked by ``check_swap``)."""
        self._arrays = check_swap(self._packed, new_packed)
        self._packed = new_packed

    def cache_size(self) -> int:
        """Distinct compilations of the jit'd forward (1 per input shape,
        unchanged by any number of ``swap``s)."""
        return int(self._jit._cache_size())
