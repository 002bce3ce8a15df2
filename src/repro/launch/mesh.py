"""Production mesh builders (single-pod 16×16 and multi-pod 2×16×16).

Functions, not module-level constants: importing this module never touches
jax device state (required so smoke tests see 1 CPU device while dryrun.py
sees 512 forced host devices).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pods: int = 0):
    """16×16 single pod, or pods×16×16 (pods=2 is the assignment's
    multi-pod target; pods=4 = 1024 chips exercises the 1000+-node scale
    the capacity-bound cells need — see EXPERIMENTS.md §Dry-run)."""
    if pods == 0:
        pods = 2 if multi_pod else 1
    shape = (pods, 16, 16) if pods > 1 else (16, 16)
    axes = ("pod", "data", "model") if pods > 1 else ("data", "model")
    return _make_mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes: ("pod","data") on multi-pod, ("data",) else."""
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))


def make_local_mesh():
    """1-device mesh with the production axis names (tests/examples)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_data_mesh(n_shards: int, devices=None):
    """(n_shards, 1) mesh over ("data", "model") — the pure data-parallel
    deployment mesh (parallel/bcnn_data_parallel.py). Carrying the trivial
    "model" axis keeps the production axis names, so the sharding helpers
    (parallel/sharding.py: ``dp_axes``/``batch_spec``) apply unchanged.

    ``devices``: explicit device list (first ``n_shards`` are used); default
    ``jax.devices()``.
    """
    if devices is None:
        devices = jax.devices()
    if n_shards > len(devices):
        raise ValueError(f"data mesh needs {n_shards} devices, have "
                         f"{len(devices)}")
    return _make_mesh((n_shards, 1), ("data", "model"),
                      devices=list(devices)[:n_shards])
