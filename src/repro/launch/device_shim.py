"""Pre-jax-import simulated-device shim (jax-free on purpose).

Multi-device harnesses on a plain-CPU host need
``--xla_force_host_platform_device_count=N`` in ``XLA_FLAGS``, and XLA
reads that flag once, at backend init — i.e. it must be set BEFORE the
first ``import jax`` anywhere in the process. This module therefore
imports only ``os``/``sys`` so an entry point (``launch/serve_bcnn.py``)
can import it above its jax import and key the decision on raw
``sys.argv``.
"""
from __future__ import annotations

import os
import sys


def force_host_devices(n: int) -> None:
    """Request ``n`` simulated host devices unless the operator already
    pinned a count via ``XLA_FLAGS``. A no-op for ``n <= 1`` — and after
    jax has initialized its backend, setting this has no effect, hence
    the pre-import contract above."""
    if n > 1 and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()


def argv_flag_value(flag: str, argv: list[str] | None = None) -> int:
    """Integer value of ``--flag N`` or ``--flag=N`` in ``argv`` (default
    ``sys.argv``); 0 when absent or non-integer. Raw-argv parsing because
    this runs before argparse (and before jax) can."""
    argv = sys.argv if argv is None else argv
    for i, a in enumerate(argv):
        val = None
        if a == flag and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith(flag + "="):
            val = a.split("=", 1)[1]
        if val is not None:
            try:
                return int(val)
            except ValueError:
                return 0
    return 0
