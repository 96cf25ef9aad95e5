"""Which platform this process runs on, decided before jax starts.

Tests and the virtual-mesh dry run need N CPU devices in one process; that
is two environment variables which jax reads when its backend initializes,
so they must be set before the first ``jax.devices()`` / jit. Nothing here
touches jax's config after import: ``JAX_PLATFORMS`` is the whole switch.

A chip belongs to one process at a time. :func:`holds_accelerator` lets the
code that starts children (``net/fleet.py``) refuse to start one that would
need the chip this process already holds, instead of letting it hang.

This module must stay importable without importing jax.
"""

import os
import re
import sys


def pin_cpu(n_devices: int = 8) -> None:
    """Set env so a *not-yet-initialized* jax picks the virtual CPU platform.

    Must run before jax creates its backend. If ``XLA_FLAGS`` already forces a
    host device count, it is raised (never lowered) to ``n_devices``.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    pat = re.compile(r"--xla_force_host_platform_device_count=(\d+)")
    m = pat.search(flags)
    if m:
        count = max(int(m.group(1)), n_devices)
        flags = pat.sub(f"--xla_force_host_platform_device_count={count}", flags)
    else:
        flags = f"{flags} --xla_force_host_platform_device_count={n_devices}".strip()
    os.environ["XLA_FLAGS"] = flags


def cpu_requested() -> bool:
    """True when ``JAX_PLATFORMS`` explicitly names the CPU — the one signal
    under which a measurement path may run without an accelerator."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def holds_accelerator() -> bool:
    """True when this process has initialized a non-CPU jax backend (and so
    owns the chip). Never initializes a backend itself."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return False
    return jax.default_backend() != "cpu"
