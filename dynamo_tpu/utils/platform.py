"""Backend/platform forcing.

Tests and multi-chip dry runs execute on an n-device virtual CPU mesh.
JAX reads ``JAX_PLATFORMS`` and ``XLA_FLAGS`` when its backend
initialises, so this helper only has to set both before that happens.
"""

from __future__ import annotations

import os
import re


def force_cpu_devices(n: int) -> None:
    """Force an n-device CPU platform.  Must run before jax initialises a
    backend.  Raises the host-device-count flag if a smaller one is set."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}"
        )
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # jax may already be imported (it snapshots JAX_PLATFORMS at import)
    jax.config.update("jax_platforms", "cpu")
