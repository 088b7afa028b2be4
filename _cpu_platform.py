"""Host-platform forcing helper for driver/test entry points.

Any process that should run on the host CPU (tests, dryruns, host-only
benches) sets ``JAX_PLATFORMS=cpu`` — and, for mesh tests, the
``--xla_force_host_platform_device_count`` virtual-device flag — before
first backend use. Shared by ``__graft_entry__.py`` and
``tests/conftest.py`` so it lives in exactly one place. Lives at the repo
root (not inside ``mxnet_tpu``) because it must be importable before the
package's heavy ``__init__`` touches jax.
"""
from __future__ import annotations

import os
import re

__all__ = ["force_cpu_platform"]


def force_cpu_platform(num_devices=None):
    """Force jax onto the host CPU platform, optionally with ``num_devices``
    virtual devices (``--xla_force_host_platform_device_count``).

    Safe to call more than once; a no-op (best effort) if a backend was
    already initialized.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if num_devices is not None:
        flag = f"--xla_force_host_platform_device_count={num_devices}"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags)
        else:
            flags = (flags + " " + flag).strip()
        os.environ["XLA_FLAGS"] = flags
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized; use whatever devices exist
