"""One place for the jax platform/cache bootstrap recipe.

Tests and development run on the CPU: JAX_PLATFORMS=cpu, or
jax.config.update("jax_platforms", "cpu") when jax was imported before
the choice was made (both work until the first backend touch).  XLA_FLAGS,
however, is read exactly once at backend init — the virtual-device count
must be in place before any backend touch.  Used by tests/conftest.py,
bench.py, cmd/*, and __graft_entry__.py so the recipe cannot drift
between them.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def set_host_device_count(n: int) -> None:
    """Ensure XLA_FLAGS requests >= n virtual host devices.

    Must run before the cpu backend initializes.  Replaces an existing
    smaller count rather than silently keeping it.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if m:
        if int(m.group(1)) < n:
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n}", flags)
            os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}={n}").strip()


def force_cpu_mesh(n: int) -> None:
    """Force the cpu platform with n virtual devices + persistent cache.

    Call before any jax backend touch; raises if the backend already
    initialized with fewer devices.
    """
    set_host_device_count(n)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from kubernetes_tpu.utils.compilecache import enable_compile_cache

    jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"need {n} virtual cpu devices, have {have}: the jax cpu backend "
            "was initialized before force_cpu_mesh could set "
            f"{_COUNT_FLAG}={n} (XLA_FLAGS is read once at backend init)"
        )
