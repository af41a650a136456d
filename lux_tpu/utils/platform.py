"""Backend hygiene: virtual CPU devices and the persistent compile cache.

``JAX_PLATFORMS`` is the one platform knob: tests and CPU runs set it to
``cpu``; on a TPU host JAX picks the chip by default. Nothing here
switches platforms, and nothing falls back to the CPU when the chip
fails to initialize — that error reaches the caller.
"""

from __future__ import annotations

import os
import re

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE = os.path.join(REPO_ROOT, ".bench_cache", "xla_cache")


def virtual_cpu_flags(n_devices: int, xla_flags: str = None) -> str:
    """Return ``xla_flags`` with ``--xla_force_host_platform_device_count``
    guaranteed to be >= ``n_devices`` (existing larger values are kept;
    smaller ones are replaced). Pass the result as the subprocess/env
    XLA_FLAGS, with ``JAX_PLATFORMS=cpu``, before any backend
    initializes."""
    if xla_flags is None:
        xla_flags = os.environ.get("XLA_FLAGS", "")
    pat = r"--xla_force_host_platform_device_count=(\d+)"
    m = re.search(pat, xla_flags)
    if m:
        if int(m.group(1)) >= n_devices:
            return xla_flags
        return re.sub(
            pat, f"--xla_force_host_platform_device_count={n_devices}",
            xla_flags,
        )
    return (
        xla_flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is
    (JAX reads it itself); otherwise the fixed in-checkout
    ``.bench_cache/xla_cache``, shared by bench.py, chip_smoke.py, the
    app CLIs and the server. A later run finds the cache only at the
    same path, so it is never built from a temporary name, a pid or the
    time."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
