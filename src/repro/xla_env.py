"""Process-environment helpers for XLA.

Imports nothing from ``jax`` at module level: the XLA client reads
``XLA_FLAGS`` exactly once, at backend initialization, so callers (the
segmentation CLI's ``--shards``, the sharded benchmark's child launch)
must mutate the environment *before* the first ``import jax`` — or build
the environment of a subprocess that hasn't started yet.
"""

from __future__ import annotations

import os
import pathlib
from typing import MutableMapping, Optional

FORCE_HOST_DEVICES_FLAG = "xla_force_host_platform_device_count"

#: JAX's own variable for the persistent compilation cache directory.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: Where the cache lives when that variable is unset: a fixed path (the
#: path is part of the cache key), ignored by git.
DEFAULT_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself; otherwise the cache goes to :data:`DEFAULT_COMPILE_CACHE`.
    Entry points (``chip_smoke.py``, the segment and serve launchers, the
    benchmark runner) call this before their first compile; tests do not.
    """
    path = os.environ.get(COMPILE_CACHE_ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def force_host_device_count(
    n: int, env: Optional[MutableMapping[str, str]] = None
) -> MutableMapping[str, str]:
    """Append ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``
    unless some device-count flag is already present (an explicit user
    setting wins).  Mutates and returns ``env`` (default: ``os.environ``).

    Harmless on accelerator platforms — the flag only multiplies *host*
    (CPU) devices, which is what makes sharded execution testable on a
    laptop (DESIGN.md §11).
    """
    if env is None:
        env = os.environ
    flags = env.get("XLA_FLAGS", "")
    if FORCE_HOST_DEVICES_FLAG not in flags:
        env["XLA_FLAGS"] = f"{flags} --{FORCE_HOST_DEVICES_FLAG}={n}".strip()
    return env


__all__ = [
    "COMPILE_CACHE_ENV",
    "DEFAULT_COMPILE_CACHE",
    "FORCE_HOST_DEVICES_FLAG",
    "force_host_device_count",
    "use_compile_cache",
]
