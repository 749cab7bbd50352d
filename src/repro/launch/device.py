"""Device checks and the persistent compile cache of the entry points.

``python -m repro.launch.serve`` and ``chip_smoke.py`` call these at
start-up; nothing here runs at import, so tests keep JAX's defaults and
the platform they pin.
"""
from __future__ import annotations

import os

import jax

# root of the checkout: src/repro/launch/device.py -> ../../..
REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins: no other directory is set in code.  Otherwise the cache is the
    fixed ``.jax_cache/`` at the root of the checkout — a fixed path,
    since the path is part of what a cache entry is found by.

    Entries are keyed on the programs' metadata too.  The named scopes a
    profiler trace is reduced by are metadata only, so by default a
    program would load an entry compiled from the same instructions with
    other scopes, or none, and its trace would show those."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_tpu(count: int = 1):
    """The first ``count`` TPU devices, or ``SystemExit`` naming what JAX
    found instead.  There is no fallback: a number measured on another
    platform is not a chip number."""
    devices = jax.devices()
    found = devices[0].platform
    if found != "tpu":
        raise SystemExit(
            f"needs a TPU; JAX found platform {found!r} "
            f"({len(devices)} x {devices[0].device_kind})")
    if len(devices) < count:
        raise SystemExit(f"needs {count} TPU chips; JAX found "
                         f"{len(devices)}")
    return devices[:count]


__all__ = ["REPO_ROOT", "enable_compile_cache", "require_tpu"]
