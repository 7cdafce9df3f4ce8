"""The JAX runtime seam every entry point shares: where compiled programs
are cached, and which device the process actually got.

``enable_compile_cache`` is the ONE place a compile-cache directory is
chosen (daemon boot, ``bench.py``, ``benches/*``, ``tests/conftest.py``):
``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself, so
nothing is set here); otherwise the cache lives at ``<checkout>/.jax_cache``
— a fixed path, because the path is part of every entry's key and a
directory that moves never hits.

``describe`` is the device statement the daemon logs at boot and serves
on ``/statusz``: on a box without a chip, ``--backend tpu`` silently runs
XLA on the CPU, so callers that need the chip read the platform from here
rather than trusting the flag.  Importing this module touches no backend.
"""

from __future__ import annotations

import os

#: The repository checkout this package was imported from.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe() -> dict:
    """{platform, kind, count, native} for the devices this process got."""
    import jax

    from .core import _native

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "native": _native.load() is not None,
    }


def memory() -> list[dict]:
    """Per-device allocator bytes (``bytes_in_use`` / ``peak_bytes_in_use``)
    where the backend reports them — which devices actually held arrays.
    Empty on backends without allocator stats (XLA CPU)."""
    import jax

    rows = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats:
            rows.append({
                "id": d.id,
                "bytes_in_use": stats.get("bytes_in_use", 0),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
            })
    return rows
