"""What a device-owning process does before and after it takes the chip: place
the persistent compile cache, and report the device it got.

Only processes that own a device call into here (backend subprocesses, the
multi-host worker, in-process bench modes, tools/profile_*). The control plane
reads the report a backend returns (`Status.device_json`); it never imports
JAX itself (system/capabilities.py says why).
"""
from __future__ import annotations

import functools
import os
import sys

# one fixed, git-ignored directory inside the checkout: the path is part of
# the cache key, so a directory that moves (mkdtemp, pid, timestamp) never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Give JAX's persistent compilation cache a home before first device
    use; returns the directory in effect.

    Where JAX_COMPILATION_CACHE_DIR is set, whoever launched us placed the
    cache: JAX reads the variable itself and nothing here touches it. Where
    it is not, the cache goes to COMPILE_CACHE_DIR — exported through the
    same variable so child processes share it without importing JAX here."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
    if "jax" in sys.modules:
        # jax read its flags at import, before the export above
        sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                         COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


@functools.lru_cache(maxsize=1)
def _versions() -> dict:
    import importlib.metadata

    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def device_report() -> dict:
    """The accelerator as JAX reports it to THIS process, with per-device
    memory — the facts a caller needs to know it is not being served from a
    CPU fallback or from the first chip only. Initializes the device client;
    backend-process only."""
    import jax

    devs = jax.devices()
    per_device = []
    for d in devs:
        stats = d.memory_stats() or {}
        per_device.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "devices": per_device,
        **_versions(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
