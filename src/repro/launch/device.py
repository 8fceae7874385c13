"""Device discipline for entry points that measure or check on the chip.

Two rules every such entry point follows, in this order:

1. ``require_tpu`` before any JAX work: a run that finds no TPU, or fewer
   chips than it needs, stops here instead of falling back to the CPU
   (and the Pallas interpreter) and exiting 0.
2. ``use_compile_cache``: the persistent compilation cache lives where
   ``JAX_COMPILATION_CACHE_DIR`` says when it is set, and otherwise at
   the fixed path ``<repo>/.jax_cache``.  The path is part of the cache
   key, so it is never a temporary, pid- or time-derived directory.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def require_tpu(n_chips: int) -> List[Any]:
    """The first ``n_chips`` TPU devices; raises ``RuntimeError`` when JAX
    found no TPU or fewer than ``n_chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's default backend is {devices[0].platform!r} "
            f"({devices[0].device_kind})")
    if len(devices) < n_chips:
        raise RuntimeError(
            f"needs {n_chips} TPU chips, JAX found {len(devices)}")
    return devices[:n_chips]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_record() -> Dict[str, Any]:
    """The device as JAX reports it: platform, kind and count."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
