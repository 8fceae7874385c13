"""Device discipline: refuse anything but a TPU with enough chips, keep
JAX's persistent compilation cache at a fixed path, and report the
device as JAX names it.  (A copy of the program's ``launch/device.py``
rules, kept with the benchmark.)"""
from __future__ import annotations

import os
from typing import Any, Dict, List


def require_tpu(n_chips: int) -> List[Any]:
    """The first ``n_chips`` TPU devices; RuntimeError when JAX's backend
    is not a TPU or has fewer chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's backend is {devices[0].platform!r} "
                           f"({devices[0].device_kind})")
    if len(devices) < n_chips:
        raise RuntimeError(f"the cell needs {n_chips} TPU chips, JAX found "
                           f"{len(devices)}")
    return devices[:n_chips]


def use_compile_cache(root: str) -> str:
    """Persistent compilation cache in ``$JAX_COMPILATION_CACHE_DIR`` or
    ``<root>/.jax_cache``; every program is cached, however fast its
    compile, so a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def record(devices) -> Dict[str, Any]:
    import jax

    all_devices = jax.devices()
    return {"platform": all_devices[0].platform,
            "kind": all_devices[0].device_kind,
            "count": len(all_devices),
            "memory_peak_bytes": max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                                     for d in devices)}
