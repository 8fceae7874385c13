"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module does not touch jax device state.  The dry-run launcher forces 512
host-platform devices before any jax import; smoke tests and benchmarks
see the 1 real CPU device and use make_test_mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices) -> Mesh:
    """``jax.make_mesh`` with Auto axes.  JAX's default became Explicit
    axes, whose shardings join every array type and which
    ``with_sharding_constraint`` refuses; the trainer annotates
    activations through ``distributed.logical.shard`` and lets GSPMD
    propagate, so every mesh here is Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"production mesh needs {n} devices, found {len(devices)} — "
            "launch via repro.launch.dryrun (XLA_FLAGS host device count)"
        )
    return make_mesh(shape, axes, devices[:n])


def make_test_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """Small mesh over whatever devices exist (CPU tests)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"),
                         jax.devices()[: pod * data * model])
    return make_mesh((data, model), ("data", "model"),
                     jax.devices()[: data * model])


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks (roofline targets)."""
    flops_bf16: float      # FLOP/s
    hbm_bytes: float       # bytes of HBM
    hbm_bw: float          # bytes/s
    ici_bw: float          # bytes/s per interconnect link


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bytes=16e9,
                             hbm_bw=819e9, ici_bw=50e9),
}


def peak_figures(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; a kind missing from the
    table is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
