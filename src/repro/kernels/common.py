"""Shared kernel-launch policy.

Compile for real when the default backend is a TPU, interpret
everywhere else, and let callers still force either mode explicitly;
and the zero padding of the D axis up to a tile multiple.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def default_interpret() -> bool:
    """True when Pallas kernels should run in interpret mode (non-TPU)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an ``interpret`` argument: None means 'pick per backend'."""
    return default_interpret() if interpret is None else bool(interpret)


def pad_d(x: jax.Array, block_d: int) -> jax.Array:
    """Zero-pad the trailing (D) axis up to a multiple of ``block_d`` and
    promote to f32.  Zero padding is exact for the round kernel: a zero
    column has median 0 and contributes nothing to any accumulated
    statistic, distance, dot product, or weighted combine."""
    pad = (-x.shape[-1]) % block_d
    cfgpad = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x.astype(jnp.float32), cfgpad)
