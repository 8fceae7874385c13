"""Jitted wrappers for the fused WFAgg-E combine kernel (single-node and
the gather-free batched/indexed variant)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_d, resolve_block_d
from repro.kernels.weighted_agg.kernel import (
    weighted_agg_indexed_pallas,
    weighted_agg_pallas,
)
from repro.kernels.weighted_agg.ref import weighted_agg_ref


@functools.partial(jax.jit, static_argnames=("alpha", "block_d", "interpret", "use_kernel"))
def weighted_agg(
    local: jax.Array,
    updates: jax.Array,
    weights: jax.Array,
    alpha: float = 0.8,
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> jax.Array:
    if not use_kernel:
        return weighted_agg_ref(local, updates, weights, alpha)
    K, D = updates.shape
    block_d, interpret = resolve_block_d(D, block_d, interpret)
    wsum = weights.sum()
    w_norm = weights / jnp.maximum(wsum, 1e-12)
    eff_alpha = jnp.where(wsum > 0, alpha, 0.0)
    u = pad_d(updates, block_d)
    loc = pad_d(local, block_d)[None, :]
    out = weighted_agg_pallas(
        (eff_alpha * w_norm)[None, :].astype(jnp.float32),
        jnp.reshape(1.0 - eff_alpha, (1, 1)).astype(jnp.float32),
        loc,
        u,
        block_d=block_d,
        interpret=interpret,
    )
    return out[0, :D]


@functools.partial(jax.jit, static_argnames=("alpha", "block_d", "interpret", "use_kernel"))
def weighted_agg_indexed(
    local: jax.Array,          # (N, d)
    models: jax.Array,         # (M, d) model matrix
    neighbor_idx: jax.Array,   # (N, K) rows into models
    weights: jax.Array,        # (N, K) trust weights (0 on invalid slots)
    alpha: float = 0.8,
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Gather-free batched WFAgg-E combine: out_n = (1 - a_n) local_n +
    a_n * sum_k w'_nk models[idx[n, k]], one kernel launch for all N
    nodes, with the neighbor rows DMA'd straight from the (M, d) model
    matrix.  Nodes whose weights sum to zero keep their local model."""
    wsum = weights.sum(axis=-1)
    w_norm = weights / jnp.maximum(wsum, 1e-12)[:, None]
    eff_alpha = jnp.where(wsum > 0, alpha, 0.0)
    if not use_kernel:
        gathered = models[neighbor_idx].astype(jnp.float32)
        neighbor = jnp.einsum("nk,nkd->nd", w_norm, gathered)
        return (1.0 - eff_alpha)[:, None] * local + eff_alpha[:, None] * neighbor
    N, d = local.shape
    block_d, interpret = resolve_block_d(d, block_d, interpret)
    m = pad_d(models, block_d)
    loc = pad_d(local, block_d)
    out = weighted_agg_indexed_pallas(
        (eff_alpha[:, None] * w_norm).astype(jnp.float32),
        (1.0 - eff_alpha)[:, None].astype(jnp.float32),
        loc,
        m,
        neighbor_idx,
        block_d=block_d,
        interpret=interpret,
    )
    return out[:, 0, :d]
