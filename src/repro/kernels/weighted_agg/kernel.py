"""Pallas TPU kernel: fused WFAgg-E consensus combine.

out = lcoef * local + wvec @ updates, blocked over D.  ``wvec`` carries the
already-normalized trust weights scaled by the smoothing factor alpha, and
``lcoef`` = 1 - alpha_eff; both are computed once in ops.py (they are (K,)
and scalar — negligible), so the kernel makes exactly one HBM pass over
the (K, D) candidates fused with the (D,) local model read and (D,) write.
The K-way reduce is a (1, K) x (K, T) matmul -> MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _weighted_agg_kernel(w_ref, lcoef_ref, local_ref, u_ref, out_ref):
    u = u_ref[...].astype(jnp.float32)            # (K, T)
    w = w_ref[...].astype(jnp.float32)            # (1, K)
    lc = lcoef_ref[0, 0]
    acc = jnp.dot(w, u, preferred_element_type=jnp.float32)  # (1, T)
    out_ref[...] = lc * local_ref[...].astype(jnp.float32) + acc


def _weighted_agg_indexed_kernel(idx_ref, w_ref, lcoef_ref, local_ref, u_ref,
                                 out_ref, *, K: int):
    """Gather-free batched combine: grid (node, D block, neighbor slot).
    Each step DMAs one neighbor row block (scalar-prefetch index map) and
    accumulates w[n, k] * models[idx[n, k]] into the revisited (1, T)
    output block, seeding it with lcoef * local at the first slot — the
    (N, K, d) gossip tensor never exists."""
    del idx_ref
    k = pl.program_id(2)
    is_first = k == 0
    u = u_ref[...].astype(jnp.float32)                    # (1, T)
    w = w_ref[...].astype(jnp.float32)                    # (1, K)
    kio = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    wk = jnp.sum(jnp.where(kio == k, w, 0.0))             # scalar w[n, k]

    @pl.when(is_first)
    def _seed():
        out_ref[...] = lcoef_ref[...] * local_ref[...].astype(jnp.float32) + wk * u

    @pl.when(jnp.logical_not(is_first))
    def _accum():
        out_ref[...] += wk * u


def weighted_agg_indexed_pallas(
    wvec: jax.Array,          # (N, K) normalized weights * alpha_eff
    lcoef: jax.Array,         # (N, 1) local coefficient 1 - alpha_eff
    local: jax.Array,         # (N, D)
    models: jax.Array,        # (M, D) model matrix
    neighbor_idx: jax.Array,  # (N, K) rows into models
    *,
    block_d: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns the (N, 1, D) combine.  Every per-node operand rides as a
    (rows, 1, width) view so that each block's trailing dims are whole on
    the sublane axis, as Mosaic requires of one-row blocks."""
    N, K = wvec.shape
    M, D = models.shape
    assert D % block_d == 0
    row = pl.BlockSpec((None, 1, block_d), lambda n, i, k, ir: (n, 0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, D // block_d, K),
        in_specs=[
            pl.BlockSpec((None, 1, K), lambda n, i, k, ir: (n, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda n, i, k, ir: (n, 0, 0)),
            row,
            pl.BlockSpec((None, 1, block_d),
                         lambda n, i, k, ir: (ir[n, k], 0, i)),
        ],
        out_specs=row,
    )
    return pl.pallas_call(
        functools.partial(_weighted_agg_indexed_kernel, K=K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, 1, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(neighbor_idx.astype(jnp.int32), wvec.reshape(N, 1, K),
      lcoef.reshape(N, 1, 1), local.reshape(N, 1, D),
      models.reshape(M, 1, D))


def weighted_agg_pallas(
    wvec: jax.Array,      # (1, K) normalized weights * alpha_eff
    lcoef: jax.Array,     # (1, 1) local coefficient 1 - alpha_eff
    local: jax.Array,     # (1, D)
    updates: jax.Array,   # (K, D)
    *,
    block_d: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    K, D = updates.shape
    assert D % block_d == 0
    grid = (D // block_d,)
    return pl.pallas_call(
        _weighted_agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(wvec, lcoef, local, updates)
