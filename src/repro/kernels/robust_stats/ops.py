"""Jitted public wrappers for the WFAgg round kernel.

Each pads D to the tile width (zero padding is exact: a zero column has
median 0 and contributes nothing to any accumulated statistic, the
temporal ones included since ``prev`` is padded with zeros too, nor to
the combine) and returns the same ``RobustStats`` namedtuple as the
oracles in ref.py, with a leading N axis:

  wfagg_round_indexed          both phases — the whole gossip round in
                               one launch, trust weights derived in-kernel
  wfagg_round_indexed_stats    phase 0 alone: the statistics
  wfagg_round_indexed_combine  phase 1 alone: the WFAgg-E combine with
                               caller-given coefficients

The HLO names of their launches all start with ``wfagg_round_indexed``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_d, resolve_interpret
from repro.kernels.robust_stats.kernel import (
    COMBINE,
    STATS,
    round_tile_width,
    wfagg_round_indexed_pallas,
)
from repro.kernels.robust_stats.ref import RobustStats


def _tile(K: int, d: int, has_prev: bool, block_d: Optional[int],
          interpret: Optional[bool]) -> tuple[int, bool]:
    """(tile width, interpret) of a round-kernel launch: ``block_d`` when
    given; else on a TPU ``round_tile_width`` (K, d and a fixed VMEM
    budget), and in interpret mode ONE tile — the interpreter carries
    whole output buffers through every grid step, so fewer steps win."""
    itp = resolve_interpret(interpret)
    if block_d is None:
        block_d = (max(128, -(-d // 128) * 128) if itp
                   else round_tile_width(K, d, has_prev))
    return block_d, itp


@functools.partial(jax.jit, static_argnames=(
    "cfg", "alpha", "mean_fallback", "block_d", "interpret"))
def wfagg_round_indexed(
    local: jax.Array,          # (N, d) combine anchors (local models)
    models: jax.Array,         # (M, d) model matrix
    neighbor_idx: jax.Array,   # (N, K) rows into models
    valid: Optional[jax.Array],    # (N, K); None = all valid
    cfg,                       # WFAggConfig (static; sets the filters)
    prev: Optional[jax.Array] = None,    # (N, K, d) or (M, d) matrix
    tbands: Optional[jax.Array] = None,  # (N, 4, K) WFAgg-T EWMA bands
    prev_idx: Optional[jax.Array] = None,  # (N, K) rows into matrix prev
    alpha: Optional[float] = None,
    mean_fallback: bool = False,
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """One-launch gossip round: both phases of the round kernel.

    A single 3-D (node, phase, D tile) Pallas launch gathers each
    node's K neighbor rows of a (K, T) tile with row DMAs, accumulates
    every filter statistic, derives the trust weights at the in-kernel
    phase boundary (``core.trust.derive_trust_weights`` on the
    VMEM-resident (1, K) accumulators — the Alt-WFAgg Gram included via
    the resident-tile matmul), and writes the trust-weighted combine in
    phase 1, which gathers the tiles again: two reads of the candidates
    and one of ``prev``.  The WFAgg-T EWMA bands are precomputed from
    history by the caller (``core.trust.temporal_bands``) and ride in as
    an O(K) input; the in-kernel temporal decision is a compare against
    the kernel's own prev_dist2 / cosine statistics.

    Returns ``(out (N, d), weights (N, K), mask_d, mask_c, mask_t
    ((N, K) bool), stats)`` where ``stats`` is a ``RobustStats`` with
    (N, K)-shaped accumulators (the caller pushes the WFAgg-T ring
    buffers from its temporal tail).  ``mean_fallback`` selects the
    all-rejected behavior: local model (DFL, Eq. 3) vs uniform valid
    mean (robust all-reduce).

    Tile width T: see ``_tile``.
    """
    from repro.core import trust  # deferred: see kernel.py

    N, K = neighbor_idx.shape
    d = models.shape[-1]
    if tbands is not None and prev is None:
        raise ValueError(
            "tbands requires prev: the in-kernel WFAgg-T band compare "
            "reads the kernel's own prev_dist2/cosine temporal statistics")
    if alpha is None:
        alpha = cfg.alpha
    block_d, itp = _tile(K, d, prev is not None, block_d, interpret)
    m = pad_d(models, block_d)
    loc = pad_d(local, block_d)
    p = pad_d(prev, block_d) if prev is not None else None
    v = (jnp.ones((N, K), jnp.float32) if valid is None
         else valid.astype(jnp.float32))
    # (N, 4, K) bands flatten to 2-D for the launch (no 3-D O(K) buffer
    # may exist — the (N, K, d)-free HLO assertions grep by rank)
    tb = tbands.reshape(N, 4 * K) if tbands is not None else None
    outs = wfagg_round_indexed_pallas(
        loc, m, neighbor_idx, v, cfg, p, tb, prev_idx,
        alpha=float(alpha), mean_fallback=mean_fallback,
        need_gram=trust.needs_gram(cfg), block_d=block_d, interpret=itp)
    out = outs[0][:, 0, :d]
    weights = outs[1][:, 0, :]
    mask_d, mask_c, mask_t = (o[:, 0, :] > 0.0 for o in outs[2:5])
    stats = _round_stats(outs[5:], trust.needs_gram(cfg), prev is not None)
    return out, weights, mask_d, mask_c, mask_t, stats


def _round_stats(accs, need_gram: bool, has_prev: bool) -> RobustStats:
    """The round kernel's accumulator outputs, (dist2, dotmed, norm2,
    mednorm2[, gram][, prev_dist2, prev_dot, prev_norm2]), as a
    ``RobustStats`` of (N, K) rows."""
    dist2, dotmed, norm2, mednorm2 = accs[:4]
    rest = accs[4:]
    gram = None
    if need_gram:
        gram, rest = rest[0], rest[1:]
    tail = (None, None, None)
    if has_prev:
        tail = tuple(o[:, 0, :] for o in rest)
    return RobustStats(
        med=None, trim=None,
        dist2=dist2[:, 0, :], dotmed=dotmed[:, 0, :], norm2=norm2[:, 0, :],
        mednorm2=mednorm2[:, 0, 0],
        prev_dist2=tail[0], prev_dot=tail[1], prev_norm2=tail[2],
        gram=gram,
    )


@functools.partial(jax.jit, static_argnames=(
    "need_gram", "block_d", "interpret"))
def wfagg_round_indexed_stats(
    models: jax.Array,         # (M, d) model matrix
    neighbor_idx: jax.Array,   # (N, K) rows into models
    prev: Optional[jax.Array] = None,    # (N, K, d) or (M, d) matrix
    valid: Optional[jax.Array] = None,   # (N, K); None = all valid
    prev_idx: Optional[jax.Array] = None,  # (N, K) rows into matrix prev
    need_gram: bool = False,
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> RobustStats:
    """Phase 0 of ``wfagg_round_indexed`` alone: the round kernel's
    statistics (the Gram with ``need_gram``, the WFAgg-T tail with
    ``prev``) and no scoring or combine — the oracle is
    ``robust_stats_indexed_ref``.  For a caller that holds a slice of d
    and sums the accumulators across slices before it scores them, or
    that scores on the host.  ``valid``, ``prev`` and ``prev_idx`` mean
    what they mean to ``wfagg_round_indexed``.  The fields carry a
    leading N axis; the tile is the round's too, so a d that
    ``round_padded_width`` rounded needs no padded copy."""
    N, K = neighbor_idx.shape
    block_d, itp = _tile(K, models.shape[-1], prev is not None, block_d,
                         interpret)
    m = pad_d(models, block_d)
    p = pad_d(prev, block_d) if prev is not None else None
    v = (jnp.ones((N, K), jnp.float32) if valid is None
         else valid.astype(jnp.float32))
    outs = wfagg_round_indexed_pallas(
        None, m, neighbor_idx, v, None, p, prev_idx=prev_idx,
        alpha=1.0, need_gram=need_gram, block_d=block_d, interpret=itp,
        phases=STATS)
    return _round_stats(outs, need_gram, prev is not None)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def wfagg_round_indexed_combine(
    local: jax.Array,          # (N, d) combine anchors (local models)
    models: jax.Array,         # (M, d) model matrix
    neighbor_idx: jax.Array,   # (N, K) rows into models
    wcomb: jax.Array,          # (N, K) neighbor coefficients
    lcoef: jax.Array,          # (N,) local coefficients
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Phase 1 of ``wfagg_round_indexed`` alone: ``out_n = lcoef_n *
    local_n + sum_k wcomb_nk * models[neighbor_idx[n, k]]`` in f32, slot
    by slot, the (N, K, d) gossip tensor never built.  The coefficients
    are what ``core.trust.combine_coefficients`` makes of each node's
    trust weights (the oracle is ``weighted_agg_ref``)."""
    N, K = neighbor_idx.shape
    d = models.shape[-1]
    block_d, itp = _tile(K, d, False, block_d, interpret)
    (out,) = wfagg_round_indexed_pallas(
        pad_d(local, block_d), pad_d(models, block_d), neighbor_idx, None,
        None, alpha=1.0, block_d=block_d, interpret=itp, phases=COMBINE,
        coef=(wcomb, lcoef))
    return out[:, 0, :d]
