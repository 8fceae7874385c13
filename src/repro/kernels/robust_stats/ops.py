"""Jitted public wrappers for the fused robust-stats kernel.

Handles D padding to the block size (zero padding is exact: a zero column
has median 0, contributing nothing to any accumulated statistic — and
this extends to the temporal statistics, since ``prev`` is padded with
zeros too) and returns the same ``RobustStats`` namedtuple as the oracle
in ref.py.

``robust_stats`` operates on one (K, D) candidate matrix;
``robust_stats_batch`` runs all N nodes of a gossip round through ONE
kernel launch over the gathered (N, K, D) tensor (2-D grid), instead of
a vmap of single-node calls — vmapping a pallas_call serializes into a
per-node outer loop, while the batched grid streams every node's blocks
through the same kernel instance.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_d, resolve_block_d
from repro.kernels.robust_stats.kernel import (
    robust_stats_batch_pallas,
    robust_stats_indexed_pallas,
    robust_stats_pallas,
    wfagg_round_indexed_pallas,
)
from repro.kernels.robust_stats.ref import (
    RobustStats,
    robust_stats_indexed_ref,
    robust_stats_ref,
    trim_count,
)


@functools.partial(jax.jit, static_argnames=(
    "beta", "block_d", "interpret", "use_kernel", "need_center"))
def robust_stats(
    updates: jax.Array,
    prev: Optional[jax.Array] = None,
    beta: float = 0.1,
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
    need_center: bool = True,
) -> RobustStats:
    """Fused median / trimmed-mean / WFAgg filter statistics over (K, D).

    With ``prev`` (the previous-round candidates), the same single pass
    also emits the WFAgg-T temporal metrics (prev_dist2/prev_dot/
    prev_norm2); without it those fields are None.  ``block_d=None``
    picks a backend-appropriate tile (see auto_block_d).
    ``need_center=False`` skips the streaming (D,)-sized median/trim
    outputs (med/trim come back None) — the WFAgg filter bank consumes
    only the O(K) accumulators, so its fused path writes nothing d-sized.
    """
    if not use_kernel:
        return robust_stats_ref(updates, beta, prev=prev)
    K, D = updates.shape
    n_trim = trim_count(K, beta)
    block_d, itp = resolve_block_d(D, block_d, interpret)
    u = pad_d(updates, block_d)
    p = pad_d(prev, block_d) if prev is not None else None
    outs = robust_stats_pallas(
        u, p, n_trim=n_trim, block_d=block_d, interpret=itp,
        emit_center=need_center,
    )
    if need_center:
        med, trim = outs[0][0, :D], outs[1][0, :D]
        outs = outs[2:]
    else:
        med = trim = None
    dist2, dotmed, norm2, mednorm2 = outs[:4]
    tail = (None, None, None)
    if prev is not None:
        tail = tuple(o[0] for o in outs[4:])
    return RobustStats(
        med=med,
        trim=trim,
        dist2=dist2[0],
        dotmed=dotmed[0],
        norm2=norm2[0],
        mednorm2=mednorm2[0, 0],
        prev_dist2=tail[0],
        prev_dot=tail[1],
        prev_norm2=tail[2],
    )


@functools.partial(jax.jit, static_argnames=(
    "block_d", "interpret", "use_kernel", "need_gram"))
def robust_stats_indexed(
    models: jax.Array,
    neighbor_idx: jax.Array,
    valid: Optional[jax.Array] = None,
    prev: Optional[jax.Array] = None,
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
    need_gram: bool = False,
    prev_idx: Optional[jax.Array] = None,
) -> RobustStats:
    """Gather-free batched statistics: ``models (M, d)`` + ``neighbor_idx
    (N, K)`` replace the gathered (N, K, d) tensor — the kernel DMAs each
    neighbor's d-block straight from the model matrix (scalar-prefetch
    index map), so the K-fold gossip tensor never exists in HBM.

    ``valid (N, K)`` marks real edges on irregular (padded) topologies:
    the in-kernel median spans only valid rows; per-candidate stats of
    padded slots are finite garbage the caller masks out.  ``prev`` may
    be per-edge (N, K, d) or a previous-round model matrix (M, d) read
    through the same index table.  Output layout matches
    ``robust_stats_batch`` (leading N axis; med/trim are None — the
    filter bank never reads a d-sized center).  ``need_gram`` also emits
    the per-node (K, K) candidate Gram, accumulated from the SAME
    resident tile — no extra pass, and nothing quadratic in the total
    node count M (the Alt-WFAgg filters consume it).  ``prev_idx (N, K)``
    points matrix-form ``prev`` reads at rows OTHER than the live
    neighbor table — the chaos transport's staleness pricing (see
    dfl/faults.py).
    """
    if not use_kernel:
        return robust_stats_indexed_ref(models, neighbor_idx, valid, prev,
                                        need_gram=need_gram,
                                        prev_idx=prev_idx)
    N, K = neighbor_idx.shape
    block_d, itp = resolve_block_d(models.shape[-1], block_d, interpret)
    m = pad_d(models, block_d)
    p = pad_d(prev, block_d) if prev is not None else None
    v = (jnp.ones((N, K), jnp.float32) if valid is None
         else valid.astype(jnp.float32))
    outs = robust_stats_indexed_pallas(
        m, neighbor_idx, v, p, block_d=block_d, interpret=itp,
        need_gram=need_gram, prev_idx=prev_idx)
    dist2, dotmed, norm2, mednorm2 = outs[:4]
    rest = outs[4:]
    gram = None
    if need_gram:
        gram, rest = rest[0], rest[1:]
    tail = (None, None, None)
    if prev is not None:
        tail = tuple(o[:, 0, :] for o in rest)
    return RobustStats(
        med=None,
        trim=None,
        dist2=dist2[:, 0, :],
        dotmed=dotmed[:, 0, :],
        norm2=norm2[:, 0, :],
        mednorm2=mednorm2[:, 0, 0],
        prev_dist2=tail[0],
        prev_dot=tail[1],
        prev_norm2=tail[2],
        gram=gram,
    )


@functools.partial(jax.jit, static_argnames=(
    "beta", "block_d", "interpret", "use_kernel", "need_center"))
def robust_stats_batch(
    updates: jax.Array,
    prev: Optional[jax.Array] = None,
    beta: float = 0.1,
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
    need_center: bool = True,
) -> RobustStats:
    """Batched fused statistics over (N, K, D): one kernel launch for all
    N per-node aggregations.  Every ``RobustStats`` field gains a leading
    N axis (``mednorm2`` becomes (N,))."""
    if not use_kernel:
        return jax.vmap(lambda u, p: robust_stats_ref(u, beta, prev=p))(
            updates, prev
        ) if prev is not None else jax.vmap(
            lambda u: robust_stats_ref(u, beta))(updates)
    N, K, D = updates.shape
    n_trim = trim_count(K, beta)
    block_d, itp = resolve_block_d(D, block_d, interpret)
    u = pad_d(updates, block_d)
    p = pad_d(prev, block_d) if prev is not None else None
    outs = robust_stats_batch_pallas(
        u, p, n_trim=n_trim, block_d=block_d, interpret=itp,
        emit_center=need_center,
    )
    if need_center:
        med, trim = outs[0][:, 0, :D], outs[1][:, 0, :D]
        outs = outs[2:]
    else:
        med = trim = None
    dist2, dotmed, norm2, mednorm2 = outs[:4]
    tail = (None, None, None)
    if prev is not None:
        tail = tuple(o[:, 0, :] for o in outs[4:])
    return RobustStats(
        med=med,
        trim=trim,
        dist2=dist2[:, 0, :],
        dotmed=dotmed[:, 0, :],
        norm2=norm2[:, 0, :],
        mednorm2=mednorm2[:, 0, 0],
        prev_dist2=tail[0],
        prev_dot=tail[1],
        prev_norm2=tail[2],
    )


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
    """One-launch gossip round: the fused WFAgg-E combine folded into the
    indexed robust_stats kernel (ROADMAP's "2 passes -> ~1").

    A single 4-D (node, phase, D block, slot) Pallas launch streams the
    neighbor blocks, accumulates every filter statistic, derives the
    trust weights at the in-kernel phase boundary
    (``core.trust.derive_trust_weights`` on the VMEM-resident (1, K)
    accumulators — the Alt-WFAgg Gram included via the resident-tile
    matmul), and writes the trust-weighted combine in phase 1.  The
    WFAgg-T EWMA bands are precomputed from history by the caller
    (``core.trust.temporal_bands``) and ride in as an O(K) input; the
    in-kernel temporal decision is a compare against the kernel's own
    prev_dist2 / cosine statistics.

    Returns ``(out (N, d), weights (N, K), mask_d, mask_c, mask_t
    ((N, K) bool), stats)`` where ``stats`` is a ``RobustStats`` with
    (N, K)-shaped accumulators (the caller pushes the WFAgg-T ring
    buffers from its temporal tail).  ``mean_fallback`` selects the
    all-rejected behavior: local model (DFL, Eq. 3) vs uniform valid
    mean (robust all-reduce).

    Interpret-mode block policy: ONE D block (``interpret_blocks=1``) —
    the interpreter carries the (N, d) combine output through every grid
    step, so fewer/bigger steps beat smaller tiles; compiled TPU keeps
    1024-lane tiles.
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
    block_d, itp = resolve_block_d(d, block_d, interpret, interpret_blocks=1)
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
    dist2, dotmed, norm2, mednorm2 = outs[5:9]
    rest = outs[9:]
    gram = None
    if trust.needs_gram(cfg):
        gram, rest = rest[0], rest[1:]
    tail = (None, None, None)
    if prev is not None:
        tail = tuple(o[:, 0, :] for o in rest)
    stats = RobustStats(
        med=None, trim=None,
        dist2=dist2[:, 0, :], dotmed=dotmed[:, 0, :], norm2=norm2[:, 0, :],
        mednorm2=mednorm2[:, 0, 0],
        prev_dist2=tail[0], prev_dot=tail[1], prev_norm2=tail[2],
        gram=gram,
    )
    return out, weights, mask_d, mask_c, mask_t, stats
