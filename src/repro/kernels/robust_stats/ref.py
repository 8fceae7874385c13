"""Pure-jnp oracles for the WFAgg round kernel (kernel.py).

Given a candidate matrix ``updates (K, D)`` computes, in one logical pass:
  med       (D,)  coordinate-wise median (mean of the two middles, K even)
  trim      (D,)  beta-trimmed coordinate-wise mean
  dist2     (K,)  squared L2 distance of each candidate to the median model
  dotmed    (K,)  inner product of each candidate with the median model
  norm2     (K,)  squared L2 norm of each candidate
  mednorm2  ()    squared L2 norm of the median model

and, when the previous-round candidates ``prev (K, D)`` are supplied:
  prev_dist2 (K,) squared L2 distance to the previous update  (WFAgg-T s_t)
  prev_dot   (K,) inner product with the previous update
  prev_norm2 (K,) squared L2 norm of the previous update

These are exactly the sufficient statistics of WFAgg-D (Alg. 2), WFAgg-C
(Alg. 3) and WFAgg-T (Alg. 4) plus the Median / Trimmed-Mean baselines —
one HBM read of the candidate block serves all of them.

The oracles of the round kernel (``kernel.py``): ``robust_stats_ref``
and ``robust_stats_indexed_ref`` (the valid-aware, neighbor-indexed
form) for phase 0's accumulators, ``pairwise_dist_ref`` for its Gram,
and ``weighted_agg_ref`` for phase 1's combine.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array


class RobustStats(NamedTuple):
    med: Array
    trim: Array
    dist2: Array
    dotmed: Array
    norm2: Array
    mednorm2: Array
    # temporal tail — populated only when the kernel was given ``prev``
    prev_dist2: Optional[Array] = None
    prev_dot: Optional[Array] = None
    prev_norm2: Optional[Array] = None
    # (K, K) candidate Gram — populated only on the indexed path with
    # ``need_gram`` (accumulated from the same VMEM-resident tile, so the
    # Alt-WFAgg filters cost no extra candidate pass)
    gram: Optional[Array] = None

    def cosine_to_median(self) -> Array:
        """1 - cos(theta_j, theta_med): the WFAgg-C metric (clip-invariant)."""
        denom = jnp.sqrt(jnp.maximum(self.norm2 * self.mednorm2, 1e-24))
        return 1.0 - self.dotmed / denom

    def cosine_to_prev(self) -> Array:
        """1 - cos(theta_j^t, theta_j^{t-1}): the WFAgg-T b_t metric."""
        denom = jnp.sqrt(jnp.maximum(self.norm2 * self.prev_norm2, 1e-24))
        return 1.0 - self.prev_dot / denom


def trim_count(K: int, beta: float) -> int:
    return int(beta * K)


def robust_stats_indexed_ref(
    models: Array,            # (M, D) model matrix
    neighbor_idx: Array,      # (N, K) rows into models (padded w/ self)
    valid: Optional[Array] = None,   # (N, K) bool; None = all valid
    prev: Optional[Array] = None,    # (N, K, D) per-edge or (M, D) matrix
    need_gram: bool = False,
    prev_idx: Optional[Array] = None,  # (N, K) rows into matrix ``prev``
) -> RobustStats:
    """Oracle for the gather-free kernel (the oracle MAY gather).

    The median is taken over the valid rows only (invalid rows sort to
    +inf; the middle element indices come from the per-node valid count).
    Per-candidate statistics are computed on the raw padded rows — the
    caller masks them with ``valid`` — so every output stays finite.
    ``med``/``trim`` are None: the indexed entry serves the WFAgg filter
    bank, which never reads a d-sized center.
    """
    u = models[neighbor_idx].astype(jnp.float32)     # (N, K, D)
    N, K, _ = u.shape
    if valid is None:
        valid = jnp.ones((N, K), dtype=bool)
    vmask = valid.astype(bool)
    srt = jnp.sort(jnp.where(vmask[..., None], u, jnp.inf), axis=1)
    v = vmask.sum(axis=1)
    lo, hi = (v - 1) // 2, v // 2
    take = lambda j: jnp.take_along_axis(srt, j[:, None, None], axis=1)[:, 0, :]
    med = 0.5 * (take(lo) + take(hi))                # (N, D)
    # degree-0 rows have no valid middle (the take lands on +inf): the
    # empty median is 0, matching the kernel's guard — all stats finite,
    # and the caller's valid mask makes the node keep its local model
    med = jnp.where((v > 0)[:, None], med, 0.0)
    diff = u - med[:, None, :]
    dist2 = jnp.sum(diff * diff, axis=-1)
    dotmed = jnp.einsum("nkd,nd->nk", u, med)
    norm2 = jnp.sum(u * u, axis=-1)
    mednorm2 = jnp.sum(med * med, axis=-1)
    prev_dist2 = prev_dot = prev_norm2 = None
    if prev is not None:
        if prev_idx is not None and prev.ndim != 2:
            raise ValueError("prev_idx requires a matrix-form prev")
        pidx = neighbor_idx if prev_idx is None else prev_idx
        pe = (prev[pidx] if prev.ndim == 2 else prev).astype(jnp.float32)
        dp = u - pe
        prev_dist2 = jnp.sum(dp * dp, axis=-1)
        prev_dot = jnp.sum(u * pe, axis=-1)
        prev_norm2 = jnp.sum(pe * pe, axis=-1)
    gram = jnp.einsum("nkd,njd->nkj", u, u) if need_gram else None
    return RobustStats(None, None, dist2, dotmed, norm2, mednorm2,
                       prev_dist2, prev_dot, prev_norm2, gram)


def robust_stats_ref(updates: Array, beta: float = 0.1,
                     prev: Optional[Array] = None) -> RobustStats:
    K = updates.shape[0]
    srt = jnp.sort(updates, axis=0)
    if K % 2 == 1:
        med = srt[K // 2]
    else:
        med = 0.5 * (srt[K // 2 - 1] + srt[K // 2])
    t = trim_count(K, beta)
    trim = jnp.mean(srt[t : K - t] if t > 0 else srt, axis=0)
    diff = updates - med[None, :]
    dist2 = jnp.sum(diff * diff, axis=-1)
    dotmed = updates @ med
    norm2 = jnp.sum(updates * updates, axis=-1)
    mednorm2 = jnp.sum(med * med)
    prev_dist2 = prev_dot = prev_norm2 = None
    if prev is not None:
        dp = updates - prev
        prev_dist2 = jnp.sum(dp * dp, axis=-1)
        prev_dot = jnp.sum(updates * prev, axis=-1)
        prev_norm2 = jnp.sum(prev * prev, axis=-1)
    return RobustStats(med, trim, dist2, dotmed, norm2, mednorm2,
                       prev_dist2, prev_dot, prev_norm2)


def pairwise_dist_ref(updates: Array) -> Array:
    """(K, D) -> (K, K) squared Euclidean distances."""
    diff = updates[:, None, :] - updates[None, :, :]
    return jnp.sum(diff * diff, axis=-1)


def weighted_agg_ref(local: Array, updates: Array, weights: Array,
                     alpha: float) -> Array:
    """Eq. 3: (1-a)*local + a * sum_j w'_j theta_j with w' normalized.

    If all weights are zero the neighbor term vanishes and the local model
    is returned unchanged.
    """
    wsum = weights.sum()
    w_norm = weights / jnp.maximum(wsum, 1e-12)
    eff_alpha = jnp.where(wsum > 0, alpha, 0.0)
    return (1.0 - eff_alpha) * local + eff_alpha * (w_norm @ updates)
