"""WFAgg trust-weight derivation from O(K) sufficient statistics.

This is the scoring stage of WFAgg (Alg. 1 lines 9-22 + the valid-aware
filter masks) factored out of ``core.wfagg``.  It runs in two places:

  * on the host, between the round kernel's statistics and combine
    phases run as two launches (the d-sharded round,
    ``distributed.spmd``) and in the valid-aware reference
    (``core.wfagg._indexed_scoring``), vmapped over the N receiving
    nodes, through the sort-based ``fused_*_mask_valid`` selections;
  * INSIDE the single-launch round kernel
    (``kernels.robust_stats.kernel._wfagg_round_indexed_kernel``), at the
    phase boundary, on the VMEM-resident ``(1, K)`` accumulators of one
    node — ``derive_trust_weights``, written on 2-D tiles Mosaic lowers,
    with the same selections.

Everything here is O(K)/O(K^2) plain-jnp logic on tiny arrays; the only
import from the kernels package is the ``RobustStats`` container (pure
data, no Pallas), so the kernel body can import this module without a
cycle.  The WFAgg-T thresholds are NOT derived here — the EWMA bands
depend on the (W, K) metric history, which lives outside the kernel, so
callers precompute them with ``temporal_bands`` and the decision reduces
to four compares against the kernel's own temporal statistics
(bit-identical to ``wfagg_t_decide``'s in-band test).

``cfg`` arguments are duck-typed ``core.wfagg.WFAggConfig`` instances,
and ``stats`` arguments are duck-typed ``kernels.robust_stats.ref.
RobustStats`` containers (read-only attribute access) — this module
imports from NEITHER package, which is what keeps it importable from
both sides (``core.wfagg`` and the kernel body) without a cycle.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import aggregators as agg

Array = jax.Array
RobustStats = Any   # duck-typed: .dist2/.norm2/.cosine_to_median()/...
_EPS = 1e-12


# ---------------------------------------------------------------------------
# scoring + EWMA primitives (moved from core.wfagg; re-exported there)
# ---------------------------------------------------------------------------

def wfagg_scores(mask_d: Array, mask_c: Array, mask_t: Array, cfg) -> Array:
    """Alg. 1 lines 9-22: tau-weighted filter votes with a 2-filter floor."""
    w = (
        cfg.tau1 * mask_d.astype(jnp.float32)
        + cfg.tau2 * mask_c.astype(jnp.float32)
        + cfg.tau3 * mask_t.astype(jnp.float32)
    )
    return jnp.where(w < cfg.accept_threshold - 1e-9, 0.0, w)


def ewma_mean_std(hist: Array, count: Array, decay: float) -> Tuple[Array, Array]:
    """Exponentially weighted mean/std over a ring buffer hist (W, K).

    hist[0] is the most recent entry.  Entries beyond ``count`` are masked.
    """
    W = hist.shape[0]
    ages = jnp.arange(W, dtype=jnp.float32)
    valid = ages < count.astype(jnp.float32)
    w = jnp.where(valid, decay ** ages, 0.0)
    w = w / jnp.maximum(w.sum(), _EPS)
    mu = jnp.einsum("w,wk->k", w, hist)
    var = jnp.einsum("w,wk->k", w, (hist - mu[None, :]) ** 2)
    return mu, jnp.sqrt(jnp.maximum(var, 0.0))


def push_history(hist_s: Array, hist_b: Array, count: Array, t: Array,
                 s_t: Array, b_t: Array) -> Tuple[Array, Array, Array, Array]:
    """WFAgg-T ring-buffer advance (most recent at index 0, count capped
    at the window) — the state half of the Alg. 4 decision, single-
    sourced so every backend updates the history identically."""
    hist_s = jnp.roll(hist_s, 1, axis=0).at[0].set(s_t)
    hist_b = jnp.roll(hist_b, 1, axis=0).at[0].set(b_t)
    return hist_s, hist_b, jnp.minimum(count + 1, hist_s.shape[0]), t + 1


def temporal_bands(hist_s: Array, hist_b: Array, count: Array, t: Array,
                   cfg) -> Array:
    """Precompute the WFAgg-T acceptance bands as a FLAT (4K,) vector
    ``[lo_d | hi_d | lo_c | hi_c]`` (the kernel reshapes to (4, K)).

    Encodes ``wfagg_t_decide``'s whole decision: a candidate passes iff
    ``lo_d <= s_t <= hi_d`` and ``lo_c <= b_t <= hi_c``.  The transient /
    empty-history gate folds into the bands themselves — inactive rounds
    get ``(+inf, -inf)`` bands no finite metric can satisfy — so the
    in-kernel test is four compares with no extra flag input.  The band
    edges are the exact ``mu -/+ sd`` expressions of the decision core,
    so masks agree bit-for-bit with the host path.  (Flat rather than
    (4, K): the vmapped per-node bands must not materialize any 3-D
    O(K)-sized buffer — the round's (N, K, d)-free HLO assertions grep
    by rank, and K can collide with the literal 4.)
    """
    mu_d, sd_d = ewma_mean_std(hist_s, count, cfg.ewma_decay)
    mu_c, sd_c = ewma_mean_std(hist_b, count, cfg.ewma_decay)
    active = (t > cfg.transient) & (count > 0)
    inf = jnp.float32(jnp.inf)
    return jnp.concatenate([
        jnp.where(active, mu_d - sd_d, inf),
        jnp.where(active, mu_d + sd_d, -inf),
        jnp.where(active, mu_c - sd_c, inf),
        jnp.where(active, mu_c + sd_c, -inf),
    ])


# ---------------------------------------------------------------------------
# Gram expansions
# ---------------------------------------------------------------------------

def sq_dists_from_gram(gram: Array, norm2: Array) -> Array:
    """(K, K) squared distances from a Gram matrix + squared norms
    (``norm2`` as a (K,) vector or a (1, K) row)."""
    row = jnp.reshape(norm2, (1, -1))
    K = gram.shape[-1]
    d2 = row_to_col(row) + row - 2.0 * gram
    d2 = d2 * jnp.where(_eye(K), 0.0, 1.0)
    return jnp.maximum(d2, 0.0)


def cosine_dist_from_gram(gram: Array, norm2: Array) -> Array:
    """(K, K) cosine distance matrix from a Gram matrix + squared norms
    (``norm2`` as a (K,) vector or a (1, K) row)."""
    n = jnp.sqrt(jnp.maximum(jnp.reshape(norm2, (1, -1)), _EPS))
    return 1.0 - gram / jnp.maximum(row_to_col(n) * n, _EPS)


def needs_gram(cfg) -> bool:
    """True when an Alt-WFAgg filter consumes the (K, K) candidate Gram."""
    return cfg.distance_filter == "multi_krum" or cfg.similarity_filter == "clustering"


# ---------------------------------------------------------------------------
# filter masks from sufficient statistics (single node, (K,)-shaped)
# ---------------------------------------------------------------------------

def fused_distance_mask(stats: RobustStats, gram: Optional[Array],
                        cfg) -> Array:
    K = stats.dist2.shape[-1]
    if cfg.distance_filter == "wfagg_d":
        return agg.smallest_k_mask(stats.dist2, K - int(cfg.f) - 1)
    if cfg.distance_filter == "multi_krum":
        scores = agg.krum_scores_from_sq_dists(
            sq_dists_from_gram(gram, stats.norm2), cfg.f)
        m = cfg.multi_krum_m or max(1, K // 4)
        return agg.smallest_k_mask(scores, m)
    raise ValueError(f"unknown distance filter {cfg.distance_filter!r}")


def fused_similarity_mask(stats: RobustStats, gram: Optional[Array],
                          cfg) -> Array:
    K = stats.dist2.shape[-1]
    if cfg.similarity_filter == "wfagg_c":
        # cosine to the median model is invariant to the norm clipping of
        # Alg. 3, so the fused filter ranks the kernel's dot/norm stats
        # directly — same selection as wfagg_c_select.
        return agg.smallest_k_mask(stats.cosine_to_median(), K - int(cfg.f) - 1)
    if cfg.similarity_filter == "clustering":
        return agg.clustering_select_from_dist(
            cosine_dist_from_gram(gram, stats.norm2))
    raise ValueError(f"unknown similarity filter {cfg.similarity_filter!r}")


def fused_distance_mask_valid(stats: RobustStats, gram: Optional[Array],
                              valid: Array, cfg) -> Array:
    """Valid-aware distance mask for one node of a padded (irregular)
    slate: keep counts scale with the node's TRUE degree v (traced), and
    padded slots score +inf so they can never be selected.  Bit-identical
    to ``fused_distance_mask`` when every slot is valid."""
    K = stats.dist2.shape[-1]
    v = valid.sum()
    if cfg.distance_filter == "wfagg_d":
        scores = jnp.where(valid, stats.dist2, jnp.inf)
        return agg.smallest_k_mask_dyn(scores, v - int(cfg.f) - 1)
    if cfg.distance_filter == "multi_krum":
        d2 = sq_dists_from_gram(gram, stats.norm2)
        vpair = valid[:, None] & valid[None, :]
        scores = agg.krum_scores_from_sq_dists_dyn(
            jnp.where(vpair, d2, jnp.inf), cfg.f, v)
        m = cfg.multi_krum_m or max(1, K // 4)
        return agg.smallest_k_mask_dyn(
            jnp.where(valid, scores, jnp.inf), jnp.minimum(m, v))
    raise ValueError(f"unknown distance filter {cfg.distance_filter!r}")


def fused_similarity_mask_valid(stats: RobustStats, gram: Optional[Array],
                                valid: Array, cfg) -> Array:
    """Valid-aware similarity mask (see ``fused_distance_mask_valid``)."""
    v = valid.sum()
    if cfg.similarity_filter == "wfagg_c":
        scores = jnp.where(valid, stats.cosine_to_median(), jnp.inf)
        return agg.smallest_k_mask_dyn(scores, v - int(cfg.f) - 1)
    if cfg.similarity_filter == "clustering":
        return agg.clustering_select_from_dist_dyn(
            cosine_dist_from_gram(gram, stats.norm2), valid)
    raise ValueError(f"unknown similarity filter {cfg.similarity_filter!r}")


# ---------------------------------------------------------------------------
# the in-kernel scoring stage, on (1, K) rows
# ---------------------------------------------------------------------------
#
# Mosaic lowers neither sort, argsort, top_k nor scatter, and lays out
# 1-D vectors poorly, so the round kernel scores on 2-D tiles only:
# (1, K) rows, (K, 1) columns and (K, K) matrices.  Ranks come from
# pairwise compares, transposes from a masked sum over the identity (one
# nonzero term per sum, so they are exact), and updates from one-hot
# selects.  Each selection equals its sort-based counterpart in
# ``core.aggregators`` (the valid-aware reference oracle): a stable
# ascending order, ties broken by index, NaN last.  Boolean tiles are
# combined with logic ops, never selected between: Mosaic has no select
# on i1 vectors.

def _iota(shape, axis: int) -> Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(K: int) -> Array:
    return _iota((K, K), 0) == _iota((K, K), 1)


def row_to_col(row: Array) -> Array:
    """(1, K) -> (K, 1)."""
    return jnp.sum(jnp.where(_eye(row.shape[-1]), row, jnp.zeros_like(row)),
                   axis=1, keepdims=True)


def col_to_row(col: Array) -> Array:
    """(K, 1) -> (1, K)."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, jnp.zeros_like(col)),
                   axis=0, keepdims=True)


def _sorts_before(a: Array, b: Array, a_first_on_tie: Array) -> Array:
    """``a`` precedes ``b`` in a stable ascending sort with NaN last."""
    a_nan, b_nan = jnp.isnan(a), jnp.isnan(b)
    tie = (a == b) | (a_nan & b_nan)
    return (a < b) | (~a_nan & b_nan) | (tie & a_first_on_tie)


def row_ranks(scores: Array) -> Array:
    """(1, K) -> (1, K) int32 position of each entry in the stable
    ascending order ``jnp.argsort`` gives."""
    K = scores.shape[-1]
    first = _iota((K, K), 0) < _iota((K, K), 1)
    before = _sorts_before(row_to_col(scores), scores, first)
    return jnp.sum(before.astype(jnp.int32), axis=0, keepdims=True)


def smallest_k_row(scores: Array, k: Array) -> Array:
    """(1, K) bool row selecting the ``k`` smallest scores (``k`` traced,
    clamped to [0, K]) — ``agg.smallest_k_mask_dyn`` on a row."""
    return row_ranks(scores) < jnp.clip(k, 0, scores.shape[-1])


def krum_scores_row(d2: Array, f: int, n_valid: Array) -> Array:
    """(K, K) squared distances (+inf on invalid pairs) -> (1, K) Krum
    scores: each candidate's sum over its ``max(1, n_valid - f - 2)``
    closest peers — ``agg.krum_scores_from_sq_dists_dyn`` without the
    sort (entries are summed in index order, not sorted order)."""
    K = d2.shape[0]
    jj = _iota((K, K), 1)
    d2 = d2 + jnp.where(_eye(K), jnp.inf, 0.0)
    rank = jnp.zeros((K, K), jnp.int32)
    for l in range(K):
        col = jnp.sum(jnp.where(jj == l, d2, 0.0), axis=1, keepdims=True)
        rank += _sorts_before(col, d2, l < jj).astype(jnp.int32)
    take = rank < jnp.maximum(n_valid - int(f) - 2, 1)
    return col_to_row(jnp.sum(jnp.where(take, d2, 0.0), axis=1,
                              keepdims=True))


def _first_flat_argmin(x: Array) -> Array:
    """``jnp.argmin`` of a (K, K) tile (row-major, first hit, NaN wins)."""
    K = x.shape[0]
    flat = _iota((K, K), 0) * K + _iota((K, K), 1)
    nan = jnp.isnan(x)
    any_nan = jnp.max(nan.astype(jnp.int32)) > 0
    hit = (any_nan & nan) | (~any_nan & (x == jnp.min(x)))
    return jnp.min(jnp.where(hit, flat, K * K))


def clustering_row(D0: Array, valid: Array) -> Array:
    """(K, K) distances, (1, K) bool valid -> (1, K) bool mask of the
    larger average-linkage cluster of the valid candidates: the
    recurrence of ``agg.clustering_select_from_dist_dyn``, unrolled over
    its K - 2 merges with one-hot updates in place of scatters."""
    K = D0.shape[-1]
    if K <= 2:
        return valid
    eye = _eye(K)
    ii, jj = _iota((K, K), 0), _iota((K, K), 1)
    kio = _iota((1, K), 1)
    sizes = valid.astype(jnp.float32)
    valid_col = row_to_col(sizes) > 0.0
    D = jnp.where(valid_col & valid, D0, jnp.inf)
    n_merge = jnp.sum(valid.astype(jnp.int32)) - 2
    active, assign = valid, kio
    for s in range(K - 2):
        gate = s < n_merge
        active_col = row_to_col(active.astype(jnp.float32)) > 0.0
        flat = _first_flat_argmin(
            jnp.where(active_col & active & ~eye, D, jnp.inf))
        i0, j0 = jax.lax.div(flat, K), jax.lax.rem(flat, K)
        i, j = jnp.minimum(i0, j0), jnp.maximum(i0, j0)
        ni = jnp.sum(jnp.where(kio == i, sizes, 0.0))
        nj = jnp.sum(jnp.where(kio == j, sizes, 0.0))
        row_i = jnp.sum(jnp.where(ii == i, D, 0.0), axis=0, keepdims=True)
        row_j = jnp.sum(jnp.where(ii == j, D, 0.0), axis=0, keepdims=True)
        newrow = (ni * row_i + nj * row_j) / jnp.maximum(ni + nj, 1.0)
        nD = jnp.where(jj == i, row_to_col(newrow),
                       jnp.where(ii == i, newrow, D))
        nsizes = jnp.where(kio == j, 0.0, jnp.where(kio == i, ni + nj, sizes))
        D = jnp.where(gate, nD, D)
        active = active & ~(gate & (kio == j))
        sizes = jnp.where(gate, nsizes, sizes)
        assign = jnp.where(gate & (assign == j), i, assign)
    big = jnp.min(jnp.where(sizes == jnp.max(sizes), kio, K))
    # <= 2 valid candidates: nothing to cluster, accept them all
    return valid & ((n_merge <= 0) | (assign == big))


def _lane_block(row: Array, q: int, K: int) -> Array:
    """``row[:, q*K:(q+1)*K]`` of a (1, 4K) row, as a (1, K) row."""
    pick = _iota((K, row.shape[-1]), 1) == _iota((K, row.shape[-1]), 0) + q * K
    return col_to_row(jnp.sum(jnp.where(pick, row, 0.0), axis=1,
                              keepdims=True))


def derive_trust_weights(
    stats: RobustStats,
    gram: Optional[Array],
    valid: Array,          # (1, K) float32, 1.0 on real edges
    tbands: Optional[Array],   # (1, 4K) from temporal_bands, or None
    cfg,
) -> Tuple[Array, Array, Array, Array]:
    """One node's WFAgg scoring stage at the round kernel's phase
    boundary: (mask_d, mask_c, mask_t, weights), each a (1, K) row.

    ``stats`` holds (1, K) rows and a (1, 1) ``mednorm2``.  The masks
    equal ``fused_distance_mask_valid`` / ``fused_similarity_mask_valid``
    and ``wfagg_t_decide``'s in-band test.  ``weights`` already carries
    the valid mask (padded slots weigh 0), so a degree-0 node scores an
    all-zero vector and the combine falls back to its local model.
    """
    K = valid.shape[-1]
    valid_b = valid > 0.0
    v = jnp.sum(valid_b.astype(jnp.int32))
    keep = v - int(cfg.f) - 1
    if cfg.distance_filter == "wfagg_d":
        mask_d = smallest_k_row(jnp.where(valid_b, stats.dist2, jnp.inf), keep)
    elif cfg.distance_filter == "multi_krum":
        d2 = sq_dists_from_gram(gram, stats.norm2)
        pair = (row_to_col(valid) > 0.0) & valid_b
        scores = krum_scores_row(jnp.where(pair, d2, jnp.inf), cfg.f, v)
        m = cfg.multi_krum_m or max(1, K // 4)
        mask_d = smallest_k_row(jnp.where(valid_b, scores, jnp.inf),
                                jnp.minimum(m, v))
    else:
        raise ValueError(f"unknown distance filter {cfg.distance_filter!r}")
    if cfg.similarity_filter == "wfagg_c":
        mask_c = smallest_k_row(
            jnp.where(valid_b, stats.cosine_to_median(), jnp.inf), keep)
    elif cfg.similarity_filter == "clustering":
        mask_c = clustering_row(cosine_dist_from_gram(gram, stats.norm2),
                                valid_b)
    else:
        raise ValueError(
            f"unknown similarity filter {cfg.similarity_filter!r}")
    if tbands is None:
        mask_t = jnp.zeros_like(valid_b)
    else:
        lo_d, hi_d, lo_c, hi_c = (_lane_block(tbands, q, K) for q in range(4))
        s_t = stats.prev_dist2
        b_t = stats.cosine_to_prev()
        mask_t = ((s_t >= lo_d) & (s_t <= hi_d)
                  & (b_t >= lo_c) & (b_t <= hi_c) & valid_b)
    weights = wfagg_scores(mask_d, mask_c, mask_t, cfg) * valid
    return mask_d, mask_c, mask_t, weights


def combine_coefficients(weights: Array, alpha: float, valid: Array,
                         mean_fallback: bool) -> Tuple[Array, Array]:
    """Normalize trust weights into the WFAgg-E combine coefficients:
    returns ``(alpha_eff * w_norm (K,), 1 - alpha_eff ())`` — what the
    round kernel derives at its phase boundary, and what a caller hands
    its combine phase when that phase runs alone.

    ``mean_fallback=True`` is the mode-B (robust all-reduce) convention:
    when every candidate is rejected the combine degrades to the uniform
    mean of the VALID candidates (there is no meaningful "local" model on
    a gradient all-reduce); False is the DFL/Eq. 3 convention — the node
    keeps its local model.
    """
    wsum = weights.sum()
    w_norm = weights / jnp.maximum(wsum, _EPS)
    if mean_fallback:
        vsum = valid.sum()
        uniform = valid / jnp.maximum(vsum, 1.0)
        w_norm = jnp.where(wsum > 0, w_norm, uniform)
        # an all-invalid (degree-0) slate has no mean to fall back to
        # either: keep the local anchor rather than emitting zeros
        eff_alpha = jnp.where(vsum > 0, alpha, 0.0).astype(jnp.float32)
    else:
        eff_alpha = jnp.where(wsum > 0, alpha, 0.0).astype(jnp.float32)
    return eff_alpha * w_norm, 1.0 - eff_alpha
