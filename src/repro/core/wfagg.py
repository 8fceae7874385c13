"""WFAgg: the paper's Byzantine-robust aggregation algorithm (Section IV).

Components (each maps to a paper algorithm):
  wfagg_d_select   Alg. 2 - distance filter around the coordinate-wise median
  wfagg_c_select   Alg. 3 - cosine-similarity filter with norm clipping
  wfagg_t_select   Alg. 4 - temporal EWMA filter over round-to-round metrics
  wfagg_e          Eq. 3  - exponential-smoothing weighted aggregation
  wfagg            Alg. 1 - full pipeline: 3 filters -> tau-weighted scoring
                   (accept needs >= 2 filters) -> WFAgg-E aggregation
  alt_wfagg        paper SsVI-B2 - same scoring, with Multi-Krum as the
                   distance filter and Clustering as the similarity filter

All selectors take ``updates: (K, d)`` and return boolean masks ``(K,)``;
everything is jit/vmap-safe with static K, so the same code runs per-node
in the mode-A DFL engine and (chunked) inside the mode-B multi-pod
training step.

Execution backends (``WFAggConfig.backend``):
  reference  the plain-jnp pipeline above — each filter reads the (K, d)
             candidate matrix again (~7 full passes per aggregation).
             With a ``valid`` mask it runs the valid-aware dynamic-count
             variant (the oracle for irregular/dynamic topologies).
  fused      the Pallas path.  On the gather-free indexed batch entry
             this is the SINGLE-LAUNCH round kernel: one pallas_call
             streams the neighbor blocks, accumulates every filter
             statistic, derives the WFAgg-E trust weights at an
             in-kernel phase boundary (``core.trust``), and writes the
             trust-weighted combine — ~1 candidate pass per round.  On
             single-node / gathered entries it is the stats-kernel +
             host-scoring + combine pipeline (2 passes).
  fused_two_launch
             forces the two-launch shape on the indexed entry as well
             (stats launch, host scoring, combine launch) — the parity
             fallback for validating the single-launch kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import aggregators as agg
from repro.core import trust
from repro.kernels.pairwise_dist.ops import pairwise_gram
from repro.kernels.robust_stats.ops import (
    robust_stats, robust_stats_batch, robust_stats_indexed,
    wfagg_round_indexed)
from repro.kernels.robust_stats.ref import RobustStats, robust_stats_indexed_ref
from repro.kernels.weighted_agg.ops import weighted_agg, weighted_agg_indexed
from repro.obs import profile as obs_profile

Array = jax.Array
_EPS = 1e-12

# Fused execution backends: "fused" routes the gather-free indexed path
# through the SINGLE-LAUNCH round kernel (stats + in-kernel weight
# derivation + combine in one pallas_call); "fused_two_launch" keeps the
# separate stats and combine launches with the scoring stage on the host
# — the parity fallback (and the shape every non-indexed fused entry
# still uses, where no single-launch op exists).
_FUSED_BACKENDS = ("fused", "fused_two_launch")


def f32_dots(fn):
    """Trace ``fn`` with f32 matmul precision.  WFAgg's statistics,
    trust scores and combines are f32 algorithms, and the Pallas kernels
    compute them in f32 on the VPU; XLA's TPU default rounds dot
    operands to bf16, which reorders near-tied candidates in the jnp
    reference and rounds the WFAgg-T history when it is re-keyed.  The
    dots here are O(K)-wide, so f32 costs little."""
    @functools.wraps(fn)
    def traced_in_f32(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)
    return traced_in_f32


@dataclasses.dataclass(frozen=True)
class WFAggConfig:
    """Hyper-parameters (defaults = paper Section V-A)."""

    f: int = 2                  # estimated number of malicious candidates
    tau1: float = 0.4           # weight of the distance filter (WFAgg-D)
    tau2: float = 0.4           # weight of the similarity filter (WFAgg-C)
    tau3: float = 0.2           # weight of the temporal filter (WFAgg-T)
    alpha: float = 0.8          # WFAgg-E smoothing factor
    window: int = 3             # W - temporal window length
    transient: int = 3          # T_th - rounds before WFAgg-T activates
    ewma_decay: float = 0.5     # lambda of the exponentially weighted window
    use_temporal: bool = True   # disable to drop the (K, d) prev-update state
    # Alt-WFAgg: swap in SOTA filters of the same family.
    distance_filter: str = "wfagg_d"     # or "multi_krum"
    similarity_filter: str = "wfagg_c"   # or "clustering"
    multi_krum_m: Optional[int] = None   # Multi-Krum m (default K//4)
    # Execution backend: "fused" (Pallas filter bank; the gather-free
    # indexed batch runs the SINGLE-LAUNCH round kernel),
    # "fused_two_launch" (separate stats + combine launches — parity
    # fallback), or "reference" (plain-jnp multi-pass pipeline).  Same
    # masks/aggregate up to float tolerance; see memory_passes().
    backend: str = "fused"
    # Non-finite payload sanitizer (chaos transport, dfl/faults.py): a
    # NaN/Inf candidate row is zeroed and its edges demoted to invalid
    # BEFORE any filter statistic on every backend — the indexed
    # kernel's median/mean must never see a NaN (0 * NaN = NaN would
    # otherwise leak through even a zero combine weight).  A no-op on
    # finite inputs (bit-exact), so it defaults on.
    sanitize: bool = True

    @property
    def accept_threshold(self) -> float:
        """A model must be accepted by >= 2 filters (Alg. 1 line 19)."""
        pairs = (self.tau1 + self.tau2, self.tau1 + self.tau3, self.tau2 + self.tau3)
        return min(pairs)


class TemporalState(NamedTuple):
    """Per-receiving-node WFAgg-T state (Alg. 4).

    Each node stores only the last model per neighbor plus a ring buffer of
    the last W distance/cosine metrics (paper: 'Each node only needs to
    store the history of the distance metrics and only the last model sent
    by each neighboring node').
    """

    prev: Array      # (K, d)  last update from each neighbor
    hist_s: Array    # (W, K)  ring buffer of squared-distance metrics
    hist_b: Array    # (W, K)  ring buffer of cosine-distance metrics
    count: Array     # ()      number of metric rounds recorded so far
    t: Array         # ()      current round index


def init_temporal_state(K: int, d: int, window: int, dtype=jnp.float32) -> TemporalState:
    return TemporalState(
        prev=jnp.zeros((K, d), dtype=dtype),
        hist_s=jnp.zeros((window, K), dtype=jnp.float32),
        hist_b=jnp.zeros((window, K), dtype=jnp.float32),
        count=jnp.zeros((), dtype=jnp.int32),
        t=jnp.zeros((), dtype=jnp.int32),
    )


# ---------------------------------------------------------------------------
# Filters
# ---------------------------------------------------------------------------

def wfagg_d_select(updates: Array, f: int) -> Array:
    """Alg. 2: keep the K-f-1 candidates closest (L2) to the median model."""
    K = updates.shape[0]
    med = agg.coordinate_median(updates)
    d2 = jnp.sum((updates - med[None, :]) ** 2, axis=-1)
    return agg.smallest_k_mask(d2, K - int(f) - 1)


def wfagg_c_stats(updates: Array) -> Tuple[Array, Array]:
    """Cosine distances of norm-clipped candidates to the median model.

    Returns (alpha_j (K,), clipped updates (K, d)).  Note that positive
    rescaling cannot change a cosine, so clipping affects downstream
    magnitude only — selection matches the paper's Alg. 3 either way.
    """
    med = agg.coordinate_median(updates)
    norms = jnp.linalg.norm(updates, axis=-1)
    tau_med = jnp.median(norms)
    scale = jnp.minimum(1.0, tau_med / jnp.maximum(norms, _EPS))
    clipped = updates * scale[:, None]
    med_n = jnp.linalg.norm(med)
    cnorms = jnp.linalg.norm(clipped, axis=-1)
    cos = (clipped @ med) / jnp.maximum(cnorms * med_n, _EPS)
    return 1.0 - cos, clipped


def wfagg_c_select(updates: Array, f: int) -> Array:
    """Alg. 3: keep the K-f-1 candidates with smallest cosine distance."""
    K = updates.shape[0]
    alpha_j, _ = wfagg_c_stats(updates)
    return agg.smallest_k_mask(alpha_j, K - int(f) - 1)


# EWMA over a (W, K) ring buffer — single-sourced in core.trust (the
# single-launch kernel's band precomputation shares it).
_ewma_mean_std = trust.ewma_mean_std


def wfagg_t_decide(hist_s: Array, hist_b: Array, count: Array, t: Array,
                   s_t: Array, b_t: Array, cfg: WFAggConfig):
    """Alg. 4 decision core on precomputed round-over-round metrics.

    Factored out so callers that compute s_t/b_t elsewhere (the sharded
    per-leaf aggregation path computes them exactly from each worker's own
    previous gradient) share the EWMA thresholds and ring-buffer update.
    Returns (mask, hist_s', hist_b', count', t')."""
    mu_d, sd_d = _ewma_mean_std(hist_s, count, cfg.ewma_decay)
    mu_c, sd_c = _ewma_mean_std(hist_b, count, cfg.ewma_decay)

    in_d = (s_t >= mu_d - sd_d) & (s_t <= mu_d + sd_d)
    in_c = (b_t >= mu_c - sd_c) & (b_t <= mu_c + sd_c)
    active = (t > cfg.transient) & (count > 0)
    mask = jnp.where(active, in_d & in_c, jnp.zeros_like(in_d))
    return (mask, *trust.push_history(hist_s, hist_b, count, t, s_t, b_t))


def wfagg_t_select(state: TemporalState, updates: Array, cfg: WFAggConfig) -> Tuple[Array, TemporalState]:
    """Alg. 4: flag updates whose round-over-round change is abrupt.

    Returns (mask, new_state).  During the transient (t <= T_th) no model is
    classified benign by this filter (T3 = empty set), but metric history is
    still accumulated so the window is warm when the filter activates.
    """
    prev = state.prev
    # Both backends share this elementwise metric pass: standalone WFAgg-T
    # is already single-pass in jnp, so launching the robust_stats kernel
    # here would pay the sorting network for outputs nobody reads.  The
    # fused gain for the temporal metrics comes from the FULL wfagg
    # pipeline, where _wfagg_fused folds them into the shared kernel pass.
    s_t = jnp.sum((updates - prev) ** 2, axis=-1)
    num = jnp.sum(updates * prev, axis=-1)
    den = jnp.maximum(
        jnp.linalg.norm(updates, axis=-1) * jnp.linalg.norm(prev, axis=-1), _EPS
    )
    b_t = 1.0 - num / den

    mask, hist_s, hist_b, count, t = wfagg_t_decide(
        state.hist_s, state.hist_b, state.count, state.t, s_t, b_t, cfg)
    new_state = TemporalState(prev=updates, hist_s=hist_s, hist_b=hist_b,
                              count=count, t=t)
    return mask, new_state


# ---------------------------------------------------------------------------
# Scoring + aggregation
# ---------------------------------------------------------------------------

# Alg. 1 lines 9-22 scoring — single-sourced in core.trust so the
# in-kernel weight derivation of the single-launch round runs it too.
wfagg_scores = trust.wfagg_scores


def wfagg_e(local: Array, updates: Array, weights: Array, alpha: float) -> Array:
    """Eq. 3: theta_i <- (1-a)*theta_i + a * sum_j w'_ij theta_j.

    If every neighbor was rejected (sum w = 0) the node keeps its local
    model (the neighbor term vanishes rather than dividing by zero).
    """
    wsum = weights.sum()
    w_norm = weights / jnp.maximum(wsum, _EPS)
    neighbor = jnp.einsum("k,kd->d", w_norm, updates)
    eff_alpha = jnp.where(wsum > 0, alpha, 0.0)
    return (1.0 - eff_alpha) * local + eff_alpha * neighbor


def _distance_mask(updates: Array, cfg: WFAggConfig) -> Array:
    if cfg.distance_filter == "wfagg_d":
        return wfagg_d_select(updates, cfg.f)
    if cfg.distance_filter == "multi_krum":
        K = updates.shape[0]
        m = cfg.multi_krum_m or max(1, K // 4)
        scores = agg.krum_scores(updates, cfg.f)
        return agg.smallest_k_mask(scores, m)
    raise ValueError(f"unknown distance filter {cfg.distance_filter!r}")


def _similarity_mask(updates: Array, cfg: WFAggConfig) -> Array:
    if cfg.similarity_filter == "wfagg_c":
        return wfagg_c_select(updates, cfg.f)
    if cfg.similarity_filter == "clustering":
        return agg.clustering_select(updates)
    raise ValueError(f"unknown similarity filter {cfg.similarity_filter!r}")


# ---------------------------------------------------------------------------
# fused backend: one-pass filter bank on the robust_stats Pallas kernel
# ---------------------------------------------------------------------------
# The mask derivations live in ``core.trust`` — pure O(K)/O(K^2) logic on
# the kernel's sufficient statistics, shared verbatim with the in-kernel
# phase boundary of the single-launch round (the aliases keep this
# module's historical private names working).

_sq_dists_from_gram = trust.sq_dists_from_gram
_cosine_dist_from_gram = trust.cosine_dist_from_gram
_fused_distance_mask = trust.fused_distance_mask
_fused_similarity_mask = trust.fused_similarity_mask
_fused_distance_mask_valid = trust.fused_distance_mask_valid
_fused_similarity_mask_valid = trust.fused_similarity_mask_valid
_needs_gram = trust.needs_gram


def _wfagg_fused(
    local: Array,
    updates: Array,
    state: Optional[TemporalState],
    cfg: WFAggConfig,
) -> Tuple[Array, Optional[TemporalState], dict]:
    """Single-node fused WFAgg: every filter statistic from ONE read of the
    candidates (robust_stats kernel; + the pairwise Gram kernel when the
    Alt-WFAgg filters need the (K, K) distances), one more read for the
    fused WFAgg-E combine."""
    temporal = cfg.use_temporal and state is not None
    prev = state.prev if temporal else None
    # need_center=False: the filter bank consumes only the O(K)
    # accumulators, so the kernel skips its (D,)-sized median/trim writes
    stats = robust_stats(updates, prev=prev, need_center=False)
    gram = pairwise_gram(updates)[0] if _needs_gram(cfg) else None
    mask_d = _fused_distance_mask(stats, gram, cfg)
    mask_c = _fused_similarity_mask(stats, gram, cfg)
    if temporal:
        mask_t, hist_s, hist_b, count, t = wfagg_t_decide(
            state.hist_s, state.hist_b, state.count, state.t,
            stats.prev_dist2, stats.cosine_to_prev(), cfg)
        new_state = TemporalState(prev=updates, hist_s=hist_s, hist_b=hist_b,
                                  count=count, t=t)
    else:
        mask_t = jnp.zeros((updates.shape[0],), dtype=bool)
        new_state = state
    weights = wfagg_scores(mask_d, mask_c, mask_t, cfg)
    out = weighted_agg(local, updates, weights, alpha=cfg.alpha)
    info = {
        "mask_d": mask_d,
        "mask_c": mask_c,
        "mask_t": mask_t,
        "weights": weights,
        "n_accepted": (weights > 0).sum(),
    }
    return out, new_state, info


def wfagg(
    local: Array,
    updates: Array,
    state: Optional[TemporalState],
    cfg: WFAggConfig,
) -> Tuple[Array, Optional[TemporalState], dict]:
    """Full WFAgg (Alg. 1).  Returns (aggregated, new_state, info)."""
    if cfg.backend in _FUSED_BACKENDS:
        # single-node calls have no single-launch variant — both fused
        # flavors run the stats-kernel + host-scoring + combine pipeline
        return _wfagg_fused(local, updates, state, cfg)
    if cfg.backend != "reference":
        raise ValueError(f"unknown backend {cfg.backend!r}")
    mask_d = _distance_mask(updates, cfg)
    mask_c = _similarity_mask(updates, cfg)
    if cfg.use_temporal and state is not None:
        mask_t, new_state = wfagg_t_select(state, updates, cfg)
    else:
        mask_t = jnp.zeros((updates.shape[0],), dtype=bool)
        new_state = state
    weights = wfagg_scores(mask_d, mask_c, mask_t, cfg)
    out = wfagg_e(local, updates, weights, cfg.alpha)
    info = {
        "mask_d": mask_d,
        "mask_c": mask_c,
        "mask_t": mask_t,
        "weights": weights,
        "n_accepted": (weights > 0).sum(),
    }
    return out, new_state, info


@f32_dots
def wfagg_batch(
    local: Array,
    updates: Array,
    state: Optional[TemporalState],
    cfg: WFAggConfig,
    neighbor_idx: Optional[Array] = None,
    valid: Optional[Array] = None,
    prev_idx: Optional[Array] = None,
) -> Tuple[Array, Optional[TemporalState], dict]:
    """Batched full WFAgg over all N receiving nodes of a gossip round.

    ``local (N, d)``, ``updates (N, K, d)``, ``state`` with a leading N
    axis on every leaf.  The fused backend runs ONE robust_stats kernel
    launch with a 2-D (node, D-block) grid — a vmap of single-node Pallas
    calls would serialize into an outer per-node loop instead — and one
    batched combine; only the O(K)/O(K^2) mask logic is vmapped.  The
    reference backend vmaps the plain-jnp pipeline (same semantics,
    multi-pass traffic).

    Gather-free path: with ``neighbor_idx (N, K)``, ``updates`` is the
    (M, d) MODEL MATRIX instead of a gathered tensor — the fused kernels
    DMA each neighbor's d-blocks straight from it, so the (N, K, d)
    gossip tensor never exists in HBM.  Under the default
    backend="fused" this is ONE single-launch round kernel (stats,
    in-kernel trust weights, WFAgg-E combine — ~1 candidate pass);
    backend="fused_two_launch" keeps the stats + combine launch pair.
    ``valid (N, K)`` marks the real edges of padded irregular topologies
    (None = regular); the temporal ``prev`` state may be per-edge
    (N, K, d) or a previous-round model matrix (M, d) read through the
    same index table (in which case the new state stays a matrix and the
    round is (N, K, d)-free end to end).  ``prev_idx (N, K)`` points the
    matrix-form temporal ``prev`` at rows OTHER than the live neighbor
    table — the chaos transport's staleness re-keying (dfl/faults.py),
    where the payload an edge served last round need not be the row it
    reads this round.
    """
    if neighbor_idx is not None:
        return _wfagg_batch_indexed(local, updates, state, cfg,
                                    neighbor_idx, valid, prev_idx)
    if prev_idx is not None:
        raise ValueError("prev_idx requires neighbor_idx (indexed path)")
    if valid is not None:
        raise ValueError("valid requires neighbor_idx (padded indexed path)")
    if cfg.backend == "reference":
        if state is not None:
            return jax.vmap(lambda l, u, s: wfagg(l, u, s, cfg))(
                local, updates, state)
        out, _, info = jax.vmap(lambda l, u: wfagg(l, u, None, cfg))(
            local, updates)
        return out, None, info
    if cfg.backend not in _FUSED_BACKENDS:
        raise ValueError(f"unknown backend {cfg.backend!r}")

    N, K, _ = updates.shape
    temporal = cfg.use_temporal and state is not None
    prev = state.prev if temporal else None
    stats = robust_stats_batch(updates, prev=prev, need_center=False)
    gram = None
    if _needs_gram(cfg):
        # one extra read of the candidates: batched Gram via the MXU
        gram = jnp.einsum("nkd,njd->nkj", updates, updates,
                          preferred_element_type=jnp.float32)
    if gram is not None:
        mask_d = jax.vmap(lambda s, g: _fused_distance_mask(s, g, cfg))(stats, gram)
        mask_c = jax.vmap(lambda s, g: _fused_similarity_mask(s, g, cfg))(stats, gram)
    else:
        mask_d = jax.vmap(lambda s: _fused_distance_mask(s, None, cfg))(stats)
        mask_c = jax.vmap(lambda s: _fused_similarity_mask(s, None, cfg))(stats)
    if temporal:
        mask_t, hist_s, hist_b, count, t = jax.vmap(
            lambda hs, hb, c, tt, s, b: wfagg_t_decide(hs, hb, c, tt, s, b, cfg)
        )(state.hist_s, state.hist_b, state.count, state.t,
          stats.prev_dist2, stats.cosine_to_prev())
        new_state = TemporalState(prev=updates, hist_s=hist_s, hist_b=hist_b,
                                  count=count, t=t)
    else:
        mask_t = jnp.zeros((N, K), dtype=bool)
        new_state = state
    weights = wfagg_scores(mask_d, mask_c, mask_t, cfg)
    # batched WFAgg-E combine: the second and last (K, d)-sized pass
    out = jax.vmap(lambda l, u, w: wfagg_e(l, u, w, cfg.alpha))(
        local, updates, weights)
    info = {
        "mask_d": mask_d,
        "mask_c": mask_c,
        "mask_t": mask_t,
        "weights": weights,
        "n_accepted": (weights > 0).sum(axis=-1),
    }
    return out, new_state, info


def _indexed_scoring(
    stats: RobustStats,
    valid_b: Array,
    state: Optional[TemporalState],
    cfg: WFAggConfig,
    models: Array,
    neighbor_idx: Array,
) -> Tuple[Array, Array, Array, Array, Optional[TemporalState]]:
    """Host-side scoring stage shared by the two-launch fused path and
    the valid-aware reference oracle: vmapped trust masks, the WFAgg-T
    decision + ring-buffer update, and the tau-weighted scores.  Returns
    (mask_d, mask_c, mask_t, weights, new_state)."""
    N, K = valid_b.shape
    temporal = cfg.use_temporal and state is not None
    matrix_prev = temporal and state.prev.ndim == 2
    gram = stats.gram
    stats = stats._replace(gram=None)  # keep the vmapped mask fns uniform
    if gram is not None:
        mask_d = jax.vmap(lambda s, g, v: _fused_distance_mask_valid(s, g, v, cfg))(
            stats, gram, valid_b)
        mask_c = jax.vmap(lambda s, g, v: _fused_similarity_mask_valid(s, g, v, cfg))(
            stats, gram, valid_b)
    else:
        mask_d = jax.vmap(lambda s, v: _fused_distance_mask_valid(s, None, v, cfg))(
            stats, valid_b)
        mask_c = jax.vmap(lambda s, v: _fused_similarity_mask_valid(s, None, v, cfg))(
            stats, valid_b)
    if temporal:
        mask_t, hist_s, hist_b, count, t = jax.vmap(
            lambda hs, hb, c, tt, s, b: wfagg_t_decide(hs, hb, c, tt, s, b, cfg)
        )(state.hist_s, state.hist_b, state.count, state.t,
          stats.prev_dist2, stats.cosine_to_prev())
        mask_t = mask_t & valid_b
        new_state = TemporalState(
            prev=models if matrix_prev else models[neighbor_idx],
            hist_s=hist_s, hist_b=hist_b, count=count, t=t)
    else:
        mask_t = jnp.zeros((N, K), dtype=bool)
        new_state = state
    weights = wfagg_scores(mask_d, mask_c, mask_t, cfg) * valid_b
    return mask_d, mask_c, mask_t, weights, new_state


def _push_temporal_history(state: TemporalState, prev_new: Array,
                           s_t: Array, b_t: Array) -> TemporalState:
    """Batched WFAgg-T ring-buffer push (the state-update half of
    ``wfagg_t_decide``): the single-launch path takes its masks from the
    kernel, so only the history advance happens on the host."""
    hist_s, hist_b, count, t = jax.vmap(trust.push_history)(
        state.hist_s, state.hist_b, state.count, state.t, s_t, b_t)
    return TemporalState(prev=prev_new, hist_s=hist_s, hist_b=hist_b,
                         count=count, t=t)


def sanitize_round(models: Array, valid_b: Array,
                   state: Optional[TemporalState], cfg: WFAggConfig,
                   neighbor_idx: Array):
    """Zero the non-finite rows of the (M, d) model matrix (and of the
    temporal ``prev``) and demote the edges that read them to invalid,
    before any statistic: a corrupted payload degrades to a rejected
    slot instead of poisoning the median.  Returns ``(models, valid_b,
    state)``; a no-op on finite inputs."""
    with obs_profile.phase("sanitize"):
        finite = jnp.isfinite(models).all(axis=-1)
        models = jnp.where(finite[:, None], models, 0.0)
        valid_b = valid_b & finite[neighbor_idx]
        if cfg.use_temporal and state is not None:
            pf = jnp.isfinite(state.prev).all(axis=-1)
            state = state._replace(prev=jnp.where(pf[..., None], state.prev, 0.0))
    return models, valid_b, state


def _wfagg_batch_indexed(
    local: Array,
    models: Array,
    state: Optional[TemporalState],
    cfg: WFAggConfig,
    neighbor_idx: Array,
    valid: Optional[Array],
    prev_idx: Optional[Array] = None,
) -> Tuple[Array, Optional[TemporalState], dict]:
    """Gather-free batched WFAgg.

    backend="fused" (default): ONE kernel launch per gossip round — the
    round kernel streams neighbor blocks (phase 0), derives the trust
    weights at the in-kernel phase boundary, and writes the WFAgg-E
    combine (phase 1).  backend="fused_two_launch": the previous shape —
    a stats launch, the scoring stage on the host, a combine launch —
    kept as the parity fallback.  backend="reference": pure-jnp oracle;
    with a ``valid`` mask it runs the valid-aware multi-pass pipeline
    (same dynamic keep counts as the fused paths), without one it keeps
    the bit-parity static-count per-node pipeline.

    ``cfg.sanitize`` (default on) zeroes non-finite candidate rows and
    demotes their edges to invalid before ANY statistic, on every
    backend — corrupted payloads degrade to rejected slots instead of
    poisoning the median (a no-op on finite inputs).  On the static
    reference path (``valid=None``, dispatch is trace-time) the zeroed
    row participates as a finite zero candidate instead.
    """
    N, K = neighbor_idx.shape
    valid_b = jnp.ones((N, K), dtype=bool) if valid is None else valid.astype(bool)
    temporal = cfg.use_temporal and state is not None
    matrix_prev = temporal and state.prev.ndim == 2
    if prev_idx is not None and not matrix_prev:
        prev_idx = None        # nothing matrix-formed to re-key
    if cfg.sanitize:
        models, valid_b, state = sanitize_round(models, valid_b, state, cfg,
                                                neighbor_idx)
    prev = state.prev if temporal else None

    if cfg.backend == "reference":
        if valid is not None:
            return _wfagg_batch_indexed_reference_valid(
                local, models, state, cfg, neighbor_idx, valid_b, prev_idx)
        gathered = models[neighbor_idx]
        if state is not None:
            edge_state = (state._replace(prev=state.prev[
                neighbor_idx if prev_idx is None else prev_idx])
                          if matrix_prev else state)
            out, new_state, info = jax.vmap(
                lambda l, u, s: wfagg(l, u, s, cfg))(local, gathered, edge_state)
            if matrix_prev:
                new_state = new_state._replace(prev=models)
            return out, new_state, info
        out, _, info = jax.vmap(lambda l, u: wfagg(l, u, None, cfg))(
            local, gathered)
        return out, None, info

    if cfg.backend == "fused_two_launch":
        # the Alt-WFAgg (K, K) Gram rides along in the SAME kernel pass,
        # accumulated from the resident candidate tile — no extra read
        stats = robust_stats_indexed(
            models, neighbor_idx, valid_b if cfg.sanitize else valid,
            prev=prev, need_gram=_needs_gram(cfg), prev_idx=prev_idx)
        mask_d, mask_c, mask_t, weights, new_state = _indexed_scoring(
            stats, valid_b, state, cfg, models, neighbor_idx)
        # gather-free WFAgg-E combine: neighbor rows DMA'd by the same table
        out = weighted_agg_indexed(local, models, neighbor_idx, weights,
                                   alpha=cfg.alpha)
    elif cfg.backend == "fused":
        # single launch: stats, in-kernel weight derivation AND combine in
        # one pallas_call.  The WFAgg-T EWMA bands are the only O(K)
        # precompute (they need the host-resident metric history); the
        # ring buffers advance afterwards off the kernel's temporal tail.
        tbands = None
        if temporal:
            tbands = jax.vmap(
                lambda hs, hb, c, tt: trust.temporal_bands(hs, hb, c, tt, cfg)
            )(state.hist_s, state.hist_b, state.count, state.t)
        out, weights, mask_d, mask_c, mask_t, stats = wfagg_round_indexed(
            local, models, neighbor_idx,
            valid_b if cfg.sanitize else valid, cfg,
            prev=prev, tbands=tbands, prev_idx=prev_idx)
        new_state = state
        if temporal:
            new_state = _push_temporal_history(
                state, models if matrix_prev else models[neighbor_idx],
                stats.prev_dist2, stats.cosine_to_prev())
    else:
        raise ValueError(f"unknown backend {cfg.backend!r}")

    info = {
        "mask_d": mask_d,
        "mask_c": mask_c,
        "mask_t": mask_t,
        "valid": valid_b,
        "weights": weights,
        "n_accepted": (weights > 0).sum(axis=-1),
    }
    return out, new_state, info


def _wfagg_batch_indexed_reference_valid(
    local: Array,
    models: Array,
    state: Optional[TemporalState],
    cfg: WFAggConfig,
    neighbor_idx: Array,
    valid_b: Array,
    prev_idx: Optional[Array] = None,
) -> Tuple[Array, Optional[TemporalState], dict]:
    """Valid-aware pure-jnp reference pipeline: the oracle for irregular
    and dynamic (padded, possibly degree-0) topologies.

    Statistics come from ``robust_stats_indexed_ref`` (plain gathered
    einsums — no Pallas anywhere), the masks from the same dynamic-count
    trust logic the fused paths use (so selections agree with the kernels
    on the true per-node degree), and the combine is the vmapped Eq. 3.
    Previously this configuration raised NotImplementedError, leaving
    irregular/dynamic runs without a reference to diff against.
    """
    N, K = neighbor_idx.shape
    temporal = cfg.use_temporal and state is not None
    prev = state.prev if temporal else None
    stats = robust_stats_indexed_ref(models, neighbor_idx, valid_b, prev,
                                     need_gram=_needs_gram(cfg),
                                     prev_idx=prev_idx)
    mask_d, mask_c, mask_t, weights, new_state = _indexed_scoring(
        stats, valid_b, state, cfg, models, neighbor_idx)
    gathered = models[neighbor_idx].astype(jnp.float32)
    out = jax.vmap(lambda l, u, w: wfagg_e(l, u, w, cfg.alpha))(
        local, gathered, weights)
    info = {
        "mask_d": mask_d,
        "mask_c": mask_c,
        "mask_t": mask_t,
        "valid": valid_b,
        "weights": weights,
        "n_accepted": (weights > 0).sum(axis=-1),
    }
    return out, new_state, info


@f32_dots
def realign_temporal_history(state: TemporalState,
                             prev_idx: Array, prev_valid: Array,
                             idx: Array, valid: Array) -> TemporalState:
    """Re-key the slot-positional WFAgg-T ring buffers to a new slate.

    ``hist_s``/``hist_b`` are (N, W, K) and keyed by neighbor SLOT; on a
    round-varying topology a neighbor may occupy a different slot than
    last round (padded tables pack valid neighbors as a prefix), so
    without remapping the EWMA thresholds of Alg. 4 would score each
    neighbor against some OTHER neighbor's history — a rejoining
    attacker could inherit a clean record.  This matches slots by
    neighbor IDENTITY: column k_new receives the history of the k_old
    with ``idx[n, k_new] == prev_idx[n, k_old]`` (both slots valid), and
    a neighbor unseen last round starts with a zeroed column — its
    near-degenerate EWMA band makes the temporal filter abstain rather
    than vouch for a stranger.  The (N, d) matrix ``prev`` needs no
    remap (it is indexed by node id, identity-keyed by construction),
    and on a static slate the match is the identity permutation (no-op).
    """
    match = ((idx[:, :, None] == prev_idx[:, None, :])
             & valid.astype(bool)[:, :, None]
             & prev_valid.astype(bool)[:, None, :])   # (N, K_new, K_old)
    m = match.astype(state.hist_s.dtype)
    return state._replace(
        hist_s=jnp.einsum("nkj,nwj->nwk", m, state.hist_s),
        hist_b=jnp.einsum("nkj,nwj->nwk", m, state.hist_b),
    )


def memory_passes(cfg: WFAggConfig, include_gather: bool = False,
                  indexed: bool = False) -> int:
    """Number of (K, d)-sized HBM passes per full-WFAgg aggregation.

    reference: each filter re-reads the candidates — distance filter
    (median sort + distances = 2, or 1 Gram pass for Multi-Krum),
    similarity filter (median + norms/clip + cosine dots = 3, or 1 Gram
    pass for Clustering), temporal metrics (1), WFAgg-E combine (1).
    fused: ONE robust_stats read covers D/C/T statistics, plus the
    combine (+ 1 Gram pass only when an Alt-WFAgg filter needs K x K
    distances).  See kernels/README.md for the accounting.

    ``include_gather`` also counts the gossip-exchange materialization a
    DFL round pays BEFORE aggregating: building the (N, K, d) gathered
    tensor costs one more candidate-sized pass (write ~= read) — unless
    ``indexed`` (the gather-free neighbor-indexed path), which DMAs
    neighbor blocks straight from the (N, d) model matrix and never
    materializes the tensor.  The indexed path also folds the Alt-WFAgg
    (K, K) Gram into the stats pass (accumulated off the resident tile),
    dropping the separate Gram pass as well.

    On the indexed path, backend="fused" is the SINGLE-LAUNCH round
    kernel: stats, in-kernel weight derivation and combine in one
    pallas_call — ~1 candidate pass (the phase-1 combine re-walks the
    neighbor blocks through the same index maps, but those are the tiles
    the stats phase just made resident, so the streamed HBM traffic is
    one candidate read whenever a node's (K, d) slab fits VMEM).
    backend="fused_two_launch" keeps the separate stats + combine
    launches (2 passes) for parity runs.
    """
    t = 1 if cfg.use_temporal else 0
    gather = 1 if (include_gather and not indexed) else 0
    if cfg.backend in _FUSED_BACKENDS:
        if indexed and cfg.backend == "fused":
            return 1 + gather      # single launch: one streamed read
        gram = 1 if (_needs_gram(cfg) and not indexed) else 0
        return 2 + gram + gather
    d_passes = 1 if cfg.distance_filter == "multi_krum" else 2
    c_passes = 1 if cfg.similarity_filter == "clustering" else 3
    return d_passes + c_passes + t + 1 + gather


def alt_wfagg_config(**kw) -> WFAggConfig:
    """Alt-WFAgg (paper SsVI-B2): Multi-Krum + Clustering as the filters."""
    return WFAggConfig(distance_filter="multi_krum", similarity_filter="clustering", **kw)


# Standalone aggregators (Table I columns WFAgg-D / WFAgg-C / WFAgg-E / WFAgg-T)
def wfagg_d_agg(updates: Array, f: int = 2,
                backend: str = "reference") -> Tuple[Array, Array]:
    if backend == "fused":
        stats = robust_stats(updates, need_center=False)
        mask = agg.smallest_k_mask(stats.dist2, updates.shape[0] - int(f) - 1)
    else:
        mask = wfagg_d_select(updates, f)
    return agg.masked_mean(updates, mask), mask


def wfagg_c_agg(updates: Array, f: int = 2,
                backend: str = "reference") -> Tuple[Array, Array]:
    if backend == "fused":
        stats = robust_stats(updates, need_center=False)
        mask = agg.smallest_k_mask(stats.cosine_to_median(),
                                   updates.shape[0] - int(f) - 1)
    else:
        mask = wfagg_c_select(updates, f)
    return agg.masked_mean(updates, mask), mask


def wfagg_e_agg(local: Array, updates: Array, alpha: float = 0.8) -> Array:
    """WFAgg-E alone: uniform weights over all neighbors (no filtering)."""
    K = updates.shape[0]
    return wfagg_e(local, updates, jnp.ones((K,), jnp.float32), alpha)
