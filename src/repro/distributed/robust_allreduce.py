"""Byzantine-robust all-reduce: WFAgg (and baselines) as a drop-in
replacement for the data-parallel mean-gradient all-reduce (mode B).

Runs INSIDE a partial-manual shard_map region: manual over the candidate
axis/axes (the data-parallel workers = DFL nodes), GSPMD-auto over the
'model' axis (so the flat gradient vector stays tensor-parallel sharded
throughout — no device ever holds a full gradient).

Memory discipline (the production constraint the paper never hits):
K full candidate gradients can NEVER coexist (K x P bytes; 7.5 TB for a
470B model on a 4 TB pod).  So aggregation is two-phase:

  phase 1 (streamed): scan gradient chunks; all-gather each (K, chunk)
          block transiently; accumulate sufficient statistics —
          chunk median -> WFAgg-D distances / WFAgg-C cosines, the
          K x K Gram (Krum / Multi-Krum / Clustering), count-sketches
          (temporal filter).  Transient memory = K x chunk only.
  phase 2 (free):     consensus weights w (identical on every worker)
          -> each worker scales ITS OWN gradient by w[me] and a plain
          psum produces the weighted mean.  No second gather.

Median / Trimmed-Mean baselines are not weighted means of candidates, so
they stream the OUTPUT chunk directly in phase 1 (single pass).

The temporal filter (WFAgg-T) runs on AMS count-sketches of the gradients
(inner-product preserving), so its state is (K, sketch_dim) instead of
(K, P) — this is the beyond-paper change that makes the paper's temporal
statistics affordable at LLM scale.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import aggregators as agg_lib
from repro.core import attacks as atk
from repro.core import trust
from repro.core.wfagg import (
    TemporalState, WFAggConfig, f32_dots, wfagg_scores, wfagg_t_decide,
    wfagg_t_select)
from repro.distributed.logical import current_mesh
from repro.kernels.pairwise_dist.ops import pairwise_gram
from repro.kernels.robust_stats.ops import robust_stats, wfagg_round_indexed
from repro.obs import decision as obs_decision

Array = jax.Array
AxisNames = Union[str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class RobustAggConfig:
    method: str = "wfagg"        # mean | median | trimmed_mean | krum | multi_krum |
                                 # clustering | wfagg | alt_wfagg
    wfagg: WFAggConfig = WFAggConfig()
    trim_beta: float = 0.1
    multi_krum_m: Optional[int] = None
    chunk_size: int = 1 << 22    # coordinates per streamed chunk
    sketch_dim: int = 4096       # AMS count-sketch width (temporal filter)
    seed: int = 0
    # layout of the candidate gradients during aggregation:
    #   flat — ravel to one vector, stream chunks (paper-shaped baseline;
    #          the ravel forces a model-axis all-gather of the FULL
    #          gradient on every worker)
    #   stacked — candidates carry an explicit leading K axis sharded
    #          over the data mesh axes and aggregation runs in pure GSPMD
    #          (no manual collectives, every leaf keeps its TP sharding;
    #          GSPMD reshards K via all-to-all).  The temporal filter
    #          becomes EXACT (each worker stores its own previous
    #          gradient, candidate-sharded) instead of
    #          count-sketch-approximate.
    layout: str = "flat"
    gather_dtype: Optional[str] = None   # e.g. "bfloat16": gather candidates
                                         # in low precision (stats stay f32)
    # statistics backend for layout='stacked': "fused" runs the whole
    # wfagg/alt_wfagg aggregation — statistics, in-kernel trust-weight
    # derivation AND the weighted combine — through ONE single-launch
    # Pallas kernel over the concatenated (K, P) candidates (falls back
    # to the two-launch shape when gather_dtype quantization is on: the
    # temporal metrics must stay full-precision while the D/C stats
    # quantize, which one read cannot provide); "fused_two_launch"
    # forces the separate stats launch + host scoring + jnp combine;
    # "reference" keeps the per-leaf jnp loop.  The fused paths assume
    # the candidates fit one process (mode-A scale / shard_map-manual
    # regions); pure-GSPMD multi-pod sharding of the kernel is an open
    # item.
    backend: str = "reference"

    @property
    def needs_stats(self) -> bool:
        return self.method in ("krum", "multi_krum", "clustering", "wfagg", "alt_wfagg")

    @property
    def streaming_output(self) -> bool:
        return self.method in ("median", "trimmed_mean")


class AggState(NamedTuple):
    """Cross-step state: WFAgg-T temporal statistics over gradient sketches."""

    temporal: TemporalState


def init_agg_state(cfg: RobustAggConfig, n_candidates: int) -> AggState:
    return AggState(
        temporal=TemporalState(
            prev=jnp.zeros((n_candidates, cfg.sketch_dim), jnp.float32),
            hist_s=jnp.zeros((cfg.wfagg.window, n_candidates), jnp.float32),
            hist_b=jnp.zeros((cfg.wfagg.window, n_candidates), jnp.float32),
            count=jnp.zeros((), jnp.int32),
            t=jnp.zeros((), jnp.int32),
        )
    )


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _axes_tuple(axis: AxisNames) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def axis_size(axis: AxisNames) -> int:
    return jax.lax.axis_size(_axes_tuple(axis))


def my_index(axis: AxisNames) -> Array:
    return jax.lax.axis_index(_axes_tuple(axis))


def _whole_on_each_device(fn):
    """``fn`` — a Pallas launch, which GSPMD cannot partition — run
    whole on every device of the active mesh (inputs gathered, outputs
    replicated), or called as is outside a multi-device mesh."""
    mesh = current_mesh()
    if mesh is None or mesh.devices.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                         out_specs=jax.sharding.PartitionSpec(),
                         check_vma=False)


def _pad_chunks(flat: Array, chunk: int) -> Tuple[Array, int]:
    P = flat.shape[0]
    n_chunks = max(1, -(-P // chunk))
    pad = n_chunks * chunk - P
    return jnp.pad(flat, (0, pad)), n_chunks


def _count_sketch(chunk: Array, chunk_idx: Array, m: int, seed: int) -> Array:
    """AMS count-sketch of one chunk: bucket + sign, seeded by chunk index."""
    L = chunk.shape[0]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), chunk_idx)
    kb, ks = jax.random.split(key)
    buckets = jax.random.randint(kb, (L,), 0, m)
    signs = jax.random.rademacher(ks, (L,), jnp.float32)
    return jax.ops.segment_sum(chunk.astype(jnp.float32) * signs, buckets, num_segments=m)


# ---------------------------------------------------------------------------
# phase 1: streamed statistics
# ---------------------------------------------------------------------------

class ChunkStats(NamedTuple):
    dist2_med: Array   # (K,)  sum ||g_j - med||^2
    dot_med: Array     # (K,)  sum <g_j, med>
    med2: Array        # ()    ||med||^2
    gram: Array        # (K,K) candidate Gram matrix
    sketch: Array      # (m,)  local candidate count-sketch


def _stats_scan(flat: Array, axis: AxisNames, cfg: RobustAggConfig) -> ChunkStats:
    axes = _axes_tuple(axis)
    K = axis_size(axis)
    padded, n_chunks = _pad_chunks(flat, cfg.chunk_size)
    chunks = padded.reshape(n_chunks, cfg.chunk_size)

    def body(carry, xs):
        chunk_idx, chunk = xs
        g = jax.lax.all_gather(chunk, axes, tiled=False)     # (K, L) transient
        g = g.reshape(K, -1).astype(jnp.float32)
        med = jnp.median(g, axis=0)
        diff = g - med[None, :]
        st = ChunkStats(
            dist2_med=carry.dist2_med + jnp.sum(diff * diff, axis=1),
            dot_med=carry.dot_med + g @ med,
            med2=carry.med2 + jnp.sum(med * med),
            gram=carry.gram + jnp.dot(g, g.T, preferred_element_type=jnp.float32),
            sketch=carry.sketch + _count_sketch(chunk, chunk_idx, cfg.sketch_dim, cfg.seed),
        )
        return st, None

    init = ChunkStats(
        dist2_med=jnp.zeros((K,), jnp.float32),
        dot_med=jnp.zeros((K,), jnp.float32),
        med2=jnp.zeros((), jnp.float32),
        gram=jnp.zeros((K, K), jnp.float32),
        sketch=jnp.zeros((cfg.sketch_dim,), jnp.float32),
    )
    stats, _ = jax.lax.scan(body, init, (jnp.arange(n_chunks), chunks))
    return stats


def _streaming_coordinate_agg(flat: Array, axis: AxisNames, cfg: RobustAggConfig) -> Array:
    """Median / trimmed-mean aggregation: stream output chunks directly."""
    axes = _axes_tuple(axis)
    K = axis_size(axis)
    padded, n_chunks = _pad_chunks(flat, cfg.chunk_size)
    chunks = padded.reshape(n_chunks, cfg.chunk_size)

    def body(_, chunk):
        g = jax.lax.all_gather(chunk, axes, tiled=False).reshape(K, -1).astype(jnp.float32)
        if cfg.method == "median":
            out = jnp.median(g, axis=0)
        else:
            t = int(cfg.trim_beta * K)
            srt = jnp.sort(g, axis=0)
            out = jnp.mean(srt[t : K - t] if t > 0 else srt, axis=0)
        return None, out.astype(flat.dtype)

    _, outs = jax.lax.scan(body, None, chunks)
    return outs.reshape(-1)[: flat.shape[0]]


# ---------------------------------------------------------------------------
# phase 2: consensus weights from statistics
# ---------------------------------------------------------------------------

def _weights_from_stats(
    stats: ChunkStats,
    sketches: Optional[Array],   # (K, m) gathered candidate sketches
    state: Optional[AggState],
    cfg: RobustAggConfig,
    temporal_mask: Optional[Array] = None,   # tree layout: exact WFAgg-T mask
) -> Tuple[Array, Optional[AggState], Dict[str, Array]]:
    K = stats.dist2_med.shape[0]
    norm2 = jnp.diag(stats.gram)
    info: Dict[str, Array] = {}
    w = cfg.wfagg

    def mask_d() -> Array:
        if cfg.method == "alt_wfagg" or w.distance_filter == "multi_krum":
            scores = _krum_scores_from_gram(stats.gram, w.f)
            # WFAggConfig.multi_krum_m is the filter's own knob (what the
            # mode-A path reads in core.wfagg._distance_mask); the
            # RobustAggConfig field is the standalone-method fallback.
            m = w.multi_krum_m or cfg.multi_krum_m or max(1, K // 4)
            return agg_lib.smallest_k_mask(scores, m)
        return agg_lib.smallest_k_mask(stats.dist2_med, K - w.f - 1)

    def mask_c() -> Array:
        if cfg.method == "alt_wfagg" or w.similarity_filter == "clustering":
            return _clustering_from_gram(stats.gram)
        cos_d = 1.0 - stats.dot_med / jnp.sqrt(jnp.maximum(norm2 * stats.med2, 1e-24))
        return agg_lib.smallest_k_mask(cos_d, K - w.f - 1)

    new_state = state
    if cfg.method in ("wfagg", "alt_wfagg"):
        md, mc = mask_d(), mask_c()
        if temporal_mask is not None:
            mt = temporal_mask
        elif w.use_temporal and state is not None:
            mt, new_t = wfagg_t_select(state.temporal, sketches, w)
            new_state = AggState(temporal=new_t)
        else:
            mt = jnp.zeros((K,), bool)
        weights = wfagg_scores(md, mc, mt, w)
        info.update(mask_d=md, mask_c=mc, mask_t=mt)
        # the flight-recorder decision record (repro.obs): the same
        # packed verdict bitmask mode-A rounds emit, so a mode-B
        # all-reduce is auditable by the same report tooling
        info["record"] = obs_decision.record_from_masks(
            md, mc, mt, jnp.ones(weights.shape, bool), weights)
    elif cfg.method == "krum":
        scores = _krum_scores_from_gram(stats.gram, w.f)
        weights = jax.nn.one_hot(jnp.argmin(scores), K, dtype=jnp.float32)
    elif cfg.method == "multi_krum":
        scores = _krum_scores_from_gram(stats.gram, w.f)
        m = cfg.multi_krum_m or max(1, K // 4)
        weights = agg_lib.smallest_k_mask(scores, m).astype(jnp.float32)
    elif cfg.method == "clustering":
        weights = _clustering_from_gram(stats.gram).astype(jnp.float32)
    elif cfg.method == "mean":
        weights = jnp.ones((K,), jnp.float32)
    else:
        raise ValueError(cfg.method)

    info["weights"] = weights
    info["n_accepted"] = (weights > 0).sum()
    return weights, new_state, info


def _krum_scores_from_gram(gram: Array, f: int) -> Array:
    n = jnp.diag(gram)
    d2 = jnp.maximum(n[:, None] + n[None, :] - 2.0 * gram, 0.0)
    return agg_lib.krum_scores_from_sq_dists(d2, f)


def _clustering_from_gram(gram: Array) -> Array:
    n = jnp.sqrt(jnp.maximum(jnp.diag(gram), 1e-24))
    D0 = 1.0 - gram / (n[:, None] * n[None, :])
    return agg_lib.clustering_select_from_dist(D0)


# ---------------------------------------------------------------------------
# tree layout: per-leaf sharded aggregation (the beyond-paper fast path)
# ---------------------------------------------------------------------------

class TreeAggState(NamedTuple):
    """Cross-step state for layout='tree'.

    ``prev`` holds THIS worker's previous gradient (same pytree as the
    grads, same TP sharding — never gathered), giving the WFAgg-T filter
    exact round-over-round metrics at the cost of one gradient-sized
    buffer per worker instead of the flat layout's (K, sketch_dim)
    approximation.
    """

    prev: Any
    hist_s: Array    # (W, K)
    hist_b: Array    # (W, K)
    count: Array
    t: Array


def init_tree_agg_state(cfg: RobustAggConfig, n_candidates: int, grads_like: Any) -> TreeAggState:
    """``prev`` carries a leading candidate axis (sharded over the data
    axes in the train state, so every worker stores exactly one previous
    gradient — its own)."""
    return TreeAggState(
        prev=jax.tree.map(
            lambda l: jnp.zeros((n_candidates,) + tuple(l.shape), jnp.float32),
            grads_like),
        hist_s=jnp.zeros((cfg.wfagg.window, n_candidates), jnp.float32),
        hist_b=jnp.zeros((cfg.wfagg.window, n_candidates), jnp.float32),
        count=jnp.zeros((), jnp.int32),
        t=jnp.zeros((), jnp.int32),
    )


def _stacked_stats(stacked: Any, cfg: RobustAggConfig) -> ChunkStats:
    """WFAgg/Krum/Clustering statistics over stacked candidates.

    ``stacked`` leaves are (K, *param_shape), candidate axis sharded over
    the data mesh axes, inner dims TP-sharded.  All reductions below are
    plain jnp ops, so GSPMD reshards the candidate axis with an
    all-to-all (wire ~= ONE gradient shard per device, vs the flat
    layout's K-fold gather) and the (K,)/(K,K) statistic partials meet in
    a tiny all-reduce.  No unsharded gradient ever exists.
    """
    leaves = jax.tree.leaves(stacked)
    K = leaves[0].shape[0]
    gd = jnp.dtype(cfg.gather_dtype) if cfg.gather_dtype else None

    dist2 = jnp.zeros((K,), jnp.float32)
    dot_med = jnp.zeros((K,), jnp.float32)
    med2 = jnp.zeros((), jnp.float32)
    gram = jnp.zeros((K, K), jnp.float32)
    for leaf in leaves:
        g = (leaf.astype(gd) if gd is not None else leaf).astype(jnp.float32)
        rest = tuple(range(1, g.ndim))
        med = jnp.median(g, axis=0)
        diff = g - med[None]
        dist2 = dist2 + jnp.sum(diff * diff, axis=rest)
        dot_med = dot_med + jnp.tensordot(g, med, axes=(rest, tuple(range(med.ndim))))
        med2 = med2 + jnp.sum(med * med)
        gram = gram + jnp.tensordot(g, g, axes=(rest, rest))
    return ChunkStats(dist2_med=dist2, dot_med=dot_med, med2=med2, gram=gram,
                      sketch=jnp.zeros((0,), jnp.float32))


def _concat_candidates(tree: Any, dtype=None) -> Array:
    """Flatten a stacked candidate pytree to one (K, P) matrix (fused path)."""
    leaves = jax.tree.leaves(tree)
    K = leaves[0].shape[0]
    parts = [
        (l.astype(dtype) if dtype is not None else l).astype(jnp.float32).reshape(K, -1)
        for l in leaves
    ]
    return jnp.concatenate(parts, axis=1)


def _split_like(flat: Array, stacked: Any) -> Any:
    """Inverse of ``_concat_candidates`` for one aggregated (P,) vector:
    split it back into the stacked pytree's per-candidate leaf shapes
    (each leaf drops its leading K axis) and dtypes."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    out, off = [], 0
    for leaf in leaves:
        shape = leaf.shape[1:]
        n = math.prod(shape)
        out.append(flat[off:off + n].reshape(shape).astype(leaf.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _effective_wfagg_config(cfg: RobustAggConfig, K: int) -> WFAggConfig:
    """Resolve the WFAggConfig the trust-derivation stage should see:
    alt_wfagg swaps in the Multi-Krum/Clustering filters, and the
    Multi-Krum m follows ``_weights_from_stats``'s preference order
    (WFAggConfig.multi_krum_m, then RobustAggConfig's, then K // 4)."""
    w = cfg.wfagg
    if cfg.method == "alt_wfagg":
        w = dataclasses.replace(w, distance_filter="multi_krum",
                                similarity_filter="clustering")
    if w.distance_filter == "multi_krum":
        m = w.multi_krum_m or cfg.multi_krum_m or max(1, K // 4)
        w = dataclasses.replace(w, multi_krum_m=m)
    return w


def _stacked_stats_fused(
    stacked: Any, cfg: RobustAggConfig, prev: Optional[Any] = None,
):
    """One-pass statistics for the stacked layout via the robust_stats
    Pallas kernel: a single read of the concatenated (K, P) candidates
    yields the WFAgg-D/C metrics AND (with ``prev``) the exact WFAgg-T
    round-over-round metrics; the (K, K) Gram comes from the blocked
    pairwise kernel only when a Krum/Clustering-family rule needs it.

    Returns (ChunkStats, RobustStats) — the latter carries the temporal
    tail the caller feeds to wfagg_t_decide.
    """
    gd = jnp.dtype(cfg.gather_dtype) if cfg.gather_dtype else None
    flat = _concat_candidates(stacked, gd)
    pflat = _concat_candidates(prev) if prev is not None else None
    stats = _whole_on_each_device(
        lambda f, p: robust_stats(f, prev=p, need_center=False))(flat, pflat)
    w = cfg.wfagg
    needs_gram = (
        cfg.method in ("krum", "multi_krum", "clustering", "alt_wfagg")
        or w.distance_filter == "multi_krum"
        or w.similarity_filter == "clustering"
    )
    if needs_gram:
        gram, _ = _whole_on_each_device(pairwise_gram)(flat)
    else:
        # _weights_from_stats only reads the diagonal (norm2) in this case
        gram = jnp.diag(stats.norm2)
    chunk = ChunkStats(
        dist2_med=stats.dist2,
        dot_med=stats.dotmed,
        med2=stats.mednorm2,
        gram=gram,
        sketch=jnp.zeros((0,), jnp.float32),
    )
    return chunk, stats


def _stacked_temporal_metrics(stacked: Any, prev: Any) -> Tuple[Array, Array]:
    """Exact per-candidate round-over-round metrics (vectorized over K)."""
    leaves = jax.tree.leaves(stacked)
    K = leaves[0].shape[0]
    s = jnp.zeros((K,), jnp.float32)
    dot = jnp.zeros((K,), jnp.float32)
    n_new = jnp.zeros((K,), jnp.float32)
    n_prev = jnp.zeros((K,), jnp.float32)
    for g, p in zip(leaves, jax.tree.leaves(prev)):
        gf, pf = g.astype(jnp.float32), p.astype(jnp.float32)
        rest = tuple(range(1, gf.ndim))
        s = s + jnp.sum((gf - pf) ** 2, axis=rest)
        dot = dot + jnp.sum(gf * pf, axis=rest)
        n_new = n_new + jnp.sum(gf * gf, axis=rest)
        n_prev = n_prev + jnp.sum(pf * pf, axis=rest)
    b = 1.0 - dot / jnp.maximum(jnp.sqrt(n_new * n_prev), 1e-24)
    return s, b


def apply_stacked_attack(
    stacked: Any,
    malicious: Array,          # (K,) bool
    attack: str,
    key: Array,
    noise_mu: float = 0.1,
    noise_sigma: float = 0.1,
    alie_zmax: float = 0.5,
    prev: Any = None,
) -> Any:
    """Vectorized model-poisoning attacks on stacked candidates (pure
    GSPMD — demo/integration use).  Thin per-leaf wrapper over
    ``core.attacks.apply_matrix_attack`` — the one implementation of the
    masked-stack attack math, shared with ``dfl.engine``.

    ``prev`` optionally carries the previous-round stacked candidates
    (e.g. ``TreeAggState.prev``) so the adaptive attacks see a per-leaf
    ``DefenseView`` in mode-B too; the all-to-all stacked layout has no
    neighbor table or per-victim temporal bands, so the view is
    prev-only and band_rider degrades to its mimicry fallback — the
    correct mode-B threat model (the filter state lives per-device)."""
    if attack in ("none", "label_flip"):
        return stacked
    acfg = atk.AttackConfig(name=attack, noise_mu=noise_mu,
                            noise_sigma=noise_sigma, alie_zmax=alie_zmax)
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    prev_leaves = (jax.tree_util.tree_leaves(prev) if prev is not None
                   else [None] * len(leaves))
    out = [
        atk.apply_matrix_attack(
            attack, leaf, malicious, jax.random.fold_in(key, i), acfg,
            view=(atk.DefenseView(prev=pl) if pl is not None else None))
        for i, (leaf, pl) in enumerate(zip(leaves, prev_leaves))
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


@f32_dots
def robust_allreduce_stacked(
    stacked: Any,
    cfg: RobustAggConfig,
    state: Optional[TreeAggState] = None,
) -> Tuple[Any, Optional[TreeAggState], Dict[str, Array]]:
    """Sharded robust aggregation over stacked candidate gradients.

    Pure-GSPMD fast path (layout='stacked'): no shard_map, no manual
    collectives.  Input leaves are (K, *param_shape) with the candidate
    axis sharded over the data mesh axes; the output drops the candidate
    axis.  Same consensus semantics as ``robust_allreduce``; the WFAgg-T
    filter uses exact metrics against ``state.prev`` (each worker's
    previous gradient, still candidate-sharded — one gradient per
    device).
    """
    leaves = jax.tree.leaves(stacked)
    K = leaves[0].shape[0]

    if cfg.method == "mean":
        out = jax.tree.map(lambda l: jnp.mean(l, axis=0), stacked)
        return out, state, {"weights": jnp.ones((K,), jnp.float32),
                            "n_accepted": jnp.asarray(K)}

    if cfg.streaming_output:
        def one(leaf):
            g = leaf.astype(jnp.float32)
            if cfg.method == "median":
                o = jnp.median(g, axis=0)
            else:
                t = int(cfg.trim_beta * K)
                srt = jnp.sort(g, axis=0)
                o = jnp.mean(srt[t: K - t] if t > 0 else srt, axis=0)
            return o.astype(leaf.dtype)
        out = jax.tree.map(one, stacked)
        return out, state, {"weights": jnp.ones((K,), jnp.float32),
                            "n_accepted": jnp.asarray(K)}

    fused = cfg.backend in ("fused", "fused_two_launch")
    temporal = (cfg.method in ("wfagg", "alt_wfagg") and cfg.wfagg.use_temporal
                and state is not None)
    # Single-launch route (backend="fused"): the whole wfagg/alt_wfagg
    # aggregation — statistics, in-kernel weight derivation, weighted
    # combine — in ONE round-kernel launch over the concatenated (K, P)
    # candidates.  gather_dtype forces the two-launch shape instead: the
    # temporal metrics must stay full-precision while the D/C statistics
    # quantize, which a single candidate read cannot provide.
    if (cfg.backend == "fused" and cfg.method in ("wfagg", "alt_wfagg")
            and cfg.gather_dtype is None):
        return _stacked_one_launch(stacked, cfg, state, temporal)
    # The temporal metrics are computed on FULL-precision candidates in
    # the reference path (gather_dtype only quantizes the D/C/Gram
    # statistics), so the fused kernel may only fold them into its pass
    # when no gather_dtype rounding is in effect — otherwise the masks
    # would diverge between backends.
    fuse_temporal = fused and temporal and cfg.gather_dtype is None
    if fused:
        stats, kstats = _stacked_stats_fused(
            stacked, cfg, prev=state.prev if fuse_temporal else None)
    else:
        stats = _stacked_stats(stacked, cfg)

    new_state = state
    temporal_mask = None
    if temporal:
        if fuse_temporal:
            s_all, b_all = kstats.prev_dist2, kstats.cosine_to_prev()
        else:
            s_all, b_all = _stacked_temporal_metrics(stacked, state.prev)
        temporal_mask, hist_s, hist_b, count, t = wfagg_t_decide(
            state.hist_s, state.hist_b, state.count, state.t,
            s_all, b_all, cfg.wfagg)
        new_state = TreeAggState(
            prev=jax.tree.map(lambda g: g.astype(jnp.float32), stacked),
            hist_s=hist_s, hist_b=hist_b, count=count, t=t)
    weights, _, info = _weights_from_stats(stats, None, None, cfg,
                                           temporal_mask=temporal_mask)

    wsum = jnp.maximum(weights.sum(), 1e-12)
    any_ok = weights.sum() > 0
    w_norm = jnp.where(any_ok, weights / wsum, jnp.full((K,), 1.0 / K))
    out = jax.tree.map(
        lambda l: jnp.tensordot(w_norm, l.astype(jnp.float32),
                                axes=(0, 0)).astype(l.dtype),
        stacked)
    return out, new_state, info


def _stacked_one_launch(
    stacked: Any,
    cfg: RobustAggConfig,
    state: Optional[TreeAggState],
    temporal: bool,
) -> Tuple[Any, Optional[TreeAggState], Dict[str, Array]]:
    """Single-launch stacked wfagg/alt_wfagg: one round-kernel call on
    the concatenated (K, P) candidates does statistics + in-kernel trust
    weights + the weighted combine (the N=1, all-valid, identity-table
    instance of the DFL round kernel).

    ``alpha=1.0`` + ``mean_fallback=True`` turn the kernel's WFAgg-E
    combine into the all-reduce convention: the output is the
    trust-weight-normalized mean of the candidates, degrading to the
    uniform mean when every candidate is rejected (same fallback as the
    reference path — a gradient all-reduce has no "local model" anchor).
    """
    leaves = jax.tree.leaves(stacked)
    K = leaves[0].shape[0]
    w = _effective_wfagg_config(cfg, K)
    flat = _concat_candidates(stacked)               # (K, P) f32
    nidx = jnp.arange(K, dtype=jnp.int32)[None, :]   # identity slate
    prev = tbands = None
    if temporal:
        prev = _concat_candidates(state.prev)        # (K, P) matrix form
        tbands = trust.temporal_bands(state.hist_s, state.hist_b,
                                      state.count, state.t, w)[None]
    local = jnp.zeros_like(flat[:1])                 # unused: lcoef = 0
    out_flat, weights, mask_d, mask_c, mask_t, kstats = _whole_on_each_device(
        lambda loc, f, p, tb: wfagg_round_indexed(
            loc, f, nidx, None, w, prev=p, tbands=tb,
            alpha=1.0, mean_fallback=True))(local, flat, prev, tbands)
    new_state = state
    if temporal:
        hist_s, hist_b, count, t = trust.push_history(
            state.hist_s, state.hist_b, state.count, state.t,
            kstats.prev_dist2[0], kstats.cosine_to_prev()[0])
        new_state = TreeAggState(
            prev=jax.tree.map(lambda g: g.astype(jnp.float32), stacked),
            hist_s=hist_s, hist_b=hist_b, count=count, t=t)
    out = _split_like(out_flat[0], stacked)
    info = {
        "mask_d": mask_d[0], "mask_c": mask_c[0], "mask_t": mask_t[0],
        "weights": weights[0], "n_accepted": (weights[0] > 0).sum(),
        "record": obs_decision.record_from_masks(
            mask_d[0], mask_c[0], mask_t[0],
            jnp.ones(weights[0].shape, bool), weights[0]),
    }
    return out, new_state, info


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

@f32_dots
def robust_allreduce(
    flat: Array,
    axis: AxisNames,
    cfg: RobustAggConfig,
    state: Optional[AggState] = None,
) -> Tuple[Array, Optional[AggState], Dict[str, Array]]:
    """Robust-aggregate local flat gradient across the candidate axis.

    Returns (aggregated flat gradient — identical on every worker,
    new_state, info).  Must be called inside shard_map manual over
    ``axis``.
    """
    axes = _axes_tuple(axis)
    K = axis_size(axis)

    if cfg.method == "mean":
        out = jax.lax.psum(flat, axes) / K
        return out, state, {"weights": jnp.ones((K,), jnp.float32),
                            "n_accepted": jnp.asarray(K)}

    if cfg.streaming_output:
        out = _streaming_coordinate_agg(flat, axis, cfg)
        return out, state, {"weights": jnp.ones((K,), jnp.float32),
                            "n_accepted": jnp.asarray(K)}

    stats = _stats_scan(flat, axis, cfg)
    sketches = jax.lax.all_gather(stats.sketch, axes, tiled=False).reshape(K, -1)
    weights, new_state, info = _weights_from_stats(stats, sketches, state, cfg)

    # phase 2: weighted mean without a second gather — scale own gradient.
    me = my_index(axis)
    wsum = jnp.maximum(weights.sum(), 1e-12)
    scaled = flat * (weights[me] / wsum).astype(flat.dtype)
    out = jax.lax.psum(scaled, axes)
    # all-zero weights (every candidate rejected): fall back to the mean
    fallback = jax.lax.psum(flat, axes) / K
    out = jnp.where(weights.sum() > 0, out, fallback)
    return out, new_state, info


# ---------------------------------------------------------------------------
# distributed attack injection (integration tests / robustness demos)
# ---------------------------------------------------------------------------

def apply_distributed_attack(
    flat: Array,
    axis: AxisNames,
    malicious: Array,      # (K,) bool — which workers are Byzantine
    attack: str,
    key: Array,
    noise_mu: float = 0.1,
    noise_sigma: float = 0.1,
    alie_zmax: float = 0.5,
) -> Array:
    """Transform the local gradient if this worker is malicious.

    Omniscient attacks (ALIE, IPM) use benign-cohort statistics computed
    with masked psums — no gradient gather needed.
    """
    axes = _axes_tuple(axis)
    K = axis_size(axis)
    me = my_index(axis)
    i_am_bad = malicious[me]
    n_benign = jnp.maximum(K - malicious.sum(), 1)

    if attack in ("none", "label_flip"):
        return flat
    if attack == "noise":
        noisy = flat + noise_mu + noise_sigma * jax.random.normal(key, flat.shape, flat.dtype)
        return jnp.where(i_am_bad, noisy, flat)
    if attack == "sign_flip":
        return jnp.where(i_am_bad, -flat, flat)

    benign_w = (~malicious)[me].astype(flat.dtype)
    mu = jax.lax.psum(flat * benign_w, axes) / n_benign
    if attack.startswith("ipm"):
        eps = 100.0 if attack == "ipm_100" else 0.5
        return jnp.where(i_am_bad, -eps * mu, flat)
    if attack == "alie":
        var = jax.lax.psum(benign_w * (flat - mu) ** 2, axes) / n_benign
        mal = mu - alie_zmax * jnp.sqrt(var)
        return jnp.where(i_am_bad, mal.astype(flat.dtype), flat)
    raise ValueError(f"unknown attack {attack!r}")
