"""D-sharded WFAgg gossip rounds under ``shard_map`` (the communication
contract the SPMD linter rules enforce).

The one-launch round kernel derives its trust weights at an in-kernel
phase boundary from GLOBAL filter statistics, so it cannot survive
model-dim sharding as a single launch: a shard only sees its d/S
coordinate slice.  What DOES survive — exactly — is its two phases run
as two launches with the scoring between them, because every statistic
the scoring stage consumes is a coordinate-additive accumulator
(``RobustStats``: dist2 / dotmed / norm2 / mednorm2 / prev_* / gram are
all sums over coordinates, and the coordinate-wise median is computed
per coordinate, i.e. shard-locally):

  phase 0 (shard-local)  ``wfagg_round_indexed_stats`` on the (M, d/S)
                         model shard — one Pallas launch per shard, no
                         comm;
  psum                   ONE all-reduce of the O(N·K) statistic partials
                         across the 'model' axis reconstructs the full-d
                         statistics bit-for-bit up to float summation
                         order — this is the ONLY cross-shard collective
                         the contract allows;
  scoring (replicated)   ``core.wfagg._indexed_scoring`` — the host-side
                         trust stage of the valid-aware reference,
                         computed redundantly on every shard (O(N·K)
                         work, no comm), then each node's combine
                         coefficients (``core.trust.combine_coefficients``);
  phase 1 (shard-local)  ``wfagg_round_indexed_combine`` combines each
                         node's d/S slice with its neighbors' — the
                         WFAgg-E combine never crosses shards.

Per-device wire traffic per round is therefore O(N·K) — independent of
d — versus the O(N·d) a naive GSPMD gather would pay.  Zero-padding d
to a multiple of the shard count is exact for every statistic (a zero
column has median 0 and contributes nothing to any accumulator; see
``kernels.common.pad_d``).

Everything here stays (N, d)-sharded end to end: inputs, the scan
carry, and outputs keep ``P(None, 'model')``, so GSPMD never gets a
replicated consumer to hang a full-d all-gather on.  The analysis entry
points (``repro.analysis.entry_points``) lint the compiled HLO of these
functions against :class:`repro.analysis.collectives.CommContract`.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import trust
from repro.core import wfagg as wf
from repro.kernels.robust_stats.ops import (
    wfagg_round_indexed_combine, wfagg_round_indexed_stats)
from repro.kernels.robust_stats.ref import RobustStats

Array = jax.Array

# mesh axis the model dimension shards over (launch/mesh.py convention)
SHARD_AXIS = "model"


def aggregation_mesh(n_shards: int) -> Mesh:
    """(1, n_shards) ('data', 'model') mesh over the first devices."""
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(data=1, model=n_shards)


def shard_padded_d(d: int, n_shards: int) -> int:
    """d zero-padded up to a multiple of the shard count (exact: zero
    columns contribute nothing to any WFAgg statistic or combine)."""
    return d + (-d) % max(1, n_shards)


def pad_to_shards(x: Array, n_shards: int) -> Array:
    """Zero-pad the trailing (d) axis to a shard multiple, promote f32."""
    pad = (-x.shape[-1]) % max(1, n_shards)
    return jnp.pad(x.astype(jnp.float32),
                   [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def psum_stats(stats: RobustStats, axis: str = SHARD_AXIS) -> RobustStats:
    """Reconstruct full-d ``RobustStats`` from per-shard partials.

    Every populated field is a sum over coordinates of shard-local
    quantities, so one psum across the model axis is exact (up to float
    summation order).  ``med``/``trim`` are d-sized centers the indexed
    filter bank never emits — they must be None here (a d-sized center
    cannot cross shards without violating the contract)."""
    if stats.med is not None or stats.trim is not None:
        raise ValueError(
            "psum_stats only reconstructs the O(N*K) accumulator fields; "
            "d-sized centers (med/trim) must stay shard-local")

    def ps(x):
        return None if x is None else jax.lax.psum(x, axis)

    return RobustStats(
        med=None, trim=None,
        dist2=ps(stats.dist2), dotmed=ps(stats.dotmed),
        norm2=ps(stats.norm2), mednorm2=ps(stats.mednorm2),
        prev_dist2=ps(stats.prev_dist2), prev_dot=ps(stats.prev_dot),
        prev_norm2=ps(stats.prev_norm2), gram=ps(stats.gram))


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Constrain every array of ``tree`` to be replicated over ``mesh``.

    A caller whose data is replicated around a sharded round (the DFL
    engine) pins both sides of it with this.  Otherwise sharding
    propagation carries the round's ``P(None, 'model')`` into the
    surrounding computation and partitions it too: the engine's local
    training then runs its dots and convolutions split over the model
    axis, with all-reduces of partial products."""
    repl = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, repl), tree)


def _state_specs(state: Optional[wf.TemporalState]):
    """PartitionSpecs for a matrix-prev TemporalState: ``prev`` (N, d)
    shards over the model axis, the O(K) ring buffers replicate."""
    if state is None:
        return None
    return wf.TemporalState(prev=P(None, SHARD_AXIS), hist_s=P(), hist_b=P(),
                            count=P(), t=P())


def _check_state(state: Optional[wf.TemporalState]) -> None:
    if state is not None and state.prev.ndim != 2:
        raise NotImplementedError(
            "the sharded round shards the (N, d) matrix-form temporal "
            "state; per-edge (N, K, d) prev would re-materialize the "
            "gossip tensor it exists to avoid")


def _shard_round_body(cfg: wf.WFAggConfig, axis: str):
    """Per-shard round body: local stats -> psum -> replicated scoring ->
    local combine.  Runs under shard_map; ``local``/``models``/``prev``
    are (., d/S) shards, everything else is replicated."""

    def body(local, models, state, neighbor_idx, valid_b):
        temporal = cfg.use_temporal and state is not None
        stats = wfagg_round_indexed_stats(
            models, neighbor_idx, state.prev if temporal else None,
            valid=valid_b, need_gram=trust.needs_gram(cfg))
        stats = psum_stats(stats, axis)
        mask_d, mask_c, mask_t, weights, new_state = wf._indexed_scoring(
            stats, valid_b, state, cfg, models, neighbor_idx)
        wcomb, lcoef = jax.vmap(
            lambda w, v: trust.combine_coefficients(w, cfg.alpha, v, False)
        )(weights, valid_b)
        out = wfagg_round_indexed_combine(local, models, neighbor_idx,
                                          wcomb, lcoef)
        return out, new_state, (mask_d, mask_c, mask_t, weights)

    return body


def _round_specs(state):
    in_specs = (P(None, SHARD_AXIS), P(None, SHARD_AXIS),
                _state_specs(state), P(None, None), P(None, None))
    out_specs = (P(None, SHARD_AXIS), _state_specs(state),
                 (P(), P(), P(), P()))
    return in_specs, out_specs


@wf.f32_dots
def wfagg_batch_sharded(
    local: Array,
    models: Array,
    state: Optional[wf.TemporalState],
    cfg: wf.WFAggConfig,
    neighbor_idx: Array,
    valid: Optional[Array] = None,
    *,
    mesh: Mesh,
) -> Tuple[Array, Optional[wf.TemporalState], Dict[str, Array]]:
    """Drop-in for ``wfagg_batch(..., neighbor_idx=...)`` with the model
    dimension sharded over ``mesh``'s 'model' axis.

    Semantics match ``backend='reference'`` with a ``valid`` mask (the
    same scoring stage, on the psum-reconstructed statistics) up to
    float summation order.  d is zero-padded to a shard multiple internally
    and the pad sliced back off, so callers with replicated inputs (the
    DFL engine) can use any d; the lint entry points pre-pad and keep
    everything sharded instead."""
    _check_state(state)
    S = int(mesh.shape[SHARD_AXIS])
    N, K = neighbor_idx.shape
    d = models.shape[-1]
    valid_b = (jnp.ones((N, K), dtype=bool) if valid is None
               else valid.astype(bool))
    if cfg.sanitize:
        models, valid_b, state = wf.sanitize_round(models, valid_b, state,
                                                   cfg, neighbor_idx)

    loc = pad_to_shards(local, S)
    mod = pad_to_shards(models, S)
    st = (state._replace(prev=pad_to_shards(state.prev, S))
          if state is not None else None)

    in_specs, out_specs = _round_specs(st)
    fn = jax.shard_map(_shard_round_body(cfg, SHARD_AXIS), mesh=mesh,
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    out, new_state, (mask_d, mask_c, mask_t, weights) = fn(
        loc, mod, st, neighbor_idx, valid_b)
    if out.shape[-1] != d:
        out = out[..., :d]
        if new_state is not None:
            new_state = new_state._replace(prev=new_state.prev[..., :d])
    info = {
        "mask_d": mask_d, "mask_c": mask_c, "mask_t": mask_t,
        "valid": valid_b, "weights": weights,
        "n_accepted": (weights > 0).sum(axis=-1),
    }
    return out, new_state, info


@wf.f32_dots
def wfagg_scan_sharded(
    models: Array,
    state: Optional[wf.TemporalState],
    cfg: wf.WFAggConfig,
    sched_idx: Array,        # (R, N, K)
    sched_valid: Array,      # (R, N, K)
    *,
    mesh: Mesh,
) -> Tuple[Array, Optional[wf.TemporalState]]:
    """A whole dynamic schedule of sharded gossip rounds in one
    ``lax.scan`` INSIDE the shard_map region: the (N, d/S) model shard is
    the scan carry, so the model matrix never crosses the shard_map
    boundary between rounds and GSPMD has no replicated consumer to
    all-gather for.  Per round: shard-local stats, the one O(N·K) psum,
    replicated scoring (with the slot-history realignment of
    ``realign_temporal_history`` when temporal state is carried), and
    the shard-local combine.  d must already be a shard multiple
    (``pad_to_shards``)."""
    _check_state(state)
    S = int(mesh.shape[SHARD_AXIS])
    if models.shape[-1] % S:
        raise ValueError(
            f"d={models.shape[-1]} must be a multiple of the shard count "
            f"{S} — pre-pad with pad_to_shards()")
    round_body = _shard_round_body(cfg, SHARD_AXIS)
    temporal = cfg.use_temporal and state is not None

    def scan_body(m, st, sched_idx, sched_valid):
        def one_round(carry, xs):
            models_l, state_l, prev_idx, prev_val = carry
            idx, val = xs
            if temporal:
                state_l = wf.realign_temporal_history(
                    state_l, prev_idx, prev_val, idx, val)
            out, new_state, _ = round_body(models_l, models_l, state_l,
                                           idx, val)
            return (out, new_state, idx, val), None

        init = (m, st, sched_idx[0], jnp.ones_like(sched_valid[0]))
        (m, st, _, _), _ = jax.lax.scan(init=init, xs=(sched_idx, sched_valid),
                                        f=one_round)
        return m, st

    in_specs = (P(None, SHARD_AXIS), _state_specs(state),
                P(None, None, None), P(None, None, None))
    out_specs = (P(None, SHARD_AXIS), _state_specs(state))
    fn = jax.shard_map(scan_body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(models, state, sched_idx,
              sched_valid.astype(bool))


def batched_matrix_state(n: int, k: int, d: int,
                         window: int) -> wf.TemporalState:
    """Batched matrix-prev temporal state (the engine's layout): the
    (N, d) previous model MATRIX instead of an (N, K, d) per-edge
    tensor, slot-keyed (N, W, K) ring buffers."""
    return wf.TemporalState(
        prev=jnp.zeros((n, d), jnp.float32),
        hist_s=jnp.zeros((n, window, k), jnp.float32),
        hist_b=jnp.zeros((n, window, k), jnp.float32),
        count=jnp.zeros((n,), jnp.int32),
        t=jnp.zeros((n,), jnp.int32),
    )


def sharded_round_jit(cfg: wf.WFAggConfig, mesh: Mesh, n: int, k: int,
                      d: int, temporal: bool = True,
                      replicate_out: bool = False):
    """(jitted fn, example args) for ONE sharded gossip round with the
    (N, d) state pinned to ``P(None, 'model')`` at the jit boundary —
    the artifact the SPMD lint entry compiles.  d must be a shard
    multiple.

    ``replicate_out=True`` is the doctored twin for the linter's fire
    tests: demanding a REPLICATED output hands GSPMD a replicated
    consumer for the sharded model matrix, so it inserts exactly the
    full-d all-gather the spmd-* rules exist to catch."""
    S = int(mesh.shape[SHARD_AXIS])
    if d % S:
        raise ValueError(f"d={d} not a multiple of the shard count {S}")
    sharded = NamedSharding(mesh, P(None, SHARD_AXIS))
    repl = NamedSharding(mesh, P())

    state = batched_matrix_state(n, k, d, cfg.window) if temporal else None

    def run(models, state, neighbor_idx, valid):
        out, new_state, info = wfagg_batch_sharded(
            models, models, state, cfg, neighbor_idx, valid, mesh=mesh)
        return out, new_state, info["weights"]

    state_sh = (wf.TemporalState(prev=sharded, hist_s=repl, hist_b=repl,
                                 count=repl, t=repl)
                if state is not None else None)
    out_sh = repl if replicate_out else sharded
    fn = jax.jit(run, in_shardings=(sharded, state_sh, repl, repl),
                 out_shardings=(out_sh, state_sh, repl))
    models = jnp.zeros((n, d), jnp.float32)
    idx = jnp.zeros((n, k), jnp.int32)
    valid = jnp.ones((n, k), jnp.bool_)
    return fn, (models, state, idx, valid)


def sharded_scan_jit(cfg: wf.WFAggConfig, mesh: Mesh, n: int, k: int,
                     d: int, rounds: int, temporal: bool = True):
    """(jitted fn, example args) for the sharded dynamic-schedule scan."""
    S = int(mesh.shape[SHARD_AXIS])
    if d % S:
        raise ValueError(f"d={d} not a multiple of the shard count {S}")
    sharded = NamedSharding(mesh, P(None, SHARD_AXIS))
    repl = NamedSharding(mesh, P())

    state = batched_matrix_state(n, k, d, cfg.window) if temporal else None

    def run(models, state, sched_idx, sched_valid):
        return wfagg_scan_sharded(models, state, cfg, sched_idx,
                                  sched_valid, mesh=mesh)

    state_sh = (wf.TemporalState(prev=sharded, hist_s=repl, hist_b=repl,
                                 count=repl, t=repl)
                if state is not None else None)
    fn = jax.jit(run, in_shardings=(sharded, state_sh, repl, repl),
                 out_shardings=(sharded, state_sh))
    models = jnp.zeros((n, d), jnp.float32)
    sched_idx = jnp.zeros((rounds, n, k), jnp.int32)
    sched_valid = jnp.ones((rounds, n, k), jnp.bool_)
    return fn, (models, state, sched_idx, sched_valid)
