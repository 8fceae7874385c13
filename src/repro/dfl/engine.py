"""Mode-A DFL round engine: the paper's experiment, faithfully.

Each of N nodes owns an independent local model.  One round =
  1. local training (minibatch momentum-SGD on the node's IID shard;
     Label-Flipping nodes poison their labels),
  2. model-poisoning attacks replace Byzantine nodes' models,
  3. gossip: every node receives its K graph neighbors' models,
  4. per-node Byzantine-robust aggregation (any rule from the registry),
     with WFAgg keeping per-node temporal state (Alg. 4).

The whole round is ONE jitted function, vmapped over nodes — 20 nodes x
LeNet/MLP train concurrently.  On a TPU mesh the node axis shards over
'data' (annotated below), which is the faithful decentralized execution
the paper simulates with Python threads.

Dynamic topologies: ``build_round_fn(..., dynamic=True)`` returns the
round with the (N, K) neighbor table, (N, K) valid mask and (N,)
Byzantine mask as TRACED inputs, and ``run_dynamic_experiment`` scans a
``TopologySchedule`` (see ``repro.dfl.dynamics``) through it — the
graph and the attacker set change every round on one compile, with the
per-round accuracy/consistency series computed inside the scan.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.configs.lenet_mnist import PaperDFLConfig
from repro.core import aggregators as agg_lib
from repro.core import attacks as atk
from repro.core import metrics as met
from repro.core import trust
from repro.core import wfagg as wf
from repro.core.topology import Topology, TopologySchedule
from repro.data.synthetic import SyntheticImages
from repro.dfl import faults as flt
from repro.obs import decision as obs_decision
from repro.obs import profile as obs_profile
from repro.models.lenet import init_lenet, init_mlp_classifier, lenet_fwd, mlp_classifier_fwd

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DFLConfig:
    aggregator: str = "wfagg"
    attack: str = "none"
    model: str = "mlp"            # mlp | lenet
    centralized: bool = False     # CFL baseline (server over all N models)
    paper: PaperDFLConfig = PaperDFLConfig()
    batches_per_round: int = 4
    seed: int = 0
    # Model-poisoning attack hyper-parameters (ALIE z_max, noise mu/sigma,
    # IPM eps) — routed through core.attacks.apply_matrix_attack.
    attack_params: atk.AttackConfig = atk.AttackConfig()
    # WFAgg execution backend: "fused" runs the whole gossip round —
    # stats, in-kernel trust-weight derivation AND the WFAgg-E combine —
    # through ONE single-launch Pallas kernel (see core.wfagg.wfagg_batch
    # / kernels.robust_stats.ops.wfagg_round_indexed); "reference" is
    # the multi-pass jnp oracle (valid-aware, so irregular and dynamic
    # topologies run under it too).
    wfagg_backend: str = "fused"
    # > 1 shards the model dimension of the WFAgg gossip round over that
    # many devices of a (1, S) ('data', 'model') mesh via shard_map
    # (distributed/spmd.py): per-shard filter statistics, one O(N*K)
    # psum, shard-local combine.  Requires >= S visible devices; the
    # round boundary stays replicated (pad/shard/unshard inside), so the
    # rest of the engine is unchanged.  0/1 = single-process (default).
    mesh_model_shards: int = 0

    def wfagg_config(self, use_temporal=True, backend: Optional[str] = None) -> wf.WFAggConfig:
        p = self.paper
        return wf.WFAggConfig(
            f=p.f, tau1=p.tau1, tau2=p.tau2, tau3=p.tau3, alpha=p.alpha,
            window=p.window, transient=p.transient, use_temporal=use_temporal,
            backend=backend or self.wfagg_backend,
        )


class DFLState(NamedTuple):
    node_params: Any       # pytree, leading axis N
    node_momentum: Any     # pytree, leading axis N
    temporal: Optional[wf.TemporalState]   # leading axis N (per receiving node)
    rnd: Array


AGGREGATOR_NAMES = (
    "mean", "median", "trimmed_mean", "krum", "multi_krum", "clustering",
    "wfagg_d", "wfagg_c", "wfagg_t", "wfagg_e", "wfagg", "alt_wfagg",
)


def _model_fns(cfg: DFLConfig):
    if cfg.model == "lenet":
        return init_lenet, lenet_fwd
    return init_mlp_classifier, mlp_classifier_fwd


def init_dfl_state(cfg: DFLConfig, topo: Topology,
                   degree: Optional[int] = None) -> DFLState:
    """Fresh per-node models + temporal state.  ``degree`` overrides the
    neighbor-table width K (dynamic schedules are padded to the max
    degree over ALL rounds, which may exceed the base topology's)."""
    init_fn, _ = _model_fns(cfg)
    N = topo.n_nodes
    keys = jax.random.split(jax.random.PRNGKey(cfg.seed), N)
    params = jax.vmap(init_fn)(keys)
    momentum = jax.tree.map(jnp.zeros_like, params)
    flat_one, _ = ravel_pytree(jax.tree.map(lambda x: x[0], params))
    d = flat_one.shape[0]
    K = degree if degree is not None else (
        topo.n_nodes if cfg.centralized else topo.degree)
    temporal = None
    if cfg.aggregator in ("wfagg", "alt_wfagg") and not cfg.centralized:
        # Gather-free gossip rounds keep the temporal ``prev`` as the
        # previous round's (N, d) MODEL MATRIX instead of a per-edge
        # (N, K, d) tensor — prev[idx[n, k]] is exactly edge (n, k)'s
        # last received model, the indexed kernel reads it through the
        # same neighbor table, and the K-fold state buffer disappears.
        temporal = wf.TemporalState(
            prev=jnp.zeros((N, d), jnp.float32),
            hist_s=jnp.zeros((N, cfg.paper.window, K), jnp.float32),
            hist_b=jnp.zeros((N, cfg.paper.window, K), jnp.float32),
            count=jnp.zeros((N,), jnp.int32),
            t=jnp.zeros((N,), jnp.int32),
        )
    elif cfg.aggregator in ("wfagg", "alt_wfagg", "wfagg_t"):
        temporal = jax.vmap(lambda _: wf.init_temporal_state(K, d, cfg.paper.window))(
            jnp.arange(1 if cfg.centralized else N)
        )
    return DFLState(params, momentum, temporal, jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# local training
# ---------------------------------------------------------------------------

def _local_train(cfg: DFLConfig, data: SyntheticImages, malicious: Array,
                 params, momentum, rnd: Array):
    """One round of local minibatch SGD for every node (vmapped).

    ``malicious`` is the round's (N,) Byzantine mask — a traced input, so
    time-varying attacker sets (sleeper scenarios) reuse one compile."""
    _, fwd = _model_fns(cfg)
    p = cfg.paper
    label_flip = cfg.attack == "label_flip"

    def node_train(node_id, params_i, mom_i):
        def one_batch(carry, b):
            params_i, mom_i = carry
            with obs_profile.phase("data"):
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(data.seed), node_id),
                    rnd * 1000 + b)
                imgs, labels = data.batch(key, p.batch_size)
                if label_flip:
                    bad = malicious[node_id]
                    labels = jnp.where(bad, atk.flip_labels(labels, data.n_classes),
                                       labels)

            def loss(pp):
                return met.cross_entropy(fwd(pp, imgs), labels)

            grads = jax.grad(loss)(params_i)
            mom_i = jax.tree.map(lambda m, g: p.momentum * m + g, mom_i, grads)
            params_i = jax.tree.map(lambda w, m: w - p.lr * m, params_i, mom_i)
            return (params_i, mom_i), None

        (params_i, mom_i), _ = jax.lax.scan(
            one_batch, (params_i, mom_i), jnp.arange(cfg.batches_per_round)
        )
        return params_i, mom_i

    node_ids = jnp.arange(malicious.shape[0])
    return jax.vmap(node_train)(node_ids, params, momentum)


# ---------------------------------------------------------------------------
# attacks on trained models
# ---------------------------------------------------------------------------

def _apply_attacks(cfg: DFLConfig, malicious: Array, flat_models: Array,
                   rnd: Array,
                   view: Optional[atk.DefenseView] = None) -> Array:
    """Replace Byzantine rows of (N, d) with attacked models.

    Routed through ``core.attacks.apply_matrix_attack`` (the shared
    masked-stack implementation) so AttackConfig hyper-parameters — ALIE
    z_max, noise mu/sigma, IPM eps — are honored instead of hardcoded.
    ``malicious`` is traced: dynamic scenarios swap the Byzantine set
    round to round without retracing (apply_matrix_attack's benign-cohort
    statistics are masked sums, never boolean indexing).  ``view`` feeds
    the defense-aware adaptive attacks (``atk.ADAPTIVE_ATTACKS``) the
    round's filter state; assembled by ``_defense_view``."""
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 77), rnd)
    return atk.apply_matrix_attack(
        cfg.attack, flat_models, malicious, key, cfg.attack_params,
        view=view)


def _defense_view(cfg: DFLConfig, state: "DFLState", neighbor_idx: Array,
                  neighbor_valid: Optional[Array]) -> Optional[atk.DefenseView]:
    """Assemble the adaptive adversary's ``DefenseView`` for this round.

    Only built when the configured attack actually consumes it (the view
    is statically ``None`` otherwise, so oblivious runs trace zero extra
    work).  The WFAgg-T acceptance bands are precomputed EXACTLY as the
    defense's own fused path does (vmapped ``trust.temporal_bands`` over
    the pre-round temporal state) — the adversary sees the very bands it
    will be filtered by this round.  Bands/prev exist only on the
    matrix-prev gossip form (wfagg/alt_wfagg, where ``temporal.prev`` is
    the (N, d) previous model matrix, i.e. per-SENDER); aggregators
    without that state get a bandless view and the attacks degrade to
    their mimicry fallback — which is the honest threat model: there is
    no temporal filter to ride."""
    if cfg.attack not in atk.ADAPTIVE_ATTACKS or cfg.centralized:
        return None
    tbands = prev = None
    if (state.temporal is not None and state.temporal.prev.ndim == 2
            and cfg.aggregator in ("wfagg", "alt_wfagg")):
        wcfg = _wfagg_full_config(cfg, neighbor_idx.shape[1])
        if wcfg.use_temporal:
            tbands = jax.vmap(
                lambda hs, hb, c, tt: trust.temporal_bands(hs, hb, c, tt, wcfg)
            )(state.temporal.hist_s, state.temporal.hist_b,
              state.temporal.count, state.temporal.t)
            prev = state.temporal.prev
    return atk.DefenseView(neighbor_idx=neighbor_idx, valid=neighbor_valid,
                           prev=prev, tbands=tbands, f=cfg.paper.f)


# ---------------------------------------------------------------------------
# aggregation dispatch
# ---------------------------------------------------------------------------

def _wfagg_full_config(cfg: DFLConfig, K: int,
                       backend: Optional[str] = None) -> wf.WFAggConfig:
    """WFAggConfig for the full wfagg/alt_wfagg pipeline at candidate count K."""
    wcfg = cfg.wfagg_config(backend=backend)
    if cfg.aggregator == "alt_wfagg":
        wcfg = dataclasses.replace(
            wcfg, distance_filter="multi_krum", similarity_filter="clustering",
            multi_krum_m=max(1, int(cfg.paper.multi_krum_m_frac * K)),
        )
    return wcfg


def _aggregate_one(cfg: DFLConfig, local: Array, updates: Array,
                   t_state: Optional[wf.TemporalState],
                   wfagg_backend: Optional[str] = None):
    """Aggregate K received models for one node.  Returns (new_model,
    new_temporal_state).  ``wfagg_backend`` overrides the configured WFAgg
    backend — the vmapped per-node call sites force "reference" because a
    vmap of the fused Pallas path serializes node-by-node (the batched
    fused route is ``wf.wfagg_batch`` in build_round_fn)."""
    p = cfg.paper
    name = cfg.aggregator
    K = updates.shape[0]
    if name in ("mean", "median", "trimmed_mean", "krum", "multi_krum", "clustering"):
        kw: Dict[str, Any] = {"f": p.f}
        if name == "trimmed_mean":
            kw = {"beta": p.trim_beta}
        if name == "multi_krum":
            kw["m"] = max(1, int(p.multi_krum_m_frac * K))
        if name == "clustering":
            kw = {}
        out, _ = agg_lib.AGGREGATORS[name](updates, **kw)
        return out, t_state
    if name == "wfagg_d":
        out, _ = wf.wfagg_d_agg(updates, p.f)
        return out, t_state
    if name == "wfagg_c":
        out, _ = wf.wfagg_c_agg(updates, p.f)
        return out, t_state
    if name == "wfagg_e":
        return wf.wfagg_e_agg(local, updates, p.alpha), t_state
    if name == "wfagg_t":
        mask, new_t = wf.wfagg_t_select(
            t_state, updates, cfg.wfagg_config(backend=wfagg_backend))
        out = wf.wfagg_e(local, updates, mask.astype(jnp.float32), p.alpha)
        return out, new_t
    if name in ("wfagg", "alt_wfagg"):
        wcfg = _wfagg_full_config(cfg, K, backend=wfagg_backend)
        out, new_t, _ = wf.wfagg(local, updates, t_state, wcfg)
        return out, new_t
    raise ValueError(name)


def _aggregate_one_dyn(cfg: DFLConfig, local: Array, updates: Array,
                       valid: Array) -> Array:
    """Baseline aggregation over one PADDED slate: the valid-mask-aware
    ``core.aggregators.DYN_AGGREGATORS`` variants, with the same paper
    hyper-parameters as the static dispatch (Multi-Krum's keep count
    scales with the traced valid degree).  Degree-0 nodes (DoS'd /
    partitioned away) keep their local model — there is nothing to
    aggregate."""
    p = cfg.paper
    name = cfg.aggregator
    kw: Dict[str, Any] = {"f": p.f}
    if name == "trimmed_mean":
        kw = {"beta": p.trim_beta}
    if name == "clustering":
        kw = {}
    if name == "multi_krum":
        v = valid.astype(bool).sum()
        kw["m"] = jnp.maximum(
            (p.multi_krum_m_frac * v.astype(jnp.float32)).astype(jnp.int32), 1)
    out, _ = agg_lib.DYN_AGGREGATORS[name](updates, valid, **kw)
    return jnp.where(valid.astype(bool).sum() > 0, out, local)


# ---------------------------------------------------------------------------
# the round function
# ---------------------------------------------------------------------------

def build_round_fn(cfg: DFLConfig, topo: Topology, data: SyntheticImages,
                   dynamic: bool = False, telemetry: bool = False,
                   faults: Optional[flt.FaultConfig] = None) -> Callable:
    """One jitted DFL round.

    ``dynamic=False`` (default): returns ``round_fn(state)`` closed over
    the static topology — the paper's experiment.

    ``dynamic=True``: returns ``round_fn(state, neighbor_idx, valid,
    mal_mask)`` taking the round's (N, K) neighbor table, (N, K) valid
    mask and (N,) Byzantine mask as TRACED inputs — one compile serves a
    whole round-varying schedule (churn, link failure, mobility, sleeper
    attackers), graph after graph, with no retrace.  wfagg/alt_wfagg run
    the gather-free fused path; the mean/median/trimmed_mean/krum/
    multi_krum/clustering baselines route through the valid-mask-aware
    ``DYN_AGGREGATORS`` variants (a plain gather + per-node vmap — the
    baseline rows of the robustness matrix, not a kernel path).

    ``telemetry=True``: the round additionally returns a
    ``repro.obs.DecisionRecord`` — the packed per-edge verdict bitmask
    plus per-node accepted counts / mean-fallback flags / trust-weight
    entropy — as a second output: ``round_fn(...) -> (state, record)``.
    The record is built from masks the round already computes (pure
    traced jnp, no host callbacks, no extra kernel launch; see
    docs/OBSERVABILITY.md), so the model trajectory is bit-identical
    with telemetry on or off.

    NOTE: the WFAgg-T ring buffers in ``state.temporal`` are keyed by
    neighbor SLOT.  ``run_dynamic_experiment`` re-keys them to each
    round's slate by neighbor identity (``wf.realign_temporal_history``)
    before calling this; a caller driving rounds by hand on a changing
    slate must do the same, or neighbors inherit each other's histories
    when their slot shifts.
    """
    if telemetry and cfg.centralized:
        raise NotImplementedError(
            "telemetry records per-EDGE gossip verdicts; the CFL "
            "baseline has one server and no edges")
    if faults is not None and not dynamic:
        raise NotImplementedError(
            "fault injection rides the dynamic round form (traced "
            "per-round inputs); pass dynamic=True")
    if dynamic:
        if cfg.centralized:
            raise NotImplementedError("dynamic schedules are a gossip "
                                      "(decentralized) feature")
        if cfg.aggregator not in ("wfagg", "alt_wfagg") \
                and cfg.aggregator not in agg_lib.DYN_AGGREGATORS:
            raise NotImplementedError(
                f"aggregator {cfg.aggregator!r} has no valid-mask-aware "
                "form; dynamic schedules run through the wfagg/alt_wfagg "
                "gather-free path or the DYN_AGGREGATORS baselines")
        # any wfagg backend works here: the fused paths AND the reference
        # oracle all honor per-round valid masks (dynamic keep counts)
        return jax.jit(_make_round_core(cfg, data, telemetry=telemetry,
                                        faults=faults))

    neighbor_idx = jnp.asarray(topo.neighbor_indices)  # (N, K) padded
    # None on regular graphs: the indexed kernels then skip the mask and
    # the reference backend stays available for parity runs.
    neighbor_valid = (None if topo.is_regular
                      else jnp.asarray(topo.neighbor_valid))
    if neighbor_valid is not None and not cfg.centralized \
            and cfg.aggregator not in ("wfagg", "alt_wfagg") \
            and cfg.aggregator not in agg_lib.DYN_AGGREGATORS:
        raise NotImplementedError(
            f"aggregator {cfg.aggregator!r} has no valid-mask-aware form; "
            "irregular (padded) topologies are supported by the "
            "wfagg/alt_wfagg gather-free path or the DYN_AGGREGATORS "
            "baselines")
    malicious = jnp.asarray(topo.malicious)
    core = _make_round_core(cfg, data, telemetry=telemetry)
    return jax.jit(lambda state: core(state, neighbor_idx, neighbor_valid,
                                      malicious))


def _make_round_core(cfg: DFLConfig, data: SyntheticImages,
                     telemetry: bool = False,
                     faults: Optional[flt.FaultConfig] = None) -> Callable:
    """The round body, parameterized by the per-round topology inputs.
    With ``telemetry`` the body returns ``(DFLState, DecisionRecord)``;
    the record is derived from the masks/weights the aggregation already
    produced (baselines get :func:`repro.obs.record_uniform` — accepted
    = valid, no filter bits).

    With ``faults`` (a :class:`repro.dfl.faults.FaultConfig`) the body is
    the CHAOS round: it additionally takes the scan-carried
    ``TransportState`` and the round's ``FaultRound`` surface, routes the
    gossip through :func:`repro.dfl.faults.apply_transport` (drop / stale
    / duplicate / corrupt / crash re-keying over the stacked ring
    matrix), and returns ``(DFLState, TransportState[, record])``."""
    if faults is not None:
        return _make_chaos_round_core(cfg, data, telemetry, faults)

    def round_core(state: DFLState, neighbor_idx: Array,
                   neighbor_valid: Optional[Array],
                   mal_mask: Array) -> DFLState:
        # CFL: the server's WFAgg-E reference is the PREVIOUS round's
        # global model (captured before local training — the mean of
        # freshly-received models would itself be poisoned under IPM).
        prev_flat, _ = _ravel_nodes(state.node_params)
        with obs_profile.phase("local_train"):
            params, momentum = _local_train(
                cfg, data, mal_mask, state.node_params, state.node_momentum,
                state.rnd
            )
            flat, unravel_one = _ravel_nodes(params)
        with obs_profile.phase("attack"):
            view = _defense_view(cfg, state, neighbor_idx, neighbor_valid)
            flat = _apply_attacks(cfg, mal_mask, flat, state.rnd, view)

        with obs_profile.phase("aggregate"):
            record = None
            if cfg.centralized:
                if telemetry:
                    raise NotImplementedError(
                        "telemetry records per-EDGE gossip verdicts; the CFL "
                        "baseline has one server and no edges")
                # one server-side aggregation over all N received models
                t0 = (jax.tree.map(lambda x: x[0], state.temporal)
                      if state.temporal is not None else None)
                global_prev = prev_flat[0]  # all nodes share the global model in CFL
                new_global, new_t0 = _aggregate_one(cfg, global_prev, flat, t0)
                new_flat = jnp.broadcast_to(new_global, flat.shape)
                new_temporal = (
                    jax.tree.map(lambda x: x[None], new_t0) if new_t0 is not None else None
                )
            else:
                if cfg.aggregator in ("wfagg", "alt_wfagg"):
                    # gather-free gossip: all N per-node aggregations in one
                    # neighbor-indexed kernel launch — the (N, K, d) gossip
                    # tensor never exists, the kernels DMA each neighbor's
                    # d-blocks straight from the (N, d) model matrix (the
                    # reference backend gathers, for parity runs)
                    wcfg = _wfagg_full_config(cfg, neighbor_idx.shape[1])
                    if cfg.mesh_model_shards > 1:
                        from repro.distributed import spmd
                        mesh = spmd.aggregation_mesh(cfg.mesh_model_shards)
                        # the round boundary is replicated: local training
                        # and evaluation stay whole on every device
                        flat, t_in = spmd.replicate((flat, state.temporal), mesh)
                        new_flat, new_temporal, info = spmd.wfagg_batch_sharded(
                            flat, flat, t_in, wcfg,
                            neighbor_idx, neighbor_valid, mesh=mesh)
                        new_flat, new_temporal = spmd.replicate(
                            (new_flat, new_temporal), mesh)
                    else:
                        new_flat, new_temporal, info = wf.wfagg_batch(
                            flat, flat, state.temporal, wcfg,
                            neighbor_idx=neighbor_idx, valid=neighbor_valid)
                    if telemetry:
                        # the indexed info dict carries the full 2-of-3 vote
                        # (mask_d/mask_c/mask_t/valid/weights) — pack it
                        record = obs_decision.record_from_info(info)
                elif state.temporal is not None:
                    gathered = flat[neighbor_idx]  # (N, K, d) gossip exchange
                    new_flat, new_temporal = jax.vmap(
                        lambda loc, upd, ts: _aggregate_one(
                            cfg, loc, upd, ts, wfagg_backend="reference")
                    )(flat, gathered, state.temporal)
                elif neighbor_valid is not None:
                    # baseline aggregators on a padded/dynamic slate: gossip
                    # gather + the valid-mask-aware DYN_AGGREGATORS variants
                    gathered = flat[neighbor_idx]  # (N, K, d) gossip exchange
                    new_flat = jax.vmap(
                        lambda loc, upd, v: _aggregate_one_dyn(cfg, loc, upd, v)
                    )(flat, gathered, neighbor_valid)
                    new_temporal = None
                else:
                    gathered = flat[neighbor_idx]  # (N, K, d) gossip exchange
                    new_flat, _ = jax.vmap(
                        lambda loc, upd: _aggregate_one(cfg, loc, upd, None)
                    )(flat, gathered)
                    new_temporal = None
                if telemetry and record is None:
                    # baselines have no per-edge filter verdicts: uniform
                    # accept over the valid slate (degree-0 still tracked)
                    valid_all = (neighbor_valid if neighbor_valid is not None
                                 else jnp.ones(neighbor_idx.shape, bool))
                    record = obs_decision.record_uniform(valid_all)

            new_params = jax.vmap(unravel_one)(new_flat)
        new_state = DFLState(new_params, momentum, new_temporal, state.rnd + 1)
        if telemetry:
            return new_state, record
        return new_state

    return round_core


def _make_chaos_round_core(cfg: DFLConfig, data: SyntheticImages,
                           telemetry: bool, fcfg: flt.FaultConfig) -> Callable:
    """The fault-injected round body (see ``_make_round_core``).

    Differences from the clean round, in execution order:
      * crash freeze — a down node neither trains nor transmits: its
        model row and momentum are held at last round's values, and its
        own slate is all-invalid (it keeps its local model);
      * transport — :func:`repro.dfl.faults.apply_transport` re-keys the
        neighbor table over the sanitized stacked ring matrix (fresh /
        stale / corrupt-bank rows), yielding the effective table, the
        surviving valid mask, and the WFAgg-T ``prev_idx`` staleness
        re-keying;
      * history hygiene — an edge with NO accepted delivery this round
        re-centers its WFAgg-T band at the pre-round EWMA mean instead
        of recording a metric against a payload it never saw.

    Everything is pure traced jnp on scan-carried state: no host
    transfer, no extra kernel launch, no (N, K, d) tensor on the
    wfagg/alt_wfagg path (the ``chaos_scan`` lint entry pins all three).
    """
    if cfg.centralized:
        raise NotImplementedError("fault injection is a gossip (decentral"
                                  "ized) feature; CFL has no transport")
    if cfg.mesh_model_shards > 1:
        raise NotImplementedError(
            "chaos transport + model-dim sharding: the stacked ring "
            "matrix is not sharded yet (see docs/FAULTS.md)")

    def round_core(state: DFLState, neighbor_idx: Array,
                   neighbor_valid: Optional[Array], mal_mask: Array,
                   ts: flt.TransportState, fr: flt.FaultRound):
        with obs_profile.phase("transport"):
            # the stored models a crashed node keeps broadcasting
            prev_flat, _ = _ravel_nodes(state.node_params)
        with obs_profile.phase("local_train"):
            params, momentum = _local_train(
                cfg, data, mal_mask, state.node_params, state.node_momentum,
                state.rnd
            )
            flat, unravel_one = _ravel_nodes(params)
        with obs_profile.phase("attack"):
            view = _defense_view(cfg, state, neighbor_idx, neighbor_valid)
            flat = _apply_attacks(cfg, mal_mask, flat, state.rnd, view)
        with obs_profile.phase("transport"):
            # crash freeze: a down node broadcasts (and keeps) its stored
            # model; its training step and momentum advance are discarded
            down = fr.down.astype(bool)
            flat = jnp.where(down[:, None], prev_flat, flat)
            momentum = jax.tree.map(
                lambda old, new: jnp.where(
                    down.reshape((-1,) + (1,) * (new.ndim - 1)), old, new),
                state.node_momentum, momentum)

            valid = (neighbor_valid if neighbor_valid is not None
                     else jnp.ones(neighbor_idx.shape, bool))
            tout = flt.apply_transport(flat, ts, neighbor_idx, valid, fr, fcfg,
                                       state.rnd)

        with obs_profile.phase("aggregate"):
            record = None
            if cfg.aggregator in ("wfagg", "alt_wfagg"):
                wcfg = _wfagg_full_config(cfg, neighbor_idx.shape[1])
                t_in = state.temporal
                mu_s = mu_b = None
                matrix_prev = t_in is not None and t_in.prev.ndim == 2
                if matrix_prev:
                    if wcfg.use_temporal:
                        # pre-round EWMA centers: the hygiene value a no-
                        # delivery edge pushes instead of a garbage metric
                        mu_s, _ = jax.vmap(
                            lambda h, c: trust.ewma_mean_std(h, c, wcfg.ewma_decay)
                        )(t_in.hist_s, t_in.count)
                        mu_b, _ = jax.vmap(
                            lambda h, c: trust.ewma_mean_std(h, c, wcfg.ewma_decay)
                        )(t_in.hist_b, t_in.count)
                    # the carried (N, d) prev is superseded by the stacked
                    # matrix + prev_idx (the payload each edge ACTUALLY
                    # served last round, aged one round)
                    t_in = t_in._replace(prev=tout.full)
                new_flat, new_temporal, info = wf.wfagg_batch(
                    flat, tout.full, t_in, wcfg,
                    neighbor_idx=tout.eff_idx, valid=tout.eff_valid,
                    prev_idx=tout.prev_idx)
                if matrix_prev and new_temporal is not None:
                    hist_s, hist_b = new_temporal.hist_s, new_temporal.hist_b
                    if mu_s is not None:
                        hist_s = hist_s.at[:, 0, :].set(
                            jnp.where(tout.eff_valid, hist_s[:, 0, :], mu_s))
                        hist_b = hist_b.at[:, 0, :].set(
                            jnp.where(tout.eff_valid, hist_b[:, 0, :], mu_b))
                    new_temporal = new_temporal._replace(
                        prev=flat, hist_s=hist_s, hist_b=hist_b)
                if telemetry:
                    record = obs_decision.record_from_info(info)
            else:
                # baselines gather (they already do on the dynamic path);
                # the valid-aware variants see the post-fault slate
                gathered = tout.full[tout.eff_idx]
                new_flat = jax.vmap(
                    lambda loc, upd, v: _aggregate_one_dyn(cfg, loc, upd, v)
                )(flat, gathered, tout.eff_valid)
                new_temporal = None
                if telemetry:
                    record = obs_decision.record_uniform(tout.eff_valid)
            if telemetry:
                record = obs_decision.with_fault_bits(
                    record, tout.dropped, tout.stale, tout.corrupt)

            # a down receiver aggregates nothing (its slate is all-invalid so
            # this is already true on the wfagg path; make it explicit)
            new_flat = jnp.where(down[:, None], prev_flat, new_flat)
            new_params = jax.vmap(unravel_one)(new_flat)
        with obs_profile.phase("transport"):
            new_ts = flt.advance_ring(ts, flat, tout.served_lag)
        new_state = DFLState(new_params, momentum, new_temporal,
                             state.rnd + 1)
        if telemetry:
            return new_state, new_ts, record
        return new_state, new_ts

    return round_core


def _ravel_nodes(params):
    one = jax.tree.map(lambda x: x[0], params)
    _, unravel_one = ravel_pytree(one)
    flat = jax.vmap(lambda t: ravel_pytree(t)[0])(params)
    return flat, unravel_one


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------

def evaluate(cfg: DFLConfig, topo: Topology, data: SyntheticImages,
             state: DFLState, n_test: int = 512,
             malicious: Optional[np.ndarray] = None,
             adjacency: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """Per-node accuracy + consistency snapshot.

    ``malicious``/``adjacency`` override the static topology's — dynamic
    scenarios pass the schedule's ever-malicious set and the evaluation
    round's graph, so the benign cohort excludes every attacker and the
    malicious-neighbor buckets reflect the graph the nodes actually
    saw."""
    _, fwd = _model_fns(cfg)
    imgs, labels = data.test_set(n_test)
    accs = jax.vmap(lambda p: met.micro_accuracy(fwd(p, imgs), labels))(state.node_params)
    accs = np.asarray(accs)
    mal = np.asarray(topo.malicious if malicious is None else malicious)
    adj = np.asarray(topo.adjacency if adjacency is None else adjacency)
    benign = ~mal
    mal_nb = (adj & mal[None, :]).sum(axis=1)
    flat, _ = _ravel_nodes(state.node_params)
    r2 = float(met.r_squared(jnp.asarray(np.asarray(flat)[benign])))
    by_mn = {}
    # bucket by every malicious-neighbor count the topology realizes
    # (dense placements can put >= 3 malicious nodes next to a benign
    # one; hardcoded buckets would silently drop those nodes)
    for m in range(max(2, int(mal_nb.max(initial=0))) + 1):
        sel = benign & (mal_nb == m)
        by_mn[m] = float(accs[sel].mean()) if sel.any() else float("nan")
    return {
        "acc_benign_mean": float(accs[benign].mean()),
        "acc_by_malicious_neighbors": by_mn,
        "r_squared": r2,
        "acc_all": accs.tolist(),
    }


def _series_from_trace(trace) -> Dict[str, list]:
    """Columnar per-round time series (plottable) from a trace of
    ``evaluate`` dicts."""
    return {
        "round": [e["round"] for e in trace],
        "acc_benign_mean": [e["acc_benign_mean"] for e in trace],
        "r_squared": [e["r_squared"] for e in trace],
    }


def _telemetry_out(record, neighbor_idx, valid, malicious) -> Dict[str, Any]:
    """Host-side telemetry bundle: the stacked (R, …) ``DecisionRecord``
    fields plus the slate context (``(R, N, K)`` tables, ``(R, N)``
    Byzantine masks) a report needs to split attacker from benign edges
    (``repro.obs.report.filter_rates``)."""
    return {
        "verdict": np.asarray(record.verdict),
        "accepted": np.asarray(record.accepted),
        "mean_fallback": np.asarray(record.mean_fallback),
        "degree_zero": np.asarray(record.degree_zero),
        "entropy": np.asarray(record.entropy),
        "neighbor_idx": np.asarray(neighbor_idx),
        "valid": np.asarray(valid),
        "malicious": np.asarray(malicious),
    }


def run_experiment(cfg: DFLConfig, topo: Topology, data: SyntheticImages,
                   rounds: Optional[int] = None, eval_every: int = 1,
                   telemetry: bool = False) -> Dict[str, Any]:
    """Run a full DFL experiment; returns the per-round metric trace and
    the columnar ``series`` time series (accuracy, consistency).

    Decentralized runs always track the per-node mean-fallback /
    degree-0 flags (the masks are already computed; a node silently
    keeping its local model is an event worth a series column) —
    ``series["mean_fallback_count"]`` / ``series["degree_zero_count"]``
    per round, plus ``trace[i]["mean_fallback_nodes"]`` at evaluation
    rounds.  ``telemetry=True`` additionally returns the full
    per-round/per-edge record under ``out["telemetry"]`` (see
    ``repro.obs`` / docs/OBSERVABILITY.md).
    """
    rounds = rounds or cfg.paper.rounds
    if telemetry and cfg.centralized:
        raise NotImplementedError(
            "telemetry records per-EDGE gossip verdicts; the CFL "
            "baseline has one server and no edges")
    track = not cfg.centralized
    state = init_dfl_state(cfg, topo)
    round_fn = build_round_fn(cfg, topo, data, telemetry=track)
    trace = []
    records = []
    fallback_counts, degree_zero_counts = [], []
    mf = None
    for r in range(rounds):
        if track:
            state, rec = round_fn(state)
            mf = np.asarray(rec.mean_fallback)
            fallback_counts.append(int(mf.sum()))
            degree_zero_counts.append(int(np.asarray(rec.degree_zero).sum()))
            if telemetry:
                records.append(jax.device_get(rec))
        else:
            state = round_fn(state)
        if (r + 1) % eval_every == 0 or r == rounds - 1:
            e = evaluate(cfg, topo, data, state)
            e["round"] = r + 1
            if mf is not None:
                e["mean_fallback_nodes"] = np.flatnonzero(mf).tolist()
            trace.append(e)
    series = _series_from_trace(trace)
    if track:
        series["mean_fallback_count"] = fallback_counts
        series["degree_zero_count"] = degree_zero_counts
    out = {"trace": trace, "final": trace[-1], "series": series,
           "aggregator": cfg.aggregator,
           "attack": cfg.attack, "centralized": cfg.centralized}
    if telemetry:
        record = jax.tree.map(lambda *xs: np.stack(xs), *records)
        R = len(records)
        nv = (np.ones_like(topo.neighbor_indices, bool)
              if topo.is_regular else np.asarray(topo.neighbor_valid))
        out["telemetry"] = _telemetry_out(
            record,
            np.broadcast_to(np.asarray(topo.neighbor_indices), (R,) + nv.shape),
            np.broadcast_to(nv, (R,) + nv.shape),
            np.broadcast_to(np.asarray(topo.malicious),
                            (R, topo.n_nodes)))
    return out


# ---------------------------------------------------------------------------
# dynamic-topology experiments (round-varying schedules)
# ---------------------------------------------------------------------------

def build_dynamic_scan_fn(cfg: DFLConfig, topo: Topology,
                          data: SyntheticImages,
                          schedule: TopologySchedule,
                          n_test: int = 256, telemetry: bool = False,
                          faults: Optional[flt.FaultSchedule] = None):
    """The ONE-jit schedule scan behind ``run_dynamic_experiment``.

    Returns ``(state, run, sched)``: the initial state, the jitted
    ``run(state, neighbor_idx, valid, malicious) -> (state, series)``
    scan, and the schedule's ``(R, N, K)`` / ``(R, N)`` arrays.  Exposed
    separately so the static-analysis entry registry (``repro.analysis``)
    lints the EXACT computation the experiment driver runs — same jit,
    same scan body — not a re-derived lookalike.

    ``telemetry=True`` appends the per-round ``DecisionRecord`` to the
    scan outputs — ``run(...) -> (state, (accs, acc_benign, r2,
    record))`` with the record's leaves stacked to leading axis R.  The
    record is a pure traced output of masks the round already computes:
    no host callback enters the scan body (the ``dynamic_scan_telemetry``
    lint entry pins launch count and the no-host-transfer rule).

    ``faults`` (a :class:`repro.dfl.faults.FaultSchedule`) switches to
    the CHAOS form: the first return value becomes the full scan CARRY
    ``(state, prev_idx, prev_val, TransportState)``, ``run(carry,
    neighbor_idx, valid, malicious, drop, lag, dup, corrupt, down)``
    takes that carry explicitly and returns the FINAL carry (so a
    checkpointed run can stop and resume mid-schedule; see
    train/checkpoint.py and docs/FAULTS.md), and ``sched`` grows the
    five fault stacks.  Still one jit, one scan, one compile.
    """
    if schedule.n_nodes != topo.n_nodes:
        raise ValueError(
            f"schedule is for {schedule.n_nodes} nodes, topology has "
            f"{topo.n_nodes}")
    state = init_dfl_state(cfg, topo, degree=schedule.width)
    round_core = build_round_fn(cfg, topo, data, dynamic=True,
                                telemetry=telemetry,
                                faults=faults.config if faults else None)
    _, fwd = _model_fns(cfg)
    imgs, labels = data.test_set(n_test)
    sched = (jnp.asarray(schedule.neighbor_idx),
             jnp.asarray(schedule.valid),
             jnp.asarray(schedule.malicious))
    # Evaluation cohort: a node that is malicious in ANY round is an
    # attacker, full stop — a churned-out (or not-yet-woken) attacker
    # sends nothing to poison that round (the per-round mask drives the
    # ATTACK side), but its own stored model is still attacker state and
    # must not dilute the benign accuracy/consistency series.
    ever_mal = jnp.asarray(schedule.malicious.any(axis=0))

    def eval_out(st):
        with obs_profile.phase("evaluate"):
            accs = jax.vmap(
                lambda p: met.micro_accuracy(fwd(p, imgs), labels)
            )(st.node_params)
            benign = ~ever_mal
            bw = benign.astype(jnp.float32)
            acc_benign = jnp.sum(accs * bw) / jnp.maximum(bw.sum(), 1.0)
            flat, _ = _ravel_nodes(st.node_params)
            return (accs, acc_benign, met.r_squared(flat, weights=bw))

    if faults is not None:
        if faults.rounds != schedule.rounds:
            raise ValueError(
                f"fault schedule has {faults.rounds} rounds, topology "
                f"schedule has {schedule.rounds}")
        flat0, _ = _ravel_nodes(state.node_params)
        ts0 = flt.init_transport_state(
            faults.config, topo.n_nodes, schedule.width, flat0.shape[1])
        sched = sched + faults.xs()

        @jax.jit
        def run_chaos(carry, neighbor_idx, valid, malicious,
                      drop, lag, dup, corrupt, down):
            def body(carry, xs):
                st, prev_idx, prev_val, ts = carry
                idx, val, mal = xs[:3]
                fr = flt.FaultRound(*xs[3:])
                if st.temporal is not None:
                    with obs_profile.phase("aggregate"):
                        st = st._replace(temporal=wf.realign_temporal_history(
                            st.temporal, prev_idx, prev_val, idx, val))
                # the served-lag table is slot-keyed like the temporal
                # history: re-key it to this round's slate too
                with obs_profile.phase("transport"):
                    ts = ts._replace(served_lag=flt.realign_served_lag(
                        ts.served_lag, prev_idx, prev_val, idx, val))
                if telemetry:
                    st, ts, record = round_core(st, idx, val, mal, ts, fr)
                else:
                    st, ts = round_core(st, idx, val, mal, ts, fr)
                out = eval_out(st)
                if telemetry:
                    out = out + (record,)
                return (st, idx, val, ts), out
            carry, out = jax.lax.scan(
                body, carry,
                (neighbor_idx, valid, malicious, drop, lag, dup, corrupt,
                 down))
            return carry, out

        carry0 = (state, sched[0][0], sched[1][0], ts0)
        return carry0, run_chaos, sched

    @jax.jit
    def run(state, neighbor_idx, valid, malicious):
        def body(carry, xs):
            st, prev_idx, prev_val = carry
            idx, val, mal = xs
            if st.temporal is not None:
                # the WFAgg-T ring buffers are slot-keyed: re-key them to
                # this round's slate by neighbor IDENTITY, so a neighbor
                # that shifted slots (or rejoined) is scored against ITS
                # history, not whoever held the slot before
                with obs_profile.phase("aggregate"):
                    st = st._replace(temporal=wf.realign_temporal_history(
                        st.temporal, prev_idx, prev_val, idx, val))
            if telemetry:
                st, record = round_core(st, idx, val, mal)
            else:
                st = round_core(st, idx, val, mal)
            out = eval_out(st)
            if telemetry:
                out = out + (record,)
            return (st, idx, val), out
        # the round-0 "previous" slate is round 0's own (identity match:
        # the buffers are all-zero anyway, any remap is a no-op)
        init = (state, neighbor_idx[0], valid[0])
        (st, _, _), out = jax.lax.scan(
            body, init, (neighbor_idx, valid, malicious))
        return st, out

    return state, run, sched


def run_dynamic_experiment(cfg: DFLConfig, topo: Topology,
                           data: SyntheticImages,
                           schedule: TopologySchedule,
                           n_test: int = 256,
                           telemetry: bool = False,
                           faults: Optional[flt.FaultSchedule] = None,
                           stop_after: Optional[int] = None,
                           checkpoint_dir: Optional[str] = None,
                           checkpoint_name: str = "chaos",
                           resume_from: Optional[str] = None) -> Dict[str, Any]:
    """Run a DFL experiment under a round-varying topology schedule.

    ONE jit: ``lax.scan`` over the (R, N, K) neighbor-table / valid-mask
    / (R, N) malicious-mask schedule, with the round function taking all
    three as traced per-round inputs — the graph and the Byzantine set
    change every round, the compile happens once.  Per-round accuracy
    and consistency are computed INSIDE the scan (a DART-style
    robustness time series), so dynamic scenarios are plottable without
    host round-trips.  The returned dict keeps ``run_experiment``'s
    shape (trace / final / series).

    ``telemetry=True`` turns on the decision plane: the scan emits the
    per-round (N, K) verdict bitmask + per-node summaries as extra
    traced outputs, returned under ``out["telemetry"]`` alongside the
    schedule context, with the mean-fallback / degree-0 / accepted-count
    time series joined into ``series``.  Model trajectories are
    bit-identical with telemetry on or off (the record only READS masks
    the round already computes).

    Chaos transport (``faults``, a ``repro.dfl.faults.FaultSchedule``):
    the scan additionally consumes the per-round fault surface and
    carries the delivery ring (see docs/FAULTS.md).  Fault runs are
    CHECKPOINTABLE: ``stop_after=r`` runs only rounds [0, r) and — with
    ``checkpoint_dir`` — snapshots the full scan carry (models,
    momentum, WFAgg-T ring buffers, transport ring, round counter; the
    in-scan PRNG streams all derive from the carried round counter) plus
    the in-flight topology + fault schedules via ``train/checkpoint.py``.
    ``resume_from=dir`` restores that snapshot and runs the REMAINING
    rounds, reproducing the uninterrupted trajectory bit-exactly (use a
    ``make_fault_schedule("none", ...)`` schedule to checkpoint a
    fault-free run).  ``out["rounds_run"]`` records the [start, end)
    window a partial run covered.  ``out["state"]`` is the final
    ``DFLState`` (the trained node models).
    """
    if (stop_after is not None or resume_from is not None
            or checkpoint_dir is not None) and faults is None:
        raise NotImplementedError(
            "checkpoint/resume rides the chaos scan form (the run "
            "function must return its carry); pass faults="
            "make_fault_schedule('none', schedule, 0.0) for a "
            "fault-free checkpointable run")
    state, run, sched = build_dynamic_scan_fn(cfg, topo, data, schedule,
                                              n_test=n_test,
                                              telemetry=telemetry,
                                              faults=faults)
    ever_mal = jnp.asarray(schedule.malicious.any(axis=0))
    record = None
    R = schedule.rounds
    r0, r_end = 0, R
    if faults is None:
        if telemetry:
            state, (acc_all, acc_benign, r2, record) = run(state, *sched)
        else:
            state, (acc_all, acc_benign, r2) = run(state, *sched)
    else:
        from repro.train import checkpoint as ckpt
        carry = state
        if resume_from is not None:
            # the snapshot carries the schedules too: the resumed scan
            # replays the IN-FLIGHT fault surface, not a reconstruction
            carry, sched, meta = ckpt.restore_experiment_checkpoint(
                resume_from, checkpoint_name, carry, sched)
            r0 = int(meta["round"])
        r_end = R if stop_after is None else int(stop_after)
        if not r0 < r_end <= R:
            raise ValueError(
                f"round window [{r0}, {r_end}) is empty or exceeds the "
                f"{R}-round schedule")
        xs = tuple(a[r0:r_end] for a in sched)
        if telemetry:
            carry, (acc_all, acc_benign, r2, record) = run(carry, *xs)
        else:
            carry, (acc_all, acc_benign, r2) = run(carry, *xs)
        state = carry[0]
        if checkpoint_dir is not None:
            ckpt.save_experiment_checkpoint(
                checkpoint_dir, checkpoint_name, carry, sched,
                metadata={"round": r_end, "rounds_total": R,
                          "fault_config":
                              dataclasses.asdict(faults.config),
                          "fault_summary": faults.summary()})
    acc_all = np.asarray(acc_all)
    acc_benign = np.asarray(acc_benign)
    r2 = np.asarray(r2)
    trace = [{
        "round": r0 + i + 1,
        "acc_benign_mean": float(acc_benign[i]),
        "r_squared": float(r2[i]),
        "acc_all": acc_all[i].tolist(),
    } for i in range(r_end - r0)]
    # full evaluation (incl. malicious-neighbor buckets) under the FINAL
    # round's graph, with the ever-malicious cohort (same n_test as the
    # in-scan series, so final agrees with trace[-1])
    final = evaluate(cfg, topo, data, state, n_test=n_test,
                     malicious=np.asarray(ever_mal),
                     adjacency=schedule.adjacency[r_end - 1])
    final["round"] = r_end
    series = _series_from_trace(trace)
    series["degree_min_mean_max"] = (
        schedule.degree_stats()[r0:r_end].tolist())
    out = {"trace": trace, "final": final, "series": series,
           "aggregator": cfg.aggregator, "attack": cfg.attack,
           "centralized": cfg.centralized, "state": state}
    if faults is not None:
        out["faults"] = faults.summary()
        out["rounds_run"] = [r0, r_end]
    if record is not None:
        record = jax.device_get(record)
        series["mean_fallback_count"] = (
            np.asarray(record.mean_fallback).sum(axis=1).astype(int).tolist())
        series["degree_zero_count"] = (
            np.asarray(record.degree_zero).sum(axis=1).astype(int).tolist())
        series["accepted_mean"] = [
            float(x) for x in np.asarray(record.accepted).mean(axis=1)]
        out["telemetry"] = _telemetry_out(
            record, schedule.neighbor_idx[r0:r_end],
            schedule.valid[r0:r_end], schedule.malicious[r0:r_end])
    return out
