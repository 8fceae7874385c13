"""The registered lint targets.

Every computation the repo ships — the fused one-launch round, the
valid-aware reference oracle, the
dynamic-scenario scan, stacked ``robust_allreduce`` mode-B — is
registered here as an :class:`~repro.analysis.rules.EntryPoint` and gets
the FULL rule gate on every ``python -m repro.analysis`` run.  A new
subsystem (shard_map multi-pod round, compressed gossip) inherits the
gate by adding one entry: a ``build()`` returning its jitted callable
plus example args, the pinned launch count, and its (N, K, d) triple.

The builders use the same small shapes as the tier-1 tests (N=10 ring,
K=4 churn slates, the MLP model) so a lint run costs seconds, not the
paper experiment.  ``memory_passes`` table rows (the absorbed
``scripts/passes_gate.py``) are distributed over the entries each row
describes; ``scripts/passes_gate.py`` re-collects them all.
"""
from __future__ import annotations

import functools
from typing import Dict

from repro.analysis import collectives
from repro.analysis.rules import EntryPoint

# the MLP classifier the lint entries train: fc1 (784 x 64 + 64) +
# fc2 (64 x 10 + 10) raveled
MLP_D = 784 * 64 + 64 + 64 * 10 + 10

_N, _DEGREE, _ROUNDS = 10, 4, 3


def _ring_fixture():
    from repro.core.topology import make_topology
    from repro.data.synthetic import SyntheticImages
    from repro.dfl import dynamics as dyn

    topo = make_topology(n_nodes=_N, degree=_DEGREE, n_malicious=2,
                         kind="ring", seed=0)
    data = SyntheticImages()
    sched = dyn.churn_schedule(topo, _ROUNDS, seed=1)
    return topo, data, sched


def _build_dynamic_round(aggregator: str):
    """(fn, args) for one jitted dynamic round under the fused backend."""
    import jax.numpy as jnp

    from repro.dfl.engine import DFLConfig, build_round_fn, init_dfl_state

    topo, data, sched = _ring_fixture()
    cfg = DFLConfig(aggregator=aggregator, attack="ipm_100", model="mlp")
    fn = build_round_fn(cfg, topo, data, dynamic=True)
    state = init_dfl_state(cfg, topo, degree=sched.width)
    args = (state, jnp.asarray(sched.neighbor_idx[0]),
            jnp.asarray(sched.valid[0]), jnp.asarray(sched.malicious[0]))
    return fn, args


def _build_reference_round():
    """The static round on the ring topology, reference (gathering)
    backend — the parity oracle, linted with its two gather rules
    suppressed (materializing the gossip tensor is its job)."""
    from repro.dfl.engine import DFLConfig, build_round_fn, init_dfl_state

    topo, data, _ = _ring_fixture()
    cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp",
                    wfagg_backend="reference")
    fn = build_round_fn(cfg, topo, data)
    return fn, (init_dfl_state(cfg, topo),)


def _build_dynamic_scan(telemetry: bool = False):
    """The whole-schedule scan ``run_dynamic_experiment`` jits — built by
    the engine's own ``build_dynamic_scan_fn``, so the linted program IS
    the experiment driver's.  With ``telemetry`` it is the flight-
    recorder variant: the scan additionally emits the packed per-round
    verdict bitmask + per-node summaries (``repro.obs``) as pure traced
    outputs — same launch count, and the no-host-transfer-in-scan rule
    must hold over it just like the silent scan."""
    from repro.dfl.engine import DFLConfig, build_dynamic_scan_fn

    topo, data, sched = _ring_fixture()
    cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp")
    state, run, sched_arrays = build_dynamic_scan_fn(cfg, topo, data, sched,
                                                     n_test=64,
                                                     telemetry=telemetry)
    return run, (state,) + tuple(sched_arrays)


def _build_chaos_scan():
    """The fault-injected whole-schedule scan (chaos transport): drop +
    stale + duplicate + corrupt + crash-restart schedules riding as five
    extra scan stacks, the stale-delivery ring and corrupt bank folded
    into ONE stacked 2-D model matrix per round (``repro.dfl.faults``).
    Acceptance gate for docs/FAULTS.md: launch count identical to the
    clean scan (still the single fused round launch), and no host
    transfer enters the scan — the fault path must cost zero extra
    kernel launches and zero recompiles."""
    from repro.dfl import faults as flt
    from repro.dfl.engine import DFLConfig, build_dynamic_scan_fn

    topo, data, sched = _ring_fixture()
    cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp")
    fs = flt.make_fault_schedule("chaos", sched, 0.4, seed=2)
    carry0, run, arrays = build_dynamic_scan_fn(
        cfg, topo, data, sched, n_test=64, telemetry=True, faults=fs)
    return run, (carry0,) + tuple(arrays)


_STACKED_K, _STACKED_D = 6, 24 * 6 + 80

# sharded entries: shard count and the zero-padded model dim (padding d
# to a shard multiple is exact — see kernels.common.pad_d)
_SHARDS = 8
MLP_D_PAD = MLP_D + (-MLP_D) % _SHARDS


def _build_sharded_round():
    """One sharded gossip round: the round kernel's two phases as two
    launches per shard (local stats, O(N*K) psum, replicated scoring,
    local combine), the (N, d) state pinned P(None, 'model') at the jit
    boundary."""
    from repro.core.wfagg import WFAggConfig
    from repro.distributed import spmd

    cfg = WFAggConfig(f=1, window=3, transient=1)
    mesh = spmd.aggregation_mesh(_SHARDS)
    return spmd.sharded_round_jit(cfg, mesh, n=_N, k=_DEGREE, d=MLP_D_PAD)


def _build_sharded_scan():
    """The whole dynamic schedule inside ONE shard_map region: lax.scan
    carries the (N, d/S) model shard, so the model matrix never crosses
    the shard_map boundary between rounds."""
    from repro.core.wfagg import WFAggConfig
    from repro.distributed import spmd

    cfg = WFAggConfig(f=1, window=3, transient=1)
    mesh = spmd.aggregation_mesh(_SHARDS)
    return spmd.sharded_scan_jit(cfg, mesh, n=_N, k=_DEGREE, d=MLP_D_PAD,
                                 rounds=_ROUNDS)


def _build_sharded_stacked():
    """Mode-B stacked allreduce under the (1, 8) mesh via the pure-jnp
    reference stats (GSPMD-partitionable — no Pallas custom-call for the
    partitioner to replicate): leaves shard their trailing dim over
    'model', statistics meet in O(K)/O(K^2) all-reduces."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import wfagg as wf
    from repro.distributed import spmd
    from repro.distributed.robust_allreduce import (
        RobustAggConfig, init_tree_agg_state, robust_allreduce_stacked)

    K = _STACKED_K
    g = {"w": jnp.zeros((K, 24, _SHARDS), jnp.float32),
         "b": jnp.zeros((K, 80), jnp.float32)}
    cfg = RobustAggConfig(
        method="wfagg", layout="stacked", backend="reference",
        wfagg=wf.WFAggConfig(f=1, transient=1, window=2))
    state = init_tree_agg_state(cfg, K, jax.tree.map(lambda x: x[0], g))
    mesh = spmd.aggregation_mesh(_SHARDS)
    shardings = {"w": NamedSharding(mesh, P(None, None, "model")),
                 "b": NamedSharding(mesh, P(None, "model"))}
    out_sh = jax.tree.map(lambda s: NamedSharding(mesh, P(*s.spec[1:])),
                          shardings)
    st_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                         state)._replace(prev=shardings)
    fn = jax.jit(lambda grads, st: robust_allreduce_stacked(grads, cfg, st),
                 in_shardings=(shardings, st_sh),
                 out_shardings=(out_sh, st_sh, None))
    return fn, (g, state)


def _build_stacked_mode_b():
    import jax
    import jax.numpy as jnp

    from repro.core import wfagg as wf
    from repro.distributed.robust_allreduce import (
        RobustAggConfig, init_tree_agg_state, robust_allreduce_stacked)

    K = _STACKED_K
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (K, 24, 6)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (K, 80))}
    g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
    cfg = RobustAggConfig(
        method="wfagg", layout="stacked", backend="fused",
        wfagg=wf.WFAggConfig(f=1, transient=1, window=2))
    state = init_tree_agg_state(cfg, K, jax.tree.map(lambda x: x[0], g))
    fn = jax.jit(lambda grads, st: robust_allreduce_stacked(grads, cfg, st))
    return fn, (g, state)


def _compile_once_probe() -> int:
    """Drive 5 churn rounds through 5 DIFFERENT graphs and report the
    trace-cache size — the compile-once claim on live executables (this
    is the one runtime-layer rule: it executes, the rest only trace)."""
    import jax.numpy as jnp

    from repro.dfl import dynamics as dyn
    from repro.dfl.engine import DFLConfig, build_round_fn, init_dfl_state

    topo, data, _ = _ring_fixture()
    cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp")
    sched = dyn.churn_schedule(topo, 5, seed=7, p_leave=0.4)
    fn = build_round_fn(cfg, topo, data, dynamic=True)
    state = init_dfl_state(cfg, topo, degree=sched.width)
    for r in range(sched.rounds):
        state = fn(state, jnp.asarray(sched.neighbor_idx[r]),
                   jnp.asarray(sched.valid[r]),
                   jnp.asarray(sched.malicious[r]))
    return fn._cache_size()


@functools.lru_cache(maxsize=1)
def entry_points() -> Dict[str, EntryPoint]:
    """Name -> EntryPoint, in lint order."""
    from repro.core.wfagg import WFAggConfig, alt_wfagg_config

    _, _, sched = _ring_fixture()
    K = int(sched.width)
    nkd = (_N, K, MLP_D)

    entries = [
        EntryPoint(
            name="one_launch_round",
            description="fused single-launch dynamic WFAgg round "
                        "(backend='fused', the default)",
            build=lambda: _build_dynamic_round("wfagg"),
            expected_launches=1, nkd=nkd,
            compile_once=_compile_once_probe,
            passes=(("single-launch indexed gossip round (the default)",
                     WFAggConfig(),
                     dict(include_gather=True, indexed=True), 2),),
        ),
        EntryPoint(
            name="one_launch_round_alt",
            description="fused single-launch Alt-WFAgg round (in-kernel "
                        "Gram + Multi-Krum/Clustering)",
            build=lambda: _build_dynamic_round("alt_wfagg"),
            expected_launches=1, nkd=nkd,
            passes=(("single-launch indexed Alt-WFAgg (Gram folded into "
                     "the stats phase)", alt_wfagg_config(),
                     dict(include_gather=True, indexed=True), 2),),
        ),
        EntryPoint(
            name="reference_round",
            description="valid-aware pure-jnp reference oracle "
                        "(backend='reference'; gather rules suppressed — "
                        "materializing the gossip tensor is its job)",
            build=_build_reference_round,
            expected_launches=0, nkd=nkd,
            suppress=frozenset({"no-nkd-buffer", "gather-free-model-dim"}),
            passes=(("fused gathered gossip round (gather + stats + "
                     "combine)", WFAggConfig(),
                     dict(include_gather=True), 3),),
        ),
        EntryPoint(
            name="dynamic_scan",
            description="whole-schedule lax.scan (run_dynamic_experiment's "
                        "one jit: rounds + in-scan evaluation)",
            build=_build_dynamic_scan,
            expected_launches=1, nkd=nkd,
        ),
        EntryPoint(
            name="dynamic_scan_telemetry",
            description="the same whole-schedule scan with the flight "
                        "recorder's decision plane on (telemetry=True): "
                        "packed verdict bitmasks as pure traced scan "
                        "outputs — launch count unchanged, no host "
                        "transfer enters the scan (docs/OBSERVABILITY.md)",
            build=lambda: _build_dynamic_scan(telemetry=True),
            expected_launches=1, nkd=nkd,
        ),
        EntryPoint(
            name="chaos_scan",
            description="the fault-injected whole-schedule scan: drop/"
                        "stale/duplicate/corrupt/crash fault stacks + the "
                        "stale-delivery ring as scan carry, telemetry on "
                        "— one compile, launch count unchanged vs the "
                        "clean scan, no in-scan host transfer "
                        "(docs/FAULTS.md)",
            build=_build_chaos_scan,
            expected_launches=1, nkd=nkd,
        ),
        EntryPoint(
            name="stacked_mode_b",
            description="stacked robust_allreduce mode-B (N=1 identity-"
                        "slate instance of the round kernel)",
            build=_build_stacked_mode_b,
            expected_launches=1, nkd=(1, _STACKED_K, _STACKED_D),
            passes=(("fused single-node aggregation (stats + combine)",
                     WFAggConfig(), {}, 2),
                    ("fused single-node Alt-WFAgg (Gram folded into the "
                     "stats phase)", alt_wfagg_config(), {}, 2)),
        ),
        EntryPoint(
            name="sharded_one_launch_round",
            description="D-sharded gossip round under shard_map over the "
                        "(1, 8) mesh: per-shard statistics-phase launch + "
                        "O(N*K) psum + shard-local combine-phase launch "
                        "(distributed/spmd.py; needs 8 devices)",
            build=_build_sharded_round,
            expected_launches=2, nkd=(_N, _DEGREE, MLP_D_PAD),
            contract=collectives.wfagg_round_contract(
                n=_N, k=_DEGREE, n_shards=_SHARDS, rounds=1),
            min_devices=_SHARDS,
            passes=(("sharded round = the two phases as two launches "
                     "per shard", WFAggConfig(),
                     dict(include_gather=True, indexed=True), 2),),
        ),
        EntryPoint(
            name="sharded_dynamic_scan",
            description="whole dynamic schedule scanned INSIDE the "
                        "shard_map region — the (N, d/S) shard is the "
                        "scan carry, with temporal slot-history "
                        "realignment per round (needs 8 devices)",
            build=_build_sharded_scan,
            expected_launches=2, nkd=(_N, _DEGREE, MLP_D_PAD),
            contract=collectives.wfagg_round_contract(
                n=_N, k=_DEGREE, n_shards=_SHARDS, rounds=_ROUNDS),
            min_devices=_SHARDS,
        ),
        EntryPoint(
            name="sharded_stacked_mode_b",
            description="mode-B stacked allreduce jitted over the (1, 8) "
                        "mesh via the pure-jnp reference stats (GSPMD-"
                        "partitionable; statistics meet in O(K^2) "
                        "all-reduces; needs 8 devices)",
            build=_build_sharded_stacked,
            expected_launches=0, nkd=(1, _STACKED_K, 24 * _SHARDS + 80),
            contract=collectives.stacked_allreduce_contract(
                k=_STACKED_K, n_shards=_SHARDS),
            min_devices=_SHARDS,
        ),
    ]
    return {e.name: e for e in entries}
