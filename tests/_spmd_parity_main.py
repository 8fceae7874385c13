"""Subprocess body for the 8-virtual-device SPMD parity checks.

Run via ``python tests/_spmd_parity_main.py <mode>`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the
environment (set BEFORE jax imports — hence the subprocess; the tier-1
suite itself runs on however many devices the session has).

Modes:
  round   one sharded gossip round vs wfagg_batch(fused_two_launch)
  scan    R sharded rounds (lax.scan inside shard_map, with temporal
          slot-history realignment) vs the same loop single-process
  stacked mode-B robust_allreduce_stacked(backend="reference") jitted
          over the (1, 8) mesh vs the unsharded call
  nonfinite  one round with a NaN payload: the sharded path sanitizes it
          (edges that read it demoted) exactly like the one-device round
  engine  two full DFL rounds (train + attack + gossip) with
          DFLConfig.mesh_model_shards=8 vs the single-process engine,
          and local training not partitioned over the model axis
  lint    python -m repro.analysis over the three sharded entries,
          in-process — must exit 0 (zero gate failures)
  gather_fire  the doctored replicated-output twin of the sharded
          round — the full-d all-gather GSPMD inserts MUST trip
          spmd-model-dim-allgather and spmd-collective-contract

Prints PARITY_OK:<mode> on success so the pytest wrapper can assert on
stdout rather than exit codes alone.
"""
import re
import sys

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import wfagg as wf
from repro.distributed import spmd

N, K, D, ROUNDS, SEED = 10, 4, 50890, 3, 7


def _cfg():
    return wf.WFAggConfig(backend="fused_two_launch", f=1, window=3,
                          transient=1)


def _fixture(rng, rounds=1):
    models = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    # one Byzantine column so the filters actually reject something
    models = models.at[3].multiply(40.0)
    idx = np.stack([rng.choice(np.delete(np.arange(N), n), size=K,
                               replace=False) for n in range(N)])
    sched_idx = jnp.asarray(
        np.stack([np.roll(idx, r, axis=1) for r in range(rounds)]),
        dtype=jnp.int32)
    # degree churn: drop one slot per node in later rounds
    sched_valid = np.ones((rounds, N, K), dtype=bool)
    for r in range(1, rounds):
        sched_valid[r, np.arange(N), (np.arange(N) + r) % K] = False
    return models, sched_idx, jnp.asarray(sched_valid)


def _state(prev):
    return spmd.batched_matrix_state(N, K, D, _cfg().window)._replace(
        prev=prev)


def _close(name, a, b, atol=2e-4, rtol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    if not np.allclose(a, b, atol=atol, rtol=rtol):
        err = np.max(np.abs(a - b))
        raise SystemExit(f"parity FAIL [{name}]: max |diff| = {err}")


def check_round():
    cfg = _cfg()
    mesh = spmd.aggregation_mesh(8)
    rng = np.random.default_rng(SEED)
    models, sched_idx, _ = _fixture(rng)
    idx = sched_idx[0]
    state = _state(prev=models * 0.97)

    ref_out, ref_state, ref_info = wf.wfagg_batch(
        models, models, state, cfg, neighbor_idx=idx)
    out, new_state, info = spmd.wfagg_batch_sharded(
        models, models, state, cfg, idx, mesh=mesh)

    for m in ("mask_d", "mask_c", "mask_t"):
        if not np.array_equal(np.asarray(info[m]), np.asarray(ref_info[m])):
            raise SystemExit(f"parity FAIL [round {m}]: masks differ")
    _close("round weights", info["weights"], ref_info["weights"], atol=1e-6)
    _close("round out", out, ref_out)
    _close("round prev", new_state.prev, ref_state.prev)
    _close("round hist_s", new_state.hist_s, ref_state.hist_s, atol=1e-4)
    print("PARITY_OK:round")


def check_nonfinite():
    cfg = _cfg()
    mesh = spmd.aggregation_mesh(8)
    rng = np.random.default_rng(SEED + 3)
    models, sched_idx, _ = _fixture(rng)
    models = models.at[5, 17].set(jnp.nan)
    idx = sched_idx[0]
    state = _state(prev=models * 0.97)

    ref_out, _, ref_info = wf.wfagg_batch(
        models, models, state, cfg, neighbor_idx=idx)
    out, _, info = spmd.wfagg_batch_sharded(
        models, models, state, cfg, idx, mesh=mesh)
    if np.asarray(info["valid"]).all():
        raise SystemExit("parity FAIL [nonfinite]: no edge was demoted")
    for m in ("valid", "mask_d", "mask_c", "mask_t"):
        if not np.array_equal(np.asarray(info[m]), np.asarray(ref_info[m])):
            raise SystemExit(f"parity FAIL [nonfinite {m}]: masks differ")
    # node 5's own (non-finite) model anchors its combine on both paths
    keep = np.arange(N) != 5
    _close("nonfinite out", np.asarray(out)[keep], np.asarray(ref_out)[keep])
    print("PARITY_OK:nonfinite")


def check_scan():
    cfg = _cfg()
    mesh = spmd.aggregation_mesh(8)
    rng = np.random.default_rng(SEED + 1)
    models, sched_idx, sched_valid = _fixture(rng, rounds=ROUNDS)
    state = _state(prev=models)

    # single-process reference: the same realign + round loop
    m_ref, st_ref = models, state
    prev_idx, prev_val = sched_idx[0], jnp.ones_like(sched_valid[0])
    for r in range(ROUNDS):
        idx, val = sched_idx[r], sched_valid[r]
        st_ref = wf.realign_temporal_history(st_ref, prev_idx, prev_val,
                                             idx, val)
        m_ref, st_ref, _ = wf.wfagg_batch(m_ref, m_ref, st_ref, cfg,
                                          neighbor_idx=idx, valid=val)
        prev_idx, prev_val = idx, val

    pad = spmd.pad_to_shards(models, 8)
    st_pad = state._replace(prev=spmd.pad_to_shards(state.prev, 8))
    m_sh, st_sh = spmd.wfagg_scan_sharded(pad, st_pad, cfg, sched_idx,
                                          sched_valid, mesh=mesh)
    _close("scan models", m_sh[..., :D], m_ref)
    _close("scan prev", st_sh.prev[..., :D], st_ref.prev)
    _close("scan hist_s", st_sh.hist_s, st_ref.hist_s, atol=1e-4)
    print("PARITY_OK:scan")


def check_stacked():
    from repro.distributed.robust_allreduce import (
        RobustAggConfig, init_tree_agg_state, robust_allreduce_stacked)

    K_, rng = 6, np.random.default_rng(SEED + 2)
    g = {"w": jnp.asarray(rng.normal(size=(K_, 24, 8)).astype(np.float32)),
         "b": jnp.asarray(rng.normal(size=(K_, 80)).astype(np.float32))}
    cfg = RobustAggConfig(method="wfagg", layout="stacked",
                          backend="reference",
                          wfagg=wf.WFAggConfig(f=1, transient=1, window=2))
    state = init_tree_agg_state(cfg, K_, jax.tree.map(lambda x: x[0], g))

    ref_out, ref_state, _ = jax.jit(
        lambda s, st: robust_allreduce_stacked(s, cfg, st))(g, state)

    mesh = spmd.aggregation_mesh(8)
    shardings = {"w": NamedSharding(mesh, P(None, None, "model")),
                 "b": NamedSharding(mesh, P(None, "model"))}
    repl = NamedSharding(mesh, P())
    out_sh = jax.tree.map(lambda s: NamedSharding(mesh, P(*s.spec[1:])),
                          shardings)
    # prev keeps the candidate axis -> shard like the stacked input
    st_sh = jax.tree.map(lambda _: repl, state)._replace(prev=shardings)
    fn = jax.jit(lambda s, st: robust_allreduce_stacked(s, cfg, st),
                 in_shardings=(shardings, st_sh),
                 out_shardings=(out_sh, st_sh, None))
    out, new_state, _ = fn(g, state)
    _close("stacked w", out["w"], ref_out["w"])
    _close("stacked b", out["b"], ref_out["b"])
    _close("stacked hist_s", new_state.hist_s, ref_state.hist_s, atol=1e-4)
    print("PARITY_OK:stacked")


def check_engine():
    from repro.core.topology import make_topology
    from repro.data.synthetic import SyntheticImages
    from repro.dfl import dynamics as dyn
    from repro.dfl.engine import DFLConfig, build_round_fn, init_dfl_state

    topo = make_topology(n_nodes=N, degree=K, n_malicious=2, kind="ring",
                         seed=0)
    data = SyntheticImages()
    sched = dyn.churn_schedule(topo, 2, seed=1)
    finals, compute = [], []
    for shards in (0, 8):
        cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp",
                        wfagg_backend="fused_two_launch",
                        mesh_model_shards=shards)
        fn = build_round_fn(cfg, topo, data, dynamic=True)
        state = init_dfl_state(cfg, topo, degree=sched.width)
        for r in range(2):
            args = (jnp.asarray(sched.neighbor_idx[r]),
                    jnp.asarray(sched.valid[r]),
                    jnp.asarray(sched.malicious[r]))
            if r == 0:
                hlo = fn.lower(state, *args).compile().as_text()
                compute.append(sorted(set(re.findall(
                    r"= (\S+) (?:dot|convolution)\(", hlo))))
            state = fn(state, *args)
        finals.append(state)
    # local training stays whole on every device: the sharded round's
    # dots have the one-device round's shapes, none split over 'model'
    if compute[0] != compute[1]:
        raise SystemExit(f"parity FAIL [engine compute]: one-device dots "
                         f"{compute[0]} vs sharded {compute[1]}")
    ref, sh = finals
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref.node_params),
            jax.tree_util.tree_leaves_with_path(sh.node_params)):
        _close(f"engine params {jax.tree_util.keystr(path)}", b, a, atol=3e-4)
    _close("engine prev", sh.temporal.prev, ref.temporal.prev, atol=3e-4)
    print("PARITY_OK:engine")


def check_lint():
    from repro.analysis.__main__ import main as lint_main

    rc = lint_main(["--entry", "sharded_one_launch_round",
                    "--entry", "sharded_dynamic_scan",
                    "--entry", "sharded_stacked_mode_b"])
    if rc != 0:
        raise SystemExit(f"parity FAIL [lint]: exit code {rc}")
    print("PARITY_OK:lint")


def check_gather_fire():
    import dataclasses

    from repro.analysis.artifacts import Artifacts
    from repro.analysis.entry_points import entry_points
    from repro.analysis.rules import run_rules

    entry = entry_points()["sharded_one_launch_round"]
    cfg = _cfg()
    mesh = spmd.aggregation_mesh(8)
    d_pad = spmd.shard_padded_d(D, 8)
    fn, args = spmd.sharded_round_jit(cfg, mesh, n=N, k=K, d=d_pad,
                                      replicate_out=True)
    entry = dataclasses.replace(entry, build=lambda: (fn, args))
    findings = run_rules(Artifacts(fn, args), entry, {})
    fired = {f.rule for f in findings if f.severity == "error"}
    want = {"spmd-model-dim-allgather", "spmd-collective-contract"}
    if not want <= fired:
        raise SystemExit(f"parity FAIL [gather_fire]: expected {want} "
                         f"to fire on the replicated twin, got {fired}")
    print("PARITY_OK:gather_fire")


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "round"
    if len(jax.devices()) < 8:
        raise SystemExit("need 8 devices — set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8")
    {"round": check_round, "nonfinite": check_nonfinite,
     "scan": check_scan, "stacked": check_stacked,
     "engine": check_engine, "lint": check_lint,
     "gather_fire": check_gather_fire}[mode]()
