"""Chaos transport: fault schedules must be deterministic, the
sanitizer must keep every WFAgg backend finite when any single payload
is corrupted, transport re-keying must obey the staleness budget, a
fault-free fault schedule must reproduce the clean scan bit-exactly,
telemetry must not perturb trajectories, and kill-and-resume must equal
the uninterrupted run bit-for-bit (docs/FAULTS.md)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wfagg as wf
from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl import dynamics as dyn
from repro.dfl import faults as flt
from repro.dfl.engine import DFLConfig, run_dynamic_experiment
from repro.obs.decision import FAULT_BITS


def _topo(n=10, degree=4, n_mal=2, seed=0):
    return make_topology(n_nodes=n, degree=degree, n_malicious=n_mal,
                         kind="ring", placement="close", seed=seed)


def _ring_idx(N, K):
    return jnp.asarray(
        [[(n + j + 1) % N for j in range(K)] for n in range(N)],
        jnp.int32)


def _matrix_state(N, K, d, window):
    return wf.TemporalState(
        prev=jnp.zeros((N, d)), hist_s=jnp.zeros((N, window, K)),
        hist_b=jnp.zeros((N, window, K)),
        count=jnp.zeros((N,), jnp.int32), t=jnp.zeros((N,), jnp.int32))


# ---------------------------------------------------------------------------
# fault schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", flt.FAULT_NAMES)
def test_fault_schedules_deterministic_and_shaped(name):
    """Same (name, shape, intensity, seed) -> byte-identical schedule;
    shapes track the topology schedule; lags never exceed the ring."""
    topo = _topo()
    sched = dyn.make_schedule("churn", topo, 5, seed=1)
    f1 = flt.make_fault_schedule(name, sched, 0.4, seed=7)
    f2 = flt.make_fault_schedule(name, sched, 0.4, seed=7)
    for field in ("drop", "lag", "dup", "corrupt", "down"):
        assert np.array_equal(getattr(f1, field), getattr(f2, field)), field
    R, N, K = sched.rounds, sched.n_nodes, sched.width
    assert f1.drop.shape == (R, N, K) and f1.down.shape == (R, N)
    assert f1.rounds == R
    assert f1.lag.min() >= 0 and f1.lag.max() <= f1.config.ring_depth
    summary = f1.summary()
    if name == "none":
        assert all(v == 0 for v in summary.values())
    elif name != "stale":  # stale only schedules lags
        assert any(v > 0 for v in summary.values()), summary


def test_make_faulty_schedule_pairs_and_unknown_name():
    topo = _topo()
    sched, fs = dyn.make_faulty_schedule("churn", topo, 4, fault="drop",
                                         intensity=0.3, seed=2, fault_seed=3)
    assert fs.rounds == sched.rounds
    assert fs.drop.shape == (4, topo.n_nodes, sched.width)
    with pytest.raises(ValueError, match="unknown fault"):
        flt.make_fault_schedule("nope", sched, 0.1)


# ---------------------------------------------------------------------------
# sanitizer: every backend finite under a corrupted payload
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["fused", "reference"])
@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_sanitizer_every_backend_finite(backend, bad):
    """One non-finite candidate row must never reach the coordinate-wise
    median / mean-fallback paths: the sanitizer demotes the edges that
    read it BEFORE filter statistics, and the aggregate plus the carried
    temporal state stay finite on both backends."""
    N, K, d = 8, 4, 300
    cfg = wf.WFAggConfig(backend=backend, transient=1, window=2)
    idx = _ring_idx(N, K)
    valid = jnp.ones((N, K), bool)
    st = _matrix_state(N, K, d, cfg.window)
    for r in range(3):
        local = np.array(
            jax.random.normal(jax.random.PRNGKey(40 + r), (N, d)) + 0.3,
            np.float32)
        u = local.copy()
        if r == 1:
            # node 2's TRANSMITTED payload arrives bit-damaged (its own
            # local copy is fine — corruption is a transport event)
            u[2, :] = bad
        out, st, info = wf.wfagg_batch(jnp.asarray(local), jnp.asarray(u),
                                       st, cfg, neighbor_idx=idx,
                                       valid=valid)
        assert np.isfinite(np.asarray(out)).all(), (backend, bad, r)
        assert np.isfinite(np.asarray(st.prev)).all(), (backend, bad, r)
        assert np.isfinite(np.asarray(info["weights"])).all()
        if r == 1:
            # every edge reading the corrupted row was demoted
            demoted = np.asarray(idx) == 2
            w = np.asarray(info["weights"])
            assert (w[demoted] == 0).all(), (backend, bad)


def test_sanitizer_static_reference_path_finite():
    """The valid=None per-node reference dispatch (a different code
    path) also never lets a NaN candidate through to the aggregate."""
    N, K, d = 6, 4, 200
    cfg = wf.WFAggConfig(backend="reference", transient=1, window=2)
    idx = _ring_idx(N, K)
    local = np.array(jax.random.normal(jax.random.PRNGKey(3), (N, d)) + 0.2,
                     np.float32)
    u = local.copy()
    u[1, :] = np.nan
    out, _, info = wf.wfagg_batch(jnp.asarray(local), jnp.asarray(u),
                                  _matrix_state(N, K, d, cfg.window), cfg,
                                  neighbor_idx=idx)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(info["weights"])).all()


def test_sanitizer_off_reproduces_the_bug():
    """With the guard disabled the NaN propagates — proof the sanitizer
    (not luck) is what keeps the aggregate finite."""
    N, K, d = 8, 4, 200
    cfg = wf.WFAggConfig(backend="reference", use_temporal=False,
                         sanitize=False)
    local = np.array(jax.random.normal(jax.random.PRNGKey(4), (N, d)) + 0.2,
                     np.float32)
    u = local.copy()
    u[2, :] = np.nan
    out, _, _ = wf.wfagg_batch(jnp.asarray(local), jnp.asarray(u), None, cfg,
                               neighbor_idx=_ring_idx(N, K),
                               valid=jnp.ones((N, K), bool))
    assert not np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("aggregator", ["mean", "median", "wfagg"])
def test_engine_finite_under_corruption(aggregator):
    """End-to-end: a corrupt-heavy fault schedule through the one-jit
    chaos scan leaves every aggregator's accuracy series finite — the
    transport sanitizer guards the baseline (mean / coordinate-median)
    paths too, not just WFAgg's filter bank."""
    topo = _topo()
    data = SyntheticImages(seed=0)
    sched, fs = dyn.make_faulty_schedule("churn", topo, 3, fault="corrupt",
                                         intensity=0.5, seed=1, fault_seed=2)
    cfg = DFLConfig(aggregator=aggregator, attack="none", model="mlp",
                    batches_per_round=1)
    out = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                 faults=fs)
    series = np.asarray(out["series"]["acc_benign_mean"])
    assert np.isfinite(series).all()
    assert np.isfinite(out["final"]["acc_benign_mean"])
    assert out["faults"]["corrupt_rate"] > 0


# ---------------------------------------------------------------------------
# transport semantics
# ---------------------------------------------------------------------------

def test_apply_transport_rekeys_and_budgets():
    """Unit semantics of the stacked-ring re-keying: fresh edges read the
    flat block, scheduled lags read the ring block, corrupt edges read
    the bank, drops fall back to an aged redelivery, and a lag beyond
    the staleness budget (or a down receiver) demotes the edge."""
    M, K, d = 6, 3, 16
    cfg = flt.FaultConfig(ring_depth=2, staleness_budget=1, bank_size=4,
                          max_lag=2)
    idx = _ring_idx(M, K)
    valid = jnp.ones((M, K), bool)
    flat = jnp.ones((M, d), jnp.float32)
    ts = flt.TransportState(
        ring=2.0 * jnp.ones((cfg.ring_depth, M, d), jnp.float32),
        served_lag=jnp.zeros((M, K), jnp.int32))
    drop = jnp.zeros((M, K), bool).at[0, 0].set(True)
    lag = jnp.zeros((M, K), jnp.int32).at[1, 1].set(1).at[2, 2].set(2)
    corrupt = jnp.zeros((M, K), bool).at[3, 0].set(True)
    down = jnp.zeros((M,), bool).at[4].set(True)
    fr = flt.FaultRound(drop=drop, lag=lag, dup=jnp.zeros((M, K), bool),
                        corrupt=corrupt, down=down)
    out = flt.apply_transport(flat, ts, idx, valid, fr, cfg,
                              jnp.asarray(5, jnp.int32))

    eff_idx = np.asarray(out.eff_idx)
    eff_valid = np.asarray(out.eff_valid)
    nidx = np.asarray(idx)
    # fresh edge: reads the flat block at the neighbor's row
    assert eff_idx[5, 0] == nidx[5, 0] and eff_valid[5, 0]
    # dropped edge: re-serves last delivery aged to lag 1 (within budget)
    assert eff_idx[0, 0] == 1 * M + nidx[0, 0]
    assert eff_valid[0, 0] and out.dropped[0, 0] and out.stale[0, 0]
    # scheduled lag 1: ring block, still valid, flagged stale
    assert eff_idx[1, 1] == 1 * M + nidx[1, 1] and eff_valid[1, 1]
    assert out.stale[1, 1]
    # scheduled lag 2: beyond staleness_budget=1 -> demoted, not served
    assert not eff_valid[2, 2] and out.dropped[2, 2]
    # corrupt edge: re-keyed into the bank block past the ring
    assert eff_idx[3, 0] >= (cfg.ring_depth + 1) * M
    assert out.corrupt[3, 0]
    # down receiver loses its whole slate
    assert not eff_valid[4].any()
    # the sanitized stacked matrix is finite everywhere
    assert np.isfinite(np.asarray(out.full)).all()
    # sender crash: every edge READING a down sender is a drop
    sender_down = np.asarray(down)[nidx]
    assert np.asarray(out.dropped)[sender_down].all()


def test_served_lag_walks_the_ring_until_budget():
    """Consecutive drops on one edge re-age the last delivery round over
    round; the edge stays valid while within budget, then demotes."""
    M, K, d = 4, 2, 8
    cfg = flt.FaultConfig(ring_depth=3, staleness_budget=2, max_lag=2)
    idx = _ring_idx(M, K)
    valid = jnp.ones((M, K), bool)
    flat = jnp.ones((M, d), jnp.float32)
    ts = flt.init_transport_state(cfg, M, K, d)
    zeros = jnp.zeros((M, K), bool)
    fr = flt.FaultRound(drop=jnp.ones((M, K), bool),
                        lag=jnp.zeros((M, K), jnp.int32), dup=zeros,
                        corrupt=zeros, down=jnp.zeros((M,), bool))
    lags, valids = [], []
    for rnd in range(4):
        out = flt.apply_transport(flat, ts, idx, valid, fr, cfg,
                                  jnp.asarray(rnd + 10, jnp.int32))
        lags.append(int(np.asarray(out.served_lag)[0, 0]))
        valids.append(bool(np.asarray(out.eff_valid)[0, 0]))
        ts = flt.advance_ring(ts, flat, out.served_lag)
    assert lags == [1, 2, 3, 3]          # ages until the ring depth caps it
    assert valids == [True, True, False, False]  # budget=2 demotes at lag 3


# ---------------------------------------------------------------------------
# equivalences: fault-none == clean, telemetry changes nothing
# ---------------------------------------------------------------------------

def test_fault_none_equals_clean_scan():
    """An all-quiet fault schedule through the chaos scan reproduces the
    clean scan bit-exactly — the transport layer at rest is a no-op."""
    topo = _topo()
    data = SyntheticImages(seed=0)
    sched = dyn.make_schedule("churn", topo, 4, seed=1)
    cfg = DFLConfig(aggregator="wfagg", attack="alie", model="mlp",
                    batches_per_round=1)
    clean = run_dynamic_experiment(cfg, topo, data, sched, n_test=64)
    quiet = run_dynamic_experiment(
        cfg, topo, data, sched, n_test=64,
        faults=flt.make_fault_schedule("none", sched, 0.0))
    assert np.array_equal(np.asarray(clean["series"]["acc_benign_mean"]),
                          np.asarray(quiet["series"]["acc_benign_mean"]))
    assert clean["final"]["acc_benign_mean"] == quiet["final"]["acc_benign_mean"]


def test_chaos_telemetry_off_trajectory_identical():
    """Fault attribution is observation, not intervention: the same
    chaos run with and without the decision plane yields bit-identical
    accuracy series, and with it on, the verdict carries fault bits."""
    topo = _topo()
    data = SyntheticImages(seed=0)
    sched, fs = dyn.make_faulty_schedule("churn", topo, 4, fault="chaos",
                                         intensity=0.5, seed=1, fault_seed=3)
    cfg = DFLConfig(aggregator="wfagg", attack="alie", model="mlp",
                    batches_per_round=1)
    silent = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                    faults=fs)
    loud = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                  faults=fs, telemetry=True)
    assert np.array_equal(np.asarray(silent["series"]["acc_benign_mean"]),
                          np.asarray(loud["series"]["acc_benign_mean"]))
    verdict = np.asarray(loud["telemetry"]["verdict"])
    fault_bits = ((verdict >> FAULT_BITS["dropped"])
                  | (verdict >> FAULT_BITS["stale"])
                  | (verdict >> FAULT_BITS["corrupt"])) & 1
    assert fault_bits.any()

    from repro.obs import report as obs_report
    frates = obs_report.fault_rates(verdict)
    attr = obs_report.fault_attribution(frates)
    assert attr["dominant"] in ("dropped", "stale", "corrupt")
    # and a clean run's verdict carries NO fault bits
    clean = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                   telemetry=True)
    cv = np.asarray(clean["telemetry"]["verdict"])
    assert not obs_report.fault_rates(cv)["any"].any()


# ---------------------------------------------------------------------------
# crash-exact kill-and-resume
# ---------------------------------------------------------------------------

def test_kill_and_resume_bit_exact(tmp_path):
    """Stop a chaos run mid-schedule, snapshot, restore, finish: the
    stitched trajectory equals the uninterrupted one bit-for-bit —
    models, WFAgg-T ring buffers, transport ring and the in-flight fault
    schedules all survive the round trip (train/checkpoint.py)."""
    topo = _topo()
    data = SyntheticImages(seed=0)
    R, stop = 6, 3
    sched, fs = dyn.make_faulty_schedule("churn", topo, R, fault="chaos",
                                         intensity=0.4, seed=1, fault_seed=3)
    cfg = DFLConfig(aggregator="wfagg", attack="alie", model="mlp",
                    batches_per_round=1)
    full = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                  faults=fs)
    ckpt_dir = str(tmp_path / "snap")
    part = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                  faults=fs, stop_after=stop,
                                  checkpoint_dir=ckpt_dir)
    assert part["rounds_run"] == [0, stop]
    resumed = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                     faults=fs, resume_from=ckpt_dir)
    assert resumed["rounds_run"] == [stop, R]

    full_series = np.asarray(full["series"]["acc_benign_mean"])
    stitched = np.concatenate([
        np.asarray(part["series"]["acc_benign_mean"]),
        np.asarray(resumed["series"]["acc_benign_mean"])])
    assert np.array_equal(full_series, stitched)
    assert full["final"]["acc_benign_mean"] == resumed["final"]["acc_benign_mean"]
    assert full["final"]["r_squared"] == resumed["final"]["r_squared"]


def test_checkpoint_requires_faults_and_metadata(tmp_path):
    topo = _topo()
    data = SyntheticImages(seed=0)
    sched = dyn.make_schedule("churn", topo, 3, seed=1)
    cfg = DFLConfig(aggregator="wfagg", attack="none", model="mlp")
    with pytest.raises(NotImplementedError, match="chaos scan"):
        run_dynamic_experiment(cfg, topo, data, sched, stop_after=1)
    from repro.train import checkpoint as ckpt
    with pytest.raises(ValueError, match="round"):
        ckpt.save_experiment_checkpoint(str(tmp_path), "x",
                                        {"a": jnp.zeros(2)}, [jnp.zeros(2)])
