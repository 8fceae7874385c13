"""Adaptive (defense-aware) adversaries + topology attacks + the
robustness gate: band_rider's sent models must land verifiably inside
the WFAgg-T acceptance bands it rides, min_max must sit under the
distance-filter radii, eclipse/dos/collusion schedules must be
deterministic and mask-consistent, all three WFAgg backends must agree
under every adaptive attack, the baseline aggregators must run dynamic
schedules through their valid-mask-aware variants, and
scripts/robustness_gate.py must reject a doctored run (mean passed off
as wfagg under IPM)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators as agg_lib
from repro.core import attacks as atk
from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl import dynamics as dyn
from repro.dfl import engine as eng

REPO = os.path.join(os.path.dirname(__file__), os.pardir)


def _close_topo(n=10, degree=4, n_mal=2, seed=0):
    return make_topology(n_nodes=n, degree=degree, n_malicious=n_mal,
                         kind="ring", seed=seed, placement="close")


# ---------------------------------------------------------------------------
# band_rider: in-band by construction
# ---------------------------------------------------------------------------

def test_band_rider_inside_temporal_bands():
    """Run the real engine past the WFAgg-T transient, then replay the
    attack step by hand: every (benign victim, malicious sender) edge
    with an active band must see the attacker's sent model INSIDE the
    band — s_t and b_t both — because the attack solved for exactly
    that.  Also: the ride must be a real deviation (not the attacker's
    own previous model)."""
    topo = _close_topo()
    data = SyntheticImages(seed=0)
    cfg = eng.DFLConfig(aggregator="wfagg", attack="band_rider",
                        model="mlp", seed=0, batches_per_round=1)
    state = eng.init_dfl_state(cfg, topo)
    round_fn = eng.build_round_fn(cfg, topo, data)
    for _ in range(6):                      # transient=3: bands active now
        state = round_fn(state)

    mal = jnp.asarray(topo.malicious)
    nidx = jnp.asarray(topo.neighbor_indices)
    params, _ = eng._local_train(cfg, data, mal, state.node_params,
                                 state.node_momentum, state.rnd)
    flat, _ = eng._ravel_nodes(params)
    view = eng._defense_view(cfg, state, nidx, None)
    assert view is not None and view.tbands is not None
    attacked = np.asarray(eng._apply_attacks(cfg, mal, flat, state.rnd, view))

    tb = np.asarray(view.tbands).reshape(topo.n_nodes, 4, -1)
    prev = np.asarray(view.prev)
    malv = np.asarray(topo.malicious)
    idx = np.asarray(topo.neighbor_indices)
    checked = 0
    for n in range(topo.n_nodes):
        if malv[n]:
            continue
        for k in range(idx.shape[1]):
            j = idx[n, k]
            lo_d, hi_d, lo_c, hi_c = tb[n, :, k]
            if not malv[j] or not np.isfinite(hi_d):
                continue
            p, c = prev[j], attacked[j]
            s = float(((c - p) ** 2).sum())
            b = 1.0 - float((c * p).sum()
                            / max(np.linalg.norm(c) * np.linalg.norm(p),
                                  1e-12))
            tol_d = 1e-3 * max(1.0, abs(hi_d))
            assert lo_d - tol_d <= s <= hi_d + tol_d, (n, k, s, lo_d, hi_d)
            assert lo_c - 1e-4 <= b <= hi_c + 1e-4, (n, k, b, lo_c, hi_c)
            assert s > 0.0                  # a ride, not a replay
            checked += 1
    assert checked > 0                      # bands were actually active


def test_band_rider_falls_back_without_view():
    """No DefenseView (or a bandless one) -> ALIE-style mimicry from the
    benign cohort, never NaNs."""
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    mal = jnp.asarray(np.array([1, 0, 0, 0, 1, 0, 0, 0], bool))
    cfg = atk.AttackConfig(name="band_rider")
    for view in (None, atk.DefenseView(prev=u)):
        out = np.asarray(atk.apply_matrix_attack(
            "band_rider", u, mal, jax.random.PRNGKey(0), cfg, view=view))
        assert np.isfinite(out).all()
        ben = np.asarray(u)[~np.asarray(mal)]
        expect = ben.mean(0) - cfg.alie_zmax * ben.std(0)
        assert np.allclose(out[0], expect, atol=1e-5)
        assert np.allclose(out[4], expect, atol=1e-5)
        # benign rows untouched
        assert np.array_equal(out[1], np.asarray(u)[1])


def test_min_max_under_filter_radii():
    """The min_max deviation must keep the attacked model within the max
    pairwise benign distance of EVERY benign model (the Krum/Multi-Krum
    acceptance region) and within the benign radius around the
    coordinate median (WFAgg-D's region) — and still deviate."""
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.normal(size=(10, 64)).astype(np.float32))
    mal = jnp.asarray((np.arange(10) < 3))
    out = np.asarray(atk.apply_matrix_attack(
        "min_max", u, mal, jax.random.PRNGKey(0)))
    ben = np.asarray(u)[3:]
    c = out[0]
    assert np.array_equal(out[0], out[1])   # colluders send one model
    dmax = max(np.linalg.norm(a - b) for a in ben for b in ben)
    assert max(np.linalg.norm(c - b) for b in ben) <= dmax + 1e-3
    med = np.median(ben, axis=0)
    rmed = max(np.linalg.norm(b - med) for b in ben)
    assert np.linalg.norm(c - med) <= rmed + 1e-3
    mu = ben.mean(0)
    assert np.linalg.norm(c - mu) > 0.1 * dmax      # it actually deviates


# ---------------------------------------------------------------------------
# topology attacks
# ---------------------------------------------------------------------------
# (determinism / symmetry / padding invariants and the end-to-end runs
# are covered for ALL scenarios — including these — by the parametrized
# tests in test_dynamics.py; here: the attack SEMANTICS.)

def test_eclipse_monopolizes_victim_slate():
    topo = _close_topo()
    sched = dyn.make_schedule("eclipse", topo, 4, seed=0)
    mal = topo.malicious
    adj = sched.adjacency[-1]
    eclipsed = [n for n in range(topo.n_nodes)
                if not mal[n] and adj[n].sum() > 0
                and adj[n][mal].sum() == adj[n].sum()]
    assert len(eclipsed) == 1               # exactly one victim, fully
    v = eclipsed[0]
    assert adj[v].sum() == mal.sum()        # every attacker points at it
    # valid slots of the victim row reference only malicious senders
    senders = sched.neighbor_idx[-1, v][sched.valid[-1, v]]
    assert mal[senders].all()
    # everyone else's slate is unchanged from the base graph
    others = [n for n in range(topo.n_nodes) if n != v]
    base = topo.adjacency.copy()
    assert np.array_equal(adj[np.ix_(others, others)],
                          base[np.ix_(others, others)])
    # start > 0 delays the attack
    late = dyn.make_schedule("eclipse", topo, 4, seed=0, start=2)
    assert np.array_equal(late.adjacency[1], base)
    assert np.array_equal(late.adjacency[2], adj)


def test_dos_window_silences_victim_then_restores():
    topo = _close_topo()
    sched = dyn.make_schedule("dos", topo, 6, seed=0)   # window [2, 4)
    base_deg = topo.adjacency.sum(1)
    degs = sched.adjacency.sum(2)
    down = (degs == 0).any(axis=1)
    assert list(down) == [False, False, True, True, False, False]
    victim = int(np.flatnonzero(degs[2] == 0)[0])
    assert not topo.malicious[victim]
    # during the window the victim's padded row is all-invalid and
    # self-referential (the degree-0 local-fallback contract)
    assert not sched.valid[2, victim].any()
    assert (sched.neighbor_idx[2, victim] == victim).all()
    # outside the window the base graph is fully restored
    assert np.array_equal(sched.adjacency[0], topo.adjacency)
    assert np.array_equal(sched.adjacency[5], topo.adjacency)
    assert (degs[2] == np.where(np.arange(topo.n_nodes) == victim, 0,
                                base_deg - topo.adjacency[victim])).all()


def test_collusion_concentrates_attackers():
    topo = make_topology(n_nodes=12, degree=4, n_malicious=3, kind="ring",
                         seed=1, placement="spaced")
    sched = dyn.make_schedule("collusion", topo, 3, seed=0)
    mal = topo.malicious
    adj = sched.adjacency[0]
    att = np.flatnonzero(mal)
    # static across rounds; attackers share IDENTICAL victim sets,
    # no attacker-attacker edges
    assert all(np.array_equal(sched.adjacency[r], adj) for r in range(3))
    victims = np.flatnonzero(adj[att[0]])
    for a in att[1:]:
        assert np.array_equal(np.flatnonzero(adj[a]), victims)
    assert not adj[np.ix_(att, att)].any()
    assert not mal[victims].any()
    # each shared victim sees EVERY attacker — the concentration the
    # spaced placement was supposed to rule out
    for v in victims:
        assert adj[v][mal].sum() == len(att)
    # malicious mask rides through unchanged
    assert (sched.malicious == mal[None, :]).all()


# ---------------------------------------------------------------------------
# backend parity under the adaptive attacks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attack", atk.ADAPTIVE_ATTACKS + ("ipm",))
def test_backend_parity_under_adaptive_attacks(attack):
    """fused / reference must produce the same models
    under each adaptive attack (and the newly-registered generic "ipm"):
    the DefenseView is built from shared state, so any backend skew
    would compound round over round."""
    topo = _close_topo(n=8, degree=4, n_mal=2)
    data = SyntheticImages(seed=0)
    sched = dyn.make_schedule("eclipse", topo, 3, seed=2)
    finals = {}
    for backend in ("fused", "reference"):
        cfg = eng.DFLConfig(aggregator="wfagg", attack=attack, model="mlp",
                            seed=0, batches_per_round=1,
                            wfagg_backend=backend)
        out = eng.run_dynamic_experiment(cfg, topo, data, sched, n_test=64)
        finals[backend] = np.asarray(out["final"]["acc_all"])
    assert np.allclose(finals["fused"], finals["reference"], atol=1e-3)


# ---------------------------------------------------------------------------
# dynamic baselines (valid-mask-aware aggregators through the engine)
# ---------------------------------------------------------------------------

def test_dyn_aggregators_match_static_when_all_valid():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(8, 24)).astype(np.float32))
    valid = jnp.ones((8,), bool)
    for name, fn in agg_lib.DYN_AGGREGATORS.items():
        a_s, m_s = agg_lib.AGGREGATORS[name](u, f=2, m=2, beta=0.1)
        a_d, m_d = fn(u, valid, f=2, m=2, beta=0.1)
        assert np.allclose(np.asarray(a_s), np.asarray(a_d), atol=1e-5), name
        assert np.asarray(m_d).dtype == bool


def test_dyn_aggregators_ignore_invalid_slots():
    """Dyn result on a padded slate == static result on the compacted
    valid subset (padding rows carry garbage on purpose)."""
    rng = np.random.default_rng(1)
    u = np.asarray(rng.normal(size=(8, 24)), np.float32)
    u[2] = 1e6                              # garbage in invalid slots
    u[5] = -1e6
    valid = np.array([1, 1, 0, 1, 1, 0, 1, 1], bool)
    sub = jnp.asarray(u[valid])
    uj, vj = jnp.asarray(u), jnp.asarray(valid)
    for name in ("mean", "median", "trimmed_mean", "krum", "clustering"):
        a_d, m_d = agg_lib.DYN_AGGREGATORS[name](uj, vj, f=1, beta=0.1)
        a_s, _ = agg_lib.AGGREGATORS[name](sub, f=1, beta=0.1)
        assert np.allclose(np.asarray(a_d), np.asarray(a_s), atol=1e-4), name
        assert not np.asarray(m_d)[~valid].any(), name


@pytest.mark.parametrize("aggregator", ("median", "multi_krum", "clustering"))
def test_dynamic_experiment_runs_baseline_aggregators(aggregator):
    """The lifted restriction end to end: baselines under a dynamic
    schedule with degree-0 rounds — finite models, sane accuracy."""
    topo = _close_topo()
    data = SyntheticImages(seed=0)
    cfg = eng.DFLConfig(aggregator=aggregator, attack="ipm_100",
                        model="mlp", seed=0, batches_per_round=1)
    sched = dyn.make_schedule("dos", topo, 4, seed=1)
    out = eng.run_dynamic_experiment(cfg, topo, data, sched, n_test=64)
    accs = np.asarray(out["final"]["acc_all"])
    assert np.isfinite(accs).all()
    assert 0.0 <= out["final"]["acc_benign_mean"] <= 1.0
    assert len(out["trace"]) == 4


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def _load_gate_module():
    spec = importlib.util.spec_from_file_location(
        "robustness_gate", os.path.join(REPO, "scripts",
                                        "robustness_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gate_rejects_mean_substituted_for_wfagg():
    """The ISSUE's self-test contract: substituting mean's cells for
    wfagg's under ipm_100 must fail the gate, and the committed baseline
    must pass against itself."""
    gate = _load_gate_module()
    with open(os.path.join(REPO, "benchmarks",
                           "BENCH_robustness.json")) as f:
        baseline = json.load(f)
    assert gate.compare(baseline, baseline["cells"]) == []
    doctored = dict(baseline["cells"])
    for scenario in baseline["meta"]["scenarios"]:
        doctored[f"ipm_100|{scenario}|wfagg"] = \
            doctored[f"ipm_100|{scenario}|mean"]
    failures = gate.compare(baseline, doctored)
    assert failures                          # per-cell acc regression
    assert any("wfagg" in f for f in failures)
    # the structural wfagg-holds-on-static claim fires too
    assert any("robustness claim" in f for f in failures)
    # a dropped cell is a failure, not a silent pass
    partial = dict(baseline["cells"])
    partial.pop(next(iter(partial)))
    assert any("missing cell" in f
               for f in gate.compare(baseline, partial))


def test_gate_cli_self_test():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "robustness_gate.py"), "--self-test"],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "robustness_gate self-test: OK" in proc.stdout


def test_attack_names_single_source():
    """Every attack-choice surface derives from ATTACK_NAMES."""
    assert "ipm" in atk.ATTACK_NAMES
    assert set(atk.ADAPTIVE_ATTACKS) <= set(atk.ATTACK_NAMES)
    from benchmarks.robustness_matrix import (DEFAULT_ATTACKS, GATE_GRID,
                                              SMOKE_GRID)
    assert set(DEFAULT_ATTACKS) <= set(atk.ATTACK_NAMES)
    assert set(GATE_GRID["attacks"]) <= set(atk.ATTACK_NAMES)
    assert set(SMOKE_GRID["attacks"]) <= set(atk.ATTACK_NAMES)
    from benchmarks.table1_attacks import ATTACKS, FAST_ATTACKS
    assert set(ATTACKS) <= set(atk.ATTACK_NAMES)
    assert set(FAST_ATTACKS) <= set(atk.ATTACK_NAMES)
