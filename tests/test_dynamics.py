"""Dynamic-topology scenario engine: schedule generators must be
deterministic and self-consistent, degree-0 (churned-out) nodes must
fall back to their local model without NaNs, and the one-jit dynamic
round must (a) never retrace as the graph changes, (b) stay
(N, K, d)-free in HLO, and (c) match the per-node reference pipeline
under a churn schedule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wfagg as wf
from repro.core.topology import (
    make_topology, padded_neighbor_table, schedule_from_adjacencies,
    static_schedule)
from repro.data.synthetic import SyntheticImages
from repro.dfl import dynamics as dyn
from repro.dfl.engine import (
    DFLConfig, build_round_fn, init_dfl_state, run_dynamic_experiment,
    run_experiment)
from repro.kernels.robust_stats.ops import wfagg_round_indexed_stats
from repro.kernels.robust_stats.ref import robust_stats_indexed_ref

ATOL = 2e-5


def _topo(n=10, degree=4, n_mal=2, kind="ring", seed=0):
    return make_topology(n_nodes=n, degree=degree, n_malicious=n_mal,
                         kind=kind, seed=seed)


# ---------------------------------------------------------------------------
# scenario generators
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", dyn.SCENARIO_NAMES)
def test_scenarios_deterministic_and_consistent(name):
    """Same seed -> identical schedule; every schedule is internally
    consistent: valid slots are real symmetric edges of that round's
    adjacency, padded slots carry the node's own index, shapes share one
    (R, N, K) padding across rounds."""
    topo = _topo()
    R = 6
    s1 = dyn.make_schedule(name, topo, R, seed=5)
    s2 = dyn.make_schedule(name, topo, R, seed=5)
    for f in ("neighbor_idx", "valid", "malicious", "adjacency"):
        assert np.array_equal(getattr(s1, f), getattr(s2, f)), (name, f)

    N = topo.n_nodes
    assert s1.neighbor_idx.shape == (R, N, s1.width)
    assert s1.valid.shape == (R, N, s1.width)
    assert s1.malicious.shape == (R, N)
    assert s1.adjacency.shape == (R, N, N)
    assert s1.width == max(1, int(s1.adjacency.sum(axis=2).max()))
    for r in range(R):
        adj = s1.adjacency[r]
        assert np.array_equal(adj, adj.T) and not adj.diagonal().any()
        assert (s1.valid[r].sum(axis=1) == adj.sum(axis=1)).all()
        for n in range(N):
            sel = s1.neighbor_idx[r, n][s1.valid[r, n]]
            assert set(sel) == set(np.nonzero(adj[n])[0]), (name, r, n)
            assert (s1.neighbor_idx[r, n][~s1.valid[r, n]] == n).all()


def test_scenarios_differ_across_seeds_and_change_rounds():
    topo = _topo()
    a = dyn.churn_schedule(topo, 8, seed=0, p_leave=0.3)
    b = dyn.churn_schedule(topo, 8, seed=1, p_leave=0.3)
    assert not np.array_equal(a.adjacency, b.adjacency)
    # churn/link-failure/mobility must actually vary the graph
    for name in ("churn", "link_failure", "mobility"):
        s = dyn.make_schedule(name, topo, 8, seed=0)
        assert s.diff().sum() > 0, name


def test_partition_cuts_and_heals():
    topo = _topo(n=12, degree=4)
    s = dyn.partition_schedule(topo, 9, seed=2, split_at=3, heal_at=6)
    base = topo.adjacency
    assert np.array_equal(s.adjacency[0], base)
    assert np.array_equal(s.adjacency[8], base)
    mid = s.adjacency[4]
    assert mid.sum() < base.sum()
    # the partition round's graph is exactly base minus cross edges of a
    # 2-coloring: reachable sets never span both sides
    assert (base & ~mid).sum() > 0


def test_sleeper_wakes_at_round():
    topo = _topo()
    s = dyn.sleeper_schedule(topo, 6, wake_at=4)
    assert not s.malicious[:4].any()
    assert np.array_equal(s.malicious[4], topo.malicious)
    assert np.array_equal(s.malicious[5], topo.malicious)
    # static graph throughout
    assert (s.adjacency == topo.adjacency[None]).all()


def test_static_schedule_matches_topology():
    topo = _topo(kind="erdos_renyi", seed=3)
    s = static_schedule(topo, 4)
    assert s.width == topo.degree
    assert s.diff().sum() == 0          # nothing changes round to round
    for r in range(4):
        assert np.array_equal(s.adjacency[r], topo.adjacency)
        assert (s.valid[r].sum(axis=1) == topo.degrees).all()


def test_schedule_degree_stats_and_diff_shapes():
    topo = _topo()
    s = dyn.churn_schedule(topo, 5, seed=1)
    assert s.degree_stats().shape == (5, 3)
    assert s.diff().shape == (4, 2)
    assert (s.degree_stats()[:, 0] <= s.degree_stats()[:, 2]).all()


def test_make_schedule_rejects_unknown():
    with pytest.raises(ValueError):
        dyn.make_schedule("quakes", _topo(), 3)


# ---------------------------------------------------------------------------
# degree-0 (fully churned-out) nodes
# ---------------------------------------------------------------------------

def test_padded_neighbor_table_degree0_row():
    """An isolated node yields an all-invalid all-self row, and ``width``
    pads beyond this graph's own max degree."""
    adj = np.zeros((4, 4), dtype=bool)
    adj[0, 1] = adj[1, 0] = True       # node 2, 3 isolated
    t, v = padded_neighbor_table(adj)
    assert not v[2].any() and (t[2] == 2).all()
    assert not v[3].any() and (t[3] == 3).all()
    t5, v5 = padded_neighbor_table(adj, width=5)
    assert t5.shape == (4, 5) and (t5[2] == 2).all()
    with pytest.raises(ValueError):
        padded_neighbor_table(np.ones((4, 4), bool) ^ np.eye(4, dtype=bool),
                              width=1)


def test_indexed_stats_degree0_finite_zero_median():
    """The kernel's empty-median guard: an all-invalid row must produce
    finite statistics (median = 0 -> dist2 = norm2, dotmed = 0), in both
    the Pallas kernel and the jnp oracle."""
    N, K, d = 4, 3, 256
    models = jax.random.normal(jax.random.PRNGKey(0), (N, d), jnp.float32)
    idx = np.array([[1, 2, 3], [0, 2, 3], [2, 2, 2], [0, 1, 2]], np.int32)
    valid = np.array([[1, 1, 1], [1, 1, 0], [0, 0, 0], [1, 1, 1]], bool)
    kernel = lambda m, i, v: wfagg_round_indexed_stats(m, i, valid=v)  # noqa: E731
    for which, fn in (("kernel", kernel), ("oracle", robust_stats_indexed_ref)):
        st = fn(models, jnp.asarray(idx), jnp.asarray(valid))
        for name in ("dist2", "dotmed", "norm2", "mednorm2"):
            arr = np.asarray(getattr(st, name))
            assert np.isfinite(arr).all(), (which, name)
        # node 2: empty median = 0 => dist2 == norm2, dotmed == 0
        np.testing.assert_allclose(np.asarray(st.dist2)[2],
                                   np.asarray(st.norm2)[2], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(st.dotmed)[2], 0.0, atol=1e-6)
        assert float(np.asarray(st.mednorm2)[2]) == 0.0


@pytest.mark.parametrize("filters", ["wfagg", "alt"])
def test_wfagg_batch_degree0_keeps_local_model(filters):
    """A churned-out node (all-invalid row) must keep its local model
    exactly (all weights zero -> WFAgg-E alpha gates to 0), with no NaNs
    anywhere in the batch."""
    N, K, d = 6, 4, 300
    mk = wf.alt_wfagg_config if filters == "alt" else wf.WFAggConfig
    cfg = mk(backend="fused", use_temporal=False, f=1)
    models = jax.random.normal(jax.random.PRNGKey(1), (N, d), jnp.float32)
    idx = np.stack([[(n + o) % N for o in range(1, K + 1)] for n in range(N)]
                   ).astype(np.int32)
    valid = np.ones((N, K), bool)
    idx[2] = 2
    valid[2] = False                   # node 2 fully churned out
    out, _, info = wf.wfagg_batch(models, models, None, cfg,
                                  neighbor_idx=jnp.asarray(idx),
                                  valid=jnp.asarray(valid))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out[2]), np.asarray(models[2]),
                               rtol=1e-6, atol=1e-6)
    assert int(np.asarray(info["n_accepted"])[2]) == 0
    # the batch as a whole still aggregates (degree-0 doesn't poison it)
    assert (np.asarray(info["n_accepted"]) > 0).any()


# ---------------------------------------------------------------------------
# the dynamic round: compile-once, HLO-clean, reference parity
# ---------------------------------------------------------------------------

def test_dynamic_round_compiles_once_across_changing_graphs():
    """Round-varying neighbor tables / valid masks / malicious masks are
    traced inputs: R rounds through R different graphs must hit ONE
    compiled executable (no retrace per graph)."""
    topo = _topo()
    data = SyntheticImages()
    cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp")
    sched = dyn.churn_schedule(topo, 5, seed=7, p_leave=0.4)
    fn = build_round_fn(cfg, topo, data, dynamic=True)
    state = init_dfl_state(cfg, topo, degree=sched.width)
    for r in range(sched.rounds):
        state = fn(state, jnp.asarray(sched.neighbor_idx[r]),
                   jnp.asarray(sched.valid[r]),
                   jnp.asarray(sched.malicious[r]))
    assert fn._cache_size() == 1
    flat = np.asarray(jax.vmap(
        lambda t: jax.flatten_util.ravel_pytree(t)[0])(state.node_params))
    assert np.isfinite(flat).all()


def test_dynamic_round_hlo_is_gossip_tensor_free():
    """The dynamic round keeps PR 2's guarantee: no (N, K, d)-shaped f32
    buffer anywhere in the compiled HLO (shared ``repro.analysis``
    scanner — the ``no-nkd-buffer`` rule's engine)."""
    from repro.analysis import scan_nkd_buffers

    topo = _topo()
    data = SyntheticImages()
    cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp")
    sched = dyn.churn_schedule(topo, 3, seed=1)
    N, K = topo.n_nodes, sched.width
    fn = build_round_fn(cfg, topo, data, dynamic=True)
    state = init_dfl_state(cfg, topo, degree=K)
    hlo = fn.lower(state, jnp.asarray(sched.neighbor_idx[0]),
                   jnp.asarray(sched.valid[0]),
                   jnp.asarray(sched.malicious[0])).compile().as_text()
    assert scan_nkd_buffers(hlo, N, K) == []


def test_dynamic_engine_rejects_unsupported_configs():
    topo = _topo()
    data = SyntheticImages()
    # only WFAgg component ablations (slot-keyed temporal state, no
    # valid-mask-aware form) and CFL remain unsupported under schedules
    for bad in (DFLConfig(aggregator="wfagg_t"),
                DFLConfig(aggregator="wfagg", centralized=True)):
        with pytest.raises(NotImplementedError):
            build_round_fn(bad, topo, data, dynamic=True)
    # the reference backend is no longer rejected: the valid-aware
    # pure-jnp oracle honors per-round valid masks (dynamic keep counts)
    build_round_fn(DFLConfig(aggregator="wfagg", wfagg_backend="reference"),
                   topo, data, dynamic=True)
    # baseline aggregators route through the DYN_AGGREGATORS variants
    build_round_fn(DFLConfig(aggregator="median"), topo, data, dynamic=True)


def test_indexed_vs_reference_parity_under_churn():
    """Under a churn schedule the fused indexed round must match, node by
    node and round by round, the plain per-node reference pipeline run on
    each node's TRUE (possibly empty) neighbor slate."""
    topo = _topo(n=8, degree=4, n_mal=0)
    data = SyntheticImages()
    cfg = DFLConfig(aggregator="wfagg", attack="none", model="mlp",
                    batches_per_round=1)
    sched = dyn.churn_schedule(topo, 3, seed=9, p_leave=0.35)
    assert (sched.degrees() == 0).any()     # churn actually bites
    fn = build_round_fn(cfg, topo, data, dynamic=True)
    state = init_dfl_state(cfg, topo, degree=sched.width)
    ref_flat = None
    p = cfg.paper
    for r in range(sched.rounds):
        prev_state = state
        state = fn(state, jnp.asarray(sched.neighbor_idx[r]),
                   jnp.asarray(sched.valid[r]),
                   jnp.asarray(sched.malicious[r]))
        # reference: recompute this round's aggregation per node from the
        # trained (pre-aggregation) models.  Rounds stay inside the
        # WFAgg-T transient (3), so temporal masks are inactive in both
        # paths and the reference needs no ring-buffer bookkeeping.
        from repro.dfl.engine import _local_train, _ravel_nodes
        trained, _ = _local_train(
            cfg, data, jnp.asarray(sched.malicious[r]),
            prev_state.node_params, prev_state.node_momentum,
            prev_state.rnd)
        flat, _ = _ravel_nodes(trained)
        flat = np.asarray(flat)
        got_flat, _ = _ravel_nodes(state.node_params)
        got_flat = np.asarray(got_flat)
        rcfg = wf.WFAggConfig(f=p.f, tau1=p.tau1, tau2=p.tau2, tau3=p.tau3,
                              alpha=p.alpha, window=p.window,
                              transient=p.transient, use_temporal=False,
                              backend="reference")
        for n in range(topo.n_nodes):
            sel = sched.neighbor_idx[r, n][sched.valid[r, n]]
            if len(sel) == 0:
                np.testing.assert_allclose(got_flat[n], flat[n],
                                           rtol=ATOL, atol=ATOL)
                continue
            out_n, _, _ = wf.wfagg(jnp.asarray(flat[n]),
                                   jnp.asarray(flat[sel]), None, rcfg)
            np.testing.assert_allclose(got_flat[n], np.asarray(out_n),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"round {r} node {n}")


# ---------------------------------------------------------------------------
# temporal-history realignment across slate changes
# ---------------------------------------------------------------------------

def test_realign_temporal_history_maps_by_identity():
    """Columns move with their neighbor: a shifted slot carries its
    history along, a newly-seen neighbor starts from zero, a static
    slate is a no-op."""
    N, K, W, d = 2, 3, 2, 8
    hist = jnp.arange(N * W * K, dtype=jnp.float32).reshape(N, W, K)
    st = wf.TemporalState(prev=jnp.zeros((N, d)), hist_s=hist,
                          hist_b=10.0 * hist,
                          count=jnp.ones((N,), jnp.int32),
                          t=jnp.ones((N,), jnp.int32))
    prev_idx = jnp.asarray([[3, 5, 7], [1, 2, 0]], jnp.int32)
    ones = jnp.ones((N, K), bool)
    # identity slate -> identity histories
    same = wf.realign_temporal_history(st, prev_idx, ones, prev_idx, ones)
    np.testing.assert_array_equal(np.asarray(same.hist_s), np.asarray(hist))
    # node 0: [3,5,7] -> [7,3,9]: slot 0 gets old slot 2, slot 1 gets old
    # slot 0, slot 2 (neighbor 9, unseen) starts zeroed; node 1 drops its
    # slot-1 neighbor (slot 1 invalid this round)
    idx = jnp.asarray([[7, 3, 9], [0, 2, 1]], jnp.int32)
    valid = jnp.asarray([[1, 1, 1], [1, 0, 1]], bool)
    out = wf.realign_temporal_history(st, prev_idx, ones, idx, valid)
    h = np.asarray(hist)
    got = np.asarray(out.hist_s)
    np.testing.assert_array_equal(got[0, :, 0], h[0, :, 2])
    np.testing.assert_array_equal(got[0, :, 1], h[0, :, 0])
    np.testing.assert_array_equal(got[0, :, 2], 0.0)
    np.testing.assert_array_equal(got[1, :, 0], h[1, :, 2])   # id 0
    np.testing.assert_array_equal(got[1, :, 1], 0.0)          # invalid slot
    np.testing.assert_array_equal(got[1, :, 2], h[1, :, 0])   # id 1
    np.testing.assert_array_equal(np.asarray(out.hist_b),
                                  10.0 * np.asarray(out.hist_s))


def test_temporal_masks_invariant_to_slot_permutation():
    """The same graph listed in per-round-permuted slot order must, with
    realignment, produce slot-permuted copies of the SAME temporal masks
    and the same aggregates — i.e. histories follow neighbors, not
    slots."""
    N, K, d = 6, 4, 120
    cfg = wf.WFAggConfig(backend="fused", transient=1, f=1)
    idx_a = np.stack([[(n + o) % N for o in range(1, K + 1)]
                      for n in range(N)]).astype(np.int32)
    ones = jnp.ones((N, K), bool)
    mk_state = lambda: wf.TemporalState(
        prev=jnp.zeros((N, d)), hist_s=jnp.zeros((N, cfg.window, K)),
        hist_b=jnp.zeros((N, cfg.window, K)),
        count=jnp.zeros((N,), jnp.int32), t=jnp.zeros((N,), jnp.int32))
    st_a, st_b = mk_state(), mk_state()
    rng = np.random.default_rng(4)
    prev_idx_b = idx_a
    saw_active = False
    for r in range(5):
        u = jax.random.normal(jax.random.PRNGKey(90 + r), (N, d)) + 0.2
        perm = np.stack([rng.permutation(K) for _ in range(N)])
        idx_b = np.take_along_axis(idx_a, perm, axis=1)
        st_b = wf.realign_temporal_history(
            st_b, jnp.asarray(prev_idx_b), ones, jnp.asarray(idx_b), ones)
        prev_idx_b = idx_b
        out_a, st_a, info_a = wf.wfagg_batch(u, u, st_a, cfg,
                                             neighbor_idx=jnp.asarray(idx_a))
        out_b, st_b, info_b = wf.wfagg_batch(u, u, st_b, cfg,
                                             neighbor_idx=jnp.asarray(idx_b))
        for m in ("mask_d", "mask_c", "mask_t"):
            a = np.take_along_axis(np.asarray(info_a[m]), perm, axis=1)
            assert np.array_equal(a, np.asarray(info_b[m])), (r, m)
        np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                                   rtol=ATOL, atol=ATOL)
        saw_active = saw_active or bool(np.asarray(info_a["mask_t"]).any())
    assert saw_active    # the temporal filter actually fired in this test


# ---------------------------------------------------------------------------
# end-to-end scenario runs + series output
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", dyn.SCENARIO_NAMES)
def test_run_dynamic_experiment_all_scenarios(name):
    topo = _topo()
    data = SyntheticImages()
    cfg = DFLConfig(aggregator="wfagg", attack="sign_flip", model="mlp")
    sched = dyn.make_schedule(name, topo, 3, seed=2)
    out = run_dynamic_experiment(cfg, topo, data, sched, n_test=32)
    assert len(out["trace"]) == 3
    s = out["series"]
    assert s["round"] == [1, 2, 3]
    assert np.isfinite(s["acc_benign_mean"]).all()
    assert np.isfinite(s["r_squared"]).all()
    assert len(s["degree_min_mean_max"]) == 3
    # final keeps evaluate's dict shape
    assert set(out["final"]) >= {"acc_benign_mean", "r_squared", "acc_all",
                                 "acc_by_malicious_neighbors", "round"}


def test_run_experiment_emits_series():
    """The static path grew the same columnar series (back-compat:
    trace/final keep their shapes)."""
    topo = _topo()
    data = SyntheticImages()
    out = run_experiment(DFLConfig(aggregator="mean"), topo, data,
                         rounds=2, eval_every=1)
    assert out["series"]["round"] == [1, 2]
    assert len(out["series"]["acc_benign_mean"]) == 2
    assert out["final"] == out["trace"][-1]


def test_sleeper_malicious_mask_threads_through_attack():
    """Before the wake round the attacker rows are untouched; after it
    they are poisoned — the per-round mask reaches apply_matrix_attack."""
    from repro.dfl.engine import _apply_attacks
    topo = _topo(n=8, degree=4, n_mal=2)
    cfg = DFLConfig(attack="sign_flip")
    flat = jax.random.normal(jax.random.PRNGKey(3), (8, 32), jnp.float32)
    rnd = jnp.zeros((), jnp.int32)
    asleep = _apply_attacks(cfg, jnp.zeros((8,), bool), flat, rnd)
    np.testing.assert_allclose(np.asarray(asleep), np.asarray(flat))
    awake = _apply_attacks(cfg, jnp.asarray(topo.malicious), flat, rnd)
    mal = np.asarray(topo.malicious)
    np.testing.assert_allclose(np.asarray(awake)[mal],
                               -np.asarray(flat)[mal])
