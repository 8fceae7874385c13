"""Gather-free gossip aggregation: the neighbor-indexed kernels and the
``wfagg_batch(neighbor_idx=...)`` path must reproduce the gathered
reference — masks bit-equal, aggregates within float tolerance — across
backends, odd/even K, per-edge vs matrix temporal state, and irregular
(padded, erdos_renyi-style) degrees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wfagg as wf
from repro.core.topology import make_topology, padded_neighbor_table
from repro.core import trust
from repro.kernels.robust_stats.ops import (
    wfagg_round_indexed_combine, wfagg_round_indexed_stats)
from repro.kernels.robust_stats.ref import (
    robust_stats_indexed_ref, weighted_agg_ref)

ATOL = 2e-5


def _ring_idx(N, K):
    """(N, K) neighbor table of a K-regular ring lattice (K even) or a
    complete-graph slice (K = N - 1)."""
    if K == N - 1:
        return jnp.stack([
            jnp.concatenate([jnp.arange(n), jnp.arange(n + 1, N)])
            for n in range(N)
        ]).astype(jnp.int32)
    half = K // 2
    offs = np.concatenate([np.arange(-half, 0), np.arange(1, K - half + 1)])
    return jnp.asarray(
        (np.arange(N)[:, None] + offs[None, :]) % N, jnp.int32)


def _irregular(N, K, seed=0):
    """Padded (idx, valid) with per-node degrees in [1, K]."""
    rng = np.random.default_rng(seed)
    idx = np.full((N, K), 0, np.int32)
    valid = np.zeros((N, K), bool)
    for n in range(N):
        v = int(rng.integers(1, K + 1))
        nbrs = rng.choice([i for i in range(N) if i != n], size=v, replace=False)
        idx[n, :v] = nbrs
        idx[n, v:] = n          # pad with self (finite, in-bounds)
        valid[n, :v] = True
    return jnp.asarray(idx), jnp.asarray(valid)


# ---------------------------------------------------------------------------
# the round kernel's statistics phase, read through the neighbor table
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [5, 8])
@pytest.mark.parametrize("prev_kind", ["none", "edge", "matrix"])
def test_indexed_stats_match_gathered_batch(K, prev_kind):
    """Phase 0 through the neighbor table equals phase 0 over the
    gathered (N, K, d) slates, and the oracle."""
    N, d = 9, 900
    models = jax.random.normal(jax.random.PRNGKey(0), (N, d), jnp.float32)
    idx = _ring_idx(N, K) if K < N - 1 else _ring_idx(N, N - 1)
    prev_m = jax.random.normal(jax.random.PRNGKey(1), (N, d), jnp.float32)
    prev_arg = {"none": None, "edge": prev_m[idx], "matrix": prev_m}[prev_kind]
    got = wfagg_round_indexed_stats(models, idx, prev_arg)
    exp = wf._gathered_stats(models[idx],
                             prev_m[idx] if prev_kind != "none" else None,
                             need_gram=False)
    ref = robust_stats_indexed_ref(models, idx, None, prev_arg)
    for name in got._fields:
        g, e, r = getattr(got, name), getattr(exp, name), getattr(ref, name)
        if g is None:
            assert e is None and r is None, name
            continue
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=1e-6, atol=1e-6, err_msg=name)
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=3e-5, atol=3e-5, err_msg=name)


@pytest.mark.parametrize("with_prev", [False, True])
def test_indexed_stats_irregular_match_oracle(with_prev):
    N, K, d = 10, 6, 700
    models = jax.random.normal(jax.random.PRNGKey(2), (N, d), jnp.float32)
    idx, valid = _irregular(N, K, seed=4)
    prev = (jax.random.normal(jax.random.PRNGKey(3), (N, d), jnp.float32)
            if with_prev else None)
    got = wfagg_round_indexed_stats(models, idx, prev, valid=valid)
    ref = robust_stats_indexed_ref(models, idx, valid, prev)
    vmask = np.asarray(valid)
    for name in got._fields:
        g, r = getattr(got, name), getattr(ref, name)
        if g is None:
            assert r is None, name
            continue
        g, r = np.asarray(g), np.asarray(r)
        np.testing.assert_allclose(g, r, rtol=3e-5, atol=3e-5, err_msg=name)
        assert np.isfinite(g).all(), name  # padded slots stay finite


def _padded_slate(N, K, M, seed):
    """Padded (idx, valid) over an (M, d) matrix: degrees in [1, K], rows
    drawn with replacement (so equal rows tie in the sort), node 2 at
    degree 0; padded slots index the node's own row."""
    rng = np.random.default_rng(seed)
    idx = np.tile(np.arange(N, dtype=np.int32)[:, None], (1, K))
    valid = np.zeros((N, K), bool)
    for n in range(N):
        v = 0 if n == 2 else int(rng.integers(1, K + 1))
        idx[n, :v] = rng.integers(0, M, v)
        valid[n, :v] = True
    return jnp.asarray(idx), jnp.asarray(valid)


@pytest.mark.parametrize("need_gram", [False, True], ids=["stats", "gram"])
@pytest.mark.parametrize("prev_kind", ["edge", "prev_idx"])
@pytest.mark.parametrize("K", [4, 8, 30])
def test_indexed_stats_tiles_and_chunks_match_oracle(K, prev_kind,
                                                     need_gram):
    """Phase 0 alone over three 2,048-lane tiles of two 1,024-lane
    chunks each (d = 4,500, ragged): the flush's lane-wide partial sums,
    carried across chunks and tiles and summed across lanes at the
    node's last tile, match the oracle at the fleets' and the trainer's
    slate widths — padded slots, a degree-0 node, tied rows, per-edge
    ``prev`` or a matrix ``prev`` read through ``prev_idx``, with and
    without the Gram."""
    N, M, d = 6, 40, 4500
    models = jax.random.normal(jax.random.PRNGKey(K), (M, d)) + 0.1
    idx, valid = _padded_slate(N, K, M, seed=K)
    pmat = jax.random.normal(jax.random.PRNGKey(K + 1), (2 * M, d))
    prev_idx = None
    if prev_kind == "edge":
        prev = pmat[idx]
    else:
        prev = pmat
        prev_idx = jnp.asarray(
            np.random.default_rng(K).integers(0, 2 * M, (N, K)), jnp.int32)
    got = wfagg_round_indexed_stats(models, idx, prev, valid=valid,
                                    prev_idx=prev_idx, need_gram=need_gram,
                                    block_d=2048)
    ref = robust_stats_indexed_ref(models, idx, valid, prev,
                                   need_gram=need_gram, prev_idx=prev_idx)
    for name in got._fields:
        g, r = getattr(got, name), getattr(ref, name)
        if g is None:
            assert r is None, name
            continue
        g, r = np.asarray(g), np.asarray(r)
        assert np.isfinite(g).all(), name
        np.testing.assert_allclose(g, r, rtol=3e-5, atol=1e-3, err_msg=name)
    assert np.asarray(got.mednorm2)[2] == 0.0       # degree 0: empty median


def test_indexed_median_spans_valid_rows_only():
    """A padded slot with a huge model must not perturb the median."""
    N, K, d = 4, 3, 256
    models = jax.random.normal(jax.random.PRNGKey(5), (N, d), jnp.float32)
    models = models.at[3].set(1e6)  # the row the padded slot points at
    idx = jnp.array([[1, 2, 3], [0, 2, 3], [0, 1, 3], [0, 1, 2]], jnp.int32)
    valid = jnp.array([[1, 1, 0], [1, 1, 0], [1, 1, 0], [1, 1, 1]], bool)
    got = wfagg_round_indexed_stats(models, idx, valid=valid)
    # node 0 median = median(models[[1, 2]]) — row 3 excluded
    med2 = 0.5 * (models[1] + models[2])
    exp_d2 = np.sum((np.asarray(models[idx[0]]) - np.asarray(med2)) ** 2, -1)
    np.testing.assert_allclose(np.asarray(got.dist2[0]), exp_d2,
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# the round kernel's combine phase, read through the neighbor table
# ---------------------------------------------------------------------------

def test_weighted_agg_indexed_matches_single_node_kernel():
    """Phase 1 through the neighbor table equals phase 1 over each
    node's gathered slate, and the oracle."""
    N, K, d = 7, 6, 800
    models = jax.random.normal(jax.random.PRNGKey(6), (N, d), jnp.float32)
    local = jax.random.normal(jax.random.PRNGKey(7), (N, d), jnp.float32)
    idx = _ring_idx(N, K)
    w = jax.random.uniform(jax.random.PRNGKey(8), (N, K))
    w = w.at[2].set(0.0)   # all-rejected node keeps its local model
    wcomb, lcoef = jax.vmap(
        lambda wn: trust.combine_coefficients(wn, 0.8, wn >= 0, False))(w)
    got = wfagg_round_indexed_combine(local, models, idx, wcomb, lcoef)
    slate = jnp.arange(K, dtype=jnp.int32)[None, :]
    for n in range(N):
        one = wfagg_round_indexed_combine(local[n:n + 1], models[idx[n]],
                                          slate, wcomb[n:n + 1],
                                          lcoef[n:n + 1])[0]
        exp = weighted_agg_ref(local[n], models[idx[n]], w[n], 0.8)
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(one),
                                   rtol=ATOL, atol=ATOL, err_msg=str(n))
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(exp),
                                   rtol=ATOL, atol=ATOL, err_msg=str(n))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(local[2]),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# wfagg_batch(neighbor_idx=...) — regular-topology parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("filters", ["wfagg", "alt"])
@pytest.mark.parametrize("K", [4, 5])
def test_wfagg_batch_indexed_matches_gathered(filters, K):
    """Indexed fused vs gathered fused AND vs gathered reference: masks
    bit-equal, aggregates within tolerance, across 5 temporal rounds."""
    N, d = 8, 500
    mk = (wf.alt_wfagg_config if filters == "alt"
          else wf.WFAggConfig)
    cfg_f = mk(backend="fused")
    cfg_r = mk(backend="reference")
    idx = _ring_idx(N, K)
    st_i = wf.TemporalState(   # matrix-prev state (the engine's layout)
        prev=jnp.zeros((N, d)),
        hist_s=jnp.zeros((N, cfg_f.window, K)),
        hist_b=jnp.zeros((N, cfg_f.window, K)),
        count=jnp.zeros((N,), jnp.int32), t=jnp.zeros((N,), jnp.int32))
    st_g = jax.vmap(lambda _: wf.init_temporal_state(K, d, cfg_f.window))(
        jnp.arange(N))
    st_r = jax.vmap(lambda _: wf.init_temporal_state(K, d, cfg_f.window))(
        jnp.arange(N))
    for r in range(5):
        u = jax.random.normal(jax.random.PRNGKey(30 + r), (N, d)) + 0.3
        out_i, st_i, info_i = wf.wfagg_batch(u, u, st_i, cfg_f,
                                             neighbor_idx=idx)
        out_g, st_g, info_g = wf.wfagg_batch(u, u[idx], st_g, cfg_f)
        out_r, st_r, info_r = wf.wfagg_batch(u, u[idx], st_r, cfg_r)
        for m in ("mask_d", "mask_c", "mask_t"):
            assert np.array_equal(np.asarray(info_i[m]),
                                  np.asarray(info_g[m])), (r, m, "fused")
            assert np.array_equal(np.asarray(info_i[m]),
                                  np.asarray(info_r[m])), (r, m, "reference")
        np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_g),
                                   rtol=ATOL, atol=ATOL)
        np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_r),
                                   rtol=ATOL, atol=ATOL)
        # matrix-prev state carries the post-round models, never (N, K, d)
        assert st_i.prev.shape == (N, d)


def test_wfagg_batch_indexed_edge_state_matches_matrix_state():
    """Per-edge (N, K, d) prev and matrix (N, d) prev are equivalent on a
    static topology (prev[idx] IS the per-edge history)."""
    N, K, d = 6, 4, 300
    cfg = wf.WFAggConfig(backend="fused", transient=1)
    idx = _ring_idx(N, K)
    st_m = wf.TemporalState(
        prev=jnp.zeros((N, d)), hist_s=jnp.zeros((N, cfg.window, K)),
        hist_b=jnp.zeros((N, cfg.window, K)),
        count=jnp.zeros((N,), jnp.int32), t=jnp.zeros((N,), jnp.int32))
    st_e = jax.vmap(lambda _: wf.init_temporal_state(K, d, cfg.window))(
        jnp.arange(N))
    for r in range(4):
        u = jax.random.normal(jax.random.PRNGKey(60 + r), (N, d)) + 0.2
        out_m, st_m, info_m = wf.wfagg_batch(u, u, st_m, cfg, neighbor_idx=idx)
        out_e, st_e, info_e = wf.wfagg_batch(u, u, st_e, cfg, neighbor_idx=idx)
        assert st_m.prev.ndim == 2 and st_e.prev.ndim == 3
        for m in ("mask_d", "mask_c", "mask_t"):
            assert np.array_equal(np.asarray(info_m[m]), np.asarray(info_e[m])), (r, m)
        np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_e),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# irregular degrees: fused indexed vs per-node gathered reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("filters", ["wfagg", "alt"])
def test_wfagg_batch_indexed_irregular_matches_per_node_reference(filters):
    """On a padded irregular slate, node n's aggregation must equal the
    plain single-node reference pipeline run on its TRUE v_n neighbors
    (the gathered reference at K = v_n)."""
    N, K, d = 10, 6, 400
    models = jax.random.normal(jax.random.PRNGKey(9), (N, d), jnp.float32) + 0.3
    idx, valid = _irregular(N, K, seed=11)
    mk = wf.alt_wfagg_config if filters == "alt" else wf.WFAggConfig
    cfg = mk(backend="fused", use_temporal=False, f=1,
             **({"multi_krum_m": 2} if filters == "alt" else {}))
    out, _, info = wf.wfagg_batch(models, models, None, cfg,
                                  neighbor_idx=idx, valid=valid)
    for n in range(N):
        sel = np.asarray(idx[n])[np.asarray(valid[n])]
        v = len(sel)
        u_n = models[jnp.asarray(sel)]
        cfg_n = mk(backend="reference", use_temporal=False, f=1,
                   **({"multi_krum_m": min(2, v)} if filters == "alt" else {}))
        out_n, _, info_n = wf.wfagg(models[n], u_n, None, cfg_n)
        for m in ("mask_d", "mask_c"):
            got_m = np.asarray(info[m][n])[np.asarray(valid[n])]
            assert np.array_equal(got_m, np.asarray(info_n[m])), (n, m, v)
            assert not np.asarray(info[m][n])[~np.asarray(valid[n])].any()
        np.testing.assert_allclose(np.asarray(out[n]), np.asarray(out_n),
                                   rtol=ATOL, atol=ATOL, err_msg=str(n))


def test_wfagg_batch_indexed_irregular_temporal():
    """Temporal filter on an irregular slate: per-node decision matches
    the reference wfagg_t_decide on the valid slots, padded slots never
    pass, and the matrix prev state stays (N, d)."""
    N, K, d = 8, 5, 300
    cfg = wf.WFAggConfig(backend="fused", transient=1, f=1)
    idx, valid = _irregular(N, K, seed=13)
    st = wf.TemporalState(
        prev=jnp.zeros((N, d)), hist_s=jnp.zeros((N, cfg.window, K)),
        hist_b=jnp.zeros((N, cfg.window, K)),
        count=jnp.zeros((N,), jnp.int32), t=jnp.zeros((N,), jnp.int32))
    hist = {"s": np.zeros((N, cfg.window, K)), "b": np.zeros((N, cfg.window, K))}
    count = np.zeros((N,), np.int32)
    t = np.zeros((N,), np.int32)
    prev_m = np.zeros((N, d), np.float32)
    for r in range(4):
        u = np.asarray(jax.random.normal(jax.random.PRNGKey(80 + r), (N, d))) + 0.2
        _, st, info = wf.wfagg_batch(jnp.asarray(u), jnp.asarray(u), st, cfg,
                                     neighbor_idx=idx, valid=valid)
        mask_t = np.asarray(info["mask_t"])
        assert not mask_t[~np.asarray(valid)].any()
        for n in range(N):
            nb = np.asarray(idx[n])
            cur, prv = u[nb], prev_m[nb]
            s_t = ((cur - prv) ** 2).sum(-1)
            den = np.maximum(np.linalg.norm(cur, axis=-1)
                             * np.linalg.norm(prv, axis=-1), 1e-12)
            b_t = 1.0 - (cur * prv).sum(-1) / den
            m_ref, hs, hb, c_ref, t_ref = wf.wfagg_t_decide(
                jnp.asarray(hist["s"][n]), jnp.asarray(hist["b"][n]),
                jnp.asarray(count[n]), jnp.asarray(t[n]),
                jnp.asarray(s_t), jnp.asarray(b_t), cfg)
            m_ref = np.asarray(m_ref) & np.asarray(valid[n])
            assert np.array_equal(mask_t[n], m_ref), (r, n)
            hist["s"][n], hist["b"][n] = np.asarray(hs), np.asarray(hb)
            count[n], t[n] = int(c_ref), int(t_ref)
        prev_m = u
        assert st.prev.shape == (N, d)


# ---------------------------------------------------------------------------
# engine end-to-end on irregular topologies
# ---------------------------------------------------------------------------

def test_engine_runs_on_erdos_renyi():
    from repro.data.synthetic import SyntheticImages
    from repro.dfl.engine import DFLConfig, run_experiment

    topo = make_topology(n_nodes=12, degree=4, n_malicious=1,
                         kind="erdos_renyi", seed=3)
    assert not topo.is_regular          # the interesting case
    assert (topo.degrees >= 1).all()
    data = SyntheticImages()
    for aggregator in ("wfagg", "alt_wfagg"):
        cfg = DFLConfig(aggregator=aggregator, attack="ipm_100", model="mlp")
        out = run_experiment(cfg, topo, data, rounds=2, eval_every=2)
        assert np.isfinite(out["final"]["acc_benign_mean"])


def test_engine_irregular_aggregator_support():
    """Irregular graphs accept wfagg/alt_wfagg and every DYN_AGGREGATORS
    baseline (valid-mask-aware path); per-filter variants like wfagg_t
    have no masked implementation and must still be rejected."""
    from repro.data.synthetic import SyntheticImages
    from repro.dfl.engine import DFLConfig, build_round_fn

    topo = make_topology(n_nodes=12, degree=4, n_malicious=1,
                         kind="erdos_renyi", seed=3)
    data = SyntheticImages()
    build_round_fn(DFLConfig(aggregator="median"), topo, data)
    with pytest.raises(NotImplementedError):
        build_round_fn(DFLConfig(aggregator="wfagg_t"), topo, data)


# ---------------------------------------------------------------------------
# satellite bugfixes
# ---------------------------------------------------------------------------

def test_engine_attacks_honor_attack_config():
    """dfl.engine._apply_attacks must route AttackConfig hyper-parameters
    (previously z_max / mu / sigma were hardcoded) through the shared
    core.attacks implementation."""
    from repro.core import attacks as atk
    from repro.core.topology import paper_topology
    from repro.dfl import engine as eng

    topo = paper_topology()
    N = topo.n_nodes
    flat = jax.random.normal(jax.random.PRNGKey(0), (N, 64), jnp.float32)
    rnd = jnp.zeros((), jnp.int32)
    mal = np.asarray(topo.malicious)
    benign = np.asarray(flat)[~mal]

    mal_mask = jnp.asarray(topo.malicious)
    for zmax in (0.5, 1.5):
        cfg = eng.DFLConfig(attack="alie",
                            attack_params=atk.AttackConfig(alie_zmax=zmax))
        out = np.asarray(eng._apply_attacks(cfg, mal_mask, flat, rnd))
        expect = benign.mean(0) - zmax * benign.std(0)
        for j in np.nonzero(mal)[0]:
            np.testing.assert_allclose(out[j], expect, rtol=1e-4,
                                       err_msg=f"zmax={zmax}")
        np.testing.assert_allclose(out[~mal], benign)  # benign untouched

    # custom noise parameters reach the noise attack
    cfg = eng.DFLConfig(attack="noise", seed=0,
                        attack_params=atk.AttackConfig(noise_mu=5.0,
                                                       noise_sigma=0.0))
    out = np.asarray(eng._apply_attacks(cfg, mal_mask, flat, rnd))
    np.testing.assert_allclose(out[mal], np.asarray(flat)[mal] + 5.0,
                               rtol=1e-6)

    # custom IPM epsilon via the generic "ipm" name
    cfg = eng.DFLConfig(attack="ipm",
                        attack_params=atk.AttackConfig(ipm_eps=7.0))
    out = np.asarray(eng._apply_attacks(cfg, mal_mask, flat, rnd))
    np.testing.assert_allclose(out[mal][0], -7.0 * benign.mean(0), rtol=1e-4)


def test_stacked_attack_matches_engine_attack():
    """engine and robust_allreduce now share ONE copy of the stacked
    attack math — same inputs, same poisoned rows."""
    from repro.core import attacks as atk
    from repro.distributed.robust_allreduce import apply_stacked_attack

    K, d = 8, 96
    g = jax.random.normal(jax.random.PRNGKey(1), (K, d), jnp.float32)
    malicious = jnp.zeros((K,), bool).at[2].set(True).at[6].set(True)
    key = jax.random.PRNGKey(3)
    for attack in ("alie", "ipm_100", "ipm_0.5", "sign_flip", "noise"):
        via_stacked = apply_stacked_attack({"w": g}, malicious, attack,
                                           key)["w"]
        # apply_stacked_attack folds the leaf index into the key
        direct = atk.apply_matrix_attack(attack, g, malicious,
                                         jax.random.fold_in(key, 0))
        np.testing.assert_allclose(np.asarray(via_stacked),
                                   np.asarray(direct), rtol=1e-6,
                                   err_msg=attack)


def test_mode_b_multi_krum_m_prefers_wfagg_config():
    """alt_wfagg mask parity: distributed._weights_from_stats must honor
    WFAggConfig.multi_krum_m (like core.wfagg._distance_mask does) and
    only fall back to RobustAggConfig.multi_krum_m."""
    import dataclasses as dc

    from repro.distributed.robust_allreduce import (
        RobustAggConfig, _stacked_stats, _weights_from_stats)

    K, d = 9, 120
    u = jax.random.normal(jax.random.PRNGKey(4), (K, d), jnp.float32)
    # (WFAggConfig.m, RobustAggConfig.m) -> effective m (preference order)
    for wf_m, ra_m, eff_m in ((3, None, 3), (3, 5, 3), (None, 5, 5),
                              (None, None, max(1, K // 4))):
        wcfg = wf.alt_wfagg_config(f=1, use_temporal=False,
                                   multi_krum_m=wf_m)
        cfg = RobustAggConfig(method="alt_wfagg", wfagg=wcfg,
                              multi_krum_m=ra_m, layout="stacked")
        stats = _stacked_stats({"w": u})
        _, _, info = _weights_from_stats(stats, None, None, cfg)
        mask_a = wf._distance_mask(                   # mode-A path
            u, dc.replace(wcfg, multi_krum_m=eff_m))
        assert int(np.asarray(info["mask_d"]).sum()) == eff_m
        assert np.array_equal(np.asarray(info["mask_d"]),
                              np.asarray(mask_a)), (wf_m, ra_m)


def test_evaluate_buckets_cover_dense_placements():
    """Benign nodes with >= 3 malicious neighbors must appear in
    acc_by_malicious_neighbors instead of being silently dropped."""
    from repro.core.topology import Topology, padded_neighbor_table, ring_lattice
    from repro.data.synthetic import SyntheticImages
    from repro.dfl.engine import DFLConfig, evaluate, init_dfl_state

    n = 12
    adj = ring_lattice(n, 6)
    mal = np.zeros(n, bool)
    mal[[0, 1, 2]] = True           # contiguous cluster: node 3 sees 3
    table, valid = padded_neighbor_table(adj)
    topo = Topology(n_nodes=n, adjacency=adj, neighbor_indices=table,
                    malicious=mal, neighbor_valid=valid)
    mal_nb = topo.malicious_neighbor_count()
    assert mal_nb[~mal].max() >= 3   # the placement this test is about

    cfg = DFLConfig(aggregator="mean", model="mlp")
    state = init_dfl_state(cfg, topo)
    res = evaluate(cfg, topo, SyntheticImages(), state, n_test=64)
    by = res["acc_by_malicious_neighbors"]
    assert set(by) == set(range(int(mal_nb[~mal].max()) + 1))
    # every benign node lands in exactly one bucket (none dropped)
    counted = sum(int((~mal & (mal_nb == m)).sum()) for m in by)
    assert counted == int((~mal).sum())
    assert np.isfinite(by[3])


def test_padded_neighbor_table_invariants():
    topo = make_topology(n_nodes=16, degree=5, n_malicious=2,
                         kind="erdos_renyi", seed=7)
    idx, valid = topo.neighbor_indices, topo.neighbor_valid
    degs = topo.adjacency.sum(axis=1)
    assert (valid.sum(axis=1) == degs).all()
    for n in range(16):
        nbrs = set(np.nonzero(topo.adjacency[n])[0])
        assert set(idx[n][valid[n]]) == nbrs
        assert (idx[n][~valid[n]] == n).all()   # padded with self
    # regular graphs keep an all-valid table
    ring = make_topology(n_nodes=12, degree=4, kind="ring")
    assert ring.is_regular and ring.neighbor_valid.all()
    t2, v2 = padded_neighbor_table(ring.adjacency)
    assert np.array_equal(np.sort(t2, axis=1),
                          np.sort(ring.neighbor_indices, axis=1))
    assert v2.all()
