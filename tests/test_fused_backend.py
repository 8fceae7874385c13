"""Backend parity: the fused (single-pass Pallas) WFAgg execution path
must reproduce the reference (multi-pass jnp) pipeline — masks bit-equal,
aggregates within float tolerance — across candidate counts, temporal
state, attacks, and the batched (N, K, d) launch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attacks as atk
from repro.core import wfagg as wf
from repro.kernels.robust_stats.ops import wfagg_round_indexed_stats
from repro.kernels.robust_stats.ref import robust_stats_ref

ATOL = 1e-5


def _updates(K, d, seed=0, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (K, d), jnp.float32)


def _run_rounds(cfg, K, d, rounds=6, seed=0):
    """Drive wfagg for several rounds (past the temporal transient) and
    collect (out, info) per round."""
    local = _updates(K, d, seed + 1)[0]
    state = wf.init_temporal_state(K, d, cfg.window) if cfg.use_temporal else None
    outs = []
    for r in range(rounds):
        u = _updates(K, d, seed + 10 + r) + 0.5
        out, state, info = wf.wfagg(local, u, state, cfg)
        outs.append((out, info))
    return outs


# ---------------------------------------------------------------------------
# full WFAgg parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [5, 7, 8, 12])
@pytest.mark.parametrize("use_temporal", [True, False])
def test_wfagg_backend_parity(K, use_temporal):
    d = 700
    cfg_r = wf.WFAggConfig(backend="reference", use_temporal=use_temporal)
    cfg_f = wf.WFAggConfig(backend="fused", use_temporal=use_temporal)
    for (o_r, i_r), (o_f, i_f) in zip(
        _run_rounds(cfg_r, K, d), _run_rounds(cfg_f, K, d)
    ):
        for m in ("mask_d", "mask_c", "mask_t"):
            assert np.array_equal(np.asarray(i_r[m]), np.asarray(i_f[m])), m
        np.testing.assert_allclose(np.asarray(i_r["weights"]),
                                   np.asarray(i_f["weights"]), atol=ATOL)
        np.testing.assert_allclose(np.asarray(o_r), np.asarray(o_f),
                                   rtol=ATOL, atol=ATOL)


def test_wfagg_temporal_filter_activates():
    """Sanity: the parity runs above exercise a *live* temporal filter."""
    cfg = wf.WFAggConfig(backend="fused")
    _, info = _run_rounds(cfg, 8, 500)[-1]
    assert np.asarray(info["mask_t"]).any()


@pytest.mark.parametrize("K", [7, 8])
def test_alt_wfagg_backend_parity(K):
    """Multi-Krum + Clustering filters, fused via the statistics phase's
    Gram."""
    d = 600
    cfg_r = wf.alt_wfagg_config(backend="reference")
    cfg_f = wf.alt_wfagg_config(backend="fused")
    for (o_r, i_r), (o_f, i_f) in zip(
        _run_rounds(cfg_r, K, d), _run_rounds(cfg_f, K, d)
    ):
        for m in ("mask_d", "mask_c", "mask_t"):
            assert np.array_equal(np.asarray(i_r[m]), np.asarray(i_f[m])), m
        np.testing.assert_allclose(np.asarray(o_r), np.asarray(o_f),
                                   rtol=ATOL, atol=ATOL)


@pytest.mark.parametrize("attack", atk.ATTACK_NAMES)
def test_wfagg_parity_under_attacks(attack):
    """Masks and aggregates must agree when Byzantine rows are present."""
    K, d, n_mal = 9, 500, 2
    u = np.array(_updates(K, d, seed=3) + 1.0)
    benign = jnp.asarray(u[n_mal:])
    key = jax.random.PRNGKey(7)
    for j in range(n_mal):
        u[j] = np.asarray(atk.apply_model_attack(
            attack, jnp.asarray(u[j]), benign, jax.random.fold_in(key, j)))
    u = jnp.asarray(u)
    local = u[-1]
    cfg_r = wf.WFAggConfig(backend="reference", use_temporal=False)
    cfg_f = wf.WFAggConfig(backend="fused", use_temporal=False)
    o_r, _, i_r = wf.wfagg(local, u, None, cfg_r)
    o_f, _, i_f = wf.wfagg(local, u, None, cfg_f)
    for m in ("mask_d", "mask_c"):
        assert np.array_equal(np.asarray(i_r[m]), np.asarray(i_f[m])), m
    np.testing.assert_allclose(np.asarray(o_r), np.asarray(o_f),
                               rtol=ATOL, atol=ATOL)


# ---------------------------------------------------------------------------
# standalone filter aggregators
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [5, 8])
def test_wfagg_d_c_agg_backend_parity(K):
    u = _updates(K, 700, seed=11)
    for fn in (wf.wfagg_d_agg, wf.wfagg_c_agg):
        out_r, m_r = fn(u, 2, backend="reference")
        out_f, m_f = fn(u, 2, backend="fused")
        assert np.array_equal(np.asarray(m_r), np.asarray(m_f))
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_f),
                                   rtol=ATOL, atol=ATOL)


def test_wfagg_t_select_backend_parity():
    K, d = 8, 400
    cfg_r = wf.WFAggConfig(backend="reference", transient=1)
    cfg_f = wf.WFAggConfig(backend="fused", transient=1)
    s_r = wf.init_temporal_state(K, d, cfg_r.window)
    s_f = wf.init_temporal_state(K, d, cfg_f.window)
    for r in range(5):
        u = _updates(K, d, seed=20 + r)
        m_r, s_r = wf.wfagg_t_select(s_r, u, cfg_r)
        m_f, s_f = wf.wfagg_t_select(s_f, u, cfg_f)
        assert np.array_equal(np.asarray(m_r), np.asarray(m_f)), r
        np.testing.assert_allclose(np.asarray(s_r.hist_s), np.asarray(s_f.hist_s),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# batched kernel and batched WFAgg
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_prev", [True, False])
def test_batched_stats_match_single_node_kernel(with_prev):
    """The statistics phase over all N gathered slates at once (an
    identity slate of N*K rows, ``prev`` per edge) matches it one node
    at a time, and the oracle."""
    N, K, D = 5, 8, 1000
    u = jax.random.normal(jax.random.PRNGKey(0), (N, K, D), jnp.float32)
    p = jax.random.normal(jax.random.PRNGKey(1), (N, K, D), jnp.float32) \
        if with_prev else None
    got = wf._gathered_stats(u, p, need_gram=False)
    slate = jnp.arange(K, dtype=jnp.int32)[None, :]
    tail = ("prev_dist2", "prev_dot", "prev_norm2")
    for n in range(N):
        one = wfagg_round_indexed_stats(u[n], slate,
                                        p[n] if with_prev else None)
        ref = robust_stats_ref(u[n], prev=p[n] if with_prev else None)
        for name in ("dist2", "dotmed", "norm2", "mednorm2") + tail:
            g, s, r = getattr(got, name), getattr(one, name), getattr(ref, name)
            if name in tail and not with_prev:
                assert g is None and s is None and r is None
                continue
            np.testing.assert_allclose(g[n], s[0], rtol=3e-5, atol=3e-5,
                                       err_msg=f"batch-vs-single {name}")
            np.testing.assert_allclose(g[n], r, rtol=3e-5, atol=3e-5,
                                       err_msg=f"batch-vs-oracle {name}")


@pytest.mark.parametrize("filters", ["wfagg", "alt"])
def test_wfagg_batch_matches_per_node_reference(filters):
    N, K, d = 4, 8, 600
    if filters == "alt":
        cfg_f = wf.alt_wfagg_config(backend="fused")
        cfg_r = wf.alt_wfagg_config(backend="reference")
    else:
        cfg_f = wf.WFAggConfig(backend="fused")
        cfg_r = wf.WFAggConfig(backend="reference")
    local = jax.random.normal(jax.random.PRNGKey(0), (N, d), jnp.float32)
    state_b = jax.vmap(lambda _: wf.init_temporal_state(K, d, cfg_f.window))(
        jnp.arange(N))
    states = [wf.init_temporal_state(K, d, cfg_r.window) for _ in range(N)]
    for r in range(6):
        u = jax.random.normal(jax.random.PRNGKey(100 + r), (N, K, d)) + 0.3
        out_b, state_b, info_b = wf.wfagg_batch(local, u, state_b, cfg_f)
        for n in range(N):
            out_1, states[n], info_1 = wf.wfagg(local[n], u[n], states[n], cfg_r)
            for m in ("mask_d", "mask_c", "mask_t"):
                assert np.array_equal(np.asarray(info_b[m][n]),
                                      np.asarray(info_1[m])), (r, n, m)
            np.testing.assert_allclose(np.asarray(out_b[n]), np.asarray(out_1),
                                       rtol=ATOL, atol=ATOL)


# ---------------------------------------------------------------------------
# stacked (distributed) layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["wfagg", "alt_wfagg", "multi_krum", "clustering"])
def test_stacked_fused_matches_reference(method):
    from repro.distributed.robust_allreduce import (
        RobustAggConfig, init_tree_agg_state, robust_allreduce_stacked)

    K = 6
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (K, 32, 8)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (K, 100))}
    wcfg = wf.WFAggConfig(f=1, transient=1, window=2)
    cfg_r = RobustAggConfig(method=method, wfagg=wcfg, layout="stacked",
                            backend="reference")
    cfg_f = dataclasses.replace(cfg_r, backend="fused")
    needs_state = method in ("wfagg", "alt_wfagg")
    like = jax.tree.map(lambda x: x[0], g)
    s_r = init_tree_agg_state(cfg_r, K, like) if needs_state else None
    s_f = init_tree_agg_state(cfg_f, K, like) if needs_state else None
    for r in range(4):
        gr = jax.tree.map(lambda x: x + 0.1 * r, g)
        o_r, s_r, i_r = robust_allreduce_stacked(gr, cfg_r, s_r)
        o_f, s_f, i_f = robust_allreduce_stacked(gr, cfg_f, s_f)
        np.testing.assert_allclose(np.asarray(i_r["weights"]),
                                   np.asarray(i_f["weights"]), atol=ATOL)
        for k in g:
            np.testing.assert_allclose(np.asarray(o_r[k]), np.asarray(o_f[k]),
                                       rtol=1e-4, atol=ATOL)


# ---------------------------------------------------------------------------
# DFL engine: fused backend end-to-end
# ---------------------------------------------------------------------------

def test_engine_fused_matches_reference_metrics():
    """Acceptance: experiment metrics (benign accuracy, R^2) unchanged when
    the round function runs through the fused backend (the default)."""
    from repro.core.topology import paper_topology
    from repro.data.synthetic import SyntheticImages
    from repro.dfl.engine import DFLConfig, run_experiment

    data = SyntheticImages()
    topo = paper_topology()
    res = {}
    for backend in ("fused", "reference"):
        cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp",
                        wfagg_backend=backend)
        res[backend] = run_experiment(cfg, topo, data, rounds=2, eval_every=2)["final"]
    assert res["fused"]["acc_benign_mean"] == pytest.approx(
        res["reference"]["acc_benign_mean"], abs=0.02)
    assert res["fused"]["r_squared"] == pytest.approx(
        res["reference"]["r_squared"], abs=0.02)


def test_memory_passes_accounting():
    """The fused path must cost at least 2x fewer (K, d)-sized passes."""
    cfg_r = wf.WFAggConfig(backend="reference")
    cfg_f = wf.WFAggConfig(backend="fused")
    assert wf.memory_passes(cfg_f) == 2
    assert wf.memory_passes(cfg_r) >= 2 * wf.memory_passes(cfg_f)
    # Alt-WFAgg's Gram rides the statistics pass: no extra pass
    assert wf.memory_passes(wf.alt_wfagg_config(backend="fused")) == 2
