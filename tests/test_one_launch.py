"""Single-launch gossip round: both phases of the WFAgg round kernel in
one launch (backend="fused") must reproduce the valid-aware pure-jnp
reference oracle — masks bit-equal, aggregates within fp32 tolerance —
across every dynamics scenario (including degree-0 churned-out rows),
irregular erdos_renyi-style degrees, both filter families, and the
stacked (mode-B) layout; its two phases run as two launches with the
scoring between them (the d-sharded round's arithmetic) must reproduce
the one launch; and the jitted round must lower to exactly ONE
aggregation pallas_call with no (N, K, d) buffer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wfagg as wf
from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl import dynamics as dyn
from repro.dfl.engine import DFLConfig, build_round_fn, init_dfl_state

ATOL = 3e-5
BACKENDS = ("fused", "reference")


def _matrix_state(N, K, d, cfg):
    """Matrix-prev temporal state (the engine's (N, K, d)-free layout)."""
    return wf.TemporalState(
        prev=jnp.zeros((N, d)),
        hist_s=jnp.zeros((N, cfg.window, K)),
        hist_b=jnp.zeros((N, cfg.window, K)),
        count=jnp.zeros((N,), jnp.int32),
        t=jnp.zeros((N,), jnp.int32))


def _irregular(N, K, seed=0, min_degree=0):
    """Padded (idx, valid) with per-node degrees in [min_degree, K]."""
    rng = np.random.default_rng(seed)
    idx = np.zeros((N, K), np.int32)
    valid = np.zeros((N, K), bool)
    for n in range(N):
        v = int(rng.integers(min_degree, K + 1))
        if v:
            nbrs = rng.choice([i for i in range(N) if i != n], size=v,
                              replace=False)
            idx[n, :v] = nbrs
        idx[n, v:] = n
        valid[n, :v] = True
    return jnp.asarray(idx), jnp.asarray(valid)


# ---------------------------------------------------------------------------
# parity across every dynamics scenario (single launch vs reference)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", dyn.SCENARIO_NAMES)
def test_one_launch_parity_across_scenarios(scenario):
    """Drive the schedule's round-varying slates through the gather-free
    aggregation under both backends, with live temporal state
    re-keyed between rounds exactly like the engine: masks bit-equal,
    aggregates within fp32 tolerance, degree-0 rows keep their local
    model."""
    topo = make_topology(n_nodes=10, degree=4, n_malicious=2, kind="ring",
                         seed=0)
    params = {"churn": {"p_leave": 0.45}}.get(scenario, {})
    sched = dyn.make_schedule(scenario, topo, 3, seed=5, **params)
    N, K, d = topo.n_nodes, sched.width, 192
    cfgs = {b: wf.WFAggConfig(backend=b, transient=1, f=1) for b in BACKENDS}
    states = {b: _matrix_state(N, K, d, c) for b, c in cfgs.items()}
    prev_idx = jnp.asarray(sched.neighbor_idx[0])
    prev_val = jnp.asarray(sched.valid[0])
    saw_deg0 = False
    for r in range(sched.rounds):
        idx = jnp.asarray(sched.neighbor_idx[r])
        val = jnp.asarray(sched.valid[r])
        u = jax.random.normal(jax.random.PRNGKey(70 + r), (N, d)) + 0.3
        outs, infos = {}, {}
        for b, c in cfgs.items():
            # re-key the slot-positional ring buffers to this round's
            # slate by neighbor identity, exactly like the engine
            st = wf.realign_temporal_history(states[b], prev_idx, prev_val,
                                             idx, val)
            outs[b], states[b], infos[b] = wf.wfagg_batch(
                u, u, st, c, neighbor_idx=idx, valid=val)
        prev_idx, prev_val = idx, val
        for b in ("reference",):
            for m in ("mask_d", "mask_c", "mask_t"):
                assert np.array_equal(np.asarray(infos["fused"][m]),
                                      np.asarray(infos[b][m])), (r, b, m)
            np.testing.assert_allclose(np.asarray(outs["fused"]),
                                       np.asarray(outs[b]),
                                       rtol=ATOL, atol=ATOL,
                                       err_msg=f"{scenario} r{r} {b}")
        deg0 = np.asarray(val).sum(axis=1) == 0
        if deg0.any():
            saw_deg0 = True
            np.testing.assert_allclose(np.asarray(outs["fused"])[deg0],
                                       np.asarray(u)[deg0],
                                       rtol=1e-6, atol=1e-6)
        assert np.isfinite(np.asarray(outs["fused"])).all()
        assert states["fused"].prev.shape == (N, d)   # matrix state kept
    if scenario == "churn":
        assert saw_deg0, "churn schedule never produced a degree-0 node"


@pytest.mark.parametrize("filters", ["wfagg", "alt"])
def test_one_launch_irregular_parity(filters):
    """erdos_renyi-style irregular padded slates, both filter families
    (Alt-WFAgg exercises the in-kernel Gram + Multi-Krum/Clustering
    derivation), temporal state live."""
    N, K, d = 9, 5, 220
    idx, val = _irregular(N, K, seed=8, min_degree=0)
    assert (np.asarray(val).sum(1) == 0).any()   # a degree-0 row rides along
    mk = wf.alt_wfagg_config if filters == "alt" else wf.WFAggConfig
    cfgs = {b: mk(backend=b, transient=1, f=1,
                  **({"multi_krum_m": 2} if filters == "alt" else {}))
            for b in BACKENDS}
    states = {b: _matrix_state(N, K, d, c) for b, c in cfgs.items()}
    for r in range(4):
        u = jax.random.normal(jax.random.PRNGKey(90 + r), (N, d)) + 0.2
        outs, infos = {}, {}
        for b, c in cfgs.items():
            outs[b], states[b], infos[b] = wf.wfagg_batch(
                u, u, states[b], c, neighbor_idx=idx, valid=val)
        for b in ("reference",):
            for m in ("mask_d", "mask_c", "mask_t"):
                assert np.array_equal(np.asarray(infos["fused"][m]),
                                      np.asarray(infos[b][m])), (r, b, m)
            np.testing.assert_allclose(np.asarray(outs["fused"]),
                                       np.asarray(outs[b]),
                                       rtol=ATOL, atol=ATOL)


def test_one_launch_regular_matches_unmasked():
    """valid=None (regular slate) runs the same single launch with an
    implicit all-valid mask — must equal the explicit all-ones mask."""
    N, K, d = 8, 4, 300
    idx = jnp.asarray(
        [[(n + o) % N for o in range(1, K + 1)] for n in range(N)], jnp.int32)
    cfg = wf.WFAggConfig(backend="fused", use_temporal=False)
    u = jax.random.normal(jax.random.PRNGKey(4), (N, d)) + 0.1
    o1, _, i1 = wf.wfagg_batch(u, u, None, cfg, neighbor_idx=idx)
    o2, _, i2 = wf.wfagg_batch(u, u, None, cfg, neighbor_idx=idx,
                               valid=jnp.ones((N, K), bool))
    assert np.array_equal(np.asarray(i1["mask_d"]), np.asarray(i2["mask_d"]))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


def test_one_launch_multiblock_d_matches_single_block():
    """Force n_d > 1 through the round op (interpret mode defaults to ONE
    D block): the phase boundary fires on the LAST D block, the combine
    output is pinned during phase 0 and re-walked in phase 1 — block
    count must not change the result beyond fp32 reassociation."""
    from repro.kernels.robust_stats.ops import wfagg_round_indexed

    N, K, d = 6, 4, 384
    models = jax.random.normal(jax.random.PRNGKey(12), (N, d), jnp.float32) + 0.2
    prev = jax.random.normal(jax.random.PRNGKey(13), (N, d), jnp.float32)
    idx, val = _irregular(N, K, seed=3, min_degree=1)
    cfg = wf.WFAggConfig(transient=0, f=1)
    tbands = jax.vmap(
        lambda hs, hb: wf.trust.temporal_bands(
            hs, hb, jnp.asarray(2), jnp.asarray(3), cfg)
    )(0.5 * jnp.ones((N, cfg.window, K)), 0.5 * jnp.ones((N, cfg.window, K)))
    rs = {}
    for label, block in (("one", None), ("multi", 128)):
        rs[label] = wfagg_round_indexed(models, models, idx, val, cfg,
                                        prev=prev, tbands=tbands,
                                        block_d=block)
    for a, b in zip(rs["one"], rs["multi"]):
        for ga, gb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                       rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# the (node, phase, D tile) grid: forced tile widths against the reference
# ---------------------------------------------------------------------------

def _live_state(models, idx, prev, prev_idx, cfg, seed):
    """Temporal state past its transient whose metric history scatters
    around this round's own WFAgg-T metrics, so the in-kernel band
    compare accepts some edges and rejects others."""
    from repro.kernels.robust_stats.ref import robust_stats_indexed_ref

    N, K = idx.shape
    st = robust_stats_indexed_ref(models, idx, None, prev, prev_idx=prev_idx)
    rng = np.random.default_rng(seed)
    hist = lambda m: jnp.asarray(  # noqa: E731
        np.asarray(m)[:, None, :]
        * (1 + 0.1 * rng.standard_normal((N, cfg.window, K))), jnp.float32)
    return wf.TemporalState(
        prev=prev, hist_s=hist(st.prev_dist2), hist_b=hist(st.cosine_to_prev()),
        count=jnp.full((N,), cfg.window, jnp.int32),
        t=jnp.full((N,), cfg.window + 2, jnp.int32))


def _tiled_round(local, models, idx, valid, state, cfg, prev_idx, block_d):
    """The fused round at a forced tile width, assembled as
    ``wfagg_batch`` assembles it (bands from the state's history)."""
    from repro.kernels.robust_stats.ops import wfagg_round_indexed

    tbands = jax.vmap(
        lambda hs, hb, c, tt: wf.trust.temporal_bands(hs, hb, c, tt, cfg)
    )(state.hist_s, state.hist_b, state.count, state.t)
    out, w, md, mc, mt, _ = wfagg_round_indexed(
        local, models, idx, valid, cfg, prev=state.prev, tbands=tbands,
        prev_idx=prev_idx, block_d=block_d)
    return out, {"mask_d": md, "mask_c": mc, "mask_t": mt, "weights": w}


RETILED = {
    # name: (N, K, d, block_d, min_degree, prev rows, filters)
    "tiles_d_ragged": (7, 5, 1000, 384, 1, "table", "wfagg"),
    "chunks_per_tile": (6, 4, 4500, 2048, 1, "table", "wfagg"),
    "k13_padded_degree0": (16, 13, 700, 256, 0, "table", "wfagg"),
    "prev_idx_matrix": (9, 6, 900, 256, 0, "prev_idx", "wfagg"),
    "alt_gram": (9, 5, 640, 256, 0, "table", "alt"),
    # 2,048-lane tiles of two 1,024-lane chunks: the flush's lane-wide
    # partials carried across chunks and tiles, at the slate widths of
    # the trainer (4), paper20 (8) and er1024 (30)
    "k4_chunks_gram": (6, 4, 4500, 2048, 0, "table", "alt"),
    "k8_chunks_prev_idx_gram": (10, 8, 4500, 2048, 0, "prev_idx", "alt"),
    "k30_chunks_prev_idx": (32, 30, 2500, 2048, 0, "prev_idx", "wfagg"),
}


@pytest.mark.parametrize("case", list(RETILED))
def test_retiled_round_parity(case):
    """Several (K, T) tiles per node, d not a multiple of T, K off the
    8-row sublane tile, padded slots and degree-0 nodes, a matrix prev
    read through its own table, and the Alt-WFAgg Gram: the round kernel
    at a forced tile width must match the valid-aware reference (masks
    bit-equal, aggregates within fp32)."""
    N, K, d, block_d, min_deg, prev_rows, filters = RETILED[case]
    assert d % block_d != 0 or case == "chunks_per_tile"
    idx, val = _irregular(N, K, seed=21, min_degree=min_deg)
    if min_deg == 0:
        val = val.at[2].set(False)                  # a degree-0 node
        idx = idx.at[2].set(2)
    key = jax.random.PRNGKey(31)
    models = jax.random.normal(key, (N, d)) + 0.2
    prev_idx = None
    if prev_rows == "prev_idx":
        # the chaos transport's shape: a stacked (2N, d) matrix, each edge
        # compared against a row other than the one it reads (rows of the
        # older half, so no edge compares a model with itself)
        prev = jnp.concatenate(
            [models, models + 0.05 * jax.random.normal(
                jax.random.PRNGKey(32), (N, d))])
        rng = np.random.default_rng(5)
        prev_idx = jnp.asarray(np.where(
            np.asarray(val), rng.integers(N, 2 * N, (N, K)),
            np.arange(N, 2 * N)[:, None]), jnp.int32)
    else:
        prev = models + 0.05 * jax.random.normal(jax.random.PRNGKey(33),
                                                 (N, d))
    mk = wf.alt_wfagg_config if filters == "alt" else wf.WFAggConfig
    extra = {"multi_krum_m": 2} if filters == "alt" else {}
    cfgs = {b: mk(backend=b, transient=1, f=1, **extra) for b in BACKENDS}
    local = models + 0.01
    state = {b: _live_state(models, idx, prev, prev_idx, c, 3)
             for b, c in cfgs.items()}
    out, info = _tiled_round(local, models, idx, val, state["fused"],
                             cfgs["fused"], prev_idx, block_d)
    for b in ("reference",):
        o_b, _, i_b = wf.wfagg_batch(
            local, models, state[b], cfgs[b], neighbor_idx=idx, valid=val,
            prev_idx=prev_idx)
        for m in ("mask_d", "mask_c", "mask_t"):
            assert np.array_equal(np.asarray(info[m]),
                                  np.asarray(i_b[m])), (case, b, m)
        np.testing.assert_allclose(np.asarray(out), np.asarray(o_b),
                                   rtol=ATOL, atol=ATOL,
                                   err_msg=f"{case} {b}")
    mt = np.asarray(info["mask_t"])[np.asarray(val)]
    assert mt.any() and not mt.all(), mt
    deg0 = np.asarray(val).sum(axis=1) == 0
    np.testing.assert_allclose(np.asarray(out)[deg0],
                               np.asarray(local)[deg0], rtol=1e-6, atol=1e-6)


@pytest.mark.xfail(strict=True, reason=(
    "known defect, older than the retiled grid: with a zero-spread WFAgg-T "
    "history the band is the single point mu, and an unchanged model's "
    "cosine metric 1 - dot / sqrt(n * n) lands on either side of it by "
    "rounding, so the fused round rejects edges the reference accepts"))
def test_zero_spread_history_band_agrees():
    """Every neighbor re-sends the model it sent last round (s_t = 0,
    b_t = 0 up to rounding) against a history of exact zeros, past the
    transient: the fused round's WFAgg-T decisions must equal the
    reference's."""
    N, K, d = 9, 6, 900
    idx, val = _irregular(N, K, seed=21, min_degree=1)
    models = jax.random.normal(jax.random.PRNGKey(31), (N, d)) + 0.2
    masks = {}
    for b in ("fused", "reference"):
        cfg = wf.WFAggConfig(backend=b, transient=1, f=1)
        state = wf.TemporalState(
            prev=models, hist_s=jnp.zeros((N, cfg.window, K)),
            hist_b=jnp.zeros((N, cfg.window, K)),
            count=jnp.full((N,), cfg.window, jnp.int32),
            t=jnp.full((N,), cfg.window + 2, jnp.int32))
        if b == "fused":
            _, info = _tiled_round(models + 0.01, models, idx, val, state,
                                   cfg, None, 256)
        else:
            _, _, info = wf.wfagg_batch(models + 0.01, models, state, cfg,
                                        neighbor_idx=idx, valid=val)
        masks[b] = np.asarray(info["mask_t"])
    assert np.array_equal(masks["fused"], masks["reference"])


@pytest.mark.parametrize("block_d", [512, 1536])
def test_retiled_round_trainer_shape(block_d):
    """The robust-DP trainer's instance: N = 1, an identity table over K
    replica gradients, alpha = 1 with the uniform-mean fallback, P not a
    multiple of the tile.  The output is the trust-normalized mean of the
    valid-aware reference's weights."""
    from repro.kernels.robust_stats.ops import wfagg_round_indexed

    K, P = 6, 2500
    flat = jax.random.normal(jax.random.PRNGKey(41), (K, P)) + 0.1
    flat = flat.at[5].mul(-4.0)                     # one outlier replica
    idx = jnp.arange(K, dtype=jnp.int32)[None, :]
    cfg = wf.WFAggConfig(backend="reference", f=1, use_temporal=False)
    out, w, md, mc, mt, _ = wfagg_round_indexed(
        jnp.zeros((1, P)), flat, idx, None, cfg, alpha=1.0,
        mean_fallback=True, block_d=block_d)
    _, _, info = wf.wfagg_batch(jnp.zeros((1, P)), flat, None, cfg,
                                neighbor_idx=idx,
                                valid=jnp.ones((1, K), bool))
    for name, got in (("mask_d", md), ("mask_c", mc)):
        assert np.array_equal(np.asarray(got), np.asarray(info[name])), name
    wr = np.asarray(info["weights"][0], np.float64)
    assert 0 < wr.sum() and wr[5] == 0
    want = wr @ np.asarray(flat, np.float64) / wr.sum()
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=ATOL,
                               atol=ATOL)


# ---------------------------------------------------------------------------
# the two phases as two launches: the d-sharded round's arithmetic
# ---------------------------------------------------------------------------

SPLIT = {
    # name: (irregular valid mask, prev rows)
    "static": (False, "table"),
    "irregular": (True, "table"),
    "chaos_prev_idx": (True, "prev_idx"),
}


@pytest.mark.parametrize("case", list(SPLIT))
@pytest.mark.parametrize("filters", ["wfagg", "alt_wfagg"])
def test_split_phases_match_one_launch(filters, case):
    """The d-sharded round's arithmetic without a mesh: the statistics
    phase on each column half of d, the halves' accumulators summed (the
    psum), the host scoring stage (``core.wfagg._indexed_scoring``) and
    ``core.trust.combine_coefficients``, then the combine phase on each
    half — the same masks as the one launch, which scores in-kernel, and
    the same aggregate within fp32."""
    from repro.kernels.robust_stats.ops import (
        wfagg_round_indexed_combine, wfagg_round_indexed_stats)

    irregular, prev_rows = SPLIT[case]
    N, K, d = 9, 5, 640
    if irregular:
        idx, val = _irregular(N, K, seed=21, min_degree=0)
        val = val.at[2].set(False)                  # a degree-0 node
        idx = idx.at[2].set(2)
    else:
        idx = jnp.asarray((np.arange(N)[:, None] + np.arange(1, K + 1)) % N,
                          jnp.int32)
        val = None
    models = jax.random.normal(jax.random.PRNGKey(31), (N, d)) + 0.2
    prev_idx = None
    if prev_rows == "prev_idx":
        # the chaos transport's shape: a stacked (2N, d) matrix read
        # through its own table, rows of the older half
        prev = jnp.concatenate(
            [models, models + 0.05 * jax.random.normal(
                jax.random.PRNGKey(32), (N, d))])
        prev_idx = jnp.asarray(
            np.random.default_rng(5).integers(N, 2 * N, (N, K)), jnp.int32)
    else:
        prev = models + 0.05 * jax.random.normal(jax.random.PRNGKey(33),
                                                 (N, d))
    mk = wf.alt_wfagg_config if filters == "alt_wfagg" else wf.WFAggConfig
    extra = {"multi_krum_m": 2} if filters == "alt_wfagg" else {}
    cfg = mk(transient=1, f=1, **extra)
    state = _live_state(models, idx, prev, prev_idx, cfg, 3)
    local = models + 0.01
    out_1, info_1 = _tiled_round(local, models, idx, val, state, cfg,
                                 prev_idx, None)

    valid_b = jnp.ones((N, K), bool) if val is None else val
    halves = (slice(0, d // 2), slice(d // 2, d))
    parts = [wfagg_round_indexed_stats(
        models[:, c], idx, prev[:, c], valid=valid_b, prev_idx=prev_idx,
        need_gram=wf.trust.needs_gram(cfg)) for c in halves]
    stats = jax.tree.map(jnp.add, *parts)
    mask_d, mask_c, mask_t, weights, _ = wf._indexed_scoring(
        stats, valid_b, state, cfg, models, idx)
    wcomb, lcoef = jax.vmap(
        lambda w, v: wf.trust.combine_coefficients(w, cfg.alpha, v, False)
    )(weights, valid_b)
    out = jnp.concatenate([wfagg_round_indexed_combine(
        local[:, c], models[:, c], idx, wcomb, lcoef) for c in halves],
        axis=1)
    for name, got in (("mask_d", mask_d), ("mask_c", mask_c),
                      ("mask_t", mask_t)):
        assert np.array_equal(np.asarray(got),
                              np.asarray(info_1[name])), (case, name)
    mt = np.asarray(mask_t)[np.asarray(valid_b)]
    assert mt.any() and not mt.all(), mt
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(info_1["weights"]), atol=ATOL)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_1),
                               rtol=ATOL, atol=ATOL, err_msg=case)


# ---------------------------------------------------------------------------
# satellite: the reference backend's valid-aware oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("filters", ["wfagg", "alt"])
def test_reference_backend_honors_valid_mask(filters):
    """wfagg_batch(backend="reference") with a padded valid mask used to
    raise NotImplementedError; the valid-aware oracle must now match the
    plain single-node reference pipeline run on each node's TRUE (and
    compacted) neighbor slate."""
    N, K, d = 10, 6, 260
    models = jax.random.normal(jax.random.PRNGKey(9), (N, d), jnp.float32) + 0.3
    idx, valid = _irregular(N, K, seed=11, min_degree=1)
    mk = wf.alt_wfagg_config if filters == "alt" else wf.WFAggConfig
    cfg = mk(backend="reference", use_temporal=False, f=1,
             **({"multi_krum_m": 2} if filters == "alt" else {}))
    out, _, info = wf.wfagg_batch(models, models, None, cfg,
                                  neighbor_idx=idx, valid=valid)
    for n in range(N):
        sel = np.asarray(idx[n])[np.asarray(valid[n])]
        v = len(sel)
        cfg_n = mk(backend="reference", use_temporal=False, f=1,
                   **({"multi_krum_m": min(2, v)} if filters == "alt" else {}))
        out_n, _, info_n = wf.wfagg(models[n], models[jnp.asarray(sel)],
                                    None, cfg_n)
        for m in ("mask_d", "mask_c"):
            got = np.asarray(info[m][n])[np.asarray(valid[n])]
            assert np.array_equal(got, np.asarray(info_n[m])), (n, m, v)
            assert not np.asarray(info[m][n])[~np.asarray(valid[n])].any()
        np.testing.assert_allclose(np.asarray(out[n]), np.asarray(out_n),
                                   rtol=ATOL, atol=ATOL, err_msg=str(n))


def test_reference_backend_degree0_keeps_local():
    N, K, d = 6, 3, 128
    idx, valid = _irregular(N, K, seed=2, min_degree=0)
    valid = valid.at[1].set(False)        # force at least one empty slate
    idx = idx.at[1].set(1)
    cfg = wf.WFAggConfig(backend="reference", use_temporal=False, f=1)
    u = jax.random.normal(jax.random.PRNGKey(1), (N, d)) + 0.1
    out, _, info = wf.wfagg_batch(u, u, None, cfg, neighbor_idx=idx,
                                  valid=valid)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(u[1]),
                               rtol=1e-6, atol=1e-6)
    assert int(np.asarray(info["n_accepted"])[1]) == 0


# ---------------------------------------------------------------------------
# launch-count + HLO assertions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("aggregator", ["wfagg", "alt_wfagg"])
def test_round_is_single_pallas_launch(aggregator):
    """The jitted dynamic round must contain exactly ONE aggregation
    pallas_call under the fused backend (the split phases of the
    d-sharded round, on one device, keep two — sanity check that the
    counter sees them), and its compiled HLO must stay (N, K, d)-free.
    Both properties are asserted through the shared ``repro.analysis``
    rule API (the same walkers the ``python -m repro.analysis`` gate
    runs)."""
    from repro.analysis import count_pallas_calls, scan_nkd_buffers
    from repro.dfl.engine import _wfagg_full_config
    from repro.distributed import spmd

    topo = make_topology(n_nodes=10, degree=4, n_malicious=2, kind="ring",
                         seed=0)
    data = SyntheticImages()
    sched = dyn.churn_schedule(topo, 3, seed=1)
    N, K = topo.n_nodes, sched.width
    cfg = DFLConfig(aggregator=aggregator, attack="ipm_100", model="mlp")
    fn = build_round_fn(cfg, topo, data, dynamic=True)
    state = init_dfl_state(cfg, topo, degree=K)
    args = (state, jnp.asarray(sched.neighbor_idx[0]),
            jnp.asarray(sched.valid[0]), jnp.asarray(sched.malicious[0]))
    assert count_pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr) == 1
    hlo = fn.lower(*args).compile().as_text()
    # d-sized (N, K, d) buffers only: the alt_wfagg (N, K, K) Gram is a
    # legit O(K^2) statistic, not a gossip tensor
    hits = scan_nkd_buffers(hlo, N, K, min_d=16 * K)
    assert hits == [], hits

    wcfg = _wfagg_full_config(cfg, K)
    u = jnp.zeros((N, 64), jnp.float32)
    split = jax.make_jaxpr(
        lambda m, st, i, v: spmd.wfagg_batch_sharded(
            m, m, st, wcfg, i, v, mesh=spmd.aggregation_mesh(1)))(
        u, _matrix_state(N, K, 64, wcfg), args[1], args[2])
    assert count_pallas_calls(split.jaxpr) == 2


def test_memory_passes_one_launch_accounting():
    """The indexed single-launch round reports 2 candidate passes (its
    combine phase gathers the tiles again); Alt-WFAgg folds its Gram
    into the statistics phase, so it adds none."""
    one = wf.WFAggConfig()
    assert wf.memory_passes(one, include_gather=True, indexed=True) == 2
    assert wf.memory_passes(
        wf.alt_wfagg_config(), include_gather=True, indexed=True) == 2
    # the gathered entries run the same two phases: stats, then combine
    assert wf.memory_passes(one) == 2
    assert wf.memory_passes(wf.alt_wfagg_config()) == 2


# ---------------------------------------------------------------------------
# stacked (mode-B) layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["wfagg", "alt_wfagg"])
def test_stacked_one_launch_matches_fallbacks(method):
    import dataclasses

    from repro.distributed.robust_allreduce import (
        RobustAggConfig, init_tree_agg_state, robust_allreduce_stacked)

    K = 6
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (K, 24, 6)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (K, 80))}
    wcfg = wf.WFAggConfig(f=1, transient=1, window=2)
    base = RobustAggConfig(method=method, wfagg=wcfg, layout="stacked")
    cfgs = {b: dataclasses.replace(base, backend=b) for b in BACKENDS}
    like = jax.tree.map(lambda x: x[0], g)
    states = {b: init_tree_agg_state(c, K, like) for b, c in cfgs.items()}
    for r in range(4):
        gr = jax.tree.map(lambda x: x + 0.1 * r, g)
        res = {}
        for b, c in cfgs.items():
            out, states[b], info = robust_allreduce_stacked(gr, c, states[b])
            res[b] = (out, info)
        for b in ("reference",):
            np.testing.assert_allclose(
                np.asarray(res["fused"][1]["weights"]),
                np.asarray(res[b][1]["weights"]), atol=ATOL)
            for k in g:
                np.testing.assert_allclose(
                    np.asarray(res["fused"][0][k]),
                    np.asarray(res[b][0][k]), rtol=1e-4, atol=ATOL,
                    err_msg=(r, b, k))
