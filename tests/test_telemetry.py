"""Flight recorder (repro.obs): the decision plane must be a pure
*observer* — verdict bits bit-equal to the masks the aggregation already
computes (reference backend as oracle, across every dynamics scenario
and all three WFAgg backends), model trajectories bit-identical with
telemetry on or off — and the export plane must round-trip its own
schema (JSONL log, Perfetto trace, audit rates on hand-built verdicts).
docs/OBSERVABILITY.md documents the planes; the launch-count/purity
side is pinned statically by the ``dynamic_scan_telemetry`` entry of
``repro.analysis`` (tests/test_static_analysis.py)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wfagg as wf
from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl.dynamics import SCENARIO_NAMES, make_schedule
from repro.dfl.engine import DFLConfig, run_dynamic_experiment, run_experiment
from repro.obs import decision as obs
from repro.obs import profile as obs_profile
from repro.obs import recorder as obs_recorder
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace

BACKENDS = ("fused", "fused_two_launch", "reference")


# ---------------------------------------------------------------------------
# decision plane: pack/unpack + record semantics
# ---------------------------------------------------------------------------

def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    masks = {name: jnp.asarray(rng.random((6, 4)) < 0.5)
             for name in obs.BITS}
    v = obs.pack_verdict(masks["mask_d"], masks["mask_c"], masks["mask_t"],
                         masks["valid"], masks["accepted"])
    assert np.asarray(v).dtype == np.uint8
    back = obs.unpack_verdict(np.asarray(v))
    for name in obs.BITS:
        assert np.array_equal(back[name], np.asarray(masks[name])), name


def test_record_from_masks_semantics():
    """Hand-built 3-node slate: normal node, all-rejected node
    (mean-fallback), padded-away node (degree zero)."""
    t = True
    f = False
    mask = jnp.asarray([[t, t, f], [f, f, f], [f, f, f]])
    valid = jnp.asarray([[t, t, t], [t, t, f], [f, f, f]])
    weights = jnp.asarray([[0.5, 0.5, 0.0], [0.0, 0.0, 0.0], [0.0, 0.0, 0.0]])
    rec = obs.record_from_masks(mask, mask, mask, valid, weights)
    assert np.array_equal(np.asarray(rec.accepted), [2, 0, 0])
    assert np.array_equal(np.asarray(rec.mean_fallback), [False, True, False])
    assert np.array_equal(np.asarray(rec.degree_zero), [False, False, True])
    ent = np.asarray(rec.entropy)
    # two equal weights -> log 2; all-rejected / degree-0 -> defined as 0
    np.testing.assert_allclose(ent[0], np.log(2.0), rtol=1e-6)
    assert ent[1] == 0.0 and ent[2] == 0.0
    bits = obs.unpack_verdict(np.asarray(rec.verdict))
    assert np.array_equal(bits["valid"], np.asarray(valid))
    assert np.array_equal(bits["accepted"], np.asarray(weights > 0))


def test_record_uniform_baselines():
    valid = jnp.asarray([[True, True, False], [False, False, False]])
    rec = obs.record_uniform(valid)
    bits = obs.unpack_verdict(np.asarray(rec.verdict))
    # filter bits stay 0 (a report must check BIT_ACCEPTED first)
    for name in ("mask_d", "mask_c", "mask_t"):
        assert not bits[name].any(), name
    assert np.array_equal(bits["accepted"], np.asarray(valid))
    assert np.array_equal(np.asarray(rec.accepted), [2, 0])
    assert np.array_equal(np.asarray(rec.degree_zero), [False, True])
    np.testing.assert_allclose(np.asarray(rec.entropy), [np.log(2.0), 0.0],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# verdict bitmask vs the reference backend's masks, every scenario x backend
# ---------------------------------------------------------------------------

def _scenario_records(scenario, backend, rounds=4, N=8, K=4, d=96):
    """Drive wfagg_batch round by round over a scenario's slates (the
    engine's matrix-prev temporal layout + per-round history realign)
    and collect the DecisionRecord of every round."""
    topo = make_topology(n_nodes=N, degree=K, n_malicious=2, kind="ring",
                         seed=0)
    sched = make_schedule(scenario, topo, rounds, seed=0)
    K = sched.neighbor_idx.shape[-1]  # rewiring may widen the padded slate
    cfg = wf.WFAggConfig(backend=backend, f=1, transient=1, window=2)
    st = wf.TemporalState(
        prev=jnp.zeros((N, d)),
        hist_s=jnp.zeros((N, cfg.window, K)),
        hist_b=jnp.zeros((N, cfg.window, K)),
        count=jnp.zeros((N,), jnp.int32), t=jnp.zeros((N,), jnp.int32))
    recs = []
    for r in range(rounds):
        idx = jnp.asarray(sched.neighbor_idx[r])
        val = jnp.asarray(sched.valid[r], bool)
        if r > 0:
            st = wf.realign_temporal_history(
                st, jnp.asarray(sched.neighbor_idx[r - 1]),
                jnp.asarray(sched.valid[r - 1], bool), idx, val)
        u = jax.random.normal(jax.random.PRNGKey(100 + r), (N, d)) + 0.3
        _, st, info = wf.wfagg_batch(u, u, st, cfg, neighbor_idx=idx,
                                     valid=val)
        recs.append(jax.device_get(obs.record_from_info(info)))
    return recs


@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_verdict_matches_reference_masks_every_scenario(scenario):
    """The packed verdict of EVERY backend must agree bit-for-bit with
    the reference backend's masks on the valid lanes, for every
    dynamics scenario (padded slates, churn, DoS'd nodes included)."""
    per_backend = {b: _scenario_records(scenario, b) for b in BACKENDS}
    ref = per_backend["reference"]
    for b in BACKENDS:
        for r, (rec, rec_ref) in enumerate(zip(per_backend[b], ref)):
            bits = obs.unpack_verdict(np.asarray(rec.verdict))
            ref_bits = obs.unpack_verdict(np.asarray(rec_ref.verdict))
            assert np.array_equal(bits["valid"], ref_bits["valid"]), (b, r)
            valid = bits["valid"]
            for name in ("mask_d", "mask_c", "mask_t", "accepted"):
                assert np.array_equal(bits[name][valid],
                                      ref_bits[name][valid]), \
                    (scenario, b, r, name)
            for field in ("accepted", "mean_fallback", "degree_zero"):
                assert np.array_equal(np.asarray(getattr(rec, field)),
                                      np.asarray(getattr(rec_ref, field))), \
                    (scenario, b, r, field)


def test_record_from_info_reflects_info_masks():
    """record_from_info is a pure repack: the unpacked bits must equal
    the info dict's own masks exactly (valid lanes AND padding)."""
    for scenario in ("static", "eclipse"):
        topo = make_topology(n_nodes=8, degree=4, n_malicious=2, kind="ring",
                             seed=0)
        sched = make_schedule(scenario, topo, 3, seed=0)
        cfg = wf.WFAggConfig(backend="fused", f=1)
        idx = jnp.asarray(sched.neighbor_idx[-1])
        val = jnp.asarray(sched.valid[-1], bool)
        u = jax.random.normal(jax.random.PRNGKey(7), (8, 96)) + 0.3
        _, _, info = wf.wfagg_batch(u, u, None, cfg, neighbor_idx=idx,
                                    valid=val)
        bits = obs.unpack_verdict(np.asarray(obs.record_from_info(info).verdict))
        for name in ("mask_d", "mask_c", "mask_t"):
            assert np.array_equal(bits[name], np.asarray(info[name])), \
                (scenario, name)
        assert np.array_equal(bits["valid"], np.asarray(info["valid"]))
        assert np.array_equal(
            bits["accepted"],
            np.asarray((info["weights"] > 0) & info["valid"]))


# ---------------------------------------------------------------------------
# telemetry is an observer: bit-identical trajectories on/off
# ---------------------------------------------------------------------------

def _small():
    topo = make_topology(n_nodes=8, degree=4, n_malicious=2, kind="ring",
                         seed=0)
    data = SyntheticImages(seed=0)
    cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp",
                    seed=0)
    return cfg, topo, data


def test_trajectory_bit_identical_dynamic():
    cfg, topo, data = _small()
    sched = make_schedule("churn", topo, 3, seed=0)
    off = run_dynamic_experiment(cfg, topo, data, sched, n_test=64)
    on = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                telemetry=True)
    assert np.array_equal(np.asarray(off["series"]["acc_benign_mean"]),
                          np.asarray(on["series"]["acc_benign_mean"]))
    assert np.array_equal(np.asarray(off["final"]["acc_all"]),
                          np.asarray(on["final"]["acc_all"]))
    tel = on["telemetry"]
    R, N, K = 3, topo.n_nodes, sched.neighbor_idx.shape[-1]
    assert tel["verdict"].shape == (R, N, K)
    assert tel["verdict"].dtype == np.uint8
    for key in ("accepted", "mean_fallback", "degree_zero", "entropy"):
        assert tel[key].shape == (R, N), key
    # fallback counters ride the telemetry record in the dynamic engine
    assert len(on["series"]["mean_fallback_count"]) == R
    assert len(on["series"]["degree_zero_count"]) == R
    assert len(on["series"]["accepted_mean"]) == R


def test_trajectory_bit_identical_static():
    cfg, topo, data = _small()
    off = run_experiment(cfg, topo, data, rounds=3, eval_every=3)
    on = run_experiment(cfg, topo, data, rounds=3, eval_every=3,
                        telemetry=True)
    assert np.array_equal(np.asarray(off["final"]["acc_all"]),
                          np.asarray(on["final"]["acc_all"]))
    # static topo arrays are broadcast to (R, ...) so one report path
    # serves both engines
    tel = on["telemetry"]
    assert tel["verdict"].shape[0] == 3
    assert tel["neighbor_idx"].shape == tel["verdict"].shape
    assert tel["malicious"].shape == (3, topo.n_nodes)
    for out in (off, on):
        assert len(out["series"]["mean_fallback_count"]) == 3


def test_dos_scenario_surfaces_degree_zero():
    """The DoS window cuts the victim off entirely — the engine series
    must show degree-0 rounds (what satellite 2 exists for)."""
    cfg, topo, data = _small()
    sched = make_schedule("dos", topo, 4, seed=0)
    assert (np.asarray(sched.valid).sum(axis=-1) == 0).any(), \
        "fixture: dos schedule should DoS someone"
    out = run_dynamic_experiment(cfg, topo, data, sched, n_test=64,
                                 telemetry=True)
    assert sum(out["series"]["degree_zero_count"]) > 0


def test_centralized_telemetry_rejected():
    topo = make_topology(n_nodes=8, degree=4, n_malicious=2,
                         kind="complete", seed=0)
    cfg = DFLConfig(aggregator="mean", attack="none", model="mlp",
                    centralized=True)
    with pytest.raises(NotImplementedError):
        run_experiment(cfg, topo, SyntheticImages(seed=0), rounds=1,
                       telemetry=True)


# ---------------------------------------------------------------------------
# mode B: the all-reduce threads the same record
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_stacked_allreduce_record(backend):
    from repro.distributed.robust_allreduce import (
        RobustAggConfig, init_tree_agg_state, robust_allreduce_stacked)

    K = 6
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (K, 24, 6)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (K, 80))}
    wcfg = wf.WFAggConfig(f=1, transient=1, window=2)
    cfg = RobustAggConfig(method="wfagg", wfagg=wcfg, layout="stacked",
                          backend=backend)
    state = init_tree_agg_state(cfg, K, jax.tree.map(lambda x: x[0], g))
    for r in range(3):
        gr = jax.tree.map(lambda x: x + 0.1 * r, g)
        _, state, info = robust_allreduce_stacked(gr, cfg, state)
        assert "record" in info, backend
        rec = info["record"]
        bits = obs.unpack_verdict(np.asarray(rec.verdict))
        assert bits["valid"].all()  # mode B has no padded slate
        for name in ("mask_d", "mask_c", "mask_t"):
            assert np.array_equal(bits[name].ravel(),
                                  np.asarray(info[name]).ravel()), (r, name)
        assert np.array_equal(bits["accepted"].ravel(),
                              np.asarray(info["weights"] > 0).ravel())


# ---------------------------------------------------------------------------
# export plane: audit rates, attribution, JSONL schema, Perfetto trace
# ---------------------------------------------------------------------------

def _synthetic_telemetry():
    """1 round, 2 receiving nodes, K=2, 4-node system, node 3 malicious.
    Filter D catches both attacker edges + 1/2 benign; C accepts all;
    T rejects everything (transient-style blanket abstention)."""
    t, f = True, False
    mask_d = jnp.asarray([[[f, f], [t, f]]])   # (R=1, N=2, K=2)
    mask_c = jnp.ones((1, 2, 2), bool)
    mask_t = jnp.zeros((1, 2, 2), bool)
    valid = jnp.ones((1, 2, 2), bool)
    accepted = mask_d & mask_c
    verdict = obs.pack_verdict(mask_d, mask_c, mask_t, valid, accepted)
    return {
        "verdict": np.asarray(verdict),
        "neighbor_idx": np.asarray([[[1, 3], [0, 3]]]),
        "valid": np.ones((1, 2, 2), bool),
        "malicious": np.asarray([[False, False, False, True]]),
        "accepted": np.asarray(accepted.sum(-1), np.int32),
        "mean_fallback": np.zeros((1, 2), bool),
        "degree_zero": np.zeros((1, 2), bool),
        "entropy": np.zeros((1, 2), np.float32),
    }


def test_filter_rates_exact():
    tel = _synthetic_telemetry()
    rates = obs_report.telemetry_rates(tel)
    np.testing.assert_array_equal(rates["n_attacker_edges"], [2.0])
    np.testing.assert_array_equal(rates["n_benign_edges"], [2.0])
    # D rejected both attacker edges and one of two benign edges
    assert rates["d"]["true_catch"][0] == 1.0
    assert rates["d"]["false_pos"][0] == 0.5
    # C rejected nothing; T rejected everything
    assert rates["c"]["true_catch"][0] == 0.0
    assert rates["c"]["false_pos"][0] == 0.0
    assert rates["t"]["true_catch"][0] == 1.0
    assert rates["t"]["false_pos"][0] == 1.0
    # final = the accepted bit (d & c here)
    assert rates["final"]["true_catch"][0] == 1.0
    assert rates["final"]["false_pos"][0] == 0.5


def test_attribution_margin_rule():
    tel = _synthetic_telemetry()
    attr = obs_report.attribution(obs_report.telemetry_rates(tel))
    # D: margin 0.5; C: 0; T: 0 (catches all by rejecting all) -> D carries
    assert attr["carried_by"] == "d"
    assert attr["d"]["margin"] == 0.5
    assert attr["t"]["margin"] == 0.0
    # blanket abstention alone must NOT claim credit
    v = obs.unpack_verdict(tel["verdict"])
    v["mask_d"][:] = True  # D now accepts everything too
    tel2 = dict(tel, verdict=np.asarray(obs.pack_verdict(
        jnp.asarray(v["mask_d"]), jnp.asarray(v["mask_c"]),
        jnp.asarray(v["mask_t"]), jnp.asarray(v["valid"]),
        jnp.asarray(v["accepted"]))))
    attr2 = obs_report.attribution(obs_report.telemetry_rates(tel2))
    assert attr2["carried_by"] is None


def test_rates_nan_without_attackers():
    tel = _synthetic_telemetry()
    tel["malicious"] = np.zeros((1, 4), bool)
    rates = obs_report.telemetry_rates(tel)
    assert np.isnan(rates["d"]["true_catch"][0])
    attr = obs_report.attribution(rates)
    assert attr["carried_by"] is None


def test_event_stream_schema_roundtrip(tmp_path):
    tel = _synthetic_telemetry()
    events = obs_report.events_from_telemetry(
        tel, dict(aggregator="wfagg", attack="unit", scenario="static",
                  backend="fused"))
    assert obs_recorder.validate_events(events, strict=True) == []
    path = str(tmp_path / "flight.jsonl")
    obs_recorder.write_events(events, path)
    back = obs_recorder.read_events(path)
    assert back == json.loads(json.dumps(events))  # jsonable + stable
    # stream-level checks actually fire
    assert obs_recorder.validate_events(events[1:])  # no run_meta first
    doctored = [dict(ev) for ev in events]
    doctored[1]["verdict"] = [[1]]  # wrong (N, K) shape
    assert any("verdict" in e for e in obs_recorder.validate_events(doctored))
    with pytest.raises(ValueError):
        obs_recorder.validate_events(doctored, strict=True)


def test_flight_recorder_streams_jsonl(tmp_path):
    path = str(tmp_path / "rec.jsonl")
    with obs_recorder.FlightRecorder(path) as rec:
        rec.emit("run_meta", n_nodes=2, width=2, rounds=1,
                 aggregator="wfagg", attack="none", scenario="static",
                 backend="fused")
        rec.emit("round_timing", round=1, wall_s=0.5, kind="compile")
        with pytest.raises(ValueError):
            rec.emit("round_timing", round=2, wall_s=0.5, kind="bogus")
    assert len(obs_recorder.read_events(path)) == 2


def test_perfetto_trace_structure(tmp_path):
    tel = _synthetic_telemetry()
    events = obs_report.events_from_telemetry(
        tel, dict(aggregator="wfagg", attack="unit", scenario="static",
                  backend="fused"))
    path = str(tmp_path / "trace.json")
    obs_trace.write_trace(events, path)
    with open(path) as f:
        trace = json.load(f)
    tes = trace["traceEvents"]
    assert tes and all(ev["ph"] in ("X", "C", "M") and "pid" in ev
                       for ev in tes)
    slices = [ev for ev in tes if ev["ph"] == "X"]
    assert len(slices) == 1  # one round
    assert all(ev["dur"] > 0 for ev in slices)
    ts = [ev["ts"] for ev in tes if ev["ph"] in ("X", "C")]
    assert ts == sorted(ts)


def test_render_audit_smoke():
    tel = _synthetic_telemetry()
    events = obs_report.events_from_telemetry(
        tel, dict(aggregator="wfagg", attack="unit", scenario="static",
                  backend="fused"))
    text = obs_report.render_audit(events)
    assert "true-catch" in text and "carried by" in text.lower()


# ---------------------------------------------------------------------------
# timing plane + microbench methodology
# ---------------------------------------------------------------------------

def test_round_traffic_bytes_joins_memory_passes():
    wcfg = wf.WFAggConfig(backend="fused")
    N, K, d = 20, 8, 4096
    got = obs_profile.round_traffic_bytes(wcfg, N, K, d)
    passes = wf.memory_passes(wcfg, include_gather=True, indexed=True)
    assert got == passes * N * K * d * 4
    assert obs_profile.achieved_bytes_per_s(got, 2.0) == got / 2.0


def test_microbench_timeit_median():
    from benchmarks.agg_microbench import _timeit
    fn = jax.jit(lambda x: x + 1.0)
    comp_s, med_s = _timeit(fn, jnp.ones((64,)), reps=3)
    assert comp_s > 0 and med_s > 0
