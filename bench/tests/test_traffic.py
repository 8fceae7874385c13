"""Traffic generators: deterministic in the seed, one set of sizes for
every seed, and shaped as the program's own input types."""
import numpy as np
import pytest

from bench import fleet_traffic as ft
from bench.tests.helpers import load_json, small_mix

BIG_SEED = 3_000_000_017   # above 2**31, as the benchmark's seeds may be


@pytest.mark.parametrize("traffic", ["paper20-static-ipm05", "er1024-churn-drop30-ipm05"])
def test_same_seed_same_arrays(traffic):
    mix = ft.FleetMix.from_json(small_mix(traffic))
    a, b = ft.make_traffic(mix, BIG_SEED), ft.make_traffic(mix, BIG_SEED)
    for name in ("adjacency", "malicious", "idx", "valid", "mal", "drop"):
        x, y = getattr(a, name), getattr(b, name)
        assert (x is None and y is None) or np.array_equal(x, y), name


def test_seeds_change_the_graph_but_not_the_sizes():
    mix = ft.FleetMix.from_json(load_json("bench/traffic/er1024-churn-drop30-ipm05.json"))
    a, b = ft.make_traffic(mix, 1), ft.make_traffic(mix, BIG_SEED)
    assert not np.array_equal(a.adjacency, b.adjacency)
    for name in ("idx", "valid", "mal", "drop"):
        assert getattr(a, name).shape == getattr(b, name).shape, name
    assert a.idx.shape == (mix.schedule_rounds, 1024, mix.width)
    assert a.malicious.sum() == 102
    # mean degree about 14 (2 ln N), above ln N
    assert 13 < a.adjacency.sum(axis=1).mean() < 15


def test_churn_cuts_a_down_node_out_and_drops_are_at_the_intensity():
    mix = ft.FleetMix.from_json(load_json("bench/traffic/er1024-churn-drop30-ipm05.json"))
    t = ft.make_traffic(mix, 5)
    deg = t.valid.sum(axis=2)
    assert (deg == 0).any() and (deg > 0).mean() > 0.6
    # a down Byzantine node sends nothing and is benign for the round
    down = deg == 0
    assert not (t.mal & down & (t.adjacency.sum(axis=1) > 0)).any()
    assert abs(t.drop.mean() - 0.3) < 0.01


def test_paper_ring_and_close_placement():
    mix = ft.FleetMix.from_json(load_json("bench/traffic/paper20-static-ipm05.json"))
    t = ft.make_traffic(mix, 0)
    assert (t.adjacency.sum(axis=1) == 8).all() and t.valid.all()
    assert np.flatnonzero(t.malicious).tolist() == [0, 4]
    assert t.idx[0, 0].tolist() == [1, 2, 3, 4, 16, 17, 18, 19]


def test_program_types_match_the_program_generators():
    from repro.core import topology as topo_lib
    mix = ft.FleetMix.from_json(small_mix("paper20-static-ipm05"))
    t = ft.make_traffic(mix, 0)
    topo, sched, faults = ft.to_program(mix, t, 0)
    ref = topo_lib.make_topology(n_nodes=6, degree=4, n_malicious=1,
                                 kind="ring", placement="close")
    assert np.array_equal(topo.adjacency, ref.adjacency)
    assert np.array_equal(topo.neighbor_indices, ref.neighbor_indices)
    assert np.array_equal(topo.malicious, ref.malicious)
    prog = topo_lib.static_schedule(ref, mix.schedule_rounds)
    assert np.array_equal(sched.neighbor_idx, prog.neighbor_idx)
    assert faults is None
    assert np.array_equal(ft.spaced_malicious(1024, 102),
                          topo_lib.spaced_malicious(1024, 102))


def test_chaos_mix_builds_a_drop_only_fault_schedule():
    mix = ft.FleetMix.from_json(small_mix("er1024-churn-drop30-ipm05"))
    t = ft.make_traffic(mix, 11)
    _, sched, faults = ft.to_program(mix, t, 0)
    assert faults.rounds == sched.rounds == mix.schedule_rounds
    assert faults.config.ring_depth == 3 and faults.config.staleness_budget == 2
    assert not faults.lag.any() and not faults.corrupt.any() and not faults.down.any()


def test_child_seeds_fit_32_bit_keys_and_differ_by_tag():
    s = [ft.child_seed(BIG_SEED * 1000, tag) for tag in ("graph", "data", "weights")]
    assert len(set(s)) == 3 and all(0 <= x < 2 ** 30 for x in s)
