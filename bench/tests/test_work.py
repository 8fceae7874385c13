"""Work counts and peaks against hand-computed values."""
import numpy as np
import pytest

from bench import peaks, work
from bench.tests.helpers import lenet, lenet_cfg


def test_lenet_parameters_are_the_papers_44426():
    # conv1 5*5*1*6+6, conv2 5*5*6*16+16, fc 256*120+120, 120*84+84, 84*10+10
    assert lenet().param_count(lenet_cfg()) == 156 + 2416 + 30840 + 10164 + 850 == 44426


def test_lenet_forward_flops():
    # MACs: conv1 24*24*6*25 = 86,400; conv2 8*8*16*150 = 153,600;
    # fc 30,720 + 10,080 + 840 -> 281,640 MACs, 2 FLOPs each
    assert lenet().forward_flops(lenet_cfg()) == 2 * 281_640


def test_fleet_round_flops_of_the_paper_deployment():
    # 20 nodes * (4 batches * 64 images * 3 + 256 test images) * 563,280
    got = work.fleet_round_flops(lenet(), lenet_cfg(), n_nodes=20, n_test=256)
    assert got == 20 * (4 * 64 * 3 + 256) * 563_280
    assert got == pytest.approx(11.535e9, rel=1e-3)


def test_wfagg_round_bytes_regular_ring():
    # every node is a sender: 20 model rows (candidates and own rows),
    # 20 previous rows, 20 written rows of d floats
    from bench.fleet_traffic import padded_table, ring_lattice
    idx, valid = padded_table(ring_lattice(20, 8), 8)
    assert work.wfagg_round_bytes(idx, valid, 44426) == 60 * 44426 * 4


def test_wfagg_round_bytes_counts_distinct_rows_of_valid_edges():
    # node 0 hears 1 and 2, node 1 hears 2, node 2 hears nobody, node 3
    # hears 2 and a padded slot that points at itself
    idx = np.array([[1, 2], [2, 1], [2, 2], [2, 3]])
    valid = np.array([[True, True], [True, False], [False, False], [True, False]])
    # model rows: senders {1, 2} and own rows {0..3} -> 4; prev rows {1, 2}
    # -> 2; written rows 4
    assert work.wfagg_round_bytes(idx, valid, d=10, itemsize=4) == (4 + 2 + 4) * 10 * 4


def test_peaks_known_kind_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
