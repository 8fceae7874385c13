"""Pallas TPU kernels.

The paper's complexity analysis (Sections IV-C/D) identifies the
coordinate-wise median over K candidates as the dominant O(dK log K)
cost of every filter.  At production scale (d = 1e9..1e11) the candidate
tensor must stream HBM->VMEM as few times as possible, so ONE kernel
runs the WFAgg round: its phase 0 fuses the order statistics with every
other per-candidate statistic the filters need (and the Alt-WFAgg Gram),
its phase 1 is the WFAgg-E combine (Eq. 3).

  robust_stats  the WFAgg round kernel (kernel.py), its jitted wrappers
                for both phases or either alone (ops.py) and its
                pure-jnp oracles (ref.py)
  flash_attn    attention for the mode-B trainer (unrelated to
                aggregation)

Validated against the oracles with interpret=True off a TPU.
"""
from repro.kernels.common import default_interpret, resolve_interpret
from repro.kernels.robust_stats.ops import (
    wfagg_round_indexed, wfagg_round_indexed_combine,
    wfagg_round_indexed_stats)
from repro.kernels.robust_stats.ref import (
    RobustStats, pairwise_dist_ref, robust_stats_indexed_ref,
    robust_stats_ref, weighted_agg_ref)
