"""Communication contracts for sharded entry points.

A :class:`CommContract` declares what an entry point is ALLOWED to put on
the wire when its model dimension is sharded over the mesh: which
collective kinds may appear in the optimized HLO, how large any single
payload may be, and how many per-device wire bytes the whole module may
move once trip-count multipliers are applied.  The SPMD rule family in
``rules.py`` checks the compiled module against the contract using the
per-collective records of :mod:`repro.launch.hlo_analysis`.

The WFAgg round contract is the repo's bandwidth story in one object:
under a D-sharded mesh the ONLY cross-shard traffic is the psum of the
O(N·K) filter-statistic partials (the coordinate-additive ``RobustStats``
fields — see distributed/spmd.py), so every ceiling here is an O(N·K)
quantity with headroom, independent of d.  A full-d all-gather — what
GSPMD silently inserts when a sharded array meets a replicated consumer —
busts the per-collective ceiling by ~2 orders of magnitude and is the
exact failure mode these contracts exist to catch.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

from repro.launch import hlo_analysis as ha

# RobustStats psum payload: 6 (N, K) accumulators (dist2, dotmed, norm2,
# prev_dist2, prev_dot, prev_norm2) + the (N,) mednorm2 row, f32
_STATS_FIELDS = 6


@dataclasses.dataclass(frozen=True)
class CommContract:
    """What may cross shards, and at what size.

    axis_size             devices the model dimension shards over (the
                          module must compile with this num_partitions)
    allowed_kinds         collective opcodes the contract permits
    max_collective_bytes  ceiling on any single collective's payload
    wire_budget_bytes     ceiling on per-device wire bytes for the whole
                          module, trip-count multipliers applied
    """

    axis_size: int
    allowed_kinds: Tuple[str, ...]
    max_collective_bytes: int
    wire_budget_bytes: float
    description: str = ""

    def to_dict(self):
        return dataclasses.asdict(self)


def wfagg_round_contract(n: int, k: int, n_shards: int, rounds: int = 1,
                         need_gram: bool = False,
                         headroom: float = 4.0) -> CommContract:
    """Contract for ``rounds`` sharded WFAgg gossip rounds over N nodes
    of degree K: all-reduce only, each payload O(N·K) (O(N·K²) with the
    Alt-WFAgg Gram riding along), total wire = rounds x the psum of the
    statistic partials.  XLA's all-reduce combiner may merge the
    per-field psums of a round into one collective, so a single payload
    may carry a whole round's statistics.  ``headroom`` absorbs
    float/layout slack — NOT a full-d gather, which overshoots these
    ceilings by more than an order of magnitude at MLP size."""
    per_round = 4 * (_STATS_FIELDS * n * k + n
                     + (n * k * k if need_gram else 0))
    ring = 2 * (n_shards - 1) / max(1, n_shards)   # all-reduce ring factor
    return CommContract(
        axis_size=n_shards,
        allowed_kinds=("all-reduce",),
        max_collective_bytes=int(headroom * per_round),
        wire_budget_bytes=headroom * rounds * ring * per_round,
        description=(f"{rounds} sharded WFAgg round(s): all-reduce-only, "
                     f"O(N*K) statistic psums across {n_shards} shards"),
    )


def stacked_allreduce_contract(k: int, n_shards: int,
                               headroom: float = 4.0) -> CommContract:
    """Contract for mode-B ``robust_allreduce_stacked`` under the mesh:
    the pure-jnp reference stats reduce each leaf shard locally and meet
    in (K,)/(K,K)/scalar all-reduces — one node's view (n=1), Gram-sized
    ceiling for the pairwise statistics."""
    c = wfagg_round_contract(n=1, k=k, n_shards=n_shards, rounds=1,
                             need_gram=True, headroom=headroom)
    return dataclasses.replace(
        c, description=(f"mode-B stacked allreduce: O(K^2) statistic "
                        f"psums across {n_shards} shards"))


def contract_cost(artifacts, axis_size: int) -> ha.HloCost:
    """hlo_analysis over the entry's HLO at the contract's device count,
    memoized on the Artifacts instance (several rules share it)."""
    cached = getattr(artifacts, "_contract_cost", None)
    if cached is None or cached[0] != axis_size:
        cached = (axis_size, ha.analyze(artifacts.hlo, n_devices=axis_size))
        artifacts._contract_cost = cached
    return cached[1]
