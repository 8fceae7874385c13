"""Gossip-fleet traffic: the graph, the Byzantine placement, the churn
schedule and the transport faults of one mix, drawn from the seed.

These are copies of the program's generators (``repro.core.topology``,
``repro.dfl.dynamics.churn_schedule``, ``repro.dfl.faults._gen_drop``),
kept here so that no later change to the program moves the traffic the
benchmark offers.  Everything is numpy and deterministic in
``(mix, seed)``; the sizes (nodes, table width, schedule length) come
from the mix alone, never from the seed, so every seed runs the same
compiled shapes.

A mix is a JSON object (``bench/traffic/<name>.json``); the keys read
here are documented in ``FleetMix``.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, Optional

import numpy as np


def child_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for one consumer of ``--seed`` (any whole number,
    also above 2**32), so JAX's 32-bit PRNG keys accept it and the
    consumers draw independent streams."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), zlib.crc32(tag.encode())])
    return int(ss.generate_state(1, np.uint32)[0] >> 2)


def ring_lattice(n: int, degree: int) -> np.ndarray:
    """degree-regular ring lattice (Watts-Strogatz with p = 0)."""
    if degree % 2 or not 0 < degree < n:
        raise ValueError(f"ring lattice needs an even degree below n, got {degree}")
    i = np.arange(n)
    adj = np.zeros((n, n), bool)
    for off in range(1, degree // 2 + 1):
        adj[i, (i + off) % n] = True
        adj[(i + off) % n, i] = True
    return adj


def erdos_renyi(n: int, p: float, rng: np.random.Generator,
                min_degree: int = 1) -> np.ndarray:
    """G(n, p), with a ring edge added to each node below ``min_degree``
    (in node order, as the program's generator does)."""
    upper = np.triu(rng.random((n, n)) < p, 1)
    adj = upper | upper.T
    for i in range(n):
        if adj[i].sum() < min_degree:
            adj[i, (i + 1) % n] = adj[(i + 1) % n, i] = True
    return adj


def spaced_malicious(n: int, n_mal: int) -> np.ndarray:
    mal = np.zeros(n, bool)
    if n_mal > 0:
        mal[((np.arange(n_mal) * n) // n_mal + n // (2 * n_mal)) % n] = True
    return mal


def close_malicious(n: int, n_mal: int, degree: int) -> np.ndarray:
    mal = np.zeros(n, bool)
    mal[(np.arange(n_mal) * max(1, degree // 2)) % n] = True
    return mal


def padded_table(adj: np.ndarray, width: int):
    """(N, width) neighbor table in ascending id order, padded with the
    node's own id, and its valid mask."""
    n = adj.shape[0]
    deg = adj.sum(axis=1)
    if deg.max(initial=0) > width:
        raise ValueError(f"max degree {deg.max()} exceeds the table width {width}")
    order = np.argsort(~adj, axis=1, kind="stable")[:, :width]
    valid = np.arange(width)[None, :] < deg[:, None]
    idx = np.where(valid, order, np.arange(n)[:, None]).astype(np.int32)
    return idx, valid


@dataclasses.dataclass(frozen=True)
class FleetMix:
    """The parameters of one fleet mix, as its JSON file gives them."""

    nodes: int
    topology: str               # "ring" | "erdos_renyi"
    degree: int                 # ring degree, or the ER mean degree
    width: int                  # neighbor-table width K, fixed per mix
    n_malicious: int
    placement: str              # "close" | "spaced"
    attack: str
    aggregator: str
    schedule: str               # "static" | "churn"
    schedule_rounds: int        # rounds generated, cycled by the window
    rounds_per_chunk: int       # R: rounds in one dispatch of the scan
    check_chunks: int           # chunks of set-up the reference follows
    n_test: int
    trace_seconds: float
    chunks_in_flight: int       # dispatched chunks the window keeps queued
    p_leave: float = 0.0
    p_join: float = 0.0
    fault: str = "none"         # "none" | "drop"
    fault_intensity: float = 0.0
    ring_depth: int = 3
    staleness_budget: int = 2
    limits: Optional[Dict[str, float]] = None

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "FleetMix":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


@dataclasses.dataclass
class FleetTraffic:
    """One seed's traffic: numpy stacks shaped as the program takes them."""

    adjacency: np.ndarray      # (N, N) base graph
    malicious: np.ndarray      # (N,) base Byzantine set
    idx: np.ndarray            # (S, N, K) int32
    valid: np.ndarray          # (S, N, K) bool
    mal: np.ndarray            # (S, N) bool, per-round Byzantine set
    adjs: np.ndarray           # (S, N, N) per-round graphs
    drop: Optional[np.ndarray] = None   # (S, N, K) bool, chaos mixes only


def _base_graph(mix: FleetMix, rng: np.random.Generator) -> np.ndarray:
    if mix.topology == "ring":
        return ring_lattice(mix.nodes, mix.degree)
    if mix.topology == "erdos_renyi":
        # resample until the graph fits the mix's fixed table width, so
        # that every seed compiles one shape
        for _ in range(100):
            adj = erdos_renyi(mix.nodes, mix.degree / (mix.nodes - 1), rng)
            if adj.sum(axis=1).max() <= mix.width:
                return adj
        raise RuntimeError("no Erdos-Renyi graph within the table width")
    raise ValueError(f"unknown topology {mix.topology!r}")


def make_traffic(mix: FleetMix, seed: int) -> FleetTraffic:
    rng = np.random.default_rng(child_seed(seed, "graph"))
    adj = _base_graph(mix, rng)
    if mix.placement == "close":
        mal = close_malicious(mix.nodes, mix.n_malicious, mix.degree)
    elif mix.placement == "spaced":
        mal = spaced_malicious(mix.nodes, mix.n_malicious)
    else:
        raise ValueError(f"unknown placement {mix.placement!r}")
    S, n = mix.schedule_rounds, mix.nodes
    if mix.schedule == "static":
        adjs = np.broadcast_to(adj, (S, n, n))
        mals = np.broadcast_to(mal, (S, n))
    elif mix.schedule == "churn":
        crng = np.random.default_rng(child_seed(seed, "churn"))
        down = np.zeros(n, bool)
        adjs = np.empty((S, n, n), bool)
        mals = np.empty((S, n), bool)
        for r in range(S):
            u = crng.random(n)
            down = np.where(down, u >= mix.p_join, u < mix.p_leave)
            up = ~down
            adjs[r] = adj & up[:, None] & up[None, :]
            mals[r] = mal & up
    else:
        raise ValueError(f"unknown schedule {mix.schedule!r}")
    tables = [padded_table(adjs[r], mix.width) for r in range(S)]
    traffic = FleetTraffic(
        adjacency=adj, malicious=mal,
        idx=np.stack([t for t, _ in tables]),
        valid=np.stack([v for _, v in tables]),
        mal=np.ascontiguousarray(mals), adjs=adjs)
    if mix.fault == "drop":
        frng = np.random.default_rng(child_seed(seed, "faults"))
        traffic.drop = frng.random((S, n, mix.width)) < mix.fault_intensity
    elif mix.fault != "none":
        raise ValueError(f"unknown fault {mix.fault!r}")
    return traffic


def to_program(mix: FleetMix, traffic: FleetTraffic, fault_seed: int):
    """The program's own input types: ``(Topology, TopologySchedule,
    FaultSchedule or None)``."""
    from repro.core.topology import Topology, TopologySchedule
    from repro.dfl import faults as flt

    idx0, valid0 = padded_table(traffic.adjacency, mix.width)
    topo = Topology(n_nodes=mix.nodes, adjacency=traffic.adjacency,
                    neighbor_indices=idx0, malicious=traffic.malicious,
                    neighbor_valid=valid0)
    sched = TopologySchedule(neighbor_idx=traffic.idx, valid=traffic.valid,
                             malicious=traffic.mal, adjacency=traffic.adjs)
    if traffic.drop is None:
        return topo, sched, None
    zeros = np.zeros_like(traffic.drop)
    fcfg = flt.FaultConfig(ring_depth=mix.ring_depth,
                           staleness_budget=mix.staleness_budget,
                           max_lag=min(2, mix.ring_depth), seed=fault_seed)
    faults = flt.FaultSchedule(
        drop=traffic.drop, lag=zeros.astype(np.int32), dup=zeros,
        corrupt=zeros, down=np.zeros(traffic.mal.shape, bool), config=fcfg)
    return topo, sched, faults
