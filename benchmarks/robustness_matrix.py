"""Gated attack x scenario x aggregator robustness matrix.

The DART-style evaluation (arXiv 2407.08652 / 2407.05141) the paper
never runs: every attack (oblivious, omniscient AND the defense-aware
adaptive adversaries of ``core.attacks``) crossed with every topology
condition (including the eclipse/dos/collusion topology ATTACKS of
``repro.dfl.dynamics``) crossed with every aggregation rule — the
baselines ride the valid-mask-aware ``DYN_AGGREGATORS`` path, so
mean/median/multi_krum/clustering fill their rows of the grid under
dynamic graphs too, not just wfagg/alt_wfagg.

Every cell runs the SAME federation (one ``run_dynamic_experiment``
scan; the static scenario is a constant schedule, so a single code path
produces the whole grid) and records final benign accuracy + model
consistency R^2.  The committed ``benchmarks/BENCH_robustness.json``
pins the gate subgrid; ``scripts/robustness_gate.py`` re-runs it in CI
and fails on regression — the executable form of the robustness claims.

    PYTHONPATH=src python -m benchmarks.robustness_matrix \
        --rounds 6 --out matrix.json            # default grid
    PYTHONPATH=src python -m benchmarks.robustness_matrix --smoke
    PYTHONPATH=src python -m benchmarks.robustness_matrix --gate-grid \
        --out benchmarks/BENCH_robustness.json  # regenerate the baseline

Supersedes ``benchmarks/dynamic_report.py`` (one attack x one
aggregator across scenarios — the scenario axis of this grid).
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.core import attacks as atk
from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl.dynamics import SCENARIO_NAMES, make_schedule
from repro.dfl.engine import (
    AGGREGATOR_NAMES,
    DFLConfig,
    run_dynamic_experiment,
)

# Default grid: every adversary class x every topology class x the
# paper's aggregator lineup.  (The full SCENARIO_NAMES x ATTACK_NAMES x
# AGGREGATOR_NAMES cube is available via --attacks all etc.)
DEFAULT_ATTACKS = ("none", "sign_flip", "ipm_100", "alie",
                   "band_rider", "min_max")
DEFAULT_SCENARIOS = ("static", "churn", "eclipse", "dos", "collusion")
DEFAULT_AGGREGATORS = ("mean", "median", "multi_krum", "clustering",
                       "wfagg", "alt_wfagg")

# The gate subgrid: small enough for CI, wide enough that the committed
# baseline pins (a) an adaptive and an omniscient attack, (b) a benign
# and an adversarial topology, (c) the weakest baseline next to WFAgg —
# the cells the acceptance claims live in.  scripts/robustness_gate.py
# re-runs EXACTLY this dict; keep it in sync with BENCH_robustness.json
# (regenerate via --gate-grid).
GATE_GRID = dict(
    attacks=("none", "ipm_100", "band_rider", "min_max"),
    scenarios=("static", "eclipse"),
    aggregators=("mean", "multi_krum", "wfagg"),
    rounds=6, nodes=20, degree=8, malicious=2, topology="ring",
    placement="close", backend="fused", model="mlp", seed=0, n_test=256,
)

SMOKE_GRID = dict(
    attacks=("none", "ipm_100", "band_rider"),
    scenarios=("static", "eclipse"),
    aggregators=("mean", "wfagg"),
    rounds=3, nodes=10, degree=4, malicious=2, topology="ring",
    placement="close", backend="fused", model="mlp", seed=0, n_test=64,
)


def cell_key(attack: str, scenario: str, aggregator: str) -> str:
    return f"{attack}|{scenario}|{aggregator}"


def run_matrix(attacks=DEFAULT_ATTACKS, scenarios=DEFAULT_SCENARIOS,
               aggregators=DEFAULT_AGGREGATORS, *, rounds: int = 6,
               nodes: int = 20, degree: int = 8, malicious: int = 2,
               topology: str = "ring", placement: str = "close",
               backend: str = "fused", model: str = "mlp", seed: int = 0,
               n_test: int = 256, verbose: bool = True) -> dict:
    """Run the grid; returns ``{"meta": ..., "cells": {key: cell}}``.

    ``meta`` records every knob (so the gate can re-run the exact grid
    from the committed JSON alone) and each cell keeps the final benign
    accuracy, the final consistency R^2 and the per-round minimum
    accuracy (transient collapse shows up there before it shows up in
    the final round).

    wfagg/alt_wfagg cells additionally run with the flight recorder's
    decision plane on (``telemetry=True`` — pure traced scan outputs,
    same launch count) and carry the per-cell FILTER ATTRIBUTION: each
    filter's mean true-catch / false-positive rates over the attacked
    rounds, ``carried_by`` (the filter with the best catch-minus-FP
    margin — which filter actually carried the defense in that attack x
    scenario cell), and the mean-fallback / degree-0 round counts.  The
    gate comparator only reads final_acc/final_r2, so the new columns
    are regression-gate-safe.  See docs/OBSERVABILITY.md.
    """
    from repro.obs import report as obs_report

    topo = make_topology(n_nodes=nodes, degree=degree,
                         n_malicious=malicious, kind=topology,
                         placement=placement, seed=seed)
    data = SyntheticImages(seed=seed)
    schedules = {s: make_schedule(s, topo, rounds, seed=seed)
                 for s in scenarios}
    cells = {}
    t_start = time.time()
    for scenario in scenarios:
        sched = schedules[scenario]
        for aggregator in aggregators:
            for attack in attacks:
                cfg = DFLConfig(aggregator=aggregator, attack=attack,
                                model=model, seed=seed,
                                wfagg_backend=backend)
                telemetry = aggregator in ("wfagg", "alt_wfagg")
                t0 = time.time()
                out = run_dynamic_experiment(cfg, topo, data, sched,
                                             n_test=n_test,
                                             telemetry=telemetry)
                acc_series = out["series"]["acc_benign_mean"]
                cell = {
                    "final_acc": out["final"]["acc_benign_mean"],
                    "final_r2": out["final"]["r_squared"],
                    "min_acc": min(acc_series),
                }
                if telemetry:
                    rates = obs_report.telemetry_rates(out["telemetry"])
                    attr = obs_report.attribution(rates)
                    cell["filter_attribution"] = attr
                    cell["mean_fallback_rounds"] = sum(
                        1 for c in out["series"]["mean_fallback_count"]
                        if c > 0)
                    cell["degree_zero_rounds"] = sum(
                        1 for c in out["series"]["degree_zero_count"]
                        if c > 0)
                cells[cell_key(attack, scenario, aggregator)] = cell
                if verbose:
                    carried = (f"  carried by {attr['carried_by'].upper()}"
                               if telemetry and attr.get("carried_by")
                               else "")
                    print(f"  {cell_key(attack, scenario, aggregator):40s}"
                          f" acc {100 * cell['final_acc']:6.2f}%"
                          f"  R2 {cell['final_r2']:7.4f}"
                          f"  [{time.time() - t0:5.1f}s]{carried}",
                          flush=True)
    meta = dict(attacks=tuple(attacks), scenarios=tuple(scenarios),
                aggregators=tuple(aggregators), rounds=rounds, nodes=nodes,
                degree=degree, malicious=malicious, topology=topology,
                placement=placement, backend=backend, model=model,
                seed=seed, n_test=n_test,
                wall_s=round(time.time() - t_start, 1))
    return {"meta": meta, "cells": cells}


def print_matrix(result: dict) -> None:
    meta, cells = result["meta"], result["cells"]
    for scenario in meta["scenarios"]:
        print(f"\nscenario: {scenario}  (final benign accuracy % / R^2)")
        head = f"{'attack':>12s} " + "".join(
            f"{a:>18s}" for a in meta["aggregators"])
        print(head)
        for attack in meta["attacks"]:
            row = f"{attack:>12s} "
            for agg in meta["aggregators"]:
                c = cells[cell_key(attack, scenario, agg)]
                row += f"{100 * c['final_acc']:8.2f}/{c['final_r2']:6.3f}   "
            print(row)


def _axis(value: str, default: tuple, universe: tuple) -> tuple:
    if value == "default":
        return default
    if value == "all":
        return universe
    names = tuple(v.strip() for v in value.split(",") if v.strip())
    for v in names:
        if v not in universe:
            raise SystemExit(f"unknown axis entry {v!r}; choose from "
                             f"{universe}")
    return names


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--attacks", default="default",
                    help="comma list | 'all' (from ATTACK_NAMES)")
    ap.add_argument("--scenarios", default="default",
                    help="comma list | 'all' (from SCENARIO_NAMES)")
    ap.add_argument("--aggregators", default="default",
                    help="comma list | 'all' (from AGGREGATOR_NAMES)")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--nodes", type=int, default=20)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--malicious", type=int, default=2)
    ap.add_argument("--topology", default="ring",
                    choices=("ring", "complete", "erdos_renyi"))
    ap.add_argument("--backend", default="fused",
                    choices=("fused", "reference"),
                    help="WFAgg execution backend for wfagg/alt_wfagg cells")
    ap.add_argument("--model", default="mlp", choices=("mlp", "lenet"))
    ap.add_argument("--placement", default="close",
                    choices=("spaced", "close"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-test", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed grid (the CI robustness-matrix job)")
    ap.add_argument("--gate-grid", action="store_true",
                    help="run exactly the gate subgrid (regenerates the "
                         "committed BENCH_robustness.json baseline with "
                         "--out)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    if args.smoke or args.gate_grid:
        grid = dict(SMOKE_GRID if args.smoke else GATE_GRID)
    else:
        grid = dict(
            attacks=_axis(args.attacks, DEFAULT_ATTACKS, atk.ATTACK_NAMES),
            scenarios=_axis(args.scenarios, DEFAULT_SCENARIOS,
                            SCENARIO_NAMES),
            aggregators=_axis(args.aggregators, DEFAULT_AGGREGATORS,
                              AGGREGATOR_NAMES),
            rounds=args.rounds, nodes=args.nodes, degree=args.degree,
            malicious=args.malicious, topology=args.topology,
            placement=args.placement, backend=args.backend,
            model=args.model, seed=args.seed, n_test=args.n_test,
        )
    result = run_matrix(**grid)
    print_matrix(result)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"\nwrote {os.path.abspath(args.out)}")
    return result


if __name__ == "__main__":
    main()
