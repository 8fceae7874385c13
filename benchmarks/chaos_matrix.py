"""Gated fault x intensity x attack x aggregator chaos matrix.

The degradation-curve companion to ``benchmarks/robustness_matrix.py``:
every transport-fault kind of ``repro.dfl.faults`` (drop, stale,
duplicate, corrupt, crash_restart, and the combined chaos mix) is swept
over an intensity axis, crossed with attacks and aggregators, and every
cell runs the SAME one-jit chaos scan ``run_dynamic_experiment`` uses —
the fault schedules ride the scan as five extra stacks, so a whole
faulty run still costs one compile (pinned by the ``chaos_scan`` lint
entry).  Each (fault, attack, aggregator) triple yields a degradation
curve: final benign accuracy as a function of fault intensity, anchored
at the shared fault-free cell.

The graceful-degradation claim this pins (docs/FAULTS.md): WFAgg's
sanitizer + staleness pricing + retry-as-redundancy keep accuracy flat
under transport faults that measurably hurt plain mean — the committed
``benchmarks/BENCH_robustness.json`` carries the gate cells under its
``"chaos"`` key and ``scripts/robustness_gate.py`` re-runs and enforces
them in CI.

    PYTHONPATH=src python -m benchmarks.chaos_matrix --out chaos.json
    PYTHONPATH=src python -m benchmarks.chaos_matrix --smoke
    PYTHONPATH=src python -m benchmarks.chaos_matrix --gate-grid \
        --out /tmp/chaos_gate.json   # regenerate the "chaos" baseline
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl import faults as flt
from repro.dfl.dynamics import SCENARIO_NAMES, make_schedule
from repro.dfl.engine import DFLConfig, run_dynamic_experiment

DEFAULT_FAULTS = ("drop", "stale", "duplicate", "corrupt",
                  "crash_restart", "chaos")
DEFAULT_INTENSITIES = (0.15, 0.3, 0.5)
DEFAULT_ATTACKS = ("none", "ipm_100", "band_rider")
DEFAULT_AGGREGATORS = ("mean", "wfagg")

# The gate subgrid: the cells the graceful-degradation claims live in —
# the drop curve at the claimed 0.3 rate, the corrupt curve (the
# sanitizer's cell: any non-finite payload must be demoted before filter
# statistics), and the combined chaos mix, each against the fault-free
# anchor.  The shape is deliberately the LEAN regime — churn scenario,
# 10 nodes at degree 4, a 3-round horizon: on a dense static graph with
# rounds to spare, plain mean shrugs off 30% drops (enough fresh
# neighbors always remain, and the stale-ring redelivery that is part of
# the shared transport covers the rest), so the degradation claims only
# have teeth where the topology layer is also taking edges and
# convergence time actually matters — exactly the paper's "adverse
# conditions" regime.  scripts/robustness_gate.py re-runs EXACTLY this
# dict; keep it in sync with the "chaos" block of BENCH_robustness.json.
CHAOS_GATE = dict(
    faults=("drop", "corrupt", "chaos"),
    intensities=(0.3,),
    attacks=("none", "ipm_100"),
    aggregators=("mean", "wfagg"),
    scenario="churn", rounds=3, nodes=10, degree=4, malicious=2,
    topology="ring", placement="close", backend="fused", model="mlp",
    seed=0, fault_seed=0, n_test=256,
)

SMOKE_GRID = dict(
    faults=("drop", "chaos"),
    intensities=(0.3,),
    attacks=("none", "alie"),
    aggregators=("mean", "wfagg"),
    scenario="churn", rounds=3, nodes=10, degree=4, malicious=2,
    topology="ring", placement="close", backend="fused", model="mlp",
    seed=0, fault_seed=0, n_test=64,
)


def cell_key(fault: str, intensity: float, attack: str,
             aggregator: str) -> str:
    return f"{fault}@{intensity:g}|{attack}|{aggregator}"


def base_key(attack: str, aggregator: str) -> str:
    """The shared fault-free anchor cell of every curve."""
    return cell_key("none", 0.0, attack, aggregator)


def run_matrix(faults=DEFAULT_FAULTS, intensities=DEFAULT_INTENSITIES,
               attacks=DEFAULT_ATTACKS, aggregators=DEFAULT_AGGREGATORS,
               *, scenario: str = "churn", rounds: int = 6, nodes: int = 20,
               degree: int = 8, malicious: int = 2, topology: str = "ring",
               placement: str = "close", backend: str = "fused",
               model: str = "mlp", seed: int = 0, fault_seed: int = 0,
               n_test: int = 256, verbose: bool = True) -> dict:
    """Run the grid; returns ``{"meta": ..., "cells": {key: cell}}``.

    Every (attack, aggregator) pair first runs ONE fault-free anchor
    cell (``none@0``, through the same chaos scan with an all-quiet
    fault schedule — the fault-none == clean equivalence is a tested
    invariant), then each fault kind at each intensity.  Cells record
    final benign accuracy, final consistency R^2, per-round minimum
    accuracy, the scheduled fault rates
    (:meth:`~repro.dfl.faults.FaultSchedule.summary`), and — for wfagg
    cells, which run with telemetry on — the OBSERVED per-fault
    attribution off the packed verdict bits
    (:func:`repro.obs.report.fault_rates`): scheduled vs observed is the
    cross-check that the injection actually reached the filters.
    """
    from repro.obs import report as obs_report

    topo = make_topology(n_nodes=nodes, degree=degree,
                         n_malicious=malicious, kind=topology,
                         placement=placement, seed=seed)
    data = SyntheticImages(seed=seed)
    sched = make_schedule(scenario, topo, rounds, seed=seed)
    cells = {}
    t_start = time.time()

    def run_cell(key, fault, intensity, attack, aggregator):
        cfg = DFLConfig(aggregator=aggregator, attack=attack, model=model,
                        seed=seed, wfagg_backend=backend)
        fs = flt.make_fault_schedule(fault, sched, intensity,
                                     seed=fault_seed)
        telemetry = aggregator in ("wfagg", "alt_wfagg")
        t0 = time.time()
        out = run_dynamic_experiment(cfg, topo, data, sched, n_test=n_test,
                                     telemetry=telemetry, faults=fs)
        acc_series = out["series"]["acc_benign_mean"]
        cell = {
            "final_acc": out["final"]["acc_benign_mean"],
            "final_r2": out["final"]["r_squared"],
            "min_acc": min(acc_series),
            "scheduled": out["faults"],
        }
        if telemetry:
            frates = obs_report.fault_rates(out["telemetry"]["verdict"])
            cell["fault_attribution"] = obs_report.fault_attribution(frates)
        cells[key] = cell
        if verbose:
            print(f"  {key:36s} acc {100 * cell['final_acc']:6.2f}%"
                  f"  R2 {cell['final_r2']:7.4f}"
                  f"  [{time.time() - t0:5.1f}s]", flush=True)
        return cell

    for aggregator in aggregators:
        for attack in attacks:
            run_cell(base_key(attack, aggregator), "none", 0.0, attack,
                     aggregator)
            for fault in faults:
                for intensity in intensities:
                    run_cell(cell_key(fault, intensity, attack, aggregator),
                             fault, intensity, attack, aggregator)

    meta = dict(faults=tuple(faults), intensities=tuple(intensities),
                attacks=tuple(attacks), aggregators=tuple(aggregators),
                scenario=scenario, rounds=rounds, nodes=nodes, degree=degree,
                malicious=malicious, topology=topology, placement=placement,
                backend=backend, model=model, seed=seed,
                fault_seed=fault_seed, n_test=n_test,
                wall_s=round(time.time() - t_start, 1))
    return {"meta": meta, "cells": cells}


def degradation_curves(result: dict) -> dict:
    """``{fault|attack|aggregator: {"intensities": [0, ...], "acc":
    [...], "r2": [...]}}`` — each curve anchored at the fault-free cell
    (intensity 0), accuracy falling (or not) as intensity rises.  This
    is the JSON artifact the chaos-smoke CI job uploads."""
    meta, cells = result["meta"], result["cells"]
    curves = {}
    for aggregator in meta["aggregators"]:
        for attack in meta["attacks"]:
            anchor = cells[base_key(attack, aggregator)]
            for fault in meta["faults"]:
                xs, acc, r2 = [0.0], [anchor["final_acc"]], [anchor["final_r2"]]
                for intensity in meta["intensities"]:
                    c = cells[cell_key(fault, intensity, attack, aggregator)]
                    xs.append(float(intensity))
                    acc.append(c["final_acc"])
                    r2.append(c["final_r2"])
                curves[f"{fault}|{attack}|{aggregator}"] = {
                    "intensities": xs, "acc": acc, "r2": r2}
    return curves


def print_curves(result: dict) -> None:
    meta = result["meta"]
    curves = degradation_curves(result)
    print("\ndegradation curves (final benign accuracy % by fault "
          "intensity; 0 = fault-free anchor)")
    xs = [0.0] + [float(i) for i in meta["intensities"]]
    head = f"{'fault | attack | aggregator':>40s}" + "".join(
        f"{x:>9g}" for x in xs)
    print(head)
    for key, curve in curves.items():
        row = f"{key:>40s}"
        for a in curve["acc"]:
            row += f"{100 * a:9.2f}"
        print(row)


def _axis(value, default, universe=None, cast=str):
    if value == "default":
        return default
    names = tuple(cast(v.strip()) for v in str(value).split(",") if v.strip())
    if universe is not None:
        for v in names:
            if v not in universe:
                raise SystemExit(
                    f"unknown axis entry {v!r}; choose from {universe}")
    return names


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--faults", default="default",
                    help=f"comma list from {flt.FAULT_NAMES}")
    ap.add_argument("--intensities", default="default",
                    help="comma list of floats in [0, 1]")
    ap.add_argument("--attacks", default="default", help="comma list")
    ap.add_argument("--aggregators", default="default", help="comma list")
    ap.add_argument("--scenario", default="churn", choices=SCENARIO_NAMES)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--nodes", type=int, default=20)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--malicious", type=int, default=2)
    ap.add_argument("--topology", default="ring",
                    choices=("ring", "complete", "erdos_renyi"))
    ap.add_argument("--backend", default="fused",
                    choices=("fused", "reference"))
    ap.add_argument("--model", default="mlp", choices=("mlp", "lenet"))
    ap.add_argument("--placement", default="close",
                    choices=("spaced", "close"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--n-test", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed grid (the CI chaos-smoke job)")
    ap.add_argument("--gate-grid", action="store_true",
                    help="run exactly the gate subgrid (regenerates the "
                         "'chaos' block of BENCH_robustness.json)")
    ap.add_argument("--out", default="",
                    help="write {'meta', 'cells', 'curves'} JSON here")
    args = ap.parse_args(argv)

    if args.smoke or args.gate_grid:
        grid = dict(SMOKE_GRID if args.smoke else CHAOS_GATE)
    else:
        grid = dict(
            faults=_axis(args.faults, DEFAULT_FAULTS, flt.FAULT_NAMES),
            intensities=_axis(args.intensities, DEFAULT_INTENSITIES,
                              cast=float),
            attacks=_axis(args.attacks, DEFAULT_ATTACKS),
            aggregators=_axis(args.aggregators, DEFAULT_AGGREGATORS),
            scenario=args.scenario, rounds=args.rounds, nodes=args.nodes,
            degree=args.degree, malicious=args.malicious,
            topology=args.topology, placement=args.placement,
            backend=args.backend, model=args.model, seed=args.seed,
            fault_seed=args.fault_seed, n_test=args.n_test,
        )
    result = run_matrix(**grid)
    result["curves"] = degradation_curves(result)
    print_curves(result)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"\nwrote {os.path.abspath(args.out)}")
    return result


if __name__ == "__main__":
    main()
