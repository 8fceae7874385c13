"""The paper's full validation scenario (Section V) as a configurable
experiment — any aggregator x any attack x CFL/DFL, with the per-node
accuracy trace of the paper's Figure 7.

    PYTHONPATH=src python examples/dfl_paper_experiment.py \
        --aggregator wfagg --attack noise --rounds 10 --model lenet

Beyond-paper switches: ``--topology erdos_renyi`` runs the gather-free
irregular-degree path (padded neighbor tables), ``--backend
fused|reference`` selects the WFAgg execution backend (fused = the
single-launch round kernel, the default), and
``--scenario churn|link_failure|partition|mobility|sleeper|eclipse|dos|
collusion`` runs the whole experiment under a round-varying topology
schedule (one jit, lax.scan over the schedule — the graph and the
Byzantine set change every round with no retrace) and prints the
DART-style per-round robustness time series.  ``--telemetry`` turns on
the flight recorder's decision plane (repro.obs): per-round per-filter
true-catch/false-positive rates are printed after the trace, and
``--events-out``/``--trace-out`` write the JSONL event log and the
Perfetto trace_event JSON (docs/OBSERVABILITY.md; the full audit lives
in ``python -m repro.obs.report``).  Every backend handles
irregular topologies and dynamic scenarios: the fused paths in-kernel,
the reference backend via the valid-aware pure-jnp oracle — and the
baseline aggregators (mean/median/trimmed_mean/krum/multi_krum/
clustering) run scenarios too, through the valid-mask-aware
``DYN_AGGREGATORS`` variants.  ``--attack band_rider|min_max`` runs the
defense-aware adaptive adversaries (see docs/THREAT_MODEL.md).
"""
import argparse

import numpy as np

from repro.core.aggregators import DYN_AGGREGATORS
from repro.core.attacks import ATTACK_NAMES
from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl.dynamics import SCENARIO_NAMES, make_schedule
from repro.dfl.engine import (AGGREGATOR_NAMES, DFLConfig,
                              run_dynamic_experiment, run_experiment)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--aggregator", default="wfagg", choices=AGGREGATOR_NAMES)
    ap.add_argument("--attack", default="noise", choices=ATTACK_NAMES)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--model", default="mlp", choices=("mlp", "lenet"))
    ap.add_argument("--centralized", action="store_true")
    ap.add_argument("--nodes", type=int, default=20)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--malicious", type=int, default=2)
    ap.add_argument("--placement", default="close", choices=("close", "spaced"))
    ap.add_argument("--topology", default="ring",
                    choices=("ring", "complete", "erdos_renyi"),
                    help="gossip graph; erdos_renyi exercises the "
                         "irregular-degree (padded-table) path")
    ap.add_argument("--backend", default="fused",
                    choices=("fused", "reference"),
                    help="WFAgg execution backend (fused = single-launch "
                         "gather-free round kernel; reference = "
                         "multi-pass jnp, valid-aware)")
    ap.add_argument("--scenario", default="",
                    choices=("",) + SCENARIO_NAMES,
                    help="dynamic-topology scenario: the experiment runs "
                         "under a round-varying neighbor-table schedule "
                         "(see repro.dfl.dynamics.SCENARIOS)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="flight-recorder decision plane: per-filter "
                         "true-catch/false-positive audit after the "
                         "trace (docs/OBSERVABILITY.md)")
    ap.add_argument("--events-out", default="",
                    help="write the telemetry JSONL event log here "
                         "(implies --telemetry)")
    ap.add_argument("--trace-out", default="",
                    help="write Perfetto trace_event JSON here — load "
                         "at ui.perfetto.dev (implies --telemetry)")
    args = ap.parse_args()
    if args.events_out or args.trace_out:
        args.telemetry = True
    if args.telemetry and args.centralized:
        ap.error("--telemetry records per-edge gossip verdicts; the CFL "
                 "baseline has no edges")
    if args.scenario:
        if args.centralized:
            ap.error("--scenario is a decentralized (gossip) feature")
        if args.aggregator not in ("wfagg", "alt_wfagg") \
                and args.aggregator not in DYN_AGGREGATORS:
            ap.error(f"--scenario needs a valid-mask-aware aggregator: "
                     f"wfagg, alt_wfagg or one of "
                     f"{', '.join(DYN_AGGREGATORS)}")

    kind = "complete" if args.centralized else args.topology
    topo = make_topology(n_nodes=args.nodes, degree=args.degree,
                         n_malicious=args.malicious, kind=kind,
                         seed=args.seed, placement=args.placement)
    data = SyntheticImages(seed=args.seed)
    cfg = DFLConfig(aggregator=args.aggregator, attack=args.attack,
                    model=args.model, centralized=args.centralized,
                    seed=args.seed, wfagg_backend=args.backend)
    schedule = None
    if args.scenario:
        schedule = make_schedule(args.scenario, topo, args.rounds,
                                 seed=args.seed)
        out = run_dynamic_experiment(cfg, topo, data, schedule,
                                     telemetry=args.telemetry)
    else:
        out = run_experiment(cfg, topo, data, rounds=args.rounds,
                             eval_every=1, telemetry=args.telemetry)

    degs = topo.degrees
    print(f"aggregator={args.aggregator} attack={args.attack} "
          f"{'CFL' if args.centralized else 'DFL'} rounds={args.rounds} "
          f"topology={kind} backend={args.backend} "
          f"scenario={args.scenario or 'static'} "
          f"degrees={int(degs.min())}..{int(degs.max())}")
    mal = set(map(int, topo.malicious.nonzero()[0]))
    print(f"malicious nodes: {sorted(mal)}")
    if schedule is not None:
        dstats = schedule.degree_stats()
        diff = schedule.diff()
        for e in out["trace"]:
            r = e["round"] - 1
            churn = (f"  edges +{int(diff[r - 1][0])}/-{int(diff[r - 1][1])}"
                     if r > 0 else "")
            print(f"round {e['round']:2d}  benign acc "
                  f"{100 * e['acc_benign_mean']:6.2f}%  "
                  f"R2 {e['r_squared']:8.4f}  "
                  f"deg {dstats[r][0]:.0f}/{dstats[r][1]:.1f}/{dstats[r][2]:.0f}"
                  f"  mal {int(schedule.malicious[r].sum())}{churn}")
    else:
        for e in out["trace"]:
            print(f"round {e['round']:2d}  benign acc "
                  f"{100 * e['acc_benign_mean']:6.2f}%  "
                  f"R2 {e['r_squared']:8.4f}")

    # paper Fig. 7: per-node accuracy at the final round
    print("\nper-node final accuracy (x = malicious):")
    accs = out["final"]["acc_all"]
    for i, a in enumerate(accs):
        marker = " x" if i in mal else "  "
        print(f"  node {i:2d}{marker} {100 * a:6.2f}%  " + "#" * int(40 * a))

    if args.telemetry:
        from repro.obs import recorder as obs_recorder
        from repro.obs import report as obs_report
        from repro.obs import trace as obs_trace

        events = obs_report.events_from_telemetry(
            out["telemetry"],
            dict(aggregator=args.aggregator, attack=args.attack,
                 scenario=args.scenario or "static",
                 backend=args.backend))
        print()
        print(obs_report.render_audit(events))
        if args.events_out:
            obs_recorder.write_events(events, args.events_out)
            print(f"\nwrote event log:     {args.events_out}")
        if args.trace_out:
            obs_trace.write_trace(events, args.trace_out)
            print(f"wrote Perfetto trace: {args.trace_out}")


if __name__ == "__main__":
    main()
