"""Quickstart: WFAgg vs plain Mean under a strong Byzantine attack.

Runs the paper's 20-node decentralized federation (8-regular ring, 2
Byzantine nodes) on the synthetic MNIST-shaped task, once with the
non-robust Mean aggregator and once with WFAgg, under the IPM-100 attack
— the attack that fully collapses the mean in the paper's Table I.
A final block repeats the WFAgg run on a DYNAMIC topology (node churn)
to show the scenario engine's 5-line entry point, then pits an ADAPTIVE
adversary (min_max — it observes the defense's filter radii, see
docs/THREAT_MODEL.md) against Multi-Krum and WFAgg.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl.dynamics import make_schedule
from repro.dfl.engine import DFLConfig, run_dynamic_experiment, run_experiment


def main() -> None:
    topo = make_topology(n_nodes=20, degree=8, n_malicious=2, kind="ring",
                         placement="close")
    data = SyntheticImages()
    print(f"topology: {topo.n_nodes} nodes, degree {topo.degree}, "
          f"malicious: {list(map(int, topo.malicious.nonzero()[0]))}")

    for agg in ("mean", "wfagg"):
        cfg = DFLConfig(aggregator=agg, attack="ipm_100", model="mlp")
        out = run_experiment(cfg, topo, data, rounds=6, eval_every=2)
        print(f"\n=== aggregator: {agg}  (attack: IPM-100) ===")
        for e in out["trace"]:
            by = e["acc_by_malicious_neighbors"]
            print(f"  round {e['round']:2d}  benign acc {100 * e['acc_benign_mean']:6.2f}%  "
                  f"(0/1/2 m.n.: {100 * by[0]:.1f}/{100 * by[1]:.1f}/{100 * by[2]:.1f})  "
                  f"R2 {e['r_squared']:7.4f}")

    print("\nWFAgg holds accuracy where the mean collapses — the paper's "
          "central claim (Table I, IPM-100 row).")
    print("(Each WFAgg gossip round above ran as ONE kernel launch: the "
          "default backend fuses the filter statistics, the trust-weight "
          "derivation and the WFAgg-E combine into a single-launch "
          "Pallas kernel — 2 candidate passes per round; see "
          "src/repro/kernels/README.md.  That single-launch claim, and "
          "every other structural invariant of the round, is pinned by "
          "the computation linter: PYTHONPATH=src python -m "
          "repro.analysis — docs/STATIC_ANALYSIS.md.)")

    # Dynamic topology in 5 lines: the same experiment under node churn —
    # the graph (and each node's neighbor slate) changes EVERY round,
    # through one compile of the gather-free round function.
    schedule = make_schedule("churn", topo, rounds=6, p_leave=0.2)
    cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp")
    out = run_dynamic_experiment(cfg, topo, data, schedule)
    print("\n=== aggregator: wfagg  (attack: IPM-100, scenario: churn) ===")
    for e in out["trace"]:
        print(f"  round {e['round']:2d}  benign acc "
              f"{100 * e['acc_benign_mean']:6.2f}%  "
              f"R2 {e['r_squared']:7.4f}")

    # Adaptive adversary in 3 lines: attack="min_max" scales its
    # deviation to sit just inside the distance-filter acceptance radii
    # it observes (DefenseView) — it walks straight through Multi-Krum,
    # while WFAgg's 2-of-3 filter vote still contains it.
    print("\n=== adaptive attack: min_max (defense-aware) ===")
    for agg in ("multi_krum", "wfagg"):
        cfg = DFLConfig(aggregator=agg, attack="min_max", model="mlp")
        out = run_experiment(cfg, topo, data, rounds=6, eval_every=6)
        print(f"  {agg:11s} final benign acc "
              f"{100 * out['final']['acc_benign_mean']:6.2f}%")
    print("(The full attack x scenario x aggregator grid: "
          "PYTHONPATH=src python -m benchmarks.robustness_matrix.\n"
          " To see WHICH filter caught the attack — per-round per-filter "
          "true-catch/false-positive audit, JSONL event log, Perfetto "
          "trace — run the flight recorder: PYTHONPATH=src python -m "
          "repro.obs.report — docs/OBSERVABILITY.md.)")


if __name__ == "__main__":
    main()
