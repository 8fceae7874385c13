"""Benchmark driver — one section per paper table/figure.

  table1        paper Table I   (accuracy per aggregator x attack, CFL+DFL)
  r2            paper Figs 4/6  (R^2 model consistency, DFL)
  roofline      Section Roofline report from dry-run artifacts

``python -m benchmarks.run`` runs the fast versions of everything;
``--only table1 --full`` etc. for the complete sweeps.
"""
from __future__ import annotations

import argparse
import os
import time

HERE = os.path.dirname(__file__)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list: table1,r2,roofline")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--rounds", type=int, default=10)
    args = ap.parse_args()
    selected = set(args.only.split(",")) if args.only else {
        "table1", "r2", "roofline"}

    t0 = time.time()
    results = {}

    if "table1" in selected:
        print("=" * 72)
        print("== Table I: aggregator x attack accuracy (CFL + DFL) ==")
        from benchmarks import table1_attacks
        argv = ["--rounds", str(args.rounds),
                "--out", os.path.join(HERE, "out_table1.json")]
        if args.full:
            argv.append("--full")
        results["table1"] = table1_attacks.main(argv)

    if "r2" in selected:
        print("=" * 72)
        print("== R^2 model consistency (paper Figs 4/6) ==")
        from benchmarks import consistency_r2
        results["r2"] = consistency_r2.main(
            ["--rounds", str(args.rounds),
             "--out", os.path.join(HERE, "out_r2.json")])

    if "roofline" in selected:
        print("=" * 72)
        print("== roofline report (from dry-run artifacts) ==")
        from benchmarks import roofline
        n = len(roofline.load())
        if n == 0:
            print("no artifacts found — run `python -m repro.launch.dryrun --all` first")
        else:
            roofline.main([])

    print("=" * 72)
    print(f"total benchmark wall time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
