#!/usr/bin/env python3
"""Readings that set the output check's limits, made on the chip at a
cell's own size, all in one process (one compile):

* the program against the reference, one line per ``--seeds`` seed (the
  lower readings);
* the control, the reference computed in bfloat16 in the program's
  place, against the float32 reference, one line per ``--control-seeds``
  seed, and each planted fault of ``--faults`` on the same seeds (the
  upper readings).

    python3 bench/calibrate.py --workload fleet-paper20-ipm05 --seeds 1,2,3 \\
        --control-seeds 4,5,6 --faults half_batch,no_exchange,altered_answer

``--precision highest`` makes a witness rather than a limit: it runs
the program with every matmul and convolution at that precision (JAX's
default matmul precision; the round kernel has none), so that what
rounding in the program's local training does to the check shows.

Each reading is one JSON line on standard output.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--precision", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import device as dev
    from bench.run import load_json, resolve

    cell = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    try:
        dev.require_tpu(cell.chips)
    except RuntimeError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    dev.use_compile_cache(ROOT)
    import jax
    import jax.numpy as jnp

    if args.precision:
        jax.config.update("jax_default_matmul_precision", args.precision)

    Fleet = cell.runner.Fleet
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        fl = Fleet(cell.model, cell.config, cell.mix, seed)
        fl.build()
        prog = fl.drive_check_chunks()
        t1 = time.perf_counter()
        fl.free()
        ref = fl.reference()
        t2 = time.perf_counter()
        emit(workload=cell.name, seed=seed, kind="program",
             precision=args.precision or "default",
             program_s=t1 - t0, reference_s=t2 - t1, **fl.numbers(prog, ref))
    for seed in cseeds:
        fl = Fleet(cell.model, cell.config, cell.mix, seed)
        ref = fl.reference()
        for kind in ["bfloat16"] + faults:
            t0 = time.perf_counter()
            other = (fl.reference(dtype=jnp.bfloat16) if kind == "bfloat16"
                     else fl.reference(fault=kind))
            emit(workload=cell.name, seed=seed, kind=kind,
                 reference_s=time.perf_counter() - t0, **fl.numbers(other, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
