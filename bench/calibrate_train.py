#!/usr/bin/env python3
"""Readings that set a trainer cell's output-check limits, made on the
chip at the cell's own size, all in one process (one compile of the
step):

* the program against the reference, one line per ``--seeds`` seed (the
  lower readings);
* the control, the reference with the inputs of every matmul rounded to
  float8 e4m3 (below the configuration's bfloat16 compute), against the
  float32 reference, one line per ``--control-seeds`` seed, and each
  planted fault of ``--faults`` (the reference's ``FAULTS``) on the same
  seeds (the upper readings);
* with ``--time-steps N``, the robustness tax: N robust-DP steps of the
  cell and N steps of the same configuration and traffic through the
  ``gspmd`` mean step (mean all-reduce, no attack), each back to back
  with the mix's steps in flight, seconds a step.

    python3 bench/calibrate_train.py --workload train-qwen1.5-0.5b-k4 \\
        --seeds 1,2,3 --control-seeds 4,5 \\
        --faults half_batch,no_exchange,altered_weight --time-steps 20

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


def timed_steps(step, state, place, n: int, mix) -> dict:
    """Seconds a step over ``n`` steps back to back after 2 warm ones,
    the mix's ``steps_in_flight`` queued."""
    for i in range(2):
        state, m = step(state, place(i))
    float(m["loss"])
    pending = []
    t0 = time.perf_counter()
    for i in range(n):
        state, m = step(state, place(2 + i))
        pending.append(m)
        if len(pending) >= mix["steps_in_flight"]:
            float(pending.pop(0)["loss"])
    for m in pending:
        float(m["loss"])
    return {"steps": n, "seconds_per_step": (time.perf_counter() - t0) / n}


def gspmd_steps(tr, n: int) -> dict:
    """The cell's configuration and traffic through the launcher with
    ``--mode gspmd --agg mean`` and no attack."""
    import jax
    import jax.numpy as jnp

    from repro.launch import train as launch
    from repro.train import trainer

    argv = tr.program_argv()
    for flag, value in (("--mode", "gspmd"), ("--agg", "mean"), ("--attack", "none"),
                        ("--n-malicious", "0")):
        argv[argv.index(flag) + 1] = value
    pcfg, mesh, tc = launch.build_everything(launch.make_parser().parse_args(argv))
    state = launch.init_sharded_state(pcfg, tc, mesh)
    sharding = trainer.batch_shardings(
        tc, mesh, {"tokens": jax.ShapeDtypeStruct(tr.tokens(0).shape, jnp.int32)})
    place = lambda i: {"tokens": jax.device_put(tr.tokens(i), sharding["tokens"])}  # noqa: E731
    t0 = time.perf_counter()
    with mesh:
        step = trainer.build_train_step(pcfg, tc, mesh).lower(state, place(0)).compile()
    return dict(timed_steps(step, state, place, n, tr.mix),
                compile_s=time.perf_counter() - t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--time-steps", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import device as dev
    from bench.run import load_json, resolve

    cell = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    try:
        dev.require_tpu(cell.chips)
    except RuntimeError as e:
        print(f"calibrate_train: {e}", file=sys.stderr)
        return 2
    dev.use_compile_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    tr = cell.runner.Trainer(cell.model, cell.config, cell.mix, (seeds + cseeds + [0])[0])
    if seeds or args.time_steps:
        tr.build()
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        tr.reseed(seed)
        if i:
            tr.init_state()
        prog = tr.drive_check_steps()
        t1 = time.perf_counter()
        tr.free(keep_compiled=True)
        ref = tr.reference()
        emit(workload=cell.name, seed=seed, kind="program", program_s=t1 - t0,
             reference_s=time.perf_counter() - t1, **tr.numbers(prog, ref))
    if args.time_steps:
        tr.init_state()
        emit(workload=cell.name, kind="robust_dp_step",
             **timed_steps(tr.compiled, tr.state, tr.place, args.time_steps, cell.mix))
        tr.free()
        emit(workload=cell.name, kind="gspmd_mean_step", **gspmd_steps(tr, args.time_steps))
    tr.free()
    for seed in cseeds:
        tr.reseed(seed)
        ref = tr.reference()
        for kind in ["fp8"] + faults:
            t0 = time.perf_counter()
            other = (tr.reference(fp8=True) if kind == "fp8"
                     else tr.reference(fault=kind))
            emit(workload=cell.name, seed=seed, kind=kind,
                 reference_s=time.perf_counter() - t0, **tr.numbers(other, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
