"""Drive the trainer cell's whole run on 4 virtual CPU devices at a small
size, past the harness's look for a chip: the sound run, the control
(the reference with fp8-rounded matmul inputs, judged against the
float32 reference by the cell's limits) and the timed path broken by
each fault the output check must catch.  Prints one JSON line,
{case: {"correct": ..., "checks": ...}}.

Run by ``bench/tests/test_check_train.py`` in a process of its own, with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import run as harness  # noqa: E402
from bench.compare import judge  # noqa: E402

WORKLOAD = "train-qwen1.5-0.5b-k4"
FAULTS = ("state_unchanged", "half_batch", "no_exchange", "altered_answer")


def small_cell():
    """The cell at 1 layer, d_model 64 (16 heads of 4), d_ff 128, vocab
    512 and 16 positions; everything else as the cell has it."""
    cell = harness.resolve(harness.load_json(os.path.join(ROOT, "BENCHMARK.json")), WORKLOAD)
    cell.config = dict(cell.config, hidden_size=64, intermediate_size=128, head_dim=4,
                       vocab_size=512, num_hidden_layers=1)
    cell.mix = dict(cell.mix, seq_len=16)
    return cell


@contextlib.contextmanager
def broken(fault: str):
    """Break the program's timed path: its step returns the state it was
    given; each worker's loss is over half its tokens; every chip applies
    its own candidate (no exchange); or the attacker's aggregation
    weight is raised to the largest."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.logical import current_mesh
    from repro.models import model as M
    from repro.train import trainer as tr

    if fault == "state_unchanged":
        mod, name = tr, "build_train_step"
        build = tr.build_train_step

        def patch(cfg, tc, mesh):
            step = build(cfg, tc, mesh)
            return jax.jit(lambda s, b: (s, step(s, b)[1]))
    elif fault == "half_batch":
        mod, name = M, "loss_fn"
        loss = M.loss_fn

        def patch(cfg, p, batch):
            tokens = batch["tokens"]
            return loss(cfg, p, dict(batch, tokens=tokens[:, : tokens.shape[1] // 2]))
    elif fault in ("no_exchange", "altered_answer"):
        mod, name = tr, "robust_allreduce_stacked"
        agg = tr.robust_allreduce_stacked

        def patch(stacked, cfg, state):
            out, new_state, info = agg(stacked, cfg, state)
            if fault == "no_exchange":
                own = jax.shard_map(lambda g: jax.tree.map(lambda x: x[0], g),
                                    mesh=current_mesh(), in_specs=P("data"),
                                    out_specs=P(), check_vma=False)(stacked)
                return own, new_state, info
            w = info["weights"].at[2].set(jnp.maximum(info["weights"].max(), 0.8))
            coef = w / w.sum()
            out = jax.tree.map(lambda x: jnp.tensordot(coef, x, axes=1), stacked)
            return out, new_state, dict(info, weights=w)
    else:
        raise ValueError(fault)
    orig = getattr(mod, name)
    setattr(mod, name, patch)
    try:
        yield
    finally:
        setattr(mod, name, orig)


def run_line(cell, seed: int = 1234) -> dict:
    out = cell.runner.run(cell, seed, 0.3, False, time.perf_counter(),
                          jax.devices()[:4], lambda msg: None)
    return harness.result_line(cell, out, trace=False)


def main() -> int:
    assert jax.device_count() == 4, jax.devices()
    cell = small_cell()
    results = {}
    line = run_line(cell)
    results["sound"] = {"correct": line["correct"], "checks": line["checks"]}
    t = cell.runner.Trainer(cell.model, cell.config, cell.mix, 99)
    ref = t.reference()
    checks = judge(t.numbers(t.reference(fp8=True), ref), cell.mix["limits"])
    results["control"] = {"correct": all(c["ok"] for c in checks.values()),
                          "checks": checks}
    for fault in FAULTS:
        with broken(fault):
            line = run_line(cell)
        results[fault] = {"correct": line["correct"], "checks": line["checks"]}
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
