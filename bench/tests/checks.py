"""Drive a fleet cell's whole run on the CPU at a small size, past the
harness's look for a chip, with the timed path intact or broken
underneath by one of the faults the output check must catch."""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp

from bench import run as harness
from bench.tests.helpers import load_json, small_mix

FAULTS = ("state_unchanged", "half_batch", "no_exchange", "altered_answer")
# At 1,024 nodes the chip's bf16 local training flips near-tied WFAgg
# selections against the float32 reference, which moves single nodes'
# models by up to 94 % on sound runs; that cell compares median leaves,
# which one altered node does not move (see PERF.md).
FLEET_WIDE_FAULTS = ("state_unchanged", "half_batch", "no_exchange")


def small_cell(workload: str):
    cell = harness.resolve(load_json("BENCHMARK.json"), workload)
    cell.mix = small_mix(cell.workload["traffic"])
    return cell


@contextlib.contextmanager
def broken(fault: str, monkeypatch):
    """Break the program's timed path: its scan returns the state it was
    given; each step's loss is averaged over half its images; every node
    keeps its own model (no gossip exchange); or one node's aggregate is
    replaced by its own model where the round produces it."""
    from repro.core import metrics as met
    from repro.core import wfagg as wf
    from repro.dfl import engine

    if fault == "state_unchanged":
        build = engine.build_dynamic_scan_fn

        def build_unchanged(*a, **kw):
            carry, run, xs = build(*a, **kw)
            return carry, jax.jit(lambda c, *x: (c, run(c, *x)[1])), xs
        monkeypatch.setattr(engine, "build_dynamic_scan_fn", build_unchanged)
    elif fault == "half_batch":
        ce = met.cross_entropy
        monkeypatch.setattr(met, "cross_entropy",
                            lambda z, y: ce(z[: z.shape[0] // 2], y[: y.shape[0] // 2]))
    elif fault in ("no_exchange", "altered_answer"):
        agg = wf.wfagg_batch

        def wrapped(local, *a, **kw):
            out, state, info = agg(local, *a, **kw)
            if fault == "no_exchange":
                return local, state, info
            return out.at[1].set(local[1]), state, info
        monkeypatch.setattr(wf, "wfagg_batch", wrapped)
    else:
        raise ValueError(fault)
    yield


def run_line(cell, seed: int = 1234) -> dict:
    out = cell.runner.run(cell, seed, 0.5, False, time.perf_counter(),
                          jax.devices()[:1], lambda msg: None)
    return harness.result_line(cell, out, trace=False)


def control_checks(cell, seed: int = 99) -> dict:
    """The control: the reference computed in bfloat16 in the program's
    place, judged against the float32 reference by the cell's limits."""
    from bench.compare import judge

    fleet = cell.runner.Fleet(cell.model, cell.config, cell.mix, seed)
    ref = fleet.reference()
    ctl = fleet.reference(dtype=jnp.bfloat16)
    return judge(fleet.numbers(ctl, ref), fleet.mix.limits)
