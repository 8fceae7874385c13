#!/usr/bin/env python3
"""Bring-up check on a TPU: drive the main paths once, at real widths,
through the entry points a user calls, and check what comes out.

    python chip_smoke.py             # phase "fleet", one chip
    python chip_smoke.py --chips 4   # phase "multi", four chips

Phase "fleet" is the paper's deployment (Section V-A): 20 LeNet nodes
(d = 44,426) on an 8-regular ring, 2 Byzantine nodes placed close
together running IPM-100, WFAgg aggregation, a static schedule.  It runs
the scan behind ``run_dynamic_experiment`` with the one-launch Pallas
round (``wfagg_backend="fused"``), then ``run_dynamic_experiment`` with
the jnp reference, and requires of the two runs equal accept masks in
every round, benign final models within ``MODEL_ATOL`` and no Byzantine
sender accepted by a benign node.  It then runs the chaos round core
once, with 30% of the gossip payloads dropped.

Phase "multi" runs only what exists across chips, each beside what it is
compared with: the ``robust_dp`` trainer step of qwen1.5-0.5b at its
published widths (depth cut to fit one chip) on a (data=4, model=1)
mesh, fused against reference WFAgg; and the fleet run with its model
dimension sharded over the four chips against the one-device run.

The script refuses to run anywhere but on a TPU, and fails unless the
fused round compiled to a Mosaic kernel (``tpu_custom_call``).  Every
phase's failure propagates.  Findings go to earlier lines; the last line
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ROUNDS = 5
N_TEST = 256
# Two runs of one schedule whose programs differ only in how WFAgg
# aggregates.  A round's aggregation paths agree to f32 rounding
# (core.wfagg.f32_dots keeps XLA's dots there in f32), about 5e-8; local
# training at XLA's default TPU precision feeds bf16-rounded weights to
# its dots, which grows that to about 5e-5 over the 5 rounds (1e-7 with
# f32 training, whose compile takes five times longer).
MODEL_ATOL = 1e-4
# qwen1.5-0.5b keeps d_model 1024, 16 heads, d_ff 2816 and vocab 151936;
# only the depth is cut.  A fused step holds its arguments and its
# temporaries in HBM at once (the state is donated, so the outputs reuse
# the arguments): 9.47 GiB per chip at 2 layers, 13.49 GiB at 8, about
# 0.67 GiB per layer (13 copies of a layer's weights: parameters, AdamW
# moments, WFAgg-T history, and the four candidate gradients whole on
# each chip), against the 15.75 GiB a v5e program may use.  11 layers
# is the most that fits; 8 keep 2.2 GiB free.
TRAINER_LAYERS = 8
TRAINER_STEPS = 3
LOSS_RTOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _benign_models(params, topo):
    """(benign nodes, d) models.  A Byzantine node's own model is
    attacker state (IPM-100 drives it non-finite) and is not compared."""
    import jax
    import numpy as np
    from jax.flatten_util import ravel_pytree
    flat = jax.vmap(lambda t: ravel_pytree(t)[0])(params)
    return np.asarray(flat)[~np.asarray(topo.malicious)]


def _accepted(verdict):
    """(R, N, K) accept masks of a run's stacked per-round verdicts."""
    import numpy as np
    from repro.obs.decision import unpack_verdict
    return unpack_verdict(np.asarray(verdict))["accepted"]


def _require_mosaic(compiled, what: str) -> None:
    """Proof that Pallas compiled for the chip and was not interpreted."""
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{what} compiled without a Mosaic kernel "
                             "(no tpu_custom_call in its HLO)")


def _paper_deployment():
    from repro.configs.lenet_mnist import PAPER_DFL as p
    from repro.core.topology import make_topology, static_schedule
    from repro.data.synthetic import SyntheticImages
    topo = make_topology(n_nodes=p.n_nodes, degree=p.degree,
                         n_malicious=p.n_malicious, kind="ring",
                         placement="close")
    return topo, SyntheticImages(), static_schedule(topo, ROUNDS)


def _fleet_config(**kw):
    from repro.dfl import engine
    return engine.DFLConfig(aggregator="wfagg", attack="ipm_100",
                            model="lenet", **kw)


def _check_run(name: str, acc_benign, params, verdict, topo, sched) -> None:
    """A run's benign accuracy and models are finite, and no benign node
    accepted an edge from a Byzantine sender in any round."""
    import numpy as np
    if not (np.isfinite(np.asarray(acc_benign)).all()
            and np.isfinite(_benign_models(params, topo)).all()):
        raise AssertionError(f"[{name}] non-finite benign result")
    acc = _accepted(verdict)
    mal = np.asarray(sched.malicious)
    from_byz = np.stack([mal[r][sched.neighbor_idx[r]]
                         for r in range(len(mal))])
    leaked = acc & from_byz & ~mal[:, :, None] & np.asarray(sched.valid)
    log(f"[{name}] benign accuracy per round "
        + " ".join(f"{a:.4f}" for a in np.asarray(acc_benign))
        + f"; {int(acc.sum())} accepted edges, "
        f"{int(leaked.sum())} of them from a Byzantine sender")
    if leaked.any():
        raise AssertionError(f"[{name}] benign nodes accepted "
                             f"{int(leaked.sum())} Byzantine edges")


def _compare_runs(name: str, run_a, run_b, topo) -> None:
    """Two runs of one schedule, each given as (final node params,
    stacked per-round verdicts): the accept masks must be equal in every
    round and the benign final models within MODEL_ATOL."""
    import numpy as np
    (params_a, verdict_a), (params_b, verdict_b) = run_a, run_b
    acc_a, acc_b = _accepted(verdict_a), _accepted(verdict_b)
    if not np.array_equal(acc_a, acc_b):
        rounds = np.flatnonzero((acc_a != acc_b).any(axis=(1, 2))) + 1
        raise AssertionError(
            f"[{name}] accept masks differ on {int((acc_a != acc_b).sum())} "
            f"edges, in rounds {rounds.tolist()}")
    diff = float(np.max(np.abs(_benign_models(params_a, topo)
                               - _benign_models(params_b, topo))))
    log(f"[{name}] accept masks equal in all {len(acc_a)} rounds; "
        f"max |benign final model diff| {diff:.3e} "
        f"(tolerance {MODEL_ATOL:g})")
    if not diff <= MODEL_ATOL:
        raise AssertionError(f"[{name}] final models differ by {diff:.3e}")


def fleet_phase() -> None:
    from repro.dfl import engine
    from repro.dfl.dynamics import make_faulty_schedule

    topo, data, sched = _paper_deployment()
    cfg = _fleet_config(wfagg_backend="fused")
    # the one jitted scan run_dynamic_experiment runs, compiled ahead so
    # that its Mosaic kernel can be checked; its outputs are the fused run
    state, run, xs = engine.build_dynamic_scan_fn(
        cfg, topo, data, sched, n_test=N_TEST, telemetry=True)
    t0 = time.perf_counter()
    compiled = run.lower(state, *xs).compile()
    compile_s = time.perf_counter() - t0
    _require_mosaic(compiled, "the fused round")
    _, first_s = _timed(lambda: compiled(state, *xs))
    (final, (_, acc_benign, _, record)), steady_s = _timed(
        lambda: compiled(state, *xs))
    log(f"[fleet/fused] compile {compile_s:.2f} s, first scan {first_s:.3f} "
        f"s, steady scan {steady_s:.3f} s ({ROUNDS} rounds); "
        "tpu_custom_call present")
    fused = (final.node_params, record.verdict)
    _check_run("fleet/fused", acc_benign, *fused, topo, sched)

    out = engine.run_dynamic_experiment(
        _fleet_config(wfagg_backend="reference"), topo, data, sched,
        n_test=N_TEST, telemetry=True)
    ref = (out["state"].node_params, out["telemetry"]["verdict"])
    _check_run("fleet/reference", out["series"]["acc_benign_mean"], *ref,
               topo, sched)
    _compare_runs("fleet fused vs reference", fused, ref, topo)

    sched_c, faults = make_faulty_schedule("static", topo, ROUNDS,
                                           fault="drop", intensity=0.3)
    out = engine.run_dynamic_experiment(cfg, topo, data, sched_c,
                                        n_test=N_TEST, telemetry=True,
                                        faults=faults)
    log(f"[chaos/drop@0.3] {out['faults']}")
    _check_run("chaos/drop@0.3", out["series"]["acc_benign_mean"],
               out["state"].node_params, out["telemetry"]["verdict"],
               topo, sched_c)


def trainer_check(argv=None) -> None:
    """robust_dp stacked WFAgg step, fused against reference, on the
    (data=4, model=1) mesh that build_everything makes of four chips."""
    import jax
    import numpy as np
    from repro.configs.registry import get_config
    from repro.data.synthetic import TokenStream
    from repro.launch import train as launch
    from repro.train import trainer as tr

    argv = argv or ["--arch", "qwen1.5-0.5b",
                    "--n-layers", str(TRAINER_LAYERS),
                    "--agg", "wfagg", "--layout", "stacked", "--f", "1",
                    "--attack", "ipm_100", "--n-malicious", "1",
                    "--steps", str(TRAINER_STEPS), "--warmup", "1",
                    "--seq-len", "128", "--global-batch", "8"]
    args = launch.make_parser().parse_args(argv)
    cfg, mesh, tc = launch.build_everything(args)
    if dict(mesh.shape) != {"data": 4, "model": 1}:
        raise AssertionError(f"expected a (data=4, model=1) mesh, got "
                             f"{dict(mesh.shape)}")
    log(f"[trainer] {cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; n_layers cut "
        f"{get_config(args.arch).n_layers} -> {cfg.n_layers}; "
        f"{cfg.param_count() / 1e6:.1f}M params; mesh {dict(mesh.shape)}")
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                         batch_size=args.global_batch)
    record = {}
    for backend in ("fused", "reference"):
        # build_everything donates the state, as TrainConfig does
        tcb = dataclasses.replace(
            tc, agg=dataclasses.replace(tc.agg, backend=backend))
        state = launch.init_sharded_state(cfg, tcb, mesh)
        step = tr.build_train_step(cfg, tcb, mesh)
        with mesh:
            batch = lambda i: jax.device_put(  # noqa: E731
                stream.batch(i), tr.batch_shardings(tcb, mesh, stream.batch(i)))
            t0 = time.perf_counter()
            compiled = step.lower(state, batch(0)).compile()
            compile_s = time.perf_counter() - t0
            if backend == "fused":
                _require_mosaic(compiled, "the fused trainer step")
            ma = compiled.memory_analysis()
            log(f"[trainer/{backend}] compiled per device: arguments "
                f"{ma.argument_size_in_bytes / 2**20:.1f} MiB, outputs "
                f"{ma.output_size_in_bytes / 2**20:.1f} MiB "
                f"({ma.alias_size_in_bytes / 2**20:.1f} MiB of them "
                f"in the arguments), temporaries "
                f"{ma.temp_size_in_bytes / 2**20:.1f} MiB")
            losses, accepted = [], []
            for i in range(args.steps):
                state, m = compiled(state, batch(i))
                losses.append(float(m["loss"]))
                accepted.append(int(m["n_accepted"]))
        log(f"[trainer/{backend}] compile {compile_s:.2f} s; loss "
            + " ".join(f"{x:.6f}" for x in losses)
            + f"; n_accepted {accepted}")
        if not np.isfinite(losses).all():
            raise AssertionError(f"[trainer/{backend}] non-finite loss")
        record[backend] = (losses, accepted)
        del state
    (lf, af), (lr, ar) = record["fused"], record["reference"]
    if af != ar or not np.allclose(lf, lr, rtol=LOSS_RTOL, atol=0.0):
        raise AssertionError(f"[trainer] fused {lf} {af} != reference "
                             f"{lr} {ar}")
    log(f"[trainer] fused and reference agree: losses within rtol "
        f"{LOSS_RTOL:g}, n_accepted equal")


def sharded_round_check() -> None:
    """The fleet run with d sharded over the four chips against the
    one-device run, same deployment, end to end."""
    from repro.dfl import engine
    topo, data, sched = _paper_deployment()
    runs = []
    for shards in (0, 4):
        out = engine.run_dynamic_experiment(
            _fleet_config(wfagg_backend="fused", mesh_model_shards=shards),
            topo, data, sched, n_test=N_TEST, telemetry=True)
        runs.append((out["state"].node_params, out["telemetry"]["verdict"]))
    _compare_runs("d-sharded x4 vs one-device", runs[1], runs[0], topo)


def _peak_memory(devices, what: str) -> None:
    stats = [d.memory_stats() for d in devices]
    log(f"[memory] after {what}: peak bytes in use per device "
        + " ".join(f"{d.id}:{m['peak_bytes_in_use'] / 2**20:.1f}MiB"
                   for d, m in zip(devices, stats))
        + f" (limit {stats[0].get('bytes_limit', 0) / 2**20:.1f}MiB)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phase 'fleet'; 4: phase 'multi'")
    args = ap.parse_args(argv)

    from repro.launch import device as dev
    try:
        devices = dev.require_tpu(args.chips)
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: {e}")
    cache = dev.use_compile_cache()
    rec = dev.device_record()
    log(f"devices: {rec}; compile cache: {cache}")

    if args.chips == 1:
        fleet_phase()
        _peak_memory(devices, "phase fleet")
    else:
        trainer_check()
        _peak_memory(devices, "trainer")
        sharded_round_check()
        _peak_memory(devices, "sharded round")
    print(json.dumps({"ok": True, "device": rec}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
