"""The gossip-fleet runner: any fleet mix (``"runner": "fleet"``).

Set-up builds the mix's traffic from the seed, the benchmark's own
initial models, and the ONE jitted scan behind
``repro.dfl.engine.run_dynamic_experiment`` (``build_dynamic_scan_fn``);
it compiles the scan for one chunk of R rounds and drives the first
``check_chunks`` chunks through it.  Those chunks are the output check's
program side: the window then carries on from their state, dispatching
chunk after chunk (cycling the schedule) with the mix's
``chunks_in_flight`` queued, so that a host stall shorter than all but
one of them leaves the device busy.
Static mixes use the clean ``run(state, idx, valid, mal)``; chaos mixes
the explicit-carry form, so a chunk boundary re-keys the WFAgg-T
history exactly as an unbroken scan would.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import re
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, trace as trace_lib, work
from bench.fleet_reference import FleetReference
from bench.fleet_traffic import FleetMix, child_seed, make_traffic, to_program


@dataclasses.dataclass
class ProgramOut:
    """Host copies of what the timed scan produced in set-up."""
    params: Dict[str, Dict[str, np.ndarray]]   # after the checked chunks
    momentum: Dict[str, Dict[str, np.ndarray]]
    hist_s: np.ndarray
    hist_b: np.ndarray
    accepted: np.ndarray     # (rounds, N, K) bool
    valid: np.ndarray        # (rounds, N, K) bool, as the round saw it


@dataclasses.dataclass
class WindowOut:
    rounds: int
    seconds: float
    failed_rounds: int
    trace_dir: Optional[str] = None


class Fleet:
    """One seed of one fleet mix on the program."""

    def __init__(self, model, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        self.model, self.cfg = model, cfg
        self.mix = FleetMix.from_json(mix)
        self.seed = seed
        # the images are one fixed data set (as MNIST is): a seed the
        # program bakes into its compiled scan would defeat the compile
        # cache, so ``--seed`` varies the models, graph, churn and drops
        self.dseed = int(cfg["data_seed"])
        self.traffic = make_traffic(self.mix, seed)
        S, R = self.mix.schedule_rounds, self.mix.rounds_per_chunk
        if S % R:
            raise ValueError(f"schedule_rounds {S} is no multiple of R = {R}")
        self.params0 = jax.jit(lambda k: model.init(cfg, k, self.mix.nodes))(
            jax.random.PRNGKey(child_seed(seed, "weights")))

    # -- the program --------------------------------------------------------
    def build(self):
        from repro.configs.lenet_mnist import PaperDFLConfig
        from repro.data.synthetic import SyntheticImages
        from repro.dfl import engine

        cfg, mix = self.cfg, self.mix
        topo, sched, faults = to_program(mix, self.traffic, self.dseed)
        paper = PaperDFLConfig(
            n_nodes=mix.nodes, degree=mix.degree, n_malicious=mix.n_malicious,
            lr=cfg["lr"], momentum=cfg["momentum"], batch_size=cfg["batch_size"],
            f=cfg["f"], tau1=cfg["tau1"], tau2=cfg["tau2"], tau3=cfg["tau3"],
            alpha=cfg["alpha"], window=cfg["window"], transient=cfg["transient"])
        dcfg = engine.DFLConfig(
            aggregator=mix.aggregator, attack=mix.attack, model=cfg["model"],
            paper=paper, batches_per_round=cfg["batches_per_round"],
            seed=self.dseed, wfagg_backend="fused")
        data = SyntheticImages(n_classes=cfg["n_classes"],
                               noise=cfg["image_noise"], seed=self.dseed)
        carry, run, xs = engine.build_dynamic_scan_fn(
            dcfg, topo, data, sched, n_test=mix.n_test, telemetry=True,
            faults=faults)
        self.chaos = faults is not None
        if self.chaos:
            carry = (carry[0]._replace(node_params=self.params0),) + tuple(carry[1:])
        else:
            carry = carry._replace(node_params=self.params0)
        R = mix.rounds_per_chunk
        self.chunks = [tuple(a[c * R:(c + 1) * R] for a in xs)
                       for c in range(mix.schedule_rounds // R)]
        self.compiled = run.lower(carry, *self.chunks[0]).compile()
        hlo = self.compiled.as_text()
        self.module_name = re.search(r"HloModule\s+([\w.\-]+)", hlo).group(1)
        self.kernel_names = set(re.findall(
            r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo))
        self.carry = carry
        self.next_chunk = 0

    def _state(self, carry):
        return carry[0] if self.chaos else carry

    def _call(self):
        c = self.chunks[self.next_chunk % len(self.chunks)]
        self.next_chunk += 1
        self.carry, out = self.compiled(self.carry, *c)
        return out

    def drive_check_chunks(self) -> ProgramOut:
        """The first ``check_chunks`` chunks through the window's own
        compiled call; host copies of what the check compares."""
        from repro.obs.decision import unpack_verdict

        verdicts = []
        for _ in range(self.mix.check_chunks):
            out = self._call()
            verdicts.append(out[3].verdict)
        st = self._state(self.carry)
        host = jax.device_get((st.node_params, st.node_momentum,
                               st.temporal.hist_s, st.temporal.hist_b, verdicts))
        params, momentum, hist_s, hist_b, verdicts = host
        bits = unpack_verdict(np.concatenate(verdicts))
        return ProgramOut(params=params, momentum=momentum,
                          hist_s=hist_s, hist_b=hist_b,
                          accepted=bits["accepted"], valid=bits["valid"])

    def window(self, seconds: float, trace: bool = False) -> WindowOut:
        """Chunks back to back for ``seconds``, ``chunks_in_flight`` queued; a
        chunk is read back (its per-round benign accuracy) once the later
        ones are queued.  With ``trace``, ``trace_seconds`` of it are
        profiled."""
        R = self.mix.rounds_per_chunk
        pending: List[Any] = []
        done = failed = 0
        tdir = None
        tstate = "off"
        ann = None

        def readback():
            nonlocal done, failed
            with jax.profiler.TraceAnnotation("readback"):
                acc = np.asarray(pending.pop(0))
            done += R
            failed += int((~np.isfinite(acc)).sum())

        t0 = time.perf_counter()
        deadline = t0 + seconds
        lead = min(1.0, 0.25 * seconds)
        while True:
            now = time.perf_counter()
            if trace and tstate == "off" and now >= t0 + lead:
                tdir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(tdir)
                ann = jax.profiler.TraceAnnotation("window")
                ann.__enter__()
                t_trace = now
                tstate = "on"
            if tstate == "on" and (now >= t_trace + self.mix.trace_seconds
                                   or now >= deadline):
                while pending:
                    readback()
                ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tstate = "done"
            if now >= deadline:
                break
            with jax.profiler.TraceAnnotation("dispatch"):
                out = self._call()
            pending.append(out[1])
            if len(pending) >= self.mix.chunks_in_flight:
                readback()
        while pending:
            readback()
        t1 = time.perf_counter()
        if tstate == "on":
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return WindowOut(rounds=done, seconds=t1 - t0, failed_rounds=failed,
                         trace_dir=tdir)

    def free(self):
        """Drop every device array of the program before the reference."""
        for name in ("carry", "chunks", "compiled"):
            if hasattr(self, name):
                delattr(self, name)
        gc.collect()

    # -- the check ----------------------------------------------------------
    @property
    def check_rounds(self) -> int:
        return self.mix.check_chunks * self.mix.rounds_per_chunk

    def reference(self, dtype=jnp.float32, fault: Optional[str] = None) -> ProgramOut:
        ref = FleetReference(self.model, self.cfg, self.mix, self.traffic,
                             self.dseed, dtype=dtype, fault=fault)
        st = ref.init_state(self.params0)
        acc, val = [], []
        for r in range(self.check_rounds):
            st, a, v = ref.round(st, r)
            acc.append(np.asarray(a))
            val.append(np.asarray(v))
        f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
        params, momentum, hist_s, hist_b = jax.device_get(
            (f32(st.params), f32(st.momentum), st.hist_s, st.hist_b))
        return ProgramOut(params=params, momentum=momentum,
                          hist_s=hist_s, hist_b=hist_b,
                          accepted=np.stack(acc), valid=np.stack(val))

    def numbers(self, prog: ProgramOut, ref: ProgramOut) -> Dict[str, Any]:
        """The compared numbers (and a few diagnostics) of ``prog``
        against ``ref``."""
        p0 = jax.device_get(self.params0)
        benign = np.flatnonzero(~self.traffic.malicious)
        pairs = [(f"node{n}.{layer}.{k}",
                  prog.params[layer][k][n] - p0[layer][k][n],
                  ref.params[layer][k][n] - p0[layer][k][n])
                 for n in benign for layer in sorted(p0) for k in sorted(p0[layer])]
        mom_pairs = [(f"node{n}.{layer}.{k}", prog.momentum[layer][k][n],
                      ref.momentum[layer][k][n])
                     for n in benign for layer in sorted(p0) for k in sorted(p0[layer])]
        last = (self.check_rounds - 1) % self.mix.schedule_rounds
        slate = self.traffic.valid[last]
        hist_pairs = []
        for n in range(self.mix.nodes):
            cols = slate[n]
            hist_pairs.append((f"node{n}.hist_s", prog.hist_s[n][:, cols], ref.hist_s[n][:, cols]))
            hist_pairs.append((f"node{n}.hist_b", prog.hist_b[n][:, cols], ref.hist_b[n][:, cols]))
        out: Dict[str, Any] = {}
        for name, leaves in (("param_change", pairs), ("momentum", mom_pairs),
                             ("temporal_hist", hist_pairs)):
            out[name], out["_" + name + "_leaf"], _ = compare.worst_leaf_norm_gap(leaves)
            out[name + ".median"], _, _ = compare.worst_leaf_norm_gap(leaves, median=True)
        recv = ~self.traffic.malicious
        mism = (prog.accepted != ref.accepted)[:, recv] & (prog.valid | ref.valid)[:, recv]
        out["accept_mismatch"] = float(mism.sum())
        out["_accept_mismatch_per_round"] = mism.sum(axis=(1, 2)).tolist()
        out["_accepted_edges"] = int(prog.accepted[:, recv].sum())
        return out

    # -- per-layer data ----------------------------------------------------
    def layer_data(self, win: WindowOut, device_kind: str, chips: int) -> Dict[str, Any]:
        from bench.peaks import peaks

        pd = trace_lib.load(trace_lib.find_xplane(win.trace_dir))
        window = trace_lib.window_of(pd)
        red = trace_lib.reduce(pd, window, self.kernel_names,
                               span_names=("dispatch", "readback", "window"))
        rounds = trace_lib.module_rounds(pd, window, self.mix.rounds_per_chunk,
                                         self.module_name)
        S = self.mix.schedule_rounds
        bytes_per_round = float(np.mean([
            work.wfagg_round_bytes(self.traffic.idx[s], self.traffic.valid[s],
                                   self.cfg["d"]) for s in range(S)]))
        return {"reduction": red, "rounds": rounds, "chips": chips,
                "trace_dir": win.trace_dir,
                "peaks": peaks(device_kind),
                "flops_per_round": work.fleet_round_flops(
                    self.model, self.cfg, self.mix.nodes, self.mix.n_test),
                "bytes_per_round": bytes_per_round,
                "n_kernels": len(self.kernel_names)}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, log) -> Dict[str, Any]:
    """One run of a fleet cell: set-up, window, output check.  Returns
    the end-to-end values, the per-layer data, the checks and counts."""
    from bench import device as dev

    t = [time.perf_counter()]
    fleet = Fleet(cell.model, cell.config, cell.mix, seed)
    t.append(time.perf_counter())
    fleet.build()
    t.append(time.perf_counter())
    prog = fleet.drive_check_chunks()
    t.append(time.perf_counter())
    setup_s = t[-1] - t_start
    log("set-up: start %.2f s, traffic and weights %.2f s, build and compile "
        "%.2f s, checked chunks %.2f s" % (t[0] - t_start, t[1] - t[0],
                                            t[2] - t[1], t[3] - t[2]))
    ma = fleet.compiled.memory_analysis()
    if ma is not None:
        log("compiled chunk: arguments %d B, outputs %d B, temporaries %d B"
            % (ma.argument_size_in_bytes, ma.output_size_in_bytes,
               ma.temp_size_in_bytes))
    win = fleet.window(seconds, trace=trace)
    rec = dev.record(devices)
    data = None
    if trace:
        data = fleet.layer_data(win, rec["kind"], len(devices))
    fleet.free()
    t0 = time.perf_counter()
    ref = fleet.reference()
    numbers = fleet.numbers(prog, ref)
    log("reference %.2f s" % (time.perf_counter() - t0))
    log("compared over the first %d rounds: %s" % (fleet.check_rounds, json.dumps(
        {k: v for k, v in numbers.items()})))
    return {
        "e2e": {"rounds_per_s": win.rounds / win.seconds, "setup_s": setup_s},
        "layer_data": data,
        "numbers": {k: v for k, v in numbers.items() if not k.startswith("_")},
        "limits": fleet.mix.limits or {},
        "attempted": win.rounds,
        "failed": win.failed_rounds,
        "device": rec,
    }
