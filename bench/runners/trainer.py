"""The robust data-parallel trainer's runner: any trainer mix
(``"runner": "trainer"``).

Set-up builds the program through the launcher's normal path
(``repro.launch.train.build_everything``, then
``repro.train.trainer.build_train_step``) with the configuration's
architecture at its ``num_hidden_layers``, WFAgg over the stacked
candidates with the one-launch round (``backend="fused"``, as
``chip_smoke.py`` selects it), and the mix's attack, on a (data = K,
model = 1) mesh of the cell's chips: one worker a chip.  The
benchmark's own initial weights (the configuration reference's
``init``, from the seed) replace the program's.  It compiles the donated
step for the mix's batch and drives the first ``check_steps`` steps
through it; the output check's program side is the per-leaf norms of
their state, taken on the device.  The window then carries on from that
state: steps back to back, ``steps_in_flight`` queued, each step's
tokens drawn on the host from the seed and the step and placed with the
batch's sharding; a step's loss and ``n_accepted`` are read back once
the later steps are queued, and ``failed`` counts steps whose loss is
not finite.
"""
from __future__ import annotations

import dataclasses
import gc
import re
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, trace as trace_lib, train_work
from bench.fleet_traffic import child_seed

# the program's ArchConfig fields each configuration key must equal
PROGRAM_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                  "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
                  "head_dim": "head_dim_", "vocab_size": "vocab_size",
                  "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
                  "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
                  "attention_bias": "qkv_bias", "compute_dtype": "dtype",
                  "param_dtype": "param_dtype"}


def zipf_cdf(vocab: int, s: float) -> np.ndarray:
    """CDF over token ids 0..vocab-1 of a Zipf law: id r - 1 has weight
    1 / r^s."""
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def step_tokens(seed: int, step: int, mix, cdf: np.ndarray) -> np.ndarray:
    """(workers * seqs_per_worker, seq_len) int32 token ids of one step,
    the same for the same seed and step; worker k's batch is its k-th
    block of rows."""
    rng = np.random.default_rng([child_seed(seed, "tokens"), step])
    u = rng.random((mix["workers"] * mix["seqs_per_worker"], mix["seq_len"]))
    return np.minimum(np.searchsorted(cdf, u, side="right"), cdf.size - 1).astype(np.int32)


@dataclasses.dataclass
class ProgramOut:
    """What the check compares, from the program or the reference."""
    losses: np.ndarray          # (check_steps, K)
    weights: np.ndarray         # (check_steps, K) WFAgg's weights
    # param_change, adam_m, candidates: {leaf: norms}, the program's one
    # for each chip's copy (candidates: one), the reference's one
    norms: Dict[str, Dict[str, np.ndarray]]
    hist_s: np.ndarray          # (window, K)
    hist_b: np.ndarray


@dataclasses.dataclass
class WindowOut:
    steps: int
    seconds: float
    failed_steps: int
    trace_dir: Optional[str] = None


class Trainer:
    """One seed of one trainer mix on the program."""

    def __init__(self, model, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        self.model, self.cfg, self.mix = model, cfg, mix
        self.K = int(mix["workers"])
        self.cdf = zipf_cdf(cfg["vocab_size"], float(mix["zipf_s"]))
        self.reseed(seed)

    def reseed(self, seed: int):
        """Weights and tokens of another seed; the compiled step stays."""
        self.seed = seed
        self.key = jax.random.PRNGKey(child_seed(seed, "weights"))

    def tokens(self, step: int) -> np.ndarray:
        return step_tokens(self.seed, step, self.mix, self.cdf)

    def worker_tokens(self, step: int) -> List[np.ndarray]:
        return np.split(self.tokens(step), self.K)

    # -- the program --------------------------------------------------------
    def program_argv(self) -> List[str]:
        from repro.configs.registry import get_config

        cfg, mix, opt, w = self.cfg, self.mix, self.cfg["optimizer"], self.cfg["wfagg"]
        argv = ["--arch", cfg["arch"], "--n-layers", str(cfg["num_hidden_layers"]),
                "--mode", "robust_dp", "--agg", "wfagg", "--layout", "stacked",
                "--f", str(w["f"]),
                "--transient", str(w["transient"]), "--window", str(w["window"]),
                "--attack", mix["attack"], "--n-malicious", str(len(mix["malicious_workers"])),
                "--lr", str(opt["lr"]), "--warmup", str(opt["warmup"]),
                "--steps", str(opt["total_steps"]), "--seq-len", str(mix["seq_len"]),
                "--global-batch", str(self.K * mix["seqs_per_worker"])]
        published = get_config(cfg["arch"])
        if published.d_model != cfg["hidden_size"]:      # a small CPU-test size
            argv += ["--d-model", str(cfg["hidden_size"]),
                     "--d-ff", str(cfg["intermediate_size"]),
                     "--vocab", str(cfg["vocab_size"])]
        return argv

    def _check_program(self, pcfg, mesh, tc) -> None:
        """The program runs what the configuration and the mix state, or
        the run stops here."""
        from repro.core.topology import spaced_malicious

        cfg, mix = self.cfg, self.mix
        wrong = {k: (getattr(pcfg, f), cfg[k]) for k, f in PROGRAM_FIELDS.items()
                 if getattr(pcfg, f) != cfg[k]}
        w = tc.agg.wfagg
        for k in ("f", "tau1", "tau2", "tau3", "window", "transient", "ewma_decay"):
            if getattr(w, k) != cfg["wfagg"][k]:
                wrong["wfagg." + k] = (getattr(w, k), cfg["wfagg"][k])
        mal = np.flatnonzero(spaced_malicious(self.K, len(mix["malicious_workers"])))
        if mal.tolist() != list(mix["malicious_workers"]):
            wrong["malicious_workers"] = (mal.tolist(), mix["malicious_workers"])
        if dict(mesh.shape) != {"data": self.K, "model": 1}:
            wrong["mesh"] = (dict(mesh.shape), {"data": self.K, "model": 1})
        if not tc.donate:
            wrong["donate"] = (tc.donate, True)
        if wrong:
            raise RuntimeError(f"the program differs from the cell as (program, cell): {wrong}")

    def build(self):
        from repro.launch import train as launch
        from repro.train import trainer as tr

        args = launch.make_parser().parse_args(self.program_argv())
        pcfg, mesh, tc = launch.build_everything(args)
        # the one-launch Pallas round, as chip_smoke.py selects it
        tc = dataclasses.replace(tc, agg=dataclasses.replace(tc.agg, backend="fused"))
        self._check_program(pcfg, mesh, tc)
        self.program = (pcfg, mesh, tc)
        self.init_state()
        self.batch_sharding = tr.batch_shardings(
            tc, mesh, {"tokens": jax.ShapeDtypeStruct(self.tokens(0).shape, jnp.int32)})
        with mesh:
            self.compiled = tr.build_train_step(pcfg, tc, mesh).lower(
                self.state, self.place(0)).compile()
        hlo = self.compiled.as_text()
        self.module_name = re.search(r"HloModule\s+([\w.\-]+)", hlo).group(1)
        self.kernel_names = {n for n in re.findall(
            r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
            if n.startswith("wfagg_round_indexed")}

    def init_state(self):
        """The program's train state with the benchmark's weights of the
        seed in place of its own initial parameters."""
        from repro.launch import train as launch

        pcfg, mesh, tc = self.program
        state = launch.init_sharded_state(pcfg, tc, mesh)
        self.param_shardings = jax.tree.map(lambda x: x.sharding, state.params)
        params0 = self.initial_params(self.param_shardings)
        if jax.tree.structure(params0) != jax.tree.structure(state.params):
            raise RuntimeError("the reference's parameter pytree is not the program's")
        self.state = state._replace(params=params0)
        self.next_step = 0

    def initial_params(self, shardings=None):
        return jax.jit(lambda k: self.model.init(self.cfg, k),
                       out_shardings=shardings)(self.key)

    def place(self, step: int):
        return {"tokens": jax.device_put(self.tokens(step), self.batch_sharding["tokens"])}

    def _call(self):
        batch = self.place(self.next_step)
        self.next_step += 1
        self.state, m = self.compiled(self.state, batch)
        return m

    def drive_check_steps(self) -> ProgramOut:
        """The first ``check_steps`` steps through the window's own
        compiled step; the numbers the check compares, from their state."""
        ms = [self._call() for _ in range(self.mix["check_steps"])]
        st = self.state
        norms = jax.jit(self._norms)(st.params, st.opt_state["m"], st.agg_state.prev,
                                     self.initial_params(self.param_shardings))
        host = jax.device_get((norms, [(m["losses"], m["weights"]) for m in ms],
                               st.agg_state.hist_s, st.agg_state.hist_b))
        norms, per_step, hist_s, hist_b = host
        return ProgramOut(losses=np.stack([np.asarray(l) for l, _ in per_step]),
                          weights=np.stack([np.asarray(w) for _, w in per_step]),
                          norms=jax.tree.map(lambda x: np.asarray(x, np.float64), norms),
                          hist_s=np.asarray(hist_s), hist_b=np.asarray(hist_b))

    def _norms(self, params, m, prev, params0):
        """Per-leaf norms of the parameters' change and of Adam's first
        moment on every chip's own copy ((K,) each: the copies of a
        replicated array must agree, and a step that skipped the
        exchange would leave them apart), and of each worker's last
        candidate."""
        from jax.sharding import PartitionSpec as P

        leaf = self.model.leaf_norms

        def per_chip(p, m, p0):
            change = jax.tree.map(lambda a, b: a - b, p, p0)
            return jax.tree.map(lambda x: x[None], {"param_change": leaf(change),
                                                    "adam_m": leaf(m)})

        out = jax.shard_map(per_chip, mesh=self.program[1], in_specs=P(),
                            out_specs=P("data"), check_vma=False)(params, m, params0)
        out["candidates"] = {}
        for k in range(self.K):
            for name, v in leaf(jax.tree.map(lambda x: x[k], prev)).items():
                out["candidates"][f"worker{k}.{name}"] = v[None]
        return out

    def window(self, seconds: float, trace: bool = False) -> WindowOut:
        """Steps back to back for ``seconds``, ``steps_in_flight`` queued;
        with ``trace``, ``trace_seconds`` of it are profiled."""
        pending: List[Any] = []
        done = failed = 0
        tdir, tstate, ann = None, "off", None

        def readback():
            nonlocal done, failed
            with jax.profiler.TraceAnnotation("readback"):
                m = pending.pop(0)
                loss, _ = float(m["loss"]), int(m["n_accepted"])
            done += 1
            failed += int(not np.isfinite(loss))

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
            if tstate == "on" and (now >= t_trace + self.mix["trace_seconds"]
                                   or now >= deadline):
                while pending:
                    readback()
                ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tstate = "done"
            if now >= deadline:
                break
            with jax.profiler.TraceAnnotation("dispatch"):
                pending.append(self._call())
            if len(pending) >= self.mix["steps_in_flight"]:
                readback()
        while pending:
            readback()
        t1 = time.perf_counter()
        if tstate == "on":
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return WindowOut(steps=done, seconds=t1 - t0, failed_steps=failed, trace_dir=tdir)

    def free(self, keep_compiled: bool = False):
        """Drop every device array of the program before the reference
        (and the compiled step, unless another seed will run it)."""
        for name in ("state",) + (() if keep_compiled else ("compiled",)):
            if hasattr(self, name):
                delattr(self, name)
        gc.collect()

    # -- the check ----------------------------------------------------------
    def reference(self, fp8: bool = False, fault: Optional[str] = None) -> ProgramOut:
        """The configuration's reference, on one chip, over the checked
        steps and their tokens."""
        ref = self.model.RefTrainer(self.cfg, self.mix, self.initial_params(),
                                    fp8=fp8, fault=fault)
        infos = [ref.step(self.worker_tokens(i)) for i in range(self.mix["check_steps"])]
        n = ref.norms(self.initial_params())
        return ProgramOut(losses=np.stack([i["losses"] for i in infos]),
                          weights=np.stack([i["weights"] for i in infos]),
                          norms={k: n[k] for k in ("param_change", "adam_m", "candidates")},
                          hist_s=n["hist_s"], hist_b=n["hist_b"])

    @staticmethod
    def numbers(prog: ProgramOut, ref: ProgramOut) -> Dict[str, Any]:
        """The compared numbers (and a few diagnostics) of ``prog``
        against ``ref``: for each norm family, the worst leaf's gap
        (``bench.compare.worst_leaf_norm_gap``); the largest relative
        gap of a worker's loss; the WFAgg-T history's worst column; the
        accept decisions that differ.  The parameters' change leaves out
        the leaves whose aggregated gradient only round-off moves (Adam's
        first moment under a thousandth of the median leaf's, as the key
        bias, to which the softmax is invariant): Adam divides each
        coordinate by its own magnitude, so there rounding noise alone
        sets the step."""
        out: Dict[str, Any] = {}
        m = {k: float(np.ravel(v)[0]) for k, v in ref.norms["adam_m"].items()}
        moved = {k for k, v in m.items() if v >= 1e-3 * np.median(list(m.values()))}
        for fam in ("param_change", "adam_m", "candidates"):
            pairs = [(f"chip{c}.{k}" if len(p) > 1 else k, p[c], np.ravel(v)[0])
                     for k, v in ref.norms[fam].items()
                     if fam != "param_change" or k in moved
                     for p in [np.ravel(prog.norms[fam][k])] for c in range(len(p))]
            out[fam], out["_" + fam + "_leaf"], _ = compare.worst_leaf_norm_gap(pairs)
        hist = [(f"{h}.worker{k}", getattr(prog, h)[:, k], getattr(ref, h)[:, k])
                for h in ("hist_s", "hist_b") for k in range(prog.hist_s.shape[1])]
        out["temporal_hist"], out["_temporal_hist_leaf"], _ = compare.worst_leaf_norm_gap(hist)
        gap = np.abs(prog.losses - ref.losses) / np.abs(ref.losses)
        out["loss"] = float(np.max(np.where(np.isnan(gap), np.inf, gap)))
        out["accept_mismatch"] = float(((prog.weights > 0) != (ref.weights > 0)).sum())
        out["_losses"] = prog.losses.tolist()
        out["_ref_losses"] = ref.losses.tolist()
        out["_weights"] = prog.weights.tolist()
        out["_ref_weights"] = ref.weights.tolist()
        return out

    # -- per-layer data ----------------------------------------------------
    def layer_data(self, win: WindowOut, device_kind: str, chips: int) -> Dict[str, Any]:
        pd = trace_lib.load(trace_lib.find_xplane(win.trace_dir))
        return step_layer_data(pd, self.kernel_names, self.module_name, self.cfg,
                               self.mix, device_kind, chips, win.trace_dir)


def step_layer_data(pd, kernel_names, module_name: str, cfg, mix, device_kind: str,
                    chips: int, trace_dir: Optional[str]) -> Dict[str, Any]:
    """What the ``.train`` readers read of a traced window: the trace's
    reduction, each chip's (busy, kernel) seconds, the steps of the
    step program inside the window (``rounds``) and the work a step
    needs."""
    from bench.peaks import peaks

    window = trace_lib.window_of(pd)
    red = trace_lib.reduce(pd, window, kernel_names,
                           span_names=("dispatch", "readback", "window"))

    def seconds(events):
        return 1e-9 * trace_lib.length(trace_lib.union(trace_lib.clip(
            ((e.start, e.end) for e in events), *window)))

    per_dev = [(seconds(evs), seconds(e for e in evs if e.name in kernel_names))
               for evs in trace_lib.device_lines(pd, "XLA Ops").values()]
    return {"reduction": red, "rounds": trace_lib.module_rounds(pd, window, 1, module_name),
            "chips": chips, "trace_dir": trace_dir, "peaks": peaks(device_kind),
            "per_device": per_dev,
            "flops_per_step": train_work.train_step_flops(cfg, mix),
            "bytes_per_step": train_work.wfagg_step_bytes(cfg, mix["workers"]),
            "n_kernels": len(kernel_names)}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, log) -> Dict[str, Any]:
    """One run of a trainer cell: set-up, window, output check."""
    from bench import device as dev

    t = [time.perf_counter()]
    tr = Trainer(cell.model, cell.config, cell.mix, seed)
    tr.build()
    t.append(time.perf_counter())
    prog = tr.drive_check_steps()
    t.append(time.perf_counter())
    setup_s = t[-1] - t_start
    log("set-up: start %.2f s, build, weights and compile %.2f s, checked steps %.2f s"
        % (t[0] - t_start, t[1] - t[0], t[2] - t[1]))
    ma = tr.compiled.memory_analysis()
    if ma is not None:
        log("compiled step, per chip: arguments %d B, outputs %d B (aliased %d B), "
            "temporaries %d B" % (ma.argument_size_in_bytes, ma.output_size_in_bytes,
                                  ma.alias_size_in_bytes, ma.temp_size_in_bytes))
    win = tr.window(seconds, trace=trace)
    rec = dev.record(devices)
    data = tr.layer_data(win, rec["kind"], len(devices)) if trace else None
    tr.free()
    t0 = time.perf_counter()
    ref = tr.reference()
    numbers = tr.numbers(prog, ref)
    log("reference %.2f s" % (time.perf_counter() - t0))
    log("compared over the first %d steps: %s" % (cell.mix["check_steps"], numbers))
    return {
        "e2e": {"rounds_per_s": win.steps / win.seconds, "setup_s": setup_s},
        "layer_data": data,
        "numbers": {k: v for k, v in numbers.items() if not k.startswith("_")},
        "limits": cell.mix.get("limits") or {},
        "attempted": win.steps,
        "failed": win.failed_steps,
        "device": rec,
    }
