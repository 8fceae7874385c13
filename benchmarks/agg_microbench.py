"""Aggregation-rule microbenchmark (the paper's complexity table,
Section IV): wall-time per aggregation call vs (K, d), for every rule,
plus the Pallas kernel paths (interpret mode on CPU — correctness-grade
timing, the TPU number comes from the roofline).

Each row carries the execution ``backend`` and the analytic ``passes``
column — the number of (K, d)-sized HBM passes per aggregation (see
src/repro/kernels/README.md for the accounting).  The full-WFAgg rule is
measured under BOTH backends so the fused-vs-reference pass-count win is
visible in every run, and every invocation appends its rows to the
``BENCH_agg.json`` trajectory so later PRs can regress against it.

Timing methodology: the FIRST call
— trace + compile + one execution — is reported as its own
``compile_us`` column; ``us_per_call`` (and the GBps derived from it) is
the MEDIAN of ``reps`` further calls, each synchronized with its own
``block_until_ready``.  The old mean-with-one-final-block loop let the
async dispatch queue smear compile time and cross-call overlap into the
throughput number.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.core import aggregators as agg_lib
from repro.core import wfagg as wf

HERE = os.path.dirname(__file__)
TRAJECTORY = os.path.join(HERE, "BENCH_agg.json")


def _timeit(fn, *args, reps: int = 5) -> Tuple[float, float]:
    """(first-call seconds, median steady-state seconds).  Every call is
    individually synchronized with ``block_until_ready`` so no sample
    absorbs its neighbors' device time."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    samples = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return compile_s, statistics.median(samples)


def _row(rule: str, K: int, d: int, us: float, backend: str,
         passes: int | None = None, read_factor: float = 1.0,
         compile_us: float | None = None) -> Dict:
    """``read_factor`` scales the bytes-touched estimate for calls that
    stream more than one (K, d) tensor (batched launch, +prev input).
    ``us`` must be the steady-state (post-compile) median; the first
    call goes in ``compile_us``."""
    r = {
        "rule": rule, "K": K, "d": d, "us_per_call": round(us, 1),
        "backend": backend,
        "GBps": round(read_factor * 4e-3 * K * d / max(us, 1e-9), 2),
    }
    if compile_us is not None:
        r["compile_us"] = round(compile_us, 1)
    if passes is not None:
        r["passes"] = passes
    return r


def bench_rules(K: int, d: int) -> List[Dict]:
    key = jax.random.PRNGKey(0)
    updates = jax.random.normal(key, (K, d), jnp.float32)
    local = updates[0]
    rows = []

    cases = {
        "mean": jax.jit(lambda u: agg_lib.mean_agg(u)[0]),
        "median": jax.jit(lambda u: agg_lib.median_agg(u)[0]),
        "trimmed_mean": jax.jit(lambda u: agg_lib.trimmed_mean_agg(u)[0]),
        "krum": jax.jit(lambda u: agg_lib.krum_agg(u)[0]),
        "multi_krum": jax.jit(lambda u: agg_lib.multi_krum_agg(u)[0]),
        "clustering": jax.jit(lambda u: agg_lib.clustering_agg(u)[0]),
        "wfagg_d": jax.jit(lambda u: wf.wfagg_d_agg(u)[0]),
        "wfagg_c": jax.jit(lambda u: wf.wfagg_c_agg(u)[0]),
        "wfagg_e": jax.jit(lambda u: wf.wfagg_e_agg(local, u)),
    }
    for name, fn in cases.items():
        comp_s, med_s = _timeit(fn, updates)
        rows.append(_row(name, K, d, med_s * 1e6, "reference",
                         compile_us=comp_s * 1e6))

    # full WFAgg (3 filters + weighting + smoothing), both backends
    for backend in ("reference", "fused"):
        wcfg = wf.WFAggConfig(backend=backend)
        tstate = wf.init_temporal_state(K, d, wcfg.window)
        fn = jax.jit(lambda loc, u, ts, w=wcfg: wf.wfagg(loc, u, ts, w)[0])
        comp_s, med_s = _timeit(fn, local, updates, tstate)
        rows.append(_row(f"wfagg[{backend}]", K, d, med_s * 1e6, backend,
                         passes=wf.memory_passes(wcfg),
                         compile_us=comp_s * 1e6))

    # batched gossip round over an (N, d) model matrix: the gathered
    # launch materializes the (N, Kb, d) tensor first, the indexed one
    # DMAs neighbor blocks straight from the matrix (one pass less,
    # K-fold less HBM) — the `passes` column counts the gather
    N = 4
    models = jax.random.normal(jax.random.PRNGKey(5), (N, d), jnp.float32)
    Kb = min(K, N - 1)
    nidx = jnp.asarray(
        [[(n + o) % N for o in range(1, Kb + 1)] for n in range(N)], jnp.int32)
    wcfg = wf.WFAggConfig(backend="fused", use_temporal=False)
    for name, indexed, fn in (
        ("wfagg_batch[gathered]", False,
         jax.jit(lambda m: wf.wfagg_batch(m, m[nidx], None, wcfg)[0])),
        ("wfagg_batch[indexed]", True,
         jax.jit(lambda m: wf.wfagg_batch(m, m, None, wcfg,
                                          neighbor_idx=nidx)[0])),
    ):
        comp_s, med_s = _timeit(fn, models)
        rows.append(_row(name, Kb, d, med_s * 1e6, "fused",
                         passes=wf.memory_passes(wcfg, include_gather=True,
                                                 indexed=indexed),
                         read_factor=float(N), compile_us=comp_s * 1e6))
    return rows


def bench_dynamic(K: int, d: int, rounds: int = 4) -> List[Dict]:
    """Schedule-swap cost: a jitted lax.scan of ``rounds`` gather-free
    WFAgg gossip aggregations, once with a STATIC schedule (the same
    (N, K) neighbor table every round) and once with a DYNAMIC one (a
    different table + valid mask per round).  The delta is what a
    round-varying topology actually costs through the indexed path —
    the kernels take the table as a traced input, so it should be the
    price of an (N, K) index upload, not a recompile or a regather.
    us_per_call is normalized PER ROUND."""
    import numpy as np

    N = 8
    models = jax.random.normal(jax.random.PRNGKey(7), (N, d), jnp.float32)
    Kb = min(K, N - 1)
    wcfg = wf.WFAggConfig(backend="fused", use_temporal=False)
    rng = np.random.default_rng(0)
    idx = np.zeros((rounds, N, Kb), np.int32)
    val = np.zeros((rounds, N, Kb), bool)
    for r in range(rounds):
        for n in range(N):
            v = int(rng.integers(max(1, Kb - 2), Kb + 1))
            nb = rng.choice([i for i in range(N) if i != n], size=v,
                            replace=False)
            idx[r, n, :v] = nb
            idx[r, n, v:] = n
            val[r, n, :v] = True
    dyn_sched = (jnp.asarray(idx), jnp.asarray(val))
    static_sched = (jnp.broadcast_to(dyn_sched[0][0], dyn_sched[0].shape),
                    jnp.broadcast_to(dyn_sched[1][0], dyn_sched[1].shape))

    @jax.jit
    def run(m, sched_idx, sched_val):
        def body(m, xs):
            i, v = xs
            out, _, _ = wf.wfagg_batch(m, m, None, wcfg,
                                       neighbor_idx=i, valid=v)
            return out, ()
        m, _ = jax.lax.scan(body, m, (sched_idx, sched_val))
        return m

    rows = []
    for name, sched in (("wfagg_round[sched-static]", static_sched),
                        ("wfagg_round[sched-dynamic]", dyn_sched)):
        comp_s, med_s = _timeit(run, models, *sched, reps=3)
        rows.append(_row(name, Kb, d, med_s * 1e6 / rounds, "fused",
                         passes=wf.memory_passes(wcfg, include_gather=True,
                                                 indexed=True),
                         read_factor=float(N),
                         compile_us=comp_s * 1e6))
    return rows


def bench_one_launch(K: int, d: int, rounds: int = 4) -> List[Dict]:
    """Single-launch vs two-launch gossip round: the same jitted scan of
    gather-free WFAgg aggregations, once through the one-launch round
    kernel (backend="fused": stats + in-kernel weights + combine in one
    pallas_call) and once through the two-launch fallback
    (backend="fused_two_launch").  Outputs are parity-exact (fp32); the
    delta is the second kernel launch + the host scoring round-trip.
    us_per_call is normalized PER ROUND.

    Interpret-mode caveat: the one-launch kernel has more per-step
    inputs/outputs, so at smoke sizes (d ~ 4k) the interpreter's fixed
    per-step cost dominates and the one-launch row can come out SLOWER;
    its d-proportional cost is the lower one (fewer d-sized buffer
    carries), so at the baseline sizes (d >= ~100k, where the candidate
    traffic the kernel exists for actually dominates) one-launch wins —
    that is the comparison BENCH_agg.json records."""
    N = 8
    models = jax.random.normal(jax.random.PRNGKey(11), (N, d), jnp.float32)
    Kb = min(K, N - 1)
    nidx = jnp.asarray(
        [[(n + o) % N for o in range(1, Kb + 1)] for n in range(N)], jnp.int32)

    rows = []
    for name, backend in (("wfagg_round[one-launch]", "fused"),
                          ("wfagg_round[two-launch]", "fused_two_launch")):
        wcfg = wf.WFAggConfig(backend=backend, use_temporal=False)

        @jax.jit
        def run(m, w=wcfg):
            def body(m, _):
                out, _, _ = wf.wfagg_batch(m, m, None, w, neighbor_idx=nidx)
                return out, ()
            m, _ = jax.lax.scan(body, m, jnp.arange(rounds))
            return m

        # interpret-mode timings are noisy right after the heavier bench
        # sections (allocator churn): the median over per-call-blocked
        # reps keeps the one-vs-two-launch comparison honest
        comp_s, med_s = _timeit(run, models, reps=5)
        rows.append(_row(name, Kb, d, med_s * 1e6 / rounds, backend,
                         passes=wf.memory_passes(wcfg, include_gather=True,
                                                 indexed=True),
                         read_factor=float(N),
                         compile_us=comp_s * 1e6))
    return rows


def _bench_sharded_row(shards: int, K: int, d: int) -> Dict:
    """One sharded-round timing row over the first ``shards`` devices
    of this process (1 = the same round single-device)."""
    from repro.distributed import spmd

    N = 8
    Kb = min(K, N - 1)
    d_pad = spmd.shard_padded_d(d, max(shards, 1))
    wcfg = wf.WFAggConfig(backend="fused_two_launch", use_temporal=False)
    nidx = jnp.asarray(
        [[(n + o) % N for o in range(1, Kb + 1)] for n in range(N)], jnp.int32)
    models = jax.random.normal(jax.random.PRNGKey(13), (N, d_pad), jnp.float32)
    if shards > 1:
        mesh = spmd.aggregation_mesh(shards)
        fn = jax.jit(lambda m: spmd.wfagg_batch_sharded(
            m, m, None, wcfg, nidx, mesh=mesh)[0])
    else:
        fn = jax.jit(lambda m: wf.wfagg_batch(
            m, m, None, wcfg, neighbor_idx=nidx)[0])
    comp_s, med_s = _timeit(fn, models, reps=3)
    return _row(f"wfagg_round[sharded-{shards}dev]", Kb, d_pad,
                med_s * 1e6, "fused_two_launch",
                passes=wf.memory_passes(wcfg, include_gather=True,
                                        indexed=True),
                read_factor=float(N), compile_us=comp_s * 1e6)


def bench_sharded(K: int, d: int, shards: int = 8) -> List[Dict]:
    """The d-sharded gossip round (distributed/spmd.py) vs the same
    two-launch round on one device, both in this process — one process
    owns the chips.  It needs ``shards`` devices: on a CPU, force them
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
    launching.  Interpret-mode caveat applies: on virtual CPU devices
    the sharded row measures the shard_map + psum orchestration
    overhead, not a speedup — the wire-traffic win is what
    ``python -m repro.analysis`` verifies statically."""
    if jax.device_count() < shards:
        raise SystemExit(
            f"--sharded needs {shards} devices, found {jax.device_count()}")
    return [_bench_sharded_row(s, K, d) for s in (1, shards)]


def bench_kernels(K: int, d: int) -> List[Dict]:
    from repro.kernels.pairwise_dist.ops import pairwise_sq_dists
    from repro.kernels.robust_stats.ops import (
        robust_stats, robust_stats_batch, robust_stats_indexed)
    from repro.kernels.weighted_agg.ops import weighted_agg, weighted_agg_indexed

    key = jax.random.PRNGKey(1)
    updates = jax.random.normal(key, (K, d), jnp.float32)
    prev = jax.random.normal(jax.random.PRNGKey(2), (K, d), jnp.float32)
    batch = jnp.stack([updates] * 4)
    local = updates[0]
    weights = jnp.ones((K,), jnp.float32)
    # gather-free rows: N=4 nodes exchanging over an (M, d) model matrix
    # through a neighbor table — same aggregate work as the batch4 row,
    # minus the (N, K, d) gossip tensor (indexed DMA instead of gather).
    # M = K + 1 model rows so every slate is K DISTINCT non-self rows —
    # with fewer rows the GBps column would credit re-reads of the same
    # few vectors as distinct HBM traffic.
    N, M = 4, K + 1
    models = jax.random.normal(jax.random.PRNGKey(3), (M, d), jnp.float32)
    nidx = jnp.asarray(
        [[(n + o) % M for o in range(1, K + 1)] for n in range(N)], jnp.int32)
    wbatch = jnp.ones((N, K), jnp.float32)
    rows = []
    for name, backend, factor, fn in (
        ("robust_stats[pallas]", "fused", 1.0, lambda: robust_stats(updates)),
        ("robust_stats+prev[pallas]", "fused", 2.0, lambda: robust_stats(updates, prev)),
        ("robust_stats_batch4[pallas]", "fused", 4.0, lambda: robust_stats_batch(batch)),
        ("robust_stats_idx4[pallas]", "fused", 4.0,
         lambda: robust_stats_indexed(models, nidx)),
        ("robust_stats_idx4+prev[pallas]", "fused", 8.0,
         lambda: robust_stats_indexed(models, nidx, prev=models)),
        ("robust_stats[jnp-ref]", "reference", 1.0, lambda: robust_stats(updates, use_kernel=False)),
        ("pairwise[pallas]", "fused", 1.0, lambda: pairwise_sq_dists(updates)),
        ("pairwise[jnp-ref]", "reference", 1.0, lambda: pairwise_sq_dists(updates, use_kernel=False)),
        ("weighted_agg[pallas]", "fused", 1.0, lambda: weighted_agg(local, updates, weights)),
        ("weighted_agg_idx4[pallas]", "fused", 4.0,
         lambda: weighted_agg_indexed(models[:N], models, nidx, wbatch)),
        ("weighted_agg[jnp-ref]", "reference", 1.0, lambda: weighted_agg(local, updates, weights, use_kernel=False)),
    ):
        comp_s, med_s = _timeit(fn, reps=3)
        rows.append(_row(name, K, d, med_s * 1e6, backend,
                         read_factor=factor, compile_us=comp_s * 1e6))
    return rows


def append_trajectory(rows: List[Dict], path: str = TRAJECTORY) -> None:
    """Append one benchmark snapshot to the BENCH_agg.json trajectory."""
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
        except (json.JSONDecodeError, OSError):
            history = []
    history.append({
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_backend": jax.default_backend(),
        "rows": rows,
    })
    with open(path, "w") as f:
        json.dump(history, f, indent=1)


def main(argv=None) -> List[Dict]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="8x100000,16x100000,16x1000000")
    ap.add_argument("--kernels", action="store_true", help="include Pallas paths")
    ap.add_argument("--sharded", action="store_true",
                    help="include the d-sharded gossip round (1 vs 8 "
                         "devices of this process)")
    ap.add_argument("--out", default="")
    ap.add_argument("--bench-json", default="",
                    help="trajectory file to append to (opt-in — "
                         "benchmarks/run.py passes benchmarks/BENCH_agg.json; "
                         "ad-hoc/smoke runs default to not touching the "
                         "committed baseline)")
    args = ap.parse_args(argv)
    rows: List[Dict] = []
    for tok in args.sizes.split(","):
        K, d = (int(x) for x in tok.split("x"))
        rows += bench_rules(K, d)
        if args.kernels:
            rows += bench_kernels(K, min(d, 200_000))
            rows += bench_dynamic(K, min(d, 200_000))
            rows += bench_one_launch(K, min(d, 200_000))
        if args.sharded:
            rows += bench_sharded(K, min(d, 200_000))
    for r in rows:
        passes = f" passes={r['passes']}" if "passes" in r else ""
        comp = (f" compile={r['compile_us'] / 1e3:8.1f} ms"
                if "compile_us" in r else "")
        print(f"{r['rule']:28s} K={r['K']:3d} d={r['d']:8d} "
              f"{r['us_per_call']:10.1f} us  {r['GBps']:7.2f} GB/s{comp}"
              f"  [{r['backend']}]{passes}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    if args.bench_json:
        append_trajectory(rows, args.bench_json)
    return rows


if __name__ == "__main__":
    main()
