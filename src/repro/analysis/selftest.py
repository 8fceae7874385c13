"""Linter self-tests: every rule must FIRE on a doctored fixture.

The ``robustness_gate.py --self-test`` idiom applied to the linter
itself: each rule gets a small fixture with the defect planted — a
materialized (N, K, d) buffer, a bf16 trust downcast, an extra
pallas_call, an oversized / ragged / mis-pinned block, a callback inside
a scan, a data-dependent while — and the self-test asserts the rule
produces an error (or warning) on it AND stays quiet on a clean twin.
A linter whose rules cannot fail is noise; this is the proof they can.

    PYTHONPATH=src python -m repro.analysis --self-test
"""
from __future__ import annotations

from typing import List

from repro.analysis.artifacts import Artifacts
from repro.analysis.rules import (
    EntryPoint,
    Finding,
    RULES_BY_ID,
    gate_failures,
    run_rules,
)


def _entry(name: str, **kw) -> EntryPoint:
    d = dict(name=name, description="self-test fixture",
             build=lambda: (None, ()), expected_launches=0, nkd=(4, 3, 256))
    d.update(kw)
    return EntryPoint(**d)


def _findings(rule_id: str, fn, args, entry: EntryPoint) -> List[Finding]:
    return RULES_BY_ID[rule_id].run(Artifacts(fn, args), entry)


def _fired(rule_id: str, findings: List[Finding], severity: str = "error",
           why: str = "") -> None:
    hits = [f for f in findings if f.rule == rule_id and f.severity == severity]
    if not hits:
        raise SystemExit(
            f"self-test FAILED: rule {rule_id!r} did not fire on its "
            f"doctored fixture ({why}); findings: {findings}")
    print(f"  {rule_id}: fires ({hits[0].message.splitlines()[0][:72]}...)")


def _quiet(rule_id: str, findings: List[Finding], why: str = "") -> None:
    bad = [f for f in findings
           if f.rule == rule_id and f.severity in ("error", "warning")]
    if bad:
        raise SystemExit(
            f"self-test FAILED: rule {rule_id!r} false-positives on a "
            f"clean fixture ({why}): {bad}")


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def test_no_nkd_buffer() -> None:
    jax, jnp = _jnp()
    ep = _entry("nkd")
    # doctored: m[idx] materializes the (4, 3, 256) gossip tensor
    dirty = jax.jit(lambda m, i: m[i].sum(1))
    args = (jnp.ones((6, 256)), jnp.zeros((4, 3), jnp.int32))
    _fired("no-nkd-buffer", _findings("no-nkd-buffer", dirty, args, ep),
           why="planted f32[4,3,256] buffer")
    # clean twin: same math via one-hot matmul, no 3-D buffer
    clean = jax.jit(lambda m, i: jnp.einsum(
        "nkm,md->nd", jax.nn.one_hot(i, m.shape[0], dtype=m.dtype), m))
    _quiet("no-nkd-buffer", _findings("no-nkd-buffer", clean, args, ep),
           why="gather-free twin")
    # the 16K exclusion: an (N, K, K) Gram-sized buffer must NOT trip it
    gram = jax.jit(lambda m, i: m[i][..., :3] @ jnp.swapaxes(m[i][..., :3], -1, -2))
    _quiet("no-nkd-buffer", _findings("no-nkd-buffer", gram,
                                      (jnp.ones((6, 3)), args[1]), ep),
           why="(N, K, K) Gram exclusion")


def test_gather_free_model_dim() -> None:
    jax, jnp = _jnp()
    ep = _entry("gather")
    dirty = jax.jit(lambda m, i: m[i].sum(1))
    args = (jnp.ones((6, 256)), jnp.zeros((4, 3), jnp.int32))
    _fired("gather-free-model-dim",
           _findings("gather-free-model-dim", dirty, args, ep),
           why="gather of d=256 rows")
    # clean twin: a SMALL gather (minibatch indexing) stays legal
    small = jax.jit(lambda m, i: m[i].sum(1))
    sargs = (jnp.ones((6, 8)), jnp.zeros((4, 3), jnp.int32))
    _quiet("gather-free-model-dim",
           _findings("gather-free-model-dim", small, sargs, ep),
           why="small-dim gather exclusion")


def test_launch_count() -> None:
    jax, jnp = _jnp()
    import jax.experimental.pallas as pl

    def launch(x):
        return pl.pallas_call(
            lambda xr, orf: orf.__setitem__(..., xr[...] + 1.0),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    args = (jnp.ones((8, 128)),)
    ep = _entry("launch", expected_launches=1)
    # doctored: a second launch hiding under a scan body
    def two(x):
        y = launch(x)
        z, _ = jax.lax.scan(lambda c, _: (launch(c), None), y, None, length=2)
        return z
    _fired("launch-count",
           _findings("launch-count", jax.jit(two), args, ep),
           why="extra pallas_call under a scan")
    _quiet("launch-count",
           _findings("launch-count", jax.jit(launch), args, ep),
           why="exactly-one launch")


def test_f32_trust_invariant() -> None:
    jax, jnp = _jnp()
    ep = _entry("f32")
    # doctored: (4, 3) trust-sized f32 stat downcast to bf16
    dirty = jax.jit(lambda s: s.astype(jnp.bfloat16).astype(jnp.float32) + 1)
    _fired("f32-trust-invariant",
           _findings("f32-trust-invariant", dirty,
                     (jnp.ones((4, 3), jnp.float32),), ep),
           why="planted bf16 downcast of a (4, 3) statistic")
    # clean twins: f64->f32 is fine; a d-sized payload downcast is the
    # (future) compressed-gossip wire format, not a trust downcast
    wide = jax.jit(lambda s: s.astype(jnp.float32))
    _quiet("f32-trust-invariant",
           _findings("f32-trust-invariant", wide,
                     (jnp.ones((4, 3), jnp.float32),), ep),
           why="no sub-f32 cast")
    payload = jax.jit(lambda s: s.astype(jnp.bfloat16))
    _quiet("f32-trust-invariant",
           _findings("f32-trust-invariant", payload,
                     (jnp.ones((4, 256), jnp.float32),), ep),
           why="model-dim payload exclusion")


def test_no_host_transfer_in_scan() -> None:
    jax, jnp = _jnp()
    ep = _entry("host")

    def dirty(x):
        def body(c, _):
            jax.debug.print("c={c}", c=c.sum())   # host callback in-scan
            return c * 1.01, None
        return jax.lax.scan(body, x, None, length=4)[0]

    args = (jnp.ones((8,)),)
    _fired("no-host-transfer-in-scan",
           _findings("no-host-transfer-in-scan", jax.jit(dirty), args, ep),
           why="debug callback inside the scan while body")

    def clean(x):
        return jax.lax.scan(lambda c, _: (c * 1.01, None), x, None,
                            length=4)[0]
    _quiet("no-host-transfer-in-scan",
           _findings("no-host-transfer-in-scan", jax.jit(clean), args, ep),
           why="pure scan")


def test_vmem_budget() -> None:
    jax, jnp = _jnp()
    import jax.experimental.pallas as pl

    def kernel(xr, orf):
        orf[...] = xr[...] * 2.0

    # doctored 1: block bigger than a tiny ceiling
    def big(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(2,),
            in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
            interpret=True)(x)
    args = (jnp.ones((128, 128)),)
    ep_small = _entry("vmem", vmem_ceiling=1024)
    _fired("vmem-budget", _findings("vmem-budget", jax.jit(big), args,
                                    ep_small),
           why="oversized block vs 1 KiB ceiling")

    # doctored 2: ragged block (64 does not divide 100)
    def ragged(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(2,),
            in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
            interpret=True)(x)
    rargs = (jnp.ones((100, 128)),)
    _fired("vmem-budget", _findings("vmem-budget", jax.jit(ragged), rargs,
                                    _entry("vmem-ragged")),
           why="block shape does not divide array shape")

    # doctored 3: mis-pinned index map walks out of range (i+1, not i)
    def mispinned(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(2,),
            in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (i + 1, 0)),
            interpret=True)(x)
    _fired("vmem-budget", _findings("vmem-budget", jax.jit(mispinned), args,
                                    _entry("vmem-pin")),
           why="index map out of range at the last grid step")

    # clean twin under the default ceiling
    fs = _findings("vmem-budget", jax.jit(big), args, _entry("vmem-ok"))
    _quiet("vmem-budget", fs, why="64 KiB blocks under a 16 MiB ceiling")
    if not any(f.severity == "info" for f in fs):
        raise SystemExit("self-test FAILED: vmem-budget emitted no "
                         "residency info record on the clean fixture")


def test_compile_once() -> None:
    jax, jnp = _jnp()
    art = Artifacts(jax.jit(lambda x: x), (jnp.ones((2,)),))
    _fired("compile-once",
           RULES_BY_ID["compile-once"].run(
               art, _entry("retrace", compile_once=lambda: 3)),
           why="probe reporting a 3-entry trace cache")
    _quiet("compile-once",
           RULES_BY_ID["compile-once"].run(
               art, _entry("once", compile_once=lambda: 1)),
           why="cache size 1")


def test_memory_passes() -> None:
    jax, jnp = _jnp()
    from repro.core.wfagg import WFAggConfig
    art = Artifacts(jax.jit(lambda x: x), (jnp.ones((2,)),))
    # doctored: ceiling 0 — the real accounting (>= 1 pass) must trip it
    _fired("memory-passes",
           RULES_BY_ID["memory-passes"].run(
               art, _entry("passes", passes=(
                   ("doctored zero-pass ceiling", WFAggConfig(),
                    dict(include_gather=True, indexed=True), 0),))),
           why="documented-table regression")
    _quiet("memory-passes",
           RULES_BY_ID["memory-passes"].run(
               art, _entry("passes-ok", passes=(
                   ("single-launch pin", WFAggConfig(),
                    dict(include_gather=True, indexed=True), 2),))),
           why="table row within ceiling")


def test_unknown_trip_count() -> None:
    jax, jnp = _jnp()
    ep = _entry("trip")

    def dirty(x):
        return jax.lax.while_loop(lambda c: c[0] < c[1],
                                  lambda c: (c[0] + 1.0, c[1]),
                                  (x, 10.0))[0]
    _fired("unknown-trip-count",
           _findings("unknown-trip-count", jax.jit(dirty),
                     (jnp.float32(0),), ep),
           severity="warning", why="data-dependent while loop")

    def clean(x):
        return jax.lax.scan(lambda c, _: (c * 1.01, None), x, None,
                            length=4)[0]
    _quiet("unknown-trip-count",
           _findings("unknown-trip-count", jax.jit(clean),
                     (jnp.ones((8,)),), ep),
           why="scan carries known_trip_count")


def test_dead_computation() -> None:
    # handcrafted module: %orphan is referenced by nothing
    hlo = """\
HloModule doctored_dead

%orphan (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4] parameter(0)
  ROOT %neg = f32[4] negate(f32[4] %p.1)
}

ENTRY %main (p.0: f32[4]) -> f32[4] {
  %p.0 = f32[4] parameter(0)
  ROOT %out = f32[4] add(f32[4] %p.0, f32[4] %p.0)
}
"""
    ep = _entry("dead")
    _fired("dead-computation",
           RULES_BY_ID["dead-computation"].run(Artifacts.from_hlo(hlo), ep),
           severity="info", why="orphan computation in a doctored module")


# ---------------------------------------------------------------------------
# SPMD communication-contract fixtures: handcrafted sharded modules (the
# jaxpr layer is empty via Artifacts.from_hlo, which is all these hlo-
# layer rules need — they run on a 1-device box; the REAL sharded
# artifacts are linted in tests/test_spmd_analysis.py on 8 virtual
# devices)
# ---------------------------------------------------------------------------

_SPMD_NKD = (10, 4, 50896)          # the sharded entries' padded triple


def _spmd_contract(rounds: int = 1):
    from repro.analysis.collectives import wfagg_round_contract
    return wfagg_round_contract(n=10, k=4, n_shards=8, rounds=rounds)


def _spmd_entry(name: str, **kw):
    return _entry(name, nkd=_SPMD_NKD, contract=_spmd_contract(), **kw)


_SPMD_SUM = """\
%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}
"""

# the contract-conforming twin: ONE O(N*K) psum over the full mesh
_SPMD_CLEAN_HLO = f"""\
HloModule doctored_spmd_clean, num_partitions=8

{_SPMD_SUM}
ENTRY %main (p.0: f32[10,4]) -> f32[10,4] {{
  %p.0 = f32[10,4] parameter(0)
  ROOT %ar = f32[10,4] all-reduce(f32[10,4] %p.0), channel_id=1, replica_groups={{{{0,1,2,3,4,5,6,7}}}}, use_global_device_ids=true, to_apply=%sum
}}
"""


def _spmd_art(body: str, header: str = "num_partitions=8") -> Artifacts:
    return Artifacts.from_hlo(
        f"HloModule doctored_spmd, {header}\n\n{_SPMD_SUM}\n{body}")


def test_spmd_collective_contract() -> None:
    ep = _spmd_entry("spmd-contract")
    # doctored 1: a replicated candidate matrix forces GSPMD to insert
    # the full-d all-gather — a kind the contract never allows
    dirty_kind = _spmd_art("""\
ENTRY %main (p.0: f32[10,6362]) -> f32[10,50896] {
  %p.0 = f32[10,6362] parameter(0)
  ROOT %ag = f32[10,50896] all-gather(f32[10,6362] %p.0), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={1}, use_global_device_ids=true
}
""")
    _fired("spmd-collective-contract",
           RULES_BY_ID["spmd-collective-contract"].run(dirty_kind, ep),
           why="all-gather where the contract allows all-reduce only")
    # doctored 2: an allowed kind but a model-dim-sized payload
    dirty_size = _spmd_art("""\
ENTRY %main (p.0: f32[10,4,128]) -> f32[10,4,128] {
  %p.0 = f32[10,4,128] parameter(0)
  ROOT %ar = f32[10,4,128] all-reduce(f32[10,4,128] %p.0), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, to_apply=%sum
}
""")
    _fired("spmd-collective-contract",
           RULES_BY_ID["spmd-collective-contract"].run(dirty_size, ep),
           why="all-reduce payload over the O(N*K) ceiling")
    _quiet("spmd-collective-contract",
           RULES_BY_ID["spmd-collective-contract"].run(
               Artifacts.from_hlo(_SPMD_CLEAN_HLO), ep),
           why="one O(N*K) psum over the full mesh")


def test_spmd_model_dim_allgather() -> None:
    ep = _spmd_entry("spmd-allgather")
    dirty = _spmd_art("""\
ENTRY %main (p.0: f32[10,6362]) -> f32[10,50896] {
  %p.0 = f32[10,6362] parameter(0)
  ROOT %ag = f32[10,50896] all-gather(f32[10,6362] %p.0), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={1}, use_global_device_ids=true
}
""")
    _fired("spmd-model-dim-allgather",
           RULES_BY_ID["spmd-model-dim-allgather"].run(dirty, ep),
           why="boundary all-gather rebuilding the full-d matrix")
    _quiet("spmd-model-dim-allgather",
           RULES_BY_ID["spmd-model-dim-allgather"].run(
               Artifacts.from_hlo(_SPMD_CLEAN_HLO), ep),
           why="psum-only module")


def test_spmd_replica_groups() -> None:
    ep = _spmd_entry("spmd-groups")
    # doctored 1: singleton groups — a dead collective
    singleton = _spmd_art("""\
ENTRY %main (p.0: f32[10,4]) -> f32[10,4] {
  %p.0 = f32[10,4] parameter(0)
  ROOT %ar = f32[10,4] all-reduce(f32[10,4] %p.0), channel_id=1, replica_groups={{0},{1},{2},{3},{4},{5},{6},{7}}, use_global_device_ids=true, to_apply=%sum
}
""")
    _fired("spmd-replica-groups",
           RULES_BY_ID["spmd-replica-groups"].run(singleton, ep),
           why="singleton replica groups")
    # doctored 2: half-mesh groups — the other shards keep partial stats
    partial = _spmd_art("""\
ENTRY %main (p.0: f32[10,4]) -> f32[10,4] {
  %p.0 = f32[10,4] parameter(0)
  ROOT %ar = f32[10,4] all-reduce(f32[10,4] %p.0), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%sum
}
""")
    _fired("spmd-replica-groups",
           RULES_BY_ID["spmd-replica-groups"].run(partial, ep),
           why="replica groups cover half the mesh")
    # doctored 3: module not actually partitioned
    unsharded = _spmd_art("""\
ENTRY %main (p.0: f32[10,4]) -> f32[10,4] {
  %p.0 = f32[10,4] parameter(0)
  ROOT %neg = f32[10,4] negate(f32[10,4] %p.0)
}
""", header="num_partitions=1")
    _fired("spmd-replica-groups",
           RULES_BY_ID["spmd-replica-groups"].run(unsharded, ep),
           why="num_partitions=1 against an 8-shard contract")
    _quiet("spmd-replica-groups",
           RULES_BY_ID["spmd-replica-groups"].run(
               Artifacts.from_hlo(_SPMD_CLEAN_HLO), ep),
           why="full-mesh groups")


def test_spmd_wire_budget() -> None:
    ep = _spmd_entry("spmd-wire")
    # doctored: the contract prices ONE round, but the psum sits in a
    # while body the compiler multiplies 1000x
    dirty = _spmd_art("""\
%cond (c.1: (s32[], f32[10,4])) -> pred[] {
  %c.1 = (s32[], f32[10,4]) parameter(0)
  %i.1 = s32[] get-tuple-element((s32[], f32[10,4]) %c.1), index=0
  %lim = s32[] constant(1000)
  ROOT %lt = pred[] compare(s32[] %i.1, s32[] %lim), direction=LT
}

%body (c.0: (s32[], f32[10,4])) -> (s32[], f32[10,4]) {
  %c.0 = (s32[], f32[10,4]) parameter(0)
  %i.0 = s32[] get-tuple-element((s32[], f32[10,4]) %c.0), index=0
  %x.0 = f32[10,4] get-tuple-element((s32[], f32[10,4]) %c.0), index=1
  %one = s32[] constant(1)
  %ip = s32[] add(s32[] %i.0, s32[] %one)
  %ar = f32[10,4] all-reduce(f32[10,4] %x.0), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, to_apply=%sum
  ROOT %t = (s32[], f32[10,4]) tuple(s32[] %ip, f32[10,4] %ar)
}

ENTRY %main (p.0: f32[10,4]) -> (s32[], f32[10,4]) {
  %p.0 = f32[10,4] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[10,4]) tuple(s32[] %zero, f32[10,4] %p.0)
  ROOT %w = (s32[], f32[10,4]) while((s32[], f32[10,4]) %init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"1000"}}
}
""")
    _fired("spmd-wire-budget",
           RULES_BY_ID["spmd-wire-budget"].run(dirty, ep),
           why="psum multiplied 1000x into a loop body")
    clean_fs = RULES_BY_ID["spmd-wire-budget"].run(
        Artifacts.from_hlo(_SPMD_CLEAN_HLO), ep)
    _quiet("spmd-wire-budget", clean_fs, why="one psum within budget")
    if not any(f.severity == "info" for f in clean_fs):
        raise SystemExit("self-test FAILED: spmd-wire-budget emitted no "
                         "utilization info record on the clean fixture")


def test_spmd_sharded_nkd_buffer() -> None:
    ep = _spmd_entry("spmd-nkd")
    # doctored: the per-shard (N, K, d/S) gossip tensor re-materialized
    dirty = _spmd_art("""\
ENTRY %main (p.0: f32[10,4]) -> f32[10,4,6362] {
  %p.0 = f32[10,4] parameter(0)
  ROOT %big = f32[10,4,6362] broadcast(f32[10,4] %p.0), dimensions={0,1}
}
""")
    _fired("spmd-sharded-nkd-buffer",
           RULES_BY_ID["spmd-sharded-nkd-buffer"].run(dirty, ep),
           why="per-shard (10, 4, 6362) gossip tensor")
    # the threshold scales with d/S: a (N, K, K)-sized Gram stays legal
    gram = _spmd_art("""\
ENTRY %main (p.0: f32[10,4]) -> f32[10,4,4] {
  %p.0 = f32[10,4] parameter(0)
  ROOT %g = f32[10,4,4] broadcast(f32[10,4] %p.0), dimensions={0,1}
}
""")
    _quiet("spmd-sharded-nkd-buffer",
           RULES_BY_ID["spmd-sharded-nkd-buffer"].run(gram, ep),
           why="O(K^2) Gram exclusion")
    _quiet("spmd-sharded-nkd-buffer",
           RULES_BY_ID["spmd-sharded-nkd-buffer"].run(
               Artifacts.from_hlo(_SPMD_CLEAN_HLO), ep),
           why="no 3-D buffer at all")


def test_suppression_mechanism() -> None:
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda m, i: m[i].sum(1))
    args = (jnp.ones((6, 256)), jnp.zeros((4, 3), jnp.int32))
    ep = _entry("sup", suppress=frozenset({"no-nkd-buffer",
                                           "gather-free-model-dim"}))
    fs = run_rules(Artifacts(fn, args), ep)
    sup = [f for f in fs if f.suppressed]
    if not sup:
        raise SystemExit("self-test FAILED: entry-level suppression "
                         "produced no suppressed findings")
    if gate_failures(fs):
        raise SystemExit("self-test FAILED: suppressed findings still "
                         f"fail the gate: {gate_failures(fs)}")
    print(f"  suppression: {len(sup)} finding(s) kept but gated out")


def main() -> None:
    tests = [
        test_no_nkd_buffer, test_gather_free_model_dim, test_launch_count,
        test_f32_trust_invariant, test_no_host_transfer_in_scan,
        test_vmem_budget, test_compile_once, test_memory_passes,
        test_unknown_trip_count, test_dead_computation,
        test_spmd_collective_contract, test_spmd_model_dim_allgather,
        test_spmd_replica_groups, test_spmd_wire_budget,
        test_spmd_sharded_nkd_buffer,
        test_suppression_mechanism,
    ]
    print("repro.analysis self-test: every rule must fire on its doctored "
          "fixture")
    for t in tests:
        t()
    print("repro.analysis self-test: OK")


if __name__ == "__main__":
    main()
