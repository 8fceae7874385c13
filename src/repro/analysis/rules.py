"""Rule framework + the core structural rules.

Every rule is a small dataclass: an id, a severity, the artifact layer
it inspects (``jaxpr`` / ``hlo`` / ``pallas`` / ``runtime`` / ``config``)
and a check function returning :class:`Finding`\\ s.  Rules encode the
repo's compiled-computation claims — gather-free gossip, no (N, K, d)
materialization, 2 candidate passes per round, compile-once dynamic
schedules, f32 trust arithmetic, bounded VMEM — as machine-checked
properties instead of ad-hoc HLO greps copy-pasted across test files.

Suppression: an entry point declares ``suppress={rule_id, ...}`` for
properties it intentionally violates (the reference oracle materializes
the gather — that is its job), and the CLI accepts extra
``--suppress rule-id[@entry]`` pins.  Suppressed findings are still
reported (``suppressed: true`` in the JSON) but never fail the gate.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.artifacts import Artifacts, count_pallas_calls, walk_eqns

SEVERITIES = ("error", "warning", "info")

# dtypes the f32-trust-invariant refuses for trust/temporal arithmetic
_SUB_F32 = ("bfloat16", "float16", "float8_e4m3fn", "float8_e5m2",
            "float8_e4m3b11fnuz", "float8_e4m3fnuz", "float8_e5m2fnuz")

# HLO custom-call targets that move data to the host (Python callbacks)
_HOST_CALLBACK_TARGETS = ("xla_python_cpu_callback", "xla_ffi_python_cpu_callback",
                          "xla_python_gpu_callback", "tpu_py_callback")
_HOST_TRANSFER_OPS = ("infeed", "outfeed", "send", "recv",
                      "send-done", "recv-done")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    severity: str
    entry: str
    message: str
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)
    suppressed: bool = False

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Rule:
    """One static-analysis rule.

    ``check(artifacts, entry)`` returns the findings; ``entry`` is the
    registered :class:`EntryPoint` (rules read its pinned expectations —
    launch count, (N, K, d) triple, VMEM ceiling)."""
    id: str
    severity: str
    layer: str          # jaxpr | hlo | pallas | runtime | config
    description: str
    check: Callable[[Artifacts, "EntryPoint"], List[Finding]]

    def run(self, artifacts: Artifacts, entry: "EntryPoint") -> List[Finding]:
        return self.check(artifacts, entry)


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """A registered lint target.

    ``build()`` returns ``(fn, args)`` — the jitted callable plus example
    arguments.  New subsystems (shard_map rounds, compressed gossip)
    inherit the full gate by registering an entry here; see
    docs/STATIC_ANALYSIS.md for the two-line recipe."""
    name: str
    description: str
    build: Callable[[], Tuple[Callable, Tuple]]
    expected_launches: int
    nkd: Tuple[int, int, int]            # (N, K, d) of the gossip round
    suppress: frozenset = frozenset()
    vmem_ceiling: int = 16 * 1024 * 1024             # ~16 MB/core VMEM
    compile_once: Optional[Callable[[], int]] = None  # -> trace-cache size
    # memory_passes pins: rows of (desc, WFAggConfig, kwargs, ceiling) —
    # the absorbed scripts/passes_gate.py table, distributed over the
    # entries each row describes
    passes: Tuple[Tuple[str, Any, Dict[str, Any], int], ...] = ()
    # sharded entries: the declared communication contract (collectives.
    # CommContract) the spmd-* rule family enforces, and the device count
    # build() needs — the CLI records a skip instead of building when
    # fewer devices are visible (virtual CPU devices count)
    contract: Optional[Any] = None
    min_devices: int = 1


# ---------------------------------------------------------------------------
# HLO text helpers (the shared forms of the old per-test greps)
# ---------------------------------------------------------------------------

def scan_nkd_buffers(hlo: str, n: int, k: int, min_d: int = 0,
                     dtype: str = "f32") -> List[int]:
    """All ``d`` for which a ``dtype[n, k, d]`` buffer (d > min_d)
    appears anywhere in the HLO module — while bodies included, since the
    module text prints every computation.  ``min_d=0`` is the strict
    form; the one-launch round passes ``min_d=16*k`` so the legitimate
    O(K²) Alt-WFAgg Gram ((N, K, K)) is not mistaken for a gossip
    tensor."""
    pat = re.compile(rf"{re.escape(dtype)}\[{n},{k},(\d+)\]")
    return sorted({int(m) for m in pat.findall(hlo) if int(m) > min_d})


def scan_gather_model_dim(hlo: str, min_d: int) -> List[str]:
    """Lines where a ``gather``/``scatter`` instruction touches a
    model-dim-sized operand (any output dimension >= ``min_d``).  Small
    gathers (minibatch indexing, neighbor-table lookups) pass; a K-fold
    gossip gather of d-sized rows does not."""
    hits = []
    shape_re = re.compile(r"[a-z][a-z0-9]*\[([0-9,]*)\]")
    for line in hlo.splitlines():
        if not re.search(r"\b(gather|scatter)\(", line):
            continue
        dims = []
        for tok in shape_re.findall(line):
            dims += [int(x) for x in tok.split(",") if x.strip()]
        if dims and max(dims) >= min_d:
            hits.append(line.strip()[:160])
    return hits


def _hlo_call_graph(hlo: str):
    """(computations, entry, edges, while_bodies) from the module text —
    a thin re-use of launch.hlo_analysis's splitter."""
    from repro.launch import hlo_analysis as ha
    comps, entry = ha._split_computations(hlo)
    edges: Dict[str, List[str]] = {c: [] for c in comps}
    while_roots: List[str] = []
    for cname, lines in comps.items():
        for line in lines:
            for m in ha._BODY_RE.finditer(line):
                edges[cname].append(m.group(1))
                while_roots.append(m.group(1))
            for m in ha._COND_RE.finditer(line):
                edges[cname].append(m.group(1))
            for m in ha._CALLS_RE.finditer(line):
                edges[cname].append(m.group(1))
            for m in ha._TO_APPLY_RE.finditer(line):
                edges[cname].append(m.group(1))
            for m in ha._CALLED_COMPS_RE.finditer(line):
                edges[cname] += [b.strip().lstrip("%")
                                 for b in m.group(1).split(",") if b.strip()]
            for m in ha._TRUE_FALSE_RE.finditer(line):
                edges[cname].append(m.group(1))
            m = ha._BRANCHES_RE.search(line)
            if m:
                edges[cname] += [b.strip().lstrip("%")
                                 for b in m.group(1).split(",") if b.strip()]
    return comps, entry, edges, while_roots


def scan_host_transfers_in_while(hlo: str) -> List[Tuple[str, str]]:
    """(computation, line) pairs for host transfers — infeed/outfeed/
    send/recv or Python-callback custom-calls — inside any computation
    reachable from a ``while`` body."""
    comps, _, edges, while_roots = _hlo_call_graph(hlo)
    reachable: set = set()
    stack = list(while_roots)
    while stack:
        c = stack.pop()
        if c in reachable:
            continue
        reachable.add(c)
        stack += edges.get(c, [])
    hits = []
    op_re = re.compile(r"=\s*\(?[\w\[\],{}<> ]*?\)?\s*(" +
                       "|".join(_HOST_TRANSFER_OPS) + r")\(")
    for cname in reachable:
        for line in comps.get(cname, []):
            if op_re.search(line):
                hits.append((cname, line.strip()[:160]))
            elif "custom-call" in line and any(
                    t in line for t in _HOST_CALLBACK_TARGETS):
                hits.append((cname, line.strip()[:160]))
    return hits


# ---------------------------------------------------------------------------
# rule checks
# ---------------------------------------------------------------------------

def _check_nkd(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    n, k, _ = entry.nkd
    hits = scan_nkd_buffers(artifacts.hlo, n, k, min_d=16 * k)
    return [Finding(
        "no-nkd-buffer", "error", entry.name,
        f"(N={n}, K={k}, d)-shaped f32 buffer(s) materialized: d={hits} — "
        "the K-fold gossip tensor must never exist in HBM",
        {"d_values": hits})] if hits else []


def _check_gather(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    _, k, d = entry.nkd
    min_d = max(16 * k + 1, d // 2)
    hits = scan_gather_model_dim(artifacts.hlo, min_d)
    return [Finding(
        "gather-free-model-dim", "error", entry.name,
        f"{len(hits)} gather/scatter op(s) touch a model-dim-sized "
        f"(>= {min_d}) operand — the indexed path must DMA neighbor "
        "blocks, never gather them",
        {"lines": hits[:8]})] if hits else []


def _check_launch_count(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    got = count_pallas_calls(artifacts.jaxpr.jaxpr)
    if got == entry.expected_launches:
        return []
    return [Finding(
        "launch-count", "error", entry.name,
        f"{got} pallas_call eqn(s) traced, pinned {entry.expected_launches} "
        "— a launch regression (single-launch falling back to two) or an "
        "unregistered new kernel",
        {"got": got, "expected": entry.expected_launches})]


def _check_f32_trust(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    """Temporal metrics and trust scores are O(K)-sized; model payloads
    are d-sized.  Any f32 -> sub-f32 convert of a NON-model-dim buffer is
    a trust-arithmetic downcast (d-sized downcasts are the province of a
    future compressed-gossip wire format and stay legal)."""
    _, _, d = entry.nkd
    findings = []
    for eqn in walk_eqns(artifacts.jaxpr.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = np.dtype(eqn.params.get("new_dtype"))
        if new.name not in _SUB_F32:
            continue
        src = eqn.invars[0].aval
        if np.dtype(src.dtype) != np.dtype(np.float32):
            continue
        size = int(np.prod(src.shape)) if src.shape else 1
        if size >= max(d // 2, 1):
            continue                      # model-dim payload: allowed
        findings.append(Finding(
            "f32-trust-invariant", "error", entry.name,
            f"f32 -> {new.name} downcast of a trust/temporal-sized buffer "
            f"{tuple(src.shape)} — filter statistics must stay f32",
            {"shape": list(src.shape), "dtype": new.name}))
    return findings


def _check_host_transfer(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    hits = scan_host_transfers_in_while(artifacts.hlo)
    return [Finding(
        "no-host-transfer-in-scan", "error", entry.name,
        f"{len(hits)} device->host transfer(s)/callback(s) inside a while "
        "body — the round scan must stay on-device",
        {"hits": [f"{c}: {l}" for c, l in hits[:8]]})] if hits else []


def _eval_index_map(ij, coords, smem_shapes) -> Optional[Tuple[int, ...]]:
    """Evaluate a BlockSpec index-map jaxpr at integer grid ``coords``.
    SMEM scalar-prefetch refs are fed zero tables (block index 0 is
    always in range), so pure-grid arithmetic — the pinning expressions
    like ``i * p`` — is what gets validated."""
    from jax.extend.core import ClosedJaxpr, jaxpr_as_fun
    args = [np.int32(c) for c in coords]
    args += [np.zeros(s, np.int32) for s in smem_shapes]
    try:
        out = jaxpr_as_fun(ClosedJaxpr(ij.jaxpr, ij.consts))(*args)
    except Exception:
        return None
    return tuple(int(o) for o in out)


def _check_vmem(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    findings = []
    for info in artifacts.pallas_calls:
        vmem = info.vmem_bytes()
        detail = {
            "kernel": info.name, "grid": list(info.grid),
            "block_bytes": info.block_bytes,
            "scratch_bytes": info.scratch_bytes,
            "vmem_bytes": vmem, "ceiling": entry.vmem_ceiling,
        }
        if vmem > entry.vmem_ceiling:
            findings.append(Finding(
                "vmem-budget", "error", entry.name,
                f"kernel {info.name!r}: modelled per-grid-step VMEM "
                f"residency {vmem / 2**20:.1f} MiB exceeds the "
                f"{entry.vmem_ceiling / 2**20:.0f} MiB ceiling "
                "(2x double-buffered blocks + scratch)", detail))
        # divisibility: a block dim that does not divide its (padded)
        # array dim silently reads ragged tails
        for b in info.blocks:
            bs, ash = b.block_shape, b.array_shape
            if len(bs) != len(ash):
                continue
            ragged = [(x, y) for x, y in zip(ash, bs) if y and x % y != 0]
            if ragged:
                findings.append(Finding(
                    "vmem-budget", "error", entry.name,
                    f"kernel {info.name!r} operand {b.origin}: block shape "
                    f"{bs} does not divide array shape {ash} — the ops "
                    "wrappers must pad D to the block size",
                    {"kernel": info.name, "origin": b.origin,
                     "block_shape": list(bs), "array_shape": list(ash)}))
        # pinned-index-map validation: every evaluated block index must
        # stay inside the array across the whole grid (catches a broken
        # pin like `i + p` walking the output out of range in phase 1)
        smem_shapes = []  # scalar-prefetch aval shapes, from any block's map
        for b in info.blocks:
            extra = len(b.index_map_jaxpr.in_avals) - len(info.grid)
            if extra > 0:
                smem_shapes = [tuple(a.shape)
                               for a in b.index_map_jaxpr.in_avals[-extra:]]
                break
        coords_list = _grid_sample(info.grid)
        for b in info.blocks:
            if len(b.block_shape) != len(b.array_shape):
                continue
            nblocks = [max(1, -(-x // y)) if y else 1
                       for x, y in zip(b.array_shape, b.block_shape)]
            for coords in coords_list:
                idx = _eval_index_map(b.index_map_jaxpr, coords, smem_shapes)
                if idx is None or len(idx) != len(nblocks):
                    continue
                if any(i < 0 or i >= nb for i, nb in zip(idx, nblocks)):
                    findings.append(Finding(
                        "vmem-budget", "error", entry.name,
                        f"kernel {info.name!r} operand {b.origin}: index map "
                        f"returns block {idx} at grid {coords} but the array "
                        f"only has {nblocks} blocks",
                        {"kernel": info.name, "origin": b.origin,
                         "grid_coords": list(coords), "block_idx": list(idx)}))
                    break
        findings.append(Finding(
            "vmem-budget", "info", entry.name,
            f"kernel {info.name!r}: {vmem / 2**20:.2f} MiB/step of "
            f"{entry.vmem_ceiling / 2**20:.0f} MiB "
            f"({100.0 * vmem / entry.vmem_ceiling:.0f}%)", detail))
    return findings


def _grid_sample(grid: Tuple[int, ...], cap: int = 512) -> List[Tuple[int, ...]]:
    """All grid points when small, otherwise the corners of each axis
    plus a deterministic stride sample."""
    total = int(np.prod(grid)) if grid else 0
    if total == 0:
        return []
    if total <= cap:
        pts = np.indices(grid).reshape(len(grid), -1).T
        return [tuple(int(x) for x in p) for p in pts]
    # corner sample: first/last block of every axis, others at 0 and max
    axes = [(0, g - 1) if g > 1 else (0,) for g in grid]
    import itertools
    return [tuple(p) for p in itertools.product(*axes)][:cap]


def _check_compile_once(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    if entry.compile_once is None:
        return []
    size = int(entry.compile_once())
    if size == 1:
        return []
    return [Finding(
        "compile-once", "error", entry.name,
        f"trace cache holds {size} executables after a round-varying "
        "schedule — the dynamic round retraced per graph",
        {"cache_size": size})]


def _check_memory_passes(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    if not entry.passes:
        return []
    from repro.core.wfagg import memory_passes
    findings = []
    for desc, cfg, kwargs, ceiling in entry.passes:
        got = memory_passes(cfg, **kwargs)
        if got <= ceiling:
            findings.append(Finding(
                "memory-passes", "info", entry.name,
                f"{desc}: memory_passes = {got} (ceiling {ceiling})",
                {"desc": desc, "got": got, "ceiling": ceiling}))
        else:
            findings.append(Finding(
                "memory-passes", "error", entry.name,
                f"{desc}: memory_passes regressed to {got} (documented "
                f"ceiling {ceiling})",
                {"desc": desc, "got": got, "ceiling": ceiling}))
    return findings


def _check_unknown_trip(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    from repro.launch import hlo_analysis as ha
    cost = ha.analyze(artifacts.hlo, n_devices=1)
    findings = []
    if cost.unknown_trip_whiles:
        findings.append(Finding(
            "unknown-trip-count", "warning", entry.name,
            f"{cost.unknown_trip_whiles} while loop(s) without "
            "known_trip_count — the roofline model multiplies their "
            "bodies by 1, under-reporting cost",
            {"unknown_trip_whiles": cost.unknown_trip_whiles,
             "trip_counts": cost.trip_counts[:16]}))
    return findings


def _check_dead_computation(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    from repro.launch import hlo_analysis as ha
    cost = ha.analyze(artifacts.hlo, n_devices=1)
    dead = getattr(cost, "dead_computations", []) or []
    if not dead:
        return []
    return [Finding(
        "dead-computation", "info", entry.name,
        f"{len(dead)} computation(s) unreachable from the entry — dead "
        "code the compiler kept (or a call-graph edge the analyzer "
        "missed)", {"computations": dead[:16]})]


# ---------------------------------------------------------------------------
# SPMD communication-contract rules (entries with entry.contract set)
# ---------------------------------------------------------------------------
#
# These read the per-collective records hlo_analysis parses out of the
# sharded optimized HLO (kind, payload bytes, replica groups, trip-count
# multiplier) and hold them against the entry's declared CommContract:
# under a D-sharded mesh the only cross-shard traffic the WFAgg round
# may emit is the O(N*K) statistic psum — never a model-dim gather.

def _contract_records(artifacts: Artifacts, entry: EntryPoint):
    from repro.analysis.collectives import contract_cost
    cost = contract_cost(artifacts, entry.contract.axis_size)
    return cost, (cost.collectives or [])


def _check_spmd_contract(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    if entry.contract is None:
        return []
    ct = entry.contract
    _, colls = _contract_records(artifacts, entry)
    findings = []
    for r in colls:
        if r.kind not in ct.allowed_kinds:
            findings.append(Finding(
                "spmd-collective-contract", "error", entry.name,
                f"{r.kind} {r.name!r} ({r.out_bytes} B) — contract allows "
                f"only {ct.allowed_kinds}: GSPMD inserted cross-shard "
                "traffic the sharded round never declared",
                {"collective": r.to_dict(), "allowed": list(ct.allowed_kinds)}))
        elif r.out_bytes > ct.max_collective_bytes:
            findings.append(Finding(
                "spmd-collective-contract", "error", entry.name,
                f"{r.kind} {r.name!r} moves {r.out_bytes} B, over the "
                f"{ct.max_collective_bytes} B per-collective ceiling — the "
                "trust-weight reduction is O(N*K); anything bigger is "
                "model-dim payload on the wire",
                {"collective": r.to_dict(),
                 "ceiling": ct.max_collective_bytes}))
    return findings


def _check_spmd_allgather(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    if entry.contract is None:
        return []
    ct = entry.contract
    _, _, d = entry.nkd
    # half of one model row's SHARD: generous against O(N*K) psums, far
    # below any d-sized buffer a boundary all-gather would rebuild
    min_b = 4 * max(d // max(1, ct.axis_size), 1) // 2
    findings = []
    for r in _contract_records(artifacts, entry)[1]:
        if r.kind in ("all-gather", "all-to-all") and r.out_bytes >= min_b:
            findings.append(Finding(
                "spmd-model-dim-allgather", "error", entry.name,
                f"{r.kind} {r.name!r} rebuilds {r.out_bytes} B of model-dim "
                f"payload (>= {min_b} B) — a sharded array met a replicated "
                "consumer and GSPMD un-sharded it; keep (N, d) buffers "
                "P(None, 'model') end to end",
                {"collective": r.to_dict(), "min_bytes": min_b}))
    return findings


def _check_spmd_replica_groups(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    if entry.contract is None:
        return []
    ct = entry.contract
    cost, colls = _contract_records(artifacts, entry)
    findings = []
    if cost.num_partitions != ct.axis_size:
        findings.append(Finding(
            "spmd-replica-groups", "error", entry.name,
            f"module compiled with num_partitions={cost.num_partitions}, "
            f"contract declares a {ct.axis_size}-shard mesh — the entry "
            "is not actually sharding d",
            {"num_partitions": cost.num_partitions,
             "axis_size": ct.axis_size}))
    for r in colls:
        if r.group_size <= 1:
            findings.append(Finding(
                "spmd-replica-groups", "error", entry.name,
                f"{r.kind} {r.name!r} has singleton replica groups — a "
                "dead collective (reduces nothing, still synchronizes)",
                {"collective": r.to_dict()}))
            continue
        if r.covers_mesh(ct.axis_size) is False:
            findings.append(Finding(
                "spmd-replica-groups", "error", entry.name,
                f"{r.kind} {r.name!r} replica groups cover only "
                f"{sorted(r.participants())} of the {ct.axis_size}-device "
                "mesh — shards outside the group keep PARTIAL statistics "
                "and the filters diverge per shard",
                {"collective": r.to_dict()}))
    return findings


def _check_spmd_wire_budget(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    if entry.contract is None:
        return []
    ct = entry.contract
    _, colls = _contract_records(artifacts, entry)
    total = sum(r.mult * r.wire_bytes for r in colls)
    by_kind: Dict[str, float] = {}
    for r in colls:
        by_kind[r.kind] = by_kind.get(r.kind, 0.0) + r.mult * r.wire_bytes
    detail = {"wire_bytes": total, "budget": ct.wire_budget_bytes,
              "by_kind": by_kind, "n_collectives": len(colls)}
    if total > ct.wire_budget_bytes:
        return [Finding(
            "spmd-wire-budget", "error", entry.name,
            f"trip-count-aware per-device wire {total:.4g} B exceeds the "
            f"contract budget {ct.wire_budget_bytes:.4g} B — a collective "
            "multiplied into a loop body, or payloads grew past O(N*K)",
            detail)]
    return [Finding(
        "spmd-wire-budget", "info", entry.name,
        f"per-device wire {total:.4g} B of {ct.wire_budget_bytes:.4g} B "
        f"budget ({100.0 * total / max(ct.wire_budget_bytes, 1e-9):.0f}%)",
        detail)]


def _check_spmd_nkd(artifacts: Artifacts, entry: EntryPoint) -> List[Finding]:
    if entry.contract is None:
        return []
    n, k, d = entry.nkd
    d_shard = max(1, d // max(1, entry.contract.axis_size))
    min_d = max(16 * k, d_shard // 4)
    hits = scan_nkd_buffers(artifacts.hlo, n, k, min_d=min_d)
    return [Finding(
        "spmd-sharded-nkd-buffer", "error", entry.name,
        f"per-shard (N={n}, K={k}, d/S)-sized f32 buffer(s): d={hits} — "
        "the gossip tensor re-materialized inside the shard (the indexed "
        "kernels must DMA neighbor shards, never stack them)",
        {"d_values": hits, "min_d": min_d})] if hits else []


RULES: Tuple[Rule, ...] = (
    Rule("no-nkd-buffer", "error", "hlo",
         "No (N, K, d)-shaped f32 intermediate anywhere in the module, "
         "while bodies included (d > 16K excludes the O(K^2) Gram).",
         _check_nkd),
    Rule("gather-free-model-dim", "error", "hlo",
         "No gather/scatter touches a model-dim-sized operand.",
         _check_gather),
    Rule("launch-count", "error", "jaxpr",
         "pallas_call count through scan/cond/pjit matches the pin.",
         _check_launch_count),
    Rule("f32-trust-invariant", "error", "jaxpr",
         "Trust/temporal statistics are never downcast below f32.",
         _check_f32_trust),
    Rule("no-host-transfer-in-scan", "error", "hlo",
         "No device->host transfer or callback inside a while body.",
         _check_host_transfer),
    Rule("vmem-budget", "error", "pallas",
         "Per-grid-step VMEM residency (2x blocks + scratch) under the "
         "ceiling; block shapes divide arrays; index maps stay in range.",
         _check_vmem),
    Rule("compile-once", "error", "runtime",
         "Trace cache stays at 1 across a round-varying schedule.",
         _check_compile_once),
    Rule("memory-passes", "error", "config",
         "memory_passes() stays within the documented traffic table "
         "(the absorbed scripts/passes_gate.py).", _check_memory_passes),
    Rule("unknown-trip-count", "warning", "hlo",
         "While loops carry known_trip_count (roofline accuracy).",
         _check_unknown_trip),
    Rule("dead-computation", "info", "hlo",
         "Every computation is reachable from the entry.",
         _check_dead_computation),
    Rule("spmd-collective-contract", "error", "hlo",
         "Sharded entries emit only the contract's collective kinds, each "
         "payload under the O(N*K) per-collective ceiling.",
         _check_spmd_contract),
    Rule("spmd-model-dim-allgather", "error", "hlo",
         "No all-gather/all-to-all rebuilds model-dim payload across "
         "shards (the GSPMD boundary-un-sharding failure mode).",
         _check_spmd_allgather),
    Rule("spmd-replica-groups", "error", "hlo",
         "Collectives cover the declared mesh: no singleton groups, no "
         "partial-mesh reductions, num_partitions matches the contract.",
         _check_spmd_replica_groups),
    Rule("spmd-wire-budget", "error", "hlo",
         "Trip-count-aware per-device collective wire bytes stay within "
         "the contract budget.", _check_spmd_wire_budget),
    Rule("spmd-sharded-nkd-buffer", "error", "hlo",
         "No per-shard (N, K, d/S) gossip tensor materializes inside the "
         "sharded module.", _check_spmd_nkd),
)

RULES_BY_ID: Dict[str, Rule] = {r.id: r for r in RULES}


def parse_suppressions(specs: Sequence[str]) -> Dict[str, Optional[set]]:
    """``rule-id`` (everywhere) or ``rule-id@entry`` -> {rule: entries}
    where entries None means all."""
    out: Dict[str, Optional[set]] = {}
    for spec in specs:
        rule, _, ent = spec.partition("@")
        if rule not in RULES_BY_ID:
            raise ValueError(f"unknown rule {rule!r} in suppression {spec!r}; "
                             f"known: {sorted(RULES_BY_ID)}")
        if not ent:
            out[rule] = None
        elif out.get(rule, set()) is not None:
            out.setdefault(rule, set())
            out[rule].add(ent)
    return out


def run_rules(artifacts: Artifacts, entry: EntryPoint,
              suppressions: Optional[Dict[str, Optional[set]]] = None,
              rules: Sequence[Rule] = RULES) -> List[Finding]:
    """Run every rule on one entry point, applying entry-level and
    caller-level suppressions (suppressed findings are kept, flagged)."""
    suppressions = suppressions or {}
    findings: List[Finding] = []
    for rule in rules:
        sup_entries = suppressions.get(rule.id, "unset")
        globally = sup_entries is None
        for_entry = (isinstance(sup_entries, set) and entry.name in sup_entries)
        suppressed = (rule.id in entry.suppress) or globally or for_entry
        if suppressed and rule.layer in ("runtime",):
            continue      # don't pay to run a suppressed runtime probe
        for f in rule.run(artifacts, entry):
            findings.append(dataclasses.replace(f, suppressed=suppressed)
                            if suppressed else f)
    return findings


def gate_failures(findings: Sequence[Finding]) -> List[Finding]:
    """The findings that fail the gate: unsuppressed errors."""
    return [f for f in findings if f.severity == "error" and not f.suppressed]
