import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede any jax import: jax locks the platform and device count
# on first init.  This is the ONLY entry point that forces 512 host
# devices; smoke tests and benchmarks see the single real CPU device.
# The dry-run is a CPU compile by design: pinning the platform keeps the
# parent and every per-pair child off a TPU, which one process owns.
"""Multi-pod dry-run launcher.

For every (architecture x input shape) pair, lower + compile the real
train/prefill/decode step against the production mesh (16x16 single-pod,
2x16x16 multi-pod) with ShapeDtypeStruct inputs (zero allocation), then
extract:

  * memory_analysis()  — per-device argument/temp/output bytes (fits HBM?)
  * cost_analysis()    — per-device HLO FLOPs + bytes accessed
  * the collective schedule — parsed from the optimized HLO text, with
    per-op wire-byte estimates (ring-schedule factors per collective kind)

and derive the three roofline terms (DESIGN.md Section 8).  One JSON
artifact per pair lands in ``benchmarks/artifacts/`` for roofline.py.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all            # sweep, subprocess per pair
"""
import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, List, Optional

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts")

# the chip the production mesh is sized for (a key of launch.mesh.PEAKS)
TARGET_DEVICE_KIND = "TPU v5 lite"

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# '%name = TYPE[dims]{layout} kind(' — also matches tuple outputs '(T1, T2) kind('
_INSTR_RE = re.compile(
    r"=\s*(?P<out>\(?[a-z0-9\[\],{}<>= ]+?\)?)\s+"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota form [G,S]<=[...] : G groups of size S
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        ids = [s for s in m.group(1).split(",") if s.strip()]
        return max(1, len(ids))
    if _SRC_TGT_RE.search(line):
        return 2  # permute: one send+recv per device
    return n_devices


def parse_collectives(hlo_text: str, n_devices: int) -> Dict[str, Any]:
    """Collective schedule from optimized HLO.

    Per instruction we know the per-device OUTPUT bytes and the replica
    group size S.  Ring-schedule wire bytes per device:
      all-gather      out*(S-1)/S      (out = full gathered buffer)
      all-reduce      2*out*(S-1)/S    (reduce-scatter + all-gather)
      reduce-scatter  out*(S-1)        (out = one shard)
      all-to-all      out*(S-1)/S
      collective-permute  out          (dedicated link)
    """
    per_kind: Dict[str, Dict[str, float]] = {
        k: {"count": 0, "out_bytes": 0.0, "wire_bytes": 0.0} for k in _COLL_KINDS}
    ops: List[Dict[str, Any]] = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m or m.group("suffix") == "-done":
            continue  # count start (or sync form) once; skip the -done half
        kind = m.group("kind")
        out_b = _shape_bytes(m.group("out"))
        S = _group_size(line, n_devices)
        if kind == "all-gather":
            wire = out_b * (S - 1) / max(S, 1)
        elif kind == "all-reduce":
            wire = 2 * out_b * (S - 1) / max(S, 1)
        elif kind == "reduce-scatter":
            wire = out_b * (S - 1)
        elif kind == "all-to-all":
            wire = out_b * (S - 1) / max(S, 1)
        else:  # collective-permute
            wire = out_b
        pk = per_kind[kind]
        pk["count"] += 1
        pk["out_bytes"] += out_b
        pk["wire_bytes"] += wire
        ops.append({"kind": kind, "out_bytes": out_b, "group_size": S,
                    "wire_bytes": wire})
    total_wire = sum(k["wire_bytes"] for k in per_kind.values())
    return {"per_kind": per_kind, "total_wire_bytes": total_wire,
            "n_ops": len(ops), "largest": sorted(
                ops, key=lambda o: -o["wire_bytes"])[:8]}


def run_one(arch: str, shape_name: str, multi_pod: bool,
            layout: str = "flat", param_dtype: str = "") -> Dict[str, Any]:
    import jax

    from repro.configs.registry import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch import specs as sp
    from repro.launch.mesh import make_production_mesh, peak_figures

    peaks = peak_figures(TARGET_DEVICE_KIND)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    # Gossip-round VMEM headroom at this model size: the fused one-launch
    # round kernel traced abstractly at d = param_count under the
    # compiled-TPU block policy (repro.analysis.vmem).  Residency is
    # d-independent once the model dim is blocked — recording it per
    # config turns that scaling claim into data the roofline artifacts
    # carry (see docs/STATIC_ANALYSIS.md, vmem-budget rule).
    from repro.analysis.vmem import config_vmem_report
    rec["gossip_vmem"] = config_vmem_report(arch=arch)[0]

    variant = sp.arch_variant(cfg, shape)
    if param_dtype and variant is not None:
        import dataclasses
        variant = dataclasses.replace(variant, param_dtype=param_dtype)
    if variant is None:
        rec.update(status="skipped",
                   reason="enc-dec 500k-token decode outside operating regime "
                          "(DESIGN.md Section 6)")
        return rec
    if shape.kind in ("decode",) and not variant.supports_long_context \
            and shape.name == "long_500k":
        rec.update(status="skipped", reason="full-attention arch at 500k")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    fn, args, info = sp.build_dryrun(variant, shape, mesh, multi_pod,
                                     layout=layout)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo_text = compiled.as_text()
    coll = parse_collectives(hlo_text, n_dev)

    # trip-count-aware totals (raw cost_analysis counts scan bodies once;
    # every layer stack here is a lax.scan) — see hlo_analysis.py
    from repro.launch import hlo_analysis as ha
    tca = ha.analyze(hlo_text, n_dev)

    flops_dev = float(tca.flops)
    bytes_dev = float(tca.bytes)
    wire_dev = float(tca.wire_bytes)
    compute_s = flops_dev / peaks.flops_bf16
    memory_s = bytes_dev / peaks.hbm_bw
    collective_s = wire_dev / peaks.ici_bw
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", collective_s), key=lambda kv: kv[1])[0]

    tokens = shape.global_batch * (1 if shape.is_decode else shape.seq_len)
    n_active = cfg.active_param_count()
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    hlo_flops_global = flops_dev * n_dev

    arg_b = mem.argument_size_in_bytes if mem else 0
    temp_b = mem.temp_size_in_bytes if mem else 0
    out_b = mem.output_size_in_bytes if mem else 0
    alias_b = mem.alias_size_in_bytes if mem else 0
    peak_b = arg_b + temp_b + out_b - alias_b

    rec.update(
        status="ok", mode=info, n_devices=n_dev,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory={"argument_bytes": arg_b, "temp_bytes": temp_b,
                "output_bytes": out_b, "alias_bytes": alias_b,
                "peak_bytes": peak_b, "hbm_bytes": peaks.hbm_bytes,
                "fits": bool(peak_b <= peaks.hbm_bytes)},
        cost={"flops_per_device": flops_dev, "bytes_per_device": bytes_dev,
              "raw_flops": float(cost.get("flops", 0.0)),
              "raw_bytes": float(cost.get("bytes accessed", 0.0)),
              "transcendentals": float(cost.get("transcendentals", 0.0)),
              "n_while": tca.n_while,
              "unknown_trip_whiles": tca.unknown_trip_whiles,
              "trip_counts": tca.trip_counts[:32]},
        collectives={"per_kind_wire_bytes": tca.coll_by_kind,
                     "schedule_once": coll},
        roofline={
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s, "dominant": dominant,
            "step_s_lower_bound": max(compute_s, memory_s, collective_s),
            "model_flops": model_flops,
            "hlo_flops_global": hlo_flops_global,
            "useful_flops_ratio": (model_flops / hlo_flops_global
                                   if hlo_flops_global else 0.0),
        },
    )
    return rec


def artifact_path(arch: str, shape: str, multi_pod: bool, tag: str = "") -> str:
    mesh = "multi" if multi_pod else "single"
    suffix = f".{tag}" if tag else ""
    return os.path.join(ARTIFACT_DIR, f"{arch}.{shape}.{mesh}{suffix}.json")


def _sweep(multi_pod_too: bool, tag: str) -> int:
    """Run every pair in a subprocess (compile-state isolation)."""
    from repro.configs.registry import assigned_archs
    from repro.configs.shapes import SHAPES

    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    failures = 0
    meshes = [False, True] if multi_pod_too else [False]
    for arch in assigned_archs():
        for shape in SHAPES:
            for mp in meshes:
                out = artifact_path(arch, shape, mp, tag)
                if os.path.exists(out):
                    print(f"[skip-cached] {arch} x {shape} mp={mp}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--json", out]
                if mp:
                    cmd.append("--multi-pod")
                if tag:
                    cmd += ["--tag", tag]
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True)
                dt = time.time() - t0
                if r.returncode != 0:
                    failures += 1
                    print(f"[FAIL {dt:6.1f}s] {arch} x {shape} mp={mp}\n"
                          f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
                else:
                    print(f"[ok   {dt:6.1f}s] {arch} x {shape} mp={mp} "
                          f"{r.stdout.strip().splitlines()[-1] if r.stdout.strip() else ''}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--with-multi-pod", action="store_true",
                    help="with --all: also run every pair on the 2x16x16 mesh")
    ap.add_argument("--json", help="artifact output path")
    ap.add_argument("--tag", default="", help="artifact tag (perf experiments)")
    ap.add_argument("--layout", default="stacked", choices=("flat", "stacked"),
                    help="robust-agg gradient layout (train shapes)")
    ap.add_argument("--param-dtype", default="",
                    help="override cfg.param_dtype (perf experiments)")
    args = ap.parse_args()

    if args.all:
        sys.exit(1 if _sweep(args.with_multi_pod, args.tag) else 0)

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    try:
        rec = run_one(args.arch, args.shape, args.multi_pod, layout=args.layout,
                      param_dtype=args.param_dtype)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "status": "error", "traceback": traceback.format_exc()}
    out = args.json or artifact_path(args.arch, args.shape, args.multi_pod,
                                     args.tag)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    if rec["status"] == "ok":
        r = rec["roofline"]
        print(f"{rec['arch']} x {rec['shape']} [{rec['mesh']}] "
              f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
              f"collective={r['collective_s']:.3e}s dominant={r['dominant']} "
              f"fits={rec['memory']['fits']}")
    elif rec["status"] == "skipped":
        print(f"{rec['arch']} x {rec['shape']} SKIPPED: {rec['reason']}")
    else:
        print(rec.get("traceback", "error"), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
