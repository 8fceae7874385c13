#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; everything that
belongs to it is found by name too: its configuration
(``configs[].file``, with the plain reference of the model beside it as
``<same stem>.py``), its traffic mix (``bench/traffic/<traffic>.json``,
whose ``runner`` names ``bench/runners/<runner>.py``) and one reader per
per-layer metric (``bench/metrics/<metric>.py``).  A later cell, mix,
configuration or metric is added as new files and entries.

The run refuses anything but a TPU with the cell's chips (exit 2, no
result).  It then sets up, measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints the numbers
it compared beside their limits as the last lines of standard error and
one JSON object as the last line of standard output.  ``--trace 1``
profiles part of the window and reports the per-layer metrics instead of
the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod   # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, name: str, root: str = ROOT) -> types.SimpleNamespace:
    """Everything one cell needs, found by the names in ``bench``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = os.path.join(root, entry["file"])
    mix = load_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    # an end-to-end metric without a ``workloads`` list is every cell's;
    # a per-layer metric always lists its cells
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", cells)]
    layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return types.SimpleNamespace(
        name=name, workload=w, chips=int(w["chips"]),
        config=load_json(cfg_path),
        model=load_module(os.path.splitext(cfg_path)[0] + ".py",
                          "bench_model_" + w["config"].replace("-", "_").replace(".", "_")),
        mix=mix,
        runner=load_module(os.path.join(root, "bench", "runners", mix["runner"] + ".py"),
                           "bench_runner_" + mix["runner"]),
        end_to_end=e2e, per_layer=layer,
        readers={m["name"]: load_module(
            os.path.join(root, "bench", "metrics", m["name"] + ".py"),
            "bench_metric_" + m["name"].replace(".", "_"))
            for m in layer})


def result_line(cell, out: dict, trace: bool) -> dict:
    """The contract's last line from a runner's output."""
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(out["layer_data"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(out["e2e"][m["name"]]),
                                  "unit": m["unit"]}
    from bench.compare import judge
    checks = judge(out["numbers"], out["limits"])
    correct = bool(checks) and all(c["ok"] for c in checks.values()) \
        and out["failed"] == 0
    device = dict(out["device"])
    line = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if trace:
        red = out["layer_data"]["reduction"]
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        line["breakdown"] = {"device_ops": [[k, v] for k, v in red.device_ops],
                             "idle_gaps": [[k, v] for k, v in red.idle_gaps]}
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    cell = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    from bench import device as dev
    try:
        devices = dev.require_tpu(cell.chips)
    except RuntimeError as e:
        log(f"bench: {e}")
        return 2
    dev.use_compile_cache(ROOT)
    out = cell.runner.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, devices, log)
    try:
        line = result_line(cell, out, bool(args.trace))
    finally:
        if out.get("layer_data") and out["layer_data"].get("trace_dir"):
            shutil.rmtree(out["layer_data"]["trace_dir"], ignore_errors=True)
    for k, c in line["checks"].items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
