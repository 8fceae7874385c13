"""The harness: every cell resolves its files by name, a file it has
never seen is found the same way, the result line keeps the contract,
and a run without a TPU exits non-zero with no result."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import run as harness
from bench.tests.helpers import ROOT, load_json


def test_every_workload_resolves_its_config_traffic_runner_and_readers():
    bench = load_json("BENCHMARK.json")
    for w in bench["workloads"]:
        cell = harness.resolve(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert hasattr(cell.model, "forward") and hasattr(cell.runner, "run")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer and set(cell.readers) == {m["name"] for m in cell.per_layer}
        entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
        assert entry["file"].startswith("bench/")


def test_benchmark_names_and_units_use_allowed_characters():
    import re
    bench = load_json("BENCHMARK.json")
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert name.match(e["name"]), e["name"]
            if "unit" in e:
                assert unit.match(e["unit"]), e["unit"]
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert set(m["workloads"]) <= {w["name"] for w in bench["workloads"]}


def _new_cell_tree(tmp_path):
    """A checkout with one cell, configuration, mix, runner and metric
    the harness has never seen."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "configs" / "toy-model.json").write_text(
        json.dumps({"name": "toy-model", "width": 3}))
    (tmp_path / "bench" / "configs" / "toy-model.py").write_text(
        "def forward(params, x):\n    return x\n")
    (tmp_path / "bench" / "traffic" / "toy-mix.json").write_text(
        json.dumps({"runner": "toy", "rate": 7}))
    (tmp_path / "bench" / "runners" / "toy.py").write_text(
        "def run(cell, *args):\n    return {'mix_rate': cell.mix['rate']}\n")
    (tmp_path / "bench" / "metrics" / "toy_share.toy.py").write_text(
        "def read(data):\n    return data['x'] * 2\n")
    bench = {
        "configs": [{"name": "toy-model", "file": "bench/configs/toy-model.json"}],
        "workloads": [{"name": "toy-cell", "config": "toy-model", "traffic": "toy-mix",
                       "chips": 1}],
        "end_to_end": [{"name": "ops_per_s", "unit": "ops/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "toy_share.toy", "unit": "%", "moves": "ops_per_s",
                       "workloads": ["toy-cell"]}],
    }
    return bench


def test_a_file_the_harness_has_never_seen_is_found_by_name(tmp_path):
    bench = _new_cell_tree(tmp_path)
    cell = harness.resolve(bench, "toy-cell", root=str(tmp_path))
    assert cell.config == {"name": "toy-model", "width": 3}
    assert cell.model.forward(None, 5) == 5
    assert cell.runner.run(cell) == {"mix_rate": 7}
    # no "workloads" key on an end-to-end metric: every cell reports it
    assert {m["name"] for m in cell.end_to_end} == {"ops_per_s", "setup_s"}
    assert cell.readers["toy_share.toy"].read({"x": 4}) == 8
    with pytest.raises(KeyError):
        harness.resolve(bench, "no-such-cell", root=str(tmp_path))


def _fake_out(numbers, limits, failed=0):
    red = types.SimpleNamespace(busy_s=0.9, window_s=1.0, device_ops=[("fusion.1", 0.5)],
                                idle_gaps=[("readback", 0.01)])
    return {"e2e": {"rounds_per_s": 12.5, "setup_s": 3.0, "unused": 1.0},
            "layer_data": {"reduction": red, "x": 1},
            "numbers": numbers, "limits": limits, "attempted": 100, "failed": failed,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 123}}


def _cell():
    reader = types.SimpleNamespace(read=lambda data: 42.0)
    silent = types.SimpleNamespace(read=lambda data: None)
    return types.SimpleNamespace(
        end_to_end=[{"name": "rounds_per_s", "unit": "rounds/s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[{"name": "a.fleet", "unit": "%"}, {"name": "b.fleet", "unit": "%"}],
        readers={"a.fleet": reader, "b.fleet": silent})


def test_result_line_end_to_end_run():
    line = harness.result_line(_cell(), _fake_out({"gap": 0.1}, {"gap": 0.5}), trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True
    assert line["metrics"] == {"rounds_per_s": {"value": 12.5, "unit": "rounds/s"},
                               "setup_s": {"value": 3.0, "unit": "s"}}
    assert line["checks"] == {"gap": {"value": 0.1, "limit": 0.5}}
    assert line["device"]["kind"] == "TPU v5 lite"


def test_result_line_traced_run_reads_layer_metrics_and_skips_silent_ones():
    line = harness.result_line(_cell(), _fake_out({"gap": 0.1}, {"gap": 0.5}), trace=True)
    assert line["metrics"] == {"a.fleet": {"value": 42.0, "unit": "%"}}
    assert line["device"]["busy_s"] == 0.9 and line["device"]["window_s"] == 1.0
    assert line["breakdown"] == {"device_ops": [["fusion.1", 0.5]],
                                 "idle_gaps": [["readback", 0.01]]}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("numbers,limits,failed", [
    ({"gap": 0.6}, {"gap": 0.5}, 0),           # over its limit
    ({}, {"gap": 0.5}, 0),                     # not produced
    ({"gap": float("nan")}, {"gap": 0.5}, 0),  # non-finite
    ({"gap": 0.1}, {}, 0),                     # no limit to hold it to
    ({"gap": 0.1}, {"gap": 0.5}, 3),           # rounds that failed
])
def test_result_line_is_not_correct(numbers, limits, failed):
    line = harness.result_line(_cell(), _fake_out(numbers, limits, failed), trace=False)
    assert line["correct"] is False


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(["--workload", "fleet-paper20-ipm05", "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT)
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_a_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    p = _run(["--workload", "fleet-paper20-ipm05", "--seed", "1", "--seconds", "1",
              "--trace", "0"], str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""
