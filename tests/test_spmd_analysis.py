"""Tier-1 coverage for the SPMD communication-contract analyzer.

Three layers:
  * registry + contract well-formedness and the per-rule doctored
    fire/quiet fixtures — 1-device safe, always run;
  * text-level unit tests of the collective parser in
    ``repro.launch.hlo_analysis`` (replica groups, wire-byte model);
  * the 8-virtual-device checks (sharded-vs-single-process parity,
    the real-artifact lint gate, the replicated-output fire test) —
    subprocesses with ``XLA_FLAGS=--xla_force_host_platform_device_
    count=8`` set BEFORE jax imports, marked slow.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import RULES_BY_ID, collectives, selftest
from repro.launch import hlo_analysis as ha

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARITY = os.path.join(REPO, "tests", "_spmd_parity_main.py")

SPMD_RULES = (
    "spmd-collective-contract",
    "spmd-model-dim-allgather",
    "spmd-replica-groups",
    "spmd-wire-budget",
    "spmd-sharded-nkd-buffer",
)
SHARDED_ENTRIES = (
    "sharded_one_launch_round",
    "sharded_dynamic_scan",
    "sharded_stacked_mode_b",
)


# ---------------------------------------------------------------- registry

def test_spmd_rules_registered():
    for rid in SPMD_RULES:
        assert rid in RULES_BY_ID, rid
        rule = RULES_BY_ID[rid]
        assert rule.severity == "error"
        assert rule.layer == "hlo"


def test_sharded_entries_registered():
    from repro.analysis.entry_points import entry_points

    entries = entry_points()
    for name in SHARDED_ENTRIES:
        assert name in entries, name
        e = entries[name]
        assert e.min_devices == 8
        assert e.contract is not None
        assert e.contract.axis_size == 8
        # the contract must serialize into the JSON report
        d = json.loads(json.dumps(e.contract.to_dict()))
        assert d["axis_size"] == 8
        assert d["wire_budget_bytes"] > 0
        assert "all-reduce" in d["allowed_kinds"]


@pytest.mark.parametrize("rid", SPMD_RULES)
def test_spmd_rule_fires_on_doctored_fixture(rid):
    """One doctored fire + quiet pair per SPMD rule (the selftest body —
    SystemExit means the rule stopped firing or fired on clean HLO)."""
    getattr(selftest, "test_" + rid.replace("-", "_"))()


# ---------------------------------------------------------------- contracts

def test_round_contract_scales_with_rounds():
    one = collectives.wfagg_round_contract(10, 4, 8, rounds=1)
    three = collectives.wfagg_round_contract(10, 4, 8, rounds=3)
    assert three.wire_budget_bytes == pytest.approx(3 * one.wire_budget_bytes)
    # the per-collective ceiling is O(N*K), not O(rounds)
    assert three.max_collective_bytes == one.max_collective_bytes
    # an f32 (N, K) psum payload fits under the ceiling; a model-dim
    # gather of even one row does not
    assert 4 * 10 * 4 <= one.max_collective_bytes
    assert one.max_collective_bytes < 4 * 50896


def test_stacked_contract_allows_gram():
    c = collectives.stacked_allreduce_contract(6, 8)
    assert c.max_collective_bytes >= 4 * 6 * 6  # f32 (K, K) Gram psum
    assert c.allowed_kinds == ("all-reduce",)


# ---------------------------------------------------------- HLO parsing

def test_parse_replica_groups_forms():
    form, groups, size, n = ha.parse_replica_groups(
        "replica_groups={{0,1,2,3},{4,5,6,7}}", 8)
    assert (form, size, n) == ("list", 4, 2)
    assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]

    form, groups, size, n = ha.parse_replica_groups(
        "replica_groups=[2,4]<=[8]", 8)
    assert (form, groups, size, n) == ("iota", None, 4, 2)

    form, _, size, _ = ha.parse_replica_groups(
        "source_target_pairs={{0,1},{1,0}}", 8)
    assert (form, size) == ("pairs", 2)

    form, _, size, _ = ha.parse_replica_groups("all-reduce(f32[4] x)", 8)
    assert (form, size) == ("default", 8)


def test_collective_covers_mesh():
    rec = ha.Collective(name="ar", kind="all-reduce", out_bytes=160,
                        group_size=8, n_groups=1,
                        groups=[[0, 1, 2, 3, 4, 5, 6, 7]],
                        group_form="list", wire_bytes=280.0, mult=1.0,
                        line="")
    assert rec.covers_mesh(8) is True
    assert rec.covers_mesh(16) is False
    part = ha.Collective(name="ar", kind="all-reduce", out_bytes=160,
                         group_size=4, n_groups=1, groups=[[0, 1, 2, 3]],
                         group_form="list", wire_bytes=240.0, mult=1.0,
                         line="")
    assert part.covers_mesh(8) is False
    iota = ha.Collective(name="ar", kind="all-reduce", out_bytes=160,
                         group_size=8, n_groups=1, groups=None,
                         group_form="iota", wire_bytes=280.0, mult=1.0,
                         line="")
    assert iota.covers_mesh(8) is True
    dflt = ha.Collective(name="ar", kind="all-reduce", out_bytes=160,
                         group_size=8, n_groups=1, groups=None,
                         group_form="default", wire_bytes=280.0, mult=1.0,
                         line="")
    assert dflt.covers_mesh(8) is None


def test_analyze_collective_table_on_doctored_hlo():
    """The clean SPMD fixture yields exactly one all-reduce record with
    the ring-model wire bytes: 2 * 160 B * 7/8 = 280 B/device."""
    cost = ha.analyze(selftest._SPMD_CLEAN_HLO, n_devices=8)
    assert cost.num_partitions == 8
    assert cost.collectives is not None and len(cost.collectives) == 1
    rec = cost.collectives[0]
    assert rec.kind == "all-reduce"
    assert rec.out_bytes == 4 * 10 * 4
    assert rec.group_size == 8 and rec.covers_mesh(8) is True
    assert rec.wire_bytes == pytest.approx(2 * 160 * 7 / 8)
    assert cost.wire_bytes == pytest.approx(rec.wire_bytes)


def test_contract_cost_memoized():
    from repro.analysis.artifacts import Artifacts

    art = Artifacts.from_hlo(selftest._SPMD_CLEAN_HLO)
    c1 = collectives.contract_cost(art, 8)
    c2 = collectives.contract_cost(art, 8)
    assert c1 is c2
    assert collectives.contract_cost(art, 4) is not c1


# ---------------------------------------------------------- 1-device CLI

def test_cli_skips_sharded_entries_below_min_devices(tmp_path):
    """On fewer than 8 devices the sharded gates record a skip (never a
    silent drop) and the report carries schema_version."""
    import jax

    if len(jax.devices()) >= 8:
        pytest.skip("session already has 8 devices; skip path untestable")
    from repro.analysis.__main__ import SCHEMA_VERSION, main as lint_main

    out = tmp_path / "report.json"
    rc = lint_main(["--entry", "sharded_one_launch_round",
                    "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["schema_version"] == SCHEMA_VERSION
    rec = report["entries"]["sharded_one_launch_round"]
    assert "skipped" in rec and "XLA_FLAGS" in rec["skipped"]


# ------------------------------------------------------- 8-device checks

def _run_8dev(argv, timeout=1200):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = (os.path.join(REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, env=env, timeout=timeout, cwd=REPO)
    assert proc.returncode == 0, (
        f"exit {proc.returncode}\n--- stdout ---\n{proc.stdout}"
        f"\n--- stderr ---\n{proc.stderr}")
    return proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["round", "nonfinite", "scan", "stacked",
                                  "engine", "gather_fire"])
def test_spmd_parity_8dev(mode):
    out = _run_8dev([PARITY, mode])
    assert f"PARITY_OK:{mode}" in out


@pytest.mark.slow
def test_sharded_lint_gate_8dev(tmp_path):
    """The acceptance gate: lint all three sharded entries on 8 virtual
    devices — zero gate failures, contracts + collective tables in the
    JSON report."""
    out = tmp_path / "lint_report_spmd.json"
    stdout = _run_8dev(["-m", "repro.analysis",
                        "--entry", "sharded_one_launch_round",
                        "--entry", "sharded_dynamic_scan",
                        "--entry", "sharded_stacked_mode_b",
                        "--json", str(out)])
    assert "repro.analysis: OK" in stdout
    report = json.loads(out.read_text())
    assert report["summary"]["ok"] and report["summary"]["n_errors"] == 0
    assert report["meta"]["n_devices"] >= 8
    for name in SHARDED_ENTRIES:
        rec = report["entries"][name]
        assert "skipped" not in rec
        assert rec["contract"]["axis_size"] == 8
        colls = rec["cost"]["collectives"]
        assert colls, f"{name}: no collectives parsed"
        assert all(c["kind"] == "all-reduce" for c in colls)
        wire = sum(c["mult"] * c["wire_bytes"] for c in colls)
        assert 0 < wire <= rec["contract"]["wire_budget_bytes"]
