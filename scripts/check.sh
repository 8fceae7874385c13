#!/usr/bin/env bash
# Tier-1 gate: run before merging.
#
#   ./scripts/check.sh                 tier-1 tests, passes gate, linter
#   FAST=1 ./scripts/check.sh          skip the slow end-to-end trainer tests
#   DYNAMICS_SMOKE=1 ./scripts/check.sh
#                                      dynamics-only smoke: one short
#                                      --scenario churn experiment through
#                                      the scenario engine (the CI
#                                      dynamics job), skipping the full
#                                      pytest gate
#   OBS_SMOKE=1 ./scripts/check.sh     flight-recorder smoke: a small
#                                      telemetry-on experiment through
#                                      python -m repro.obs.report, with
#                                      the JSONL event log validated
#                                      against the schema and the
#                                      Perfetto trace written (the CI
#                                      obs job uploads both as
#                                      artifacts; OBS_EVENTS/OBS_TRACE
#                                      override the output paths)
#   CHAOS_SMOKE=1 ./scripts/check.sh   chaos-transport smoke: the fault
#                                      x intensity degradation smoke
#                                      grid (drop/chaos x none/alie x
#                                      mean/wfagg) through
#                                      benchmarks.chaos_matrix, with
#                                      the degradation-curve JSON
#                                      written for the CI chaos-smoke
#                                      job to upload (CHAOS_JSON
#                                      overrides the output path)
#   LINT_SPMD=1 ./scripts/check.sh     SPMD communication-contract gate:
#                                      lint the three sharded entries on
#                                      8 virtual CPU devices (the CI
#                                      lint-spmd job; LINT_JSON=<path>
#                                      writes the report it uploads),
#                                      then run the 8-device parity +
#                                      fire checks, skipping the full
#                                      pytest gate
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${DYNAMICS_SMOKE:-0}" == "1" ]]; then
  python examples/dfl_paper_experiment.py --scenario churn --rounds 3 \
    --model mlp --aggregator wfagg --attack ipm_100
  echo "check.sh: dynamics smoke OK"
  exit 0
fi

if [[ "${OBS_SMOKE:-0}" == "1" ]]; then
  OBS_EVENTS="${OBS_EVENTS:-obs_events.jsonl}"
  OBS_TRACE="${OBS_TRACE:-obs_trace.json}"
  python -m repro.obs.report --nodes 10 --degree 4 --rounds 4 --n-test 64 \
    --out-events "$OBS_EVENTS" --out-trace "$OBS_TRACE"
  # re-read the files the run wrote: the JSONL must round-trip through
  # the schema validator and the trace must be well-formed trace_event
  # JSON (what ui.perfetto.dev parses)
  python - "$OBS_EVENTS" "$OBS_TRACE" <<'PY'
import json, sys
from repro.obs import recorder
events = recorder.read_events(sys.argv[1])
recorder.validate_events(events, strict=True)
trace = json.load(open(sys.argv[2]))
assert isinstance(trace.get("traceEvents"), list) and trace["traceEvents"], \
    "empty traceEvents"
for ev in trace["traceEvents"]:
    assert ev["ph"] in ("X", "C", "M") and "pid" in ev, ev
print(f"obs smoke: {len(events)} events, "
      f"{len(trace['traceEvents'])} trace events — schema OK")
PY
  echo "check.sh: obs smoke OK"
  exit 0
fi

if [[ "${CHAOS_SMOKE:-0}" == "1" ]]; then
  CHAOS_JSON="${CHAOS_JSON:-chaos_matrix.json}"
  python -m benchmarks.chaos_matrix --smoke --out "$CHAOS_JSON"
  # the chaos lint entry: fault-injected dynamic scan must still be one
  # launch with no in-scan host transfer (the stacked-ring delivery
  # trick's whole point)
  python -m repro.analysis --entry chaos_scan
  echo "check.sh: chaos smoke OK"
  exit 0
fi

if [[ "${LINT_SPMD:-0}" == "1" ]]; then
  # the device-count flag must be in the environment BEFORE jax imports
  export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8"
  python -m repro.analysis \
    --entry sharded_one_launch_round \
    --entry sharded_dynamic_scan \
    --entry sharded_stacked_mode_b \
    ${LINT_JSON:+--json "$LINT_JSON"}
  for mode in round scan stacked engine gather_fire; do
    python tests/_spmd_parity_main.py "$mode"
  done
  echo "check.sh: spmd lint OK"
  exit 0
fi

if [[ "${FAST:-0}" == "1" ]]; then
  python -m pytest -x -q -m "not slow"
else
  python -m pytest -x -q
fi

# memory_passes() for the shipped configs must not exceed the traffic
# table documented in src/repro/kernels/README.md (single-launch = 2:
# phase 1 re-reads the candidates).
python scripts/passes_gate.py

# Computation linter: one static-analysis pass over the jaxprs, optimized
# HLO and Pallas block specs of every registered entry point (rule
# catalog in docs/STATIC_ANALYSIS.md).  The self-test doctors a fixture
# per rule so a rule that stops firing fails here, then the real lint
# must come back clean.  LINT=0 skips both (kernel-only iterations);
# LINT_JSON=<path> writes the machine-readable report (CI uploads it).
if [[ "${LINT:-1}" == "1" ]]; then
  python -m repro.analysis --self-test
  python -m repro.analysis ${LINT_JSON:+--json "$LINT_JSON"}
fi

# Robustness-matrix regression gate: re-runs the committed gate subgrid
# (benchmarks/BENCH_robustness.json) and fails when any attack x
# scenario x aggregator cell degrades beyond tolerance.  The comparator
# self-test is instant; the grid re-run takes a few minutes — skip it
# with ROBUSTNESS_GATE=0 (e.g. for kernel-only iterations).
python scripts/robustness_gate.py --self-test
if [[ "${ROBUSTNESS_GATE:-1}" == "1" ]]; then
  python scripts/robustness_gate.py
fi

echo "check.sh: OK"
