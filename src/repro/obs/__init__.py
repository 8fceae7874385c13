"""Flight recorder: in-scan decision telemetry, round profiling and
trace export for the DFL engine (docs/OBSERVABILITY.md).

Three planes, three modules:

- :mod:`repro.obs.decision` — the packed per-edge verdict bitmask and
  per-node summaries, emitted as pure traced outputs of the round/scan
  (import-light: engine and mode-B depend on it, so this package
  ``__init__`` must NOT pull in the heavier planes below).
- :mod:`repro.obs.profile` — the round's ``phase.*`` scopes on the
  device trace, host TraceAnnotations, achieved-bytes/s via the
  ``memory_passes`` table.
- :mod:`repro.obs.recorder` / :mod:`repro.obs.trace` /
  ``python -m repro.obs.report`` — JSONL event log, Chrome/Perfetto
  ``trace_event`` export, and the per-filter audit tables.
"""
from repro.obs.decision import (  # noqa: F401
    BIT_ACCEPTED,
    BIT_C,
    BIT_CORRUPT,
    BIT_D,
    BIT_DROPPED,
    BIT_STALE,
    BIT_T,
    BIT_VALID,
    BITS,
    DecisionRecord,
    FAULT_BITS,
    pack_verdict,
    record_from_info,
    record_from_masks,
    record_uniform,
    unpack_verdict,
    with_fault_bits,
)
