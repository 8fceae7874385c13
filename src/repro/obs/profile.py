"""Timing plane: the round's phases on the device trace, host spans for
the profiler, and the achieved-bandwidth join against the
``memory_passes`` traffic table.

:func:`phase` is the one piece that enters traced code: a
``jax.named_scope`` opened while a round is traced, so every HLO op the
phase emits carries ``phase.<name>`` in its ``metadata={op_name=...}``
and a device trace can charge the op's time to the phase
(``bench/trace.py`` ``phase_map``).  A scope changes metadata only —
fusion and scheduling ignore it — so the phases are always on and cost
nothing when no profile is taken.  The rest is host-side
instrumentation AROUND jitted computations: :func:`annotate` marks a
host slice with ``jax.profiler.TraceAnnotation``, and :func:`capture`
brackets a run with ``jax.profiler.trace`` for a full
TensorBoard/Perfetto device capture on TPU runs.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax

# The fleet round's phases, in execution order.  ``data`` nests in
# ``local_train``; ``sanitize`` nests in whichever phase holds a
# sanitizer (``transport`` for the chaos ring, ``aggregate`` for
# ``wfagg.sanitize_round``).  Glue code (a ravel, a ``where``) belongs
# to the phase whose output it produces.
PHASES = ("local_train", "data", "attack", "transport", "sanitize",
          "aggregate", "evaluate")


def phase(name: str):
    """``jax.named_scope("phase.<name>")``: open it while tracing the
    phase's work, so the compiled ops carry the phase in their HLO
    metadata.  ``name`` must be one of :data:`PHASES`."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; one of {PHASES}")
    return jax.named_scope(f"phase.{name}")


@contextlib.contextmanager
def annotate(name: str):
    """Mark a host slice (a round driven from Python) with
    ``jax.profiler.TraceAnnotation``, so a ``jax.profiler`` capture
    shows rounds as labelled host spans.  A ``jax.named_scope`` here
    would name nothing: a scope around a call to an already-jitted
    function reaches neither its lowered nor its compiled HLO.  The
    device ops are named by :func:`phase`, inside the traced round."""
    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def capture(logdir: Optional[str]):
    """Opt-in device profile capture: wraps the block in
    ``jax.profiler.trace(logdir)`` when ``logdir`` is set (TPU runs get
    a full XLA/TraceMe capture loadable in TensorBoard or Perfetto);
    no-op when falsy, so call sites don't branch."""
    if not logdir:
        yield
        return
    with jax.profiler.trace(logdir):
        yield


def round_traffic_bytes(wcfg, n_nodes: int, width: int, d: int, *,
                        indexed: bool = True,
                        include_gather: bool = True) -> float:
    """Analytic bytes moved per gossip round: the ``memory_passes``
    traffic table (src/repro/kernels/README.md) times the candidate
    bytes one pass streams — N nodes x K candidates x d floats."""
    from repro.core import wfagg as wf

    passes = wf.memory_passes(wcfg, include_gather=include_gather,
                              indexed=indexed)
    return float(passes) * n_nodes * width * d * 4.0


def achieved_bytes_per_s(traffic_bytes: float, steady_s: float) -> float:
    """Achieved HBM-ish bandwidth for one round: analytic traffic over
    measured steady-state seconds."""
    return traffic_bytes / max(steady_s, 1e-12)
