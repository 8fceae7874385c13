"""Reduction of a JAX profiler trace (``.xplane.pb``) to the per-layer
numbers: device busy time, kernel time, collective time and the part of
it no compute overlaps, idle gaps labelled by the benchmark's own host
spans, and the device operations that took most time.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation and their ``XLA Modules`` line one per
program execution.  Host spans are the ``jax.profiler.TraceAnnotation``
events the harness writes (``window``, ``dispatch``, ``readback``, ...).
All times are nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|alltoall", re.I)

Interval = Tuple[float, float]
_OP = re.compile(r"%?([^\s=(]+)")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event (TPU traces name an
    op event by its whole instruction text, ``%fusion.3 = f32[...] ...``)."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


def _events(line, ops: bool = False) -> List[Event]:
    return [Event(op_name(e.name) if ops else e.name, e.start_ns, e.end_ns)
            for e in line.events]


def device_lines(pd, line_name: str) -> Dict[str, List[Event]]:
    """{device plane name: events of its ``line_name`` line}."""
    out = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            out[plane.name] = [e for line in plane.lines if line.name == line_name
                               for e in _events(line, ops=line_name == "XLA Ops")]
    return out


def host_spans(pd, names: Iterable[str]) -> List[Event]:
    names = set(names)
    return [e for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in _events(line) if e.name in names]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def self_times(events: Sequence[Event], lo: float, hi: float):
    """Per op name, its time inside [lo, hi) not covered by ops nested in
    it (a ``while`` op spans the ops of its body on the same line); and
    the leaf events, which hold no other event.  An op is charged to the
    innermost open op that contains it; one that only overlaps an open
    op (an async collective beside compute) is not its child."""
    out: Dict[str, float] = {}
    leaves: List[Event] = []
    stack: List[list] = []      # [end, event, time of children, own time]

    def close(item):
        end, e, child, own = item
        out[e.name] = out.get(e.name, 0.0) + max(own - child, 0.0)
        if child == 0.0:
            leaves.append(e)

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        for item in [i for i in stack if i[0] <= s]:
            close(item)
        stack = [i for i in stack if i[0] > s]
        parents = [i for i in stack if i[0] >= t]
        if parents:
            parents[-1][2] += t - s     # the innermost op containing e
        stack.append([t, e, 0.0, t - s])
    for item in stack:
        close(item)
    return out, leaves


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Union of ``a`` minus union of ``b``."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float               # mean over devices of the busy union
    kernel_s: float             # mean over devices of kernel event time
    collective_s: float
    collective_exposed_s: float
    device_ops: List[Tuple[str, float]]   # top ops by self seconds
    idle_gaps: List[Tuple[str, float]]    # longest gaps, by host span
    n_devices: int


def reduce(pd, window: Interval, kernel_names: Iterable[str] = (),
           span_names: Iterable[str] = (), top: int = 10) -> Reduction:
    """Reduce the device planes of ``pd`` over ``window`` (ns)."""
    lo, hi = window
    kernels = set(kernel_names)
    per_dev = device_lines(pd, "XLA Ops")
    if not per_dev:
        raise ValueError("the trace holds no TPU device plane")
    busy, kern, coll, exposed = [], [], [], []
    op_time: Dict[str, float] = {}
    gaps: List[Interval] = []
    for name, events in per_dev.items():
        ivs = clip(((e.start, e.end) for e in events), lo, hi)
        busy_u = union(ivs)
        busy.append(length(busy_u))
        kern.append(length(union(clip(((e.start, e.end) for e in events
                                       if e.name in kernels), lo, hi))))
        selfs, leaves = self_times(events, lo, hi)
        for k, v in selfs.items():
            op_time[k] = op_time.get(k, 0.0) + v
        c_ivs = clip(((e.start, e.end) for e in leaves
                      if COLLECTIVE.search(e.name)), lo, hi)
        other = clip(((e.start, e.end) for e in leaves
                      if not COLLECTIVE.search(e.name)), lo, hi)
        coll.append(length(union(c_ivs)))
        exposed.append(length(subtract(c_ivs, other)))
        gaps += subtract([(lo, hi)], busy_u)
    spans = [s for s in host_spans(pd, span_names)]

    def label(gap: Interval) -> str:
        mid = 0.5 * (gap[0] + gap[1])
        open_ = [s for s in spans if s.start <= mid <= s.end]
        if not open_:
            return "none"
        return max(open_, key=lambda s: s.start).name   # innermost

    n = len(per_dev)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=float(np.mean(busy)) * 1e-9,
        kernel_s=float(np.mean(kern)) * 1e-9,
        collective_s=float(np.mean(coll)) * 1e-9,
        collective_exposed_s=float(np.mean(exposed)) * 1e-9,
        device_ops=[(k, v / n * 1e-9) for k, v in ops],
        idle_gaps=[(label(g), (g[1] - g[0]) * 1e-9) for g in gaps],
        n_devices=n)


def module_rounds(pd, window: Interval, rounds_per_execution: int,
                  name_part: str = "") -> float:
    """Rounds a scanned program executed inside ``window``: each of its
    executions on the first device plane's ``XLA Modules`` line counts
    ``rounds_per_execution`` times the share of it inside the window."""
    lo, hi = window
    mods = device_lines(pd, "XLA Modules")
    if not mods:
        return 0.0
    events = mods[sorted(mods)[0]]
    total = 0.0
    for e in events:
        if name_part not in e.name or e.end <= e.start:
            continue
        inside = max(0.0, min(e.end, hi) - max(e.start, lo))
        total += rounds_per_execution * inside / (e.end - e.start)
    return total


def window_of(pd, span: str = "window") -> Optional[Interval]:
    spans = host_spans(pd, [span])
    if not spans:
        return None
    return (min(s.start for s in spans), max(s.end for s in spans))
