"""The fleet round's phases on the device trace: which ``phase.*`` scope
of the program emitted each XLA op, and how much device time each phase
took.

The program opens ``jax.named_scope("phase.<name>")`` around each phase
of the round while it is traced (``repro.obs.profile.phase``; the names
are ``repro.obs.profile.PHASES``).  The scope reaches every compiled
HLO instruction's ``metadata={op_name="..."}``, as
``jit(run)/while/body/closed_call/jit(round_core)/phase.aggregate/...``.
A TPU trace names each op event by its HLO instruction
(``bench.trace.op_name``), and its ``/host:metadata`` plane keeps each
program's compiled module as an ``Hlo Proto``, so op → phase is a
lookup the trace itself answers.

Each op's self time (``bench.trace.self_times``, which takes a ``while``
op's body out of its own time) is charged to every phase on its path:
a parent holds the phases nested in it (``aggregate`` holds its
``sanitize``), and a phase opened under two parents sums over both.  An
op with no phase (the scan's own bookkeeping: loop-carry copies, the
stacking of outputs, the ``while`` itself; copies XLA adds) is charged
to ``unscoped``.  Seconds are averaged over the device planes, as
``Reduction.busy_s`` is.  A program without phase scopes reads only
``unscoped``.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, Iterator, Mapping, Optional, Sequence, Tuple

from bench import trace as trace_lib

UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"

_PHASE = re.compile(r"phase\.(\w+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def phase_path(op_name: str) -> Tuple[str, ...]:
    """The ``phase.*`` scopes of an op name, outermost first."""
    return tuple(dict.fromkeys(_PHASE.findall(op_name)))


def phase_map(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """{instruction name: its phase path} for every instruction of a
    compiled module's text (``compiled.as_text()``) whose metadata names
    a phase."""
    out = {}
    for line in hlo_text.splitlines():
        meta = _OP_NAME.search(line)
        name = _INSTR.match(line) if meta else None
        path = phase_path(meta.group(1)) if name else ()
        if path:
            out[name.group(1)] = path
    return out


# -- the Hlo Proto a trace keeps (protobuf wire format, the few fields read) --

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, val


def _sub(buf, number: int) -> Iterator[memoryview]:
    return (v for f, v in _fields(buf) if f == number)


def hlo_modules(xspace: bytes) -> Dict[str, bytes]:
    """{program name as the ``XLA Modules`` line names it, e.g.
    ``jit_run(7)``: its compiled ``Hlo Proto``} from a serialized
    XSpace's ``/host:metadata`` plane."""
    out = {}
    for plane in _sub(memoryview(xspace), 1):               # XSpace.planes
        fields = list(_fields(plane))
        if not any(f == 2 and bytes(v).decode() == METADATA_PLANE for f, v in fields):
            continue
        for f, entry in fields:
            if f != 4:                                      # XPlane.event_metadata
                continue
            for meta in _sub(entry, 2):                     # map value
                name = b"".join(bytes(v) for v in _sub(meta, 2)).decode()
                for stat in _sub(meta, 5):                  # XEventMetadata.stats
                    for blob in _sub(stat, 6):              # XStat.bytes_value
                        out[name] = bytes(blob)
    return out


def proto_phase_map(hlo_proto: bytes) -> Dict[str, Tuple[str, ...]]:
    """``phase_map`` of a serialized ``HloProto``."""
    out = {}
    for module in _sub(memoryview(hlo_proto), 1):            # HloProto.hlo_module
        for comp in _sub(module, 3):                        # .computations
            for ins in _sub(comp, 2):                       # .instructions
                name = op_name = ""
                for f, v in _fields(ins):
                    if f == 1:
                        name = bytes(v).decode()
                    elif f == 7:                            # .metadata
                        op_name = "".join(bytes(x).decode() for x in _sub(v, 2))
                path = phase_path(op_name)
                if path:
                    out[name] = path
    return out


# -- device time per phase -----------------------------------------------------

def phase_seconds(pd, window: trace_lib.Interval,
                  phase_of: Mapping[str, Sequence[str]]) -> Dict[str, float]:
    """{phase: device seconds inside ``window``}, each op's self time
    charged to every phase on its path (``unscoped`` where it has none),
    averaged over the device planes."""
    lo, hi = window
    per_dev = trace_lib.device_lines(pd, "XLA Ops")
    if not per_dev:
        raise ValueError("the trace holds no TPU device plane")
    total: Dict[str, float] = {}
    for events in per_dev.values():
        selfs, _ = trace_lib.self_times(events, lo, hi)
        for op, t in selfs.items():
            for p in phase_of.get(op) or (UNSCOPED,):
                total[p] = total.get(p, 0.0) + t
    return {p: t / len(per_dev) * 1e-9 for p, t in total.items()}


def module_in_window(pd, window: trace_lib.Interval) -> Optional[str]:
    """The program that ran longest inside ``window`` on the first device."""
    lo, hi = window
    mods = trace_lib.device_lines(pd, "XLA Modules")
    if not mods:
        return None
    inside: Dict[str, float] = {}
    for e in mods[sorted(mods)[0]]:
        inside[e.name] = inside.get(e.name, 0.0) + max(0.0, min(e.end, hi) - max(e.start, lo))
    return max(inside, key=inside.get) if inside else None


@functools.lru_cache(maxsize=1)
def _traced_phase_seconds(trace_dir: str) -> Dict[str, float]:
    path = trace_lib.find_xplane(trace_dir)
    pd = trace_lib.load(path)
    window = trace_lib.window_of(pd)
    with open(path, "rb") as f:
        protos = hlo_modules(f.read())
    module = module_in_window(pd, window)
    phase_of = proto_phase_map(protos[module]) if module in protos else {}
    return phase_seconds(pd, window, phase_of)


def run_phase_seconds(data) -> Dict[str, float]:
    """``phase_seconds`` of a traced run's window (a runner's per-layer
    data, whose ``trace_dir`` holds the trace), read once per trace."""
    return _traced_phase_seconds(data["trace_dir"])


def ms_per_round(data, phase: str) -> Optional[float]:
    """Device ms a round in ``phase``; None where the phase took no time."""
    s = run_phase_seconds(data).get(phase, 0.0)
    if s <= 0 or data["rounds"] <= 0:
        return None
    return 1e3 * s / data["rounds"]


def unscoped_share(data) -> Optional[float]:
    """Share (%) of busy time in ops no phase holds; None where no op
    holds a phase (a program without phase scopes)."""
    ps = run_phase_seconds(data)
    busy = data["reduction"].busy_s
    if busy <= 0 or not any(t > 0 for p, t in ps.items() if p != UNSCOPED):
        return None
    return 100.0 * ps.get(UNSCOPED, 0.0) / busy
