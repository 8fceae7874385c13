"""Round phases on the device trace: every op of the compiled fleet scan
that does a phase's work carries exactly one top-level ``phase.*`` scope
in its HLO metadata, both sanitizers carry ``phase.sanitize``, and the
scopes change metadata only (docs/OBSERVABILITY.md)."""
import contextlib
import re

import pytest

from repro.core.topology import make_topology
from repro.data.synthetic import SyntheticImages
from repro.dfl import dynamics as dyn
from repro.dfl import engine
from repro.obs import profile as obs_profile

# op kinds that take device time of their own and come from traced code
TIMED = ("fusion", "convolution", "dot", "custom-call", "sort", "reduce-window")
# phases opened inside another phase
NESTED = ("data", "sanitize")
# the scan's own bookkeeping: reading a round's inputs, stacking its
# outputs, the loop counter
BOOKKEEPING = re.compile(r"while/body/(dynamic_slice|dynamic_update_slice|squeeze|add)$")

_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:body|condition|to_apply|branch_computations)=\{?([%\w.\-, ]+)\}?")


def _computations(hlo):
    """{computation name: [(instruction, opcode, op_name or None, text)]}
    and the entry computation's name."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            rest = m.group(2)
            head = rest.split(", metadata=")[0]
            op = _OPCODE.search(" " + head)
            name = _OP_NAME.search(rest)
            comps[cur].append((m.group(1), op.group(1) if op else "",
                               name.group(1) if name else None, rest))
    return comps, entry


def _scan_body_ops(hlo):
    """Every instruction of the outer scan's body, through nested loops,
    calls and conditionals (not into fused computations, whose ops run
    as their fusion)."""
    comps, entry = _computations(hlo)
    scan = [i for i in comps[entry] if i[1] == "while" and "phase." not in (i[2] or "")]
    assert len(scan) == 1, scan
    todo = [re.search(r"body=%?([\w.\-]+)", scan[0][3]).group(1)]
    seen, out = set(todo), []
    while todo:
        for ins in comps[todo.pop()]:
            out.append(ins)
            if ins[1] in ("while", "call", "conditional"):
                for m in _CALLS.finditer(ins[3]):
                    for c in m.group(1).split(","):
                        c = c.strip().lstrip("%")
                        if c in comps and c not in seen:
                            seen.add(c)
                            todo.append(c)
    return out


def _phases(op_name):
    return [p for p in re.findall(r"phase\.(\w+)", op_name)]


def _compile(chaos: bool, aggregator: str = "wfagg") -> str:
    topo = make_topology(n_nodes=8, degree=4, n_malicious=1, kind="ring",
                         placement="close", seed=0)
    data = SyntheticImages(seed=0)
    sched, faults = dyn.make_faulty_schedule("churn", topo, 2, fault="drop",
                                             intensity=0.3, seed=1, fault_seed=3)
    cfg = engine.DFLConfig(aggregator=aggregator, attack="ipm_0.5", model="mlp",
                           batches_per_round=2)
    carry, run, xs = engine.build_dynamic_scan_fn(
        cfg, topo, data, sched, n_test=32, telemetry=True,
        faults=faults if chaos else None)
    return run.lower(carry, *xs).compile().as_text()


@pytest.fixture(scope="module")
def compiled():
    return {"chaos": _compile(True), "static": _compile(False)}


@pytest.mark.parametrize("form", ["chaos", "static"])
def test_every_timed_op_of_the_round_has_one_phase(compiled, form):
    missing, doubled = [], []
    for name, op, op_name, _ in _scan_body_ops(compiled[form]):
        if op not in TIMED or op_name is None or name.startswith("copy"):
            continue   # XLA's own ops: loop-carry copies, reduction rewrites
        if BOOKKEEPING.search(op_name):
            continue
        path = _phases(op_name)
        top = [p for p in path if p not in NESTED]
        if not top:
            missing.append((name, op_name))
        elif len(top) > 1 or path[0] != top[0]:
            doubled.append((name, op_name))
        assert set(path) <= set(obs_profile.PHASES), op_name
    assert not missing, missing[:5]
    assert not doubled, doubled[:5]


def test_both_sanitizers_carry_the_sanitize_phase(compiled):
    paths = {tuple(_phases(op_name)) for _, _, op_name, _ in
             _scan_body_ops(compiled["chaos"]) if op_name}
    assert ("transport", "sanitize") in paths
    assert ("aggregate", "sanitize") in paths
    static = {tuple(_phases(op_name)) for _, _, op_name, _ in
              _scan_body_ops(compiled["static"]) if op_name}
    assert ("aggregate", "sanitize") in static
    assert not any("transport" in p for p in static)


def _strip(hlo):
    """The compiled text without metadata: op names, source locations
    and the stack-frame tables that index them."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"\n\nFileNames\n.*?(?=\n\n%|\n\nENTRY)", "", hlo, flags=re.S)


def test_phases_change_metadata_only(compiled, monkeypatch):
    monkeypatch.setattr(obs_profile, "phase", lambda name: contextlib.nullcontext())
    bare = _compile(True)
    assert "phase." not in bare
    assert _strip(bare) == _strip(compiled["chaos"])


def test_phase_names_are_checked():
    assert obs_profile.PHASES == ("local_train", "data", "attack", "transport",
                                  "sanitize", "aggregate", "evaluate")
    with pytest.raises(ValueError, match="unknown phase"):
        obs_profile.phase("training")
