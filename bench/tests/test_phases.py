"""Device time per round phase: the HLO metadata parsers, the ``Hlo
Proto`` a trace keeps, the charging of self times on a small
constructed trace with hand-checked numbers (ns), and the eight
readers."""
import pytest

from bench import phases as P
from bench.tests.helpers import ROOT, load_json

NAMES = {1: "%while.1 = (s32[], f32[4]) while((s32[], f32[4]) %tuple.1)",
         2: "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
         3: "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
         4: "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
         5: "%fusion.4 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
         6: "%wfagg_round_indexed.14 = f32[4]{0} custom-call(f32[4]{0} %p)",
         7: "%fusion.6 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
         8: "%copy.7 = f32[4]{0} copy(f32[4]{0} %p)",
         9: "%fusion.8 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
         10: "jit_run(3)"}
PHASE_OF = {"fusion.1": ("local_train",), "fusion.2": ("local_train", "data"),
            "fusion.3": ("transport", "sanitize"), "fusion.4": ("transport",),
            "wfagg_round_indexed.14": ("aggregate",),
            "fusion.6": ("aggregate", "sanitize"), "fusion.8": ("evaluate",)}
TOP = ("local_train", "attack", "transport", "aggregate", "evaluate")


def _events(spec):
    return "".join(f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000} }} " for m, s, e in spec)


def _meta(ids):
    return "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: \"{NAMES[i]}\" }} }} "
                   for i in ids)


def _trace(second_device=False):
    """One round: a while [0, 1000) holding local training, transport
    with its sanitizer, the kernel, the aggregation's sanitizer, an
    unscoped copy and evaluation; the while's own time is [950, 1000)."""
    from jax.profiler import ProfileData
    ops = [(1, 0, 1000), (2, 0, 300), (3, 300, 400), (4, 400, 500), (5, 500, 600),
           (6, 600, 800), (7, 800, 850), (8, 850, 900), (9, 900, 950)]
    text = ('planes { id: 1 name: "/device:TPU:0" '
            'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 ' + _events(ops) + "} "
            'lines { id: 2 name: "XLA Modules" timestamp_ns: 0 '
            + _events([(10, 0, 1000)]) + "} " + _meta(range(1, 11)) + "} ")
    if second_device:
        text += ('planes { id: 2 name: "/device:TPU:1" '
                 'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
                 + _events([(2, 0, 200), (8, 200, 400)]) + "} " + _meta([2, 8]) + "} ")
    return ProfileData.from_text_proto(text)


def test_self_times_are_charged_to_every_phase_on_the_path():
    ns = {p: round(s * 1e9, 3) for p, s in
          P.phase_seconds(_trace(), (0.0, 1000.0), PHASE_OF).items()}
    # parents are inclusive; sanitize sums its transport and aggregate sites
    assert ns == {"local_train": 400.0, "data": 100.0, "transport": 200.0,
                  "sanitize": 150.0, "aggregate": 250.0, "evaluate": 50.0,
                  P.UNSCOPED: 100.0}
    # the top-level phases and unscoped add up to the self-time total
    assert sum(ns.get(p, 0.0) for p in TOP + (P.UNSCOPED,)) == 1000.0


def test_phase_seconds_are_averaged_over_devices_and_clipped_to_the_window():
    ns = {p: round(s * 1e9, 3) for p, s in
          P.phase_seconds(_trace(True), (0.0, 1000.0), PHASE_OF).items()}
    assert ns["local_train"] == (400.0 + 200.0) / 2
    assert ns[P.UNSCOPED] == (100.0 + 200.0) / 2
    clipped = P.phase_seconds(_trace(), (0.0, 350.0), PHASE_OF)
    assert {p: round(s * 1e9, 3) for p, s in clipped.items()} == {
        "local_train": 350.0, "data": 50.0, P.UNSCOPED: 0.0}


def test_a_program_without_scopes_reads_only_unscoped():
    ns = P.phase_seconds(_trace(), (0.0, 1000.0), {})
    assert list(ns) == [P.UNSCOPED]
    assert ns[P.UNSCOPED] == pytest.approx(1000e-9)


def test_the_program_in_the_window_is_found():
    assert P.module_in_window(_trace(), (0.0, 1000.0)) == "jit_run(3)"


def test_phase_map_parses_compiled_hlo_lines():
    hlo = "\n".join([
        '  %fusion.896 = f32[20,44426]{1,0:T(8,128)} fusion(f32[20,44426]{1,0} %a), '
        'kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run)/while/'
        'body/closed_call/jit(round_core)/phase.local_train/vmap()/while/body/'
        'closed_call/transpose(jvp())/dot_general" source_file="engine.py" '
        'source_line=171}',
        '  ROOT %select.3 = f32[8]{0} select(pred[8]{0} %p, f32[8]{0} %a, f32[8]{0} %b), '
        'metadata={op_name="jit(run)/while/body/closed_call/jit(round_core)/'
        'phase.aggregate/phase.sanitize/jit(_where)/select_n" stack_frame_id=3}',
        '  %copy.834 = f32[4]{0} copy(f32[4]{0} %x)',
        '  %add.1 = s32[] add(s32[] %i, s32[] %one), metadata={op_name="jit(run)/while/body/add"}',
    ])
    assert P.phase_map(hlo) == {"fusion.896": ("local_train",),
                                "select.3": ("aggregate", "sanitize")}
    assert P.phase_path("a/phase.x/b/phase.y/phase.x/c") == ("x", "y")


def test_the_hlo_proto_of_a_trace_gives_the_compiled_text_phases(tmp_path):
    """A real (CPU) trace of a scanned program with phase scopes: the
    ``Hlo Proto`` on its metadata plane names the same instructions and
    phases as the compiled text."""
    import jax
    import jax.numpy as jnp

    from bench import trace as T

    def body(c, _):
        with jax.named_scope("phase.local_train"):
            y = jnp.tanh(c @ c)
        with jax.named_scope("phase.aggregate"), jax.named_scope("phase.sanitize"):
            y = jnp.where(jnp.isfinite(y), y, 0.0)
        return y, y.sum()

    def phased_scan(x):
        return jax.lax.scan(body, x, None, length=3)

    run = jax.jit(phased_scan)
    x = jnp.ones((16, 16))
    compiled = run.lower(x).compile()
    jax.block_until_ready(compiled(x))
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(compiled(x))
    jax.profiler.stop_trace()
    with open(T.find_xplane(str(tmp_path)), "rb") as f:
        protos = P.hlo_modules(f.read())
    mine = [k for k in protos if k.startswith("jit_phased_scan(")]
    assert len(mine) == 1
    from_trace = P.proto_phase_map(protos[mine[0]])
    from_text = P.phase_map(compiled.as_text())
    assert from_trace == from_text
    assert {("local_train",), ("aggregate", "sanitize")} <= set(from_text.values())


def _readers(cell):
    from bench.run import resolve
    return resolve(load_json("BENCHMARK.json"), cell, ROOT).readers


PHASE_READERS = {"local_train_ms_per_round.fleet": "local_train",
                 "data_ms_per_round.fleet": "data",
                 "attack_ms_per_round.fleet": "attack",
                 "transport_ms_per_round.fleet": "transport",
                 "sanitize_ms_per_round.fleet": "sanitize",
                 "aggregate_ms_per_round.fleet": "aggregate",
                 "evaluate_ms_per_round.fleet": "evaluate"}


@pytest.mark.parametrize("cell", ["fleet-paper20-ipm05", "fleet-er1024-churn-drop30-ipm05"])
def test_readers_read_their_phase_and_none_without_it(cell, monkeypatch):
    from types import SimpleNamespace

    from repro.obs.profile import PHASES

    readers = _readers(cell)
    mine = {n for n in PHASE_READERS if n in readers}
    expected = set(PHASE_READERS) - ({"transport_ms_per_round.fleet"}
                                     if cell == "fleet-paper20-ipm05" else set())
    assert mine == expected and "unscoped_share.fleet" in readers
    assert set(PHASE_READERS.values()) == set(PHASES)
    data = {"trace_dir": "unused", "rounds": 4.0,
            "reduction": SimpleNamespace(busy_s=0.02)}
    ps = {p: 1e-3 * (i + 1) for i, p in enumerate(PHASES)}
    ps[P.UNSCOPED] = 5e-4
    monkeypatch.setattr(P, "run_phase_seconds", lambda d: ps)
    for name in mine:
        assert readers[name].read(data) == pytest.approx(1e3 * ps[PHASE_READERS[name]] / 4)
    assert readers["unscoped_share.fleet"].read(data) == pytest.approx(2.5)
    # a program without phase scopes: every reader reads nothing
    monkeypatch.setattr(P, "run_phase_seconds", lambda d: {P.UNSCOPED: 0.02})
    for name in list(mine) + ["unscoped_share.fleet"]:
        assert readers[name].read(data) is None
