"""The trace reduction on a small constructed trace with hand-checked
numbers (times in ns; XSpace offsets and durations are picoseconds)."""
import pytest

from bench import trace as T

NAMES = {1: "fusion.1", 2: "custom-call.7", 3: "all-reduce.2", 4: "jit_run(1)",
         5: "jit_run(2)", 6: "fusion.9",
         7: "%while.3 = (s32[], f32[4]) while((s32[], f32[4]) %tuple.1)",
         8: "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
         9: "%all-gather.5 = f32[8]{0} all-gather(f32[4]{0} %x)"}


def _events(spec):
    return "".join(f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000} }} " for m, s, e in spec)


def _meta(ids, names=NAMES):
    return "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: \"{names[i]}\" }} }} "
                   for i in ids)


def _trace():
    from jax.profiler import ProfileData
    tpu0 = (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
        + _events([(1, 100, 300), (2, 300, 600), (3, 550, 700), (1, 800, 880)]) + "} "
        'lines { id: 2 name: "XLA Modules" timestamp_ns: 0 '
        + _events([(4, 100, 700), (5, 800, 1200)]) + "} "
        + _meta([1, 2, 3, 4, 5]) + "} ")
    tpu1 = ('planes { id: 2 name: "/device:TPU:1" '
            'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
            + _events([(6, 0, 1000)]) + "} " + _meta([6]) + "} ")
    host_names = {1: "window", 2: "dispatch", 3: "readback"}
    host = ('planes { id: 3 name: "/host:CPU" '
            'lines { id: 1 name: "main" timestamp_ns: 0 '
            + _events([(1, 0, 1000), (2, 10, 60), (3, 700, 820)]) + "} "
            + _meta([1, 2, 3], host_names) + "} ")
    return ProfileData.from_text_proto(tpu0 + tpu1 + host)


def test_window_comes_from_the_host_span():
    assert T.window_of(_trace()) == (0.0, 1000.0)


def test_busy_kernel_collective_and_exposed_time():
    red = T.reduce(_trace(), (0.0, 1000.0), kernel_names={"custom-call.7"},
                   span_names=("window", "dispatch", "readback"))
    assert red.n_devices == 2
    assert red.window_s == pytest.approx(1000e-9)
    # TPU:0 busy [100, 700) + [800, 880) = 680; TPU:1 busy 1000
    assert red.busy_s == pytest.approx(840e-9)
    # kernel 300 on TPU:0, none on TPU:1
    assert red.kernel_s == pytest.approx(150e-9)
    # all-reduce [550, 700): 150, of which [600, 700) overlaps no compute
    assert red.collective_s == pytest.approx(75e-9)
    assert red.collective_exposed_s == pytest.approx(50e-9)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    red = T.reduce(_trace(), (0.0, 1000.0), kernel_names={"custom-call.7"},
                   span_names=("window", "dispatch", "readback"))
    gaps = [(name, round(s * 1e9)) for name, s in red.idle_gaps]
    assert gaps == [("window", 120), ("dispatch", 100), ("readback", 100)]


def test_top_device_ops_are_averaged_over_devices():
    red = T.reduce(_trace(), (0.0, 1000.0))
    ops = {name: round(s * 1e9, 3) for name, s in red.device_ops}
    assert ops == {"fusion.9": 500.0, "custom-call.7": 150.0,
                   "fusion.1": 140.0, "all-reduce.2": 75.0}
    assert [n for n, _ in red.device_ops][0] == "fusion.9"


def test_window_clips_events():
    red = T.reduce(_trace(), (200.0, 400.0), kernel_names={"custom-call.7"})
    # TPU:0 [200, 400) busy; TPU:1 busy throughout
    assert red.busy_s == pytest.approx(200e-9)
    assert red.kernel_s == pytest.approx(50e-9)


def test_module_rounds_count_the_share_inside_the_window():
    # jit_run(1) wholly inside (5 rounds), jit_run(2) half inside (2.5)
    assert T.module_rounds(_trace(), (0.0, 1000.0), 5, "jit_run") == pytest.approx(7.5)
    assert T.module_rounds(_trace(), (0.0, 1000.0), 5, "other") == 0.0


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.length(T.clip([(0, 10), (20, 30)], 5, 25)) == 10


def test_a_trace_without_a_device_plane_is_refused():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError, match="no TPU device plane"):
        T.reduce(pd, (0.0, 1.0))


def test_op_names_self_time_and_nested_collectives():
    """TPU traces name op events by their instruction text, and a while
    op spans the ops of its body; exposure counts only leaf compute."""
    from jax.profiler import ProfileData
    assert T.op_name("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %a)") == "fusion.3"
    assert T.op_name("custom-call.7") == "custom-call.7"
    pd = ProfileData.from_text_proto(
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
        + _events([(7, 0, 100), (8, 10, 40), (9, 30, 70)]) + "} "
        + _meta([7, 8, 9]) + "} ")
    red = T.reduce(pd, (0.0, 100.0))
    ops = {name: round(s * 1e9, 3) for name, s in red.device_ops}
    # while: 100 - fusion 30 - all-gather 40 = 30 of its own
    assert ops == {"all-gather.5": 40.0, "fusion.2": 30.0, "while.3": 30.0}
    assert red.busy_s == pytest.approx(100e-9)
    # all-gather [30, 70) overlaps fusion.2 on [30, 40) only
    assert red.collective_s == pytest.approx(40e-9)
    assert red.collective_exposed_s == pytest.approx(30e-9)
