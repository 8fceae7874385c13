"""The trainer cell's pieces on the CPU: it resolves every file by name,
its configuration keeps the published widths, the work counts match
hand counts, the token generator is a seeded Zipf law, the blocked
reference step computes what a whole-model gradient computes, and the
readers read a constructed trace by hand."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as harness, train_work
from bench.tests.helpers import ROOT, load_json

CELL = "train-qwen1.5-0.5b-k4"
READERS = ("mfu.train", "device_idle_share.train", "kernel_busy_share.train",
           "wfagg_round_roofline.train", "grad_ms_per_step.train",
           "aggregate_ms_per_step.train", "optimizer_ms_per_step.train",
           "collective_ms_per_step.train", "unscoped_share.train")


@pytest.fixture(scope="module")
def cell():
    return harness.resolve(load_json("BENCHMARK.json"), CELL, ROOT)


def small_config(cell, layers=2):
    return dict(cell.config, hidden_size=64, intermediate_size=128, head_dim=4,
                vocab_size=512, num_hidden_layers=layers)


def test_the_cell_resolves_its_config_reference_traffic_runner_and_readers(cell):
    assert cell.chips == 4 and cell.config["name"] == "qwen1.5-0.5b-8l"
    assert callable(cell.model.init) and callable(cell.model.RefTrainer)
    assert cell.mix["runner"] == "trainer" and callable(cell.runner.run)
    assert {m["name"] for m in cell.end_to_end} == {"rounds_per_s", "setup_s"}
    assert set(cell.readers) == set(READERS)
    assert all(m["workloads"] == [CELL] for m in cell.per_layer)


def test_the_configuration_keeps_the_published_widths(cell):
    """Every width is the program's qwen1.5-0.5b; only the depth is cut."""
    from repro.configs.registry import get_config

    c = cell.config
    assert c["arch"] == "qwen1.5-0.5b"
    p = get_config(c["arch"])
    assert (c["hidden_size"], c["intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["vocab_size"], c["rope_theta"],
            c["rms_norm_eps"], c["tie_word_embeddings"], c["attention_bias"]) == (
        p.d_model, p.d_ff, p.n_heads, p.n_kv_heads, p.head_dim_, p.vocab_size,
        p.rope_theta, p.norm_eps, p.tie_embeddings, p.qkv_bias)
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["published"]["num_hidden_layers"] == p.n_layers == 24
    assert c["num_hidden_layers"] == 8


def test_param_count_is_the_pytrees_of_program_and_reference(cell):
    from repro.configs.registry import get_config
    from repro.models import model as M

    pcfg = dataclasses.replace(get_config("qwen1.5-0.5b"), n_layers=8)
    prog = jax.eval_shape(lambda: M.init_params(pcfg, jax.random.PRNGKey(0)))
    ref = jax.eval_shape(lambda: cell.model.init(cell.config, jax.random.PRNGKey(0)))
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    size = sum(x.size for x in jax.tree.leaves(prog))
    assert size == sum(x.size for x in jax.tree.leaves(ref))
    assert train_work.param_count(cell.config) == size == 258_384_896


def test_work_counts_match_hand_counts():
    c = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 2, "head_dim": 4, "vocab_size": 10, "num_hidden_layers": 3}
    mix = {"workers": 4, "seqs_per_worker": 2, "seq_len": 5}
    # per layer: q, k, v, o 4 * 8 * 8 = 256, QKV bias 24, MLP 3 * 8 * 16 =
    # 384, two norms 16 -> 680; embedding 80, final norm 8
    assert train_work.param_count(c) == 80 + 3 * 680 + 8
    # a token a layer: 2 * (256 + 384) matmul FLOPs + 2 * 2 * 5 * 8 = 160
    # attention; head 2 * 8 * 10 for 4 of the 5 positions
    fwd = 3 * (2 * 640 + 160) * 5 + 2 * 8 * 10 * 4
    assert train_work.forward_flops(c, 5) == fwd
    assert train_work.train_step_flops(c, mix) == 3 * 8 * fwd
    assert train_work.wfagg_step_bytes(c, 4) == 9 * train_work.param_count(c) * 4


def test_the_cells_step_is_3_6_tflop_a_worker(cell):
    per_worker = train_work.train_step_flops(cell.config, cell.mix) / cell.mix["workers"]
    assert per_worker == pytest.approx(3.59e12, rel=0.01)


def test_tokens_are_a_seeded_zipf_law(cell):
    gen = cell.runner.step_tokens
    cdf = cell.runner.zipf_cdf(1000, 1.1)
    mix = dict(cell.mix, seq_len=4096)
    seed = 2 ** 31 + 77
    a, b = gen(seed, 3, mix, cdf), gen(seed, 3, mix, cdf)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (4, 4096) and a.dtype == np.int32
    assert not np.array_equal(a, gen(seed, 4, mix, cdf))
    assert not np.array_equal(a, gen(seed + 1, 3, mix, cdf))
    assert 0 <= a.min() and a.max() < 1000
    counts = np.bincount(a.ravel(), minlength=1000)
    # id 0 against id 1: 2^1.1 = 2.14 times as frequent
    assert counts[0] / counts[1] == pytest.approx(2 ** 1.1, rel=0.1)


def test_blocked_reference_step_is_the_whole_model_gradient(cell):
    """The blocked reference (layer-by-layer vjp, chunked loss) gives the
    loss and gradient the plain reference of ``repro.models.reference``
    gives with one ``jax.grad``, on the same weights (float32)."""
    from repro.configs.registry import get_config
    from repro.models import reference as R

    cfg = small_config(cell)
    M = cell.model
    params = M.init(cfg, jax.random.PRNGKey(5))
    params = jax.tree.map(lambda x: x + 0.02 * jax.random.normal(jax.random.PRNGKey(x.size),
                                                                 x.shape), params)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 700), 0, cfg["vocab_size"])
    loss, grads = M.worker_grad(M.dims(cfg), False, M._split(params), tokens)
    pcfg = dataclasses.replace(get_config("qwen1.5-0.5b"), n_layers=2, d_model=64,
                               head_dim=4, d_ff=128, vocab_size=512)
    ref_loss, ref_grads = jax.jit(lambda p: R.loss_and_grad(pcfg, p, tokens))(params)
    assert loss == pytest.approx(float(ref_loss), rel=1e-5)
    for name, g in M._split(ref_grads).items():
        gap = float(jnp.linalg.norm(grads[name] - g) / jnp.linalg.norm(g))
        assert gap <= 1e-4, (name, gap)


NAMES = {1: "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
         2: "%wfagg_round_indexed.3 = f32[4]{0} custom-call(f32[4]{0} %p)",
         3: "%all-gather.5 = f32[8]{0} all-gather(f32[4]{0} %x)",
         4: "jit_jit_step(1)", 5: "jit_jit_step(2)"}


def _trace():
    """Two steps on two chips inside a window [0, 1000) ns: chip 0 busy
    [0, 400) and [500, 900) with the kernel [100, 300) and [600, 800) and
    an all-gather [300, 400); chip 1 busy [0, 200) with the kernel
    [0, 100)."""
    from jax.profiler import ProfileData

    def events(spec):
        return "".join(f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
                       f"duration_ps: {(e - s) * 1000} }} " for m, s, e in spec)

    def meta(ids, names=NAMES):
        return "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: \"{names[i]}\" }} }} "
                       for i in ids)

    tpu0 = ('planes { id: 1 name: "/device:TPU:0" '
            'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
            + events([(1, 0, 100), (2, 100, 300), (3, 300, 400), (1, 500, 600),
                      (2, 600, 800), (1, 800, 900)]) + "} "
            'lines { id: 2 name: "XLA Modules" timestamp_ns: 0 '
            + events([(4, 0, 400), (5, 500, 900)]) + "} " + meta([1, 2, 3, 4, 5]) + "} ")
    tpu1 = ('planes { id: 2 name: "/device:TPU:1" '
            'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
            + events([(2, 0, 100), (1, 100, 200)]) + "} " + meta([1, 2]) + "} ")
    host = ('planes { id: 3 name: "/host:CPU" lines { id: 1 name: "main" timestamp_ns: 0 '
            + events([(1, 0, 1000)]) + "} " + meta([1], {1: "window"}) + "} ")
    return ProfileData.from_text_proto(tpu0 + tpu1 + host)


def test_readers_read_a_constructed_trace_by_hand(cell, monkeypatch):
    from bench import phases as P

    c = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 2, "head_dim": 4, "vocab_size": 10, "num_hidden_layers": 3}
    mix = {"workers": 2, "seqs_per_worker": 1, "seq_len": 5}
    data = cell.runner.step_layer_data(_trace(), {"wfagg_round_indexed.3"}, "jit_jit_step",
                                       c, mix, "TPU v5 lite", 2, "unused")
    assert data["rounds"] == 2 and data["per_device"] == [
        pytest.approx((800e-9, 400e-9)), pytest.approx((200e-9, 100e-9))]
    ps = {"grad": 3e-7, "data": 1e-8, "attack": 1e-8, "aggregate": 6e-7,
          "optimizer": 1e-7, P.UNSCOPED: 5e-8}
    monkeypatch.setattr(P, "run_phase_seconds", lambda d: ps)
    r = {name: cell.readers[name].read(data) for name in READERS}
    peak, hbm = 197e12, 819e9
    assert r["mfu.train"] == pytest.approx(
        100 * train_work.train_step_flops(c, mix) * 2 / (1000e-9 * 2 * peak))
    # busy 800 and 200 of 1000 ns -> idle 20 % and 80 %, mean 50 %
    assert r["device_idle_share.train"] == pytest.approx(50.0)
    # the busiest chip: kernel 400 of 800 ns
    assert r["kernel_busy_share.train"] == pytest.approx(50.0)
    least = train_work.wfagg_step_bytes(c, 2) * 2 / hbm
    assert r["wfagg_round_roofline.train"] == pytest.approx(100 * least / 500e-9)
    assert r["grad_ms_per_step.train"] == pytest.approx(1e3 * 3e-7 / 2)
    assert r["aggregate_ms_per_step.train"] == pytest.approx(1e3 * 6e-7 / 2)
    assert r["optimizer_ms_per_step.train"] == pytest.approx(1e3 * 1e-7 / 2)
    # the all-gather on chip 0 only: 100 ns, mean 50 ns, over 2 steps
    assert r["collective_ms_per_step.train"] == pytest.approx(1e3 * 50e-9 / 2)
    # busy mean 500 ns
    assert r["unscoped_share.train"] == pytest.approx(100 * 5e-8 / 500e-9)
    # a program without phase scopes: the phase readers read nothing
    monkeypatch.setattr(P, "run_phase_seconds", lambda d: {P.UNSCOPED: 5e-7})
    for name in ("grad_ms_per_step.train", "aggregate_ms_per_step.train",
                 "optimizer_ms_per_step.train", "unscoped_share.train"):
        assert cell.readers[name].read(data) is None
    assert cell.readers["kernel_busy_share.train"].read(
        dict(data, n_kernels=0)) is None


def test_the_runner_refuses_a_program_that_differs_from_the_cell(cell):
    """The parent's qwen1.5-0.5b had rope_theta 1e4 and eps 1e-5: a run
    stops before any step."""
    from repro.configs.registry import get_config
    from repro.launch import train as launch

    t = cell.runner.Trainer(cell.model, dict(cell.config, rope_theta=10000.0),
                            cell.mix, 1)
    pcfg, _, tc = launch.build_everything(launch.make_parser().parse_args(t.program_argv()))
    mesh = types.SimpleNamespace(shape={"data": 4, "model": 1})
    with pytest.raises(RuntimeError, match="rope_theta"):
        t._check_program(pcfg, mesh, tc)
    assert get_config("qwen1.5-0.5b").rope_theta == 1e6
