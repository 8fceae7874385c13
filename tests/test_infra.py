"""Infrastructure coverage: checkpointing round-trip, the training
launcher CLI, data pipeline determinism, optimizer behaviours."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import SyntheticImages, TokenStream
from repro.optim.optimizers import make_optimizer, warmup_cosine
from repro.train import checkpoint as ckpt


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "layer": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                  "b": jnp.zeros((4,), jnp.bfloat16)},
        "step": jnp.asarray(7, jnp.int32),
    }
    ckpt.save_checkpoint(str(tmp_path), "t1", tree, {"note": "hi"})
    restored, meta = ckpt.restore_checkpoint(str(tmp_path), "t1", tree)
    assert meta["note"] == "hi"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_launcher_cli_end_to_end(tmp_path, capsys):
    """repro.launch.train main() runs a few robust steps and checkpoints."""
    from repro.launch import train as T
    T.main([
        "--arch", "qwen1.5-0.5b", "--reduced",
        "--d-model", "64", "--n-layers", "2", "--vocab", "128",
        "--steps", "3", "--seq-len", "32", "--global-batch", "4",
        "--chunk-size", "4096", "--sketch-dim", "128",
        "--log-every", "1",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
    ])
    out = capsys.readouterr().out
    assert "step     3" in out
    assert "done: 3 steps" in out
    assert os.path.exists(os.path.join(str(tmp_path), "step_3.npz"))


def test_launcher_donates_the_train_state():
    """build_everything keeps TrainConfig's donation: the step takes the
    state's buffers for its outputs, so the launcher holds one copy."""
    from repro.launch import train as T
    from repro.train import trainer as tr
    args = T.make_parser().parse_args([
        "--arch", "qwen1.5-0.5b", "--reduced", "--d-model", "64", "--n-layers", "2",
        "--vocab", "128", "--seq-len", "16", "--global-batch", "2",
        "--chunk-size", "4096", "--sketch-dim", "128"])
    cfg, mesh, tc = T.build_everything(args)
    assert tc.donate
    state = T.init_sharded_state(cfg, tc, mesh)
    step = tr.build_train_step(cfg, tc, mesh)
    batch = TokenStream(vocab_size=cfg.vocab_size, seq_len=16, batch_size=2).batch(0)
    with mesh:
        new_state, _ = step(state, batch)
    assert all(x.is_deleted() for x in jax.tree.leaves(state))
    assert not any(x.is_deleted() for x in jax.tree.leaves(new_state))


def test_token_stream_deterministic():
    s = TokenStream(vocab_size=256, seq_len=16, batch_size=4, seed=3)
    b1, b2 = s.batch(5), s.batch(5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    b3 = s.batch(6)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))
    assert int(b1["tokens"].max()) < 256


def test_synthetic_images_class_structure():
    """Same-label images must be closer than cross-label (learnable task)."""
    data = SyntheticImages()
    imgs, labels = data.batch(jax.random.PRNGKey(0), 256)
    imgs, labels = np.asarray(imgs), np.asarray(labels)
    tpl = np.asarray(data.templates())
    d_own = np.linalg.norm((imgs - tpl[labels]).reshape(256, -1), axis=1)
    d_other = np.linalg.norm((imgs - tpl[(labels + 1) % 10]).reshape(256, -1), axis=1)
    assert (d_own < d_other).mean() > 0.95


@pytest.mark.parametrize("name", ["sgd", "adamw", "adafactor"])
def test_optimizers_descend_quadratic(name):
    opt = make_optimizer(name)
    params = {"w": jnp.full((8,), 5.0)}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(60):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params, 0.1)
        params = jax.tree.map(lambda p, u: p + u, params, upd)
    assert float(loss(params)) < float(jnp.sum(jnp.full((8,), 5.0) ** 2)) * 0.2


def test_warmup_cosine_schedule():
    fn = warmup_cosine(1e-3, warmup=10, total=100)
    assert float(fn(0)) < 2e-4
    assert float(fn(10)) == pytest.approx(1e-3, rel=1e-3)
    assert float(fn(99)) < float(fn(50)) < float(fn(10))


def test_microbatched_gradients_match_full_batch():
    """TrainConfig.microbatches must not change the per-worker gradient."""
    import dataclasses
    from repro.configs.registry import get_config
    from repro.distributed.robust_allreduce import RobustAggConfig
    from repro.launch.mesh import make_test_mesh
    from repro.train import trainer as tr

    cfg = dataclasses.replace(
        get_config("qwen1.5-0.5b").reduced(), n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=128, head_dim=32)
    mesh = make_test_mesh(data=jax.device_count(), model=1)
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32, batch_size=8)
    batch = stream.batch(0)
    outs = {}
    for m in (1, 4):
        tc = tr.TrainConfig(mode="robust_dp",
                            agg=RobustAggConfig(method="mean", layout="stacked"),
                            microbatches=m, donate=False, lr=1e-2, warmup=0)
        state = tr.init_train_state(cfg, tc, jax.random.PRNGKey(0), mesh)
        step = tr.build_train_step(cfg, tc, mesh)
        with mesh:
            new_state, metrics = step(state, batch)
        outs[m] = (metrics["loss"], new_state.params)
    assert float(outs[1][0]) == pytest.approx(float(outs[4][0]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(outs[1][1]), jax.tree.leaves(outs[4][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def _chip_smoke():
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["fleet", "multi"])
def test_chip_smoke_refuses_non_tpu_backend(argv, capsys):
    """Without a TPU the bring-up check stops before any JAX work: a
    non-zero exit and no ``"ok"`` line, never a CPU fallback."""
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as e:
        _chip_smoke().main(argv)
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache sits
    at the fixed <repo>/.jax_cache, never a per-run directory."""
    from repro.launch import device as dev

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert dev.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = dev.use_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_peak_figures_keyed_by_device_kind():
    from repro.launch.mesh import peak_figures

    v5e = peak_figures("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bytes, v5e.hbm_bw) == (197e12, 16e9, 819e9)
    with pytest.raises(KeyError):
        peak_figures("cpu")
