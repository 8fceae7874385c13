"""Per-architecture smoke tests: REDUCED variant of each assigned arch
(2 layers, d_model<=256, <=4 experts) runs one robust-dp train step and
one decode step on CPU; asserts output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core.wfagg import WFAggConfig
from repro.data.specs import ENC_LEN_DECODE, dummy_batch
from repro.distributed.robust_allreduce import RobustAggConfig
from repro.launch.mesh import make_test_mesh
from repro.models import model as M
from repro.train.trainer import TrainConfig, build_train_step, init_train_state

ARCH_NAMES = sorted(ARCHS)


def _mesh():
    return make_test_mesh(1, 1)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_reduced_train_step(name):
    cfg = ARCHS[name].reduced()
    mesh = _mesh()
    tc = TrainConfig(
        mode="robust_dp",
        lr=1e-3,
        agg=RobustAggConfig(method="mean", chunk_size=4096,
                            wfagg=WFAggConfig(use_temporal=False)),
    )
    state = init_train_state(cfg, tc, jax.random.PRNGKey(0), mesh)
    step = build_train_step(cfg, tc, mesh)
    batch = dummy_batch(cfg, 2, 32)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), name
    assert np.isfinite(float(metrics["grad_norm"])), name
    for leaf in jax.tree.leaves(state.params):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32))), name


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_reduced_decode_step(name):
    cfg = ARCHS[name].reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, total = 2, 16
    cache = M.init_cache(cfg, B, total,
                         enc_len=ENC_LEN_DECODE if cfg.is_encoder_decoder else 0)
    if cfg.is_encoder_decoder:
        cache["enc_out"] = 0.1 * jnp.ones_like(cache["enc_out"])
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab_size)
    step = jax.jit(lambda c, t: M.decode_step(cfg, params, c, t))
    for _ in range(3):
        logits, cache = step(cache, toks)
    assert logits.shape == (B, 1, cfg.vocab_size), name
    assert np.all(np.isfinite(np.asarray(logits, np.float32))), name
    assert int(cache["idx"]) == 3


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_full_config_metadata(name):
    """The FULL configs are exercised only via the dry-run; here we check
    the analytic parameter counts are in the advertised ballpark."""
    cfg = ARCHS[name]
    n = cfg.param_count()
    expected = {
        "moonshot-v1-16b-a3b": (10e9, 40e9),
        "stablelm-3b": (2e9, 4e9),
        "zamba2-1.2b": (0.8e9, 1.8e9),
        "arctic-480b": (400e9, 520e9),
        "deepseek-v2-lite-16b": (12e9, 20e9),
        "yi-6b": (5e9, 7e9),
        "seamless-m4t-medium": (0.5e9, 1.5e9),
        "falcon-mamba-7b": (6e9, 8.5e9),
        "qwen1.5-0.5b": (0.3e9, 0.7e9),
        "llava-next-34b": (30e9, 38e9),
    }[name]
    assert expected[0] <= n <= expected[1], (name, n)


@pytest.mark.parametrize("chunk", [8, 64])
def test_chunked_loss_counts_every_position(chunk):
    """Chunked cross-entropy equals the unchunked loss when the sequence
    (31 predicted positions) is not a multiple of the chunk, and when it
    is shorter than one chunk: the ragged tail is not dropped."""
    import dataclasses

    cfg = ARCHS["qwen1.5-0.5b"].reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = dummy_batch(cfg, 2, 32)
    full, _ = M.loss_fn(dataclasses.replace(cfg, loss_chunk=0), params, batch)
    chunked, _ = M.loss_fn(dataclasses.replace(cfg, loss_chunk=chunk),
                           params, batch)
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-5)
