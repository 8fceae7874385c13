"""The program against the plain float32 reference of Qwen1.5 and of the
robust-DP step (``repro.models.reference``), on seeded random weights
at a small size: 2 layers, d_model 64, vocab 512, QKV bias, tied
embeddings, rope_theta 1e6, eps 1e-6 (the published qwen1.5-0.5b
config cut in width and depth only); and the training step's phase
scopes, which change its compiled HLO's metadata only."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import model as M
from repro.models import reference as R

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Tolerances, each a relative L2 norm of the difference over the
# reference's norm (per leaf for the gradients).  float32: the program
# sums in other orders (chunked loss, remat) and rounds ~1e-7 a value;
# readings 6e-7 (logits), 7e-8 (loss), 1.2e-6 (gradients).  bfloat16,
# the published compute dtype: activations round at 2^-8 = 0.39 %,
# compounded through 2 layers and a 512-way softmax; readings 1.5 %,
# 0.019 % and 2.3 %.
TOL = {"float32": {"logits": 1e-5, "loss": 1e-6, "grads": 1e-5},
       "bfloat16": {"logits": 4e-2, "loss": 1e-3, "grads": 6e-2}}


def small_config(dtype):
    return dataclasses.replace(
        get_config("qwen1.5-0.5b"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512, loss_chunk=8,
        dtype=dtype)


def test_published_rope_theta_and_norm_eps():
    cfg = get_config("qwen1.5-0.5b")
    assert cfg.rope_theta == 1_000_000.0 and cfg.norm_eps == 1e-6
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size, cfg.qkv_bias, cfg.tie_embeddings) == (
        24, 1024, 16, 16, 2816, 151936, True, True)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def pair(request):
    """Program and reference outputs on one set of seeded weights (norm
    scales and biases moved off their 1 / 0 init so that they count)."""
    cfg = small_config(request.param)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    prog_logits = jax.jit(lambda p: M.forward(cfg, p, batch)[0])(params)
    (prog_loss, _), prog_grads = jax.jit(jax.value_and_grad(
        lambda p: M.loss_fn(cfg, p, batch), has_aux=True))(params)
    ref_logits = jax.jit(lambda p: R.forward(cfg, p, tokens))(params)
    ref_loss, ref_grads = jax.jit(lambda p: R.loss_and_grad(cfg, p, tokens))(params)
    return request.param, {
        "logits": (prog_logits, ref_logits), "loss": (prog_loss, ref_loss),
        "grads": (prog_grads, ref_grads)}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("what", ["logits", "loss", "grads"])
def test_program_matches_reference(pair, what):
    dtype, out = pair
    prog, ref = out[what]
    gaps = ({jax.tree_util.keystr(k): _rel(a, b) for (k, a), b in zip(
        jax.tree_util.tree_leaves_with_path(prog), jax.tree.leaves(ref))}
        if what == "grads" else {what: _rel(prog, ref)})
    assert max(gaps.values()) <= TOL[dtype][what], gaps


@pytest.fixture(scope="module")
def robust_dp_steps():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4").strip(),
               PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run([sys.executable, os.path.join(REPO, "tests",
                                                     "_robust_dp_reference_main.py")],
                       capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_training_step_phases_change_metadata_only(robust_dp_steps):
    """The fused stacked step names its phases on the device trace
    (``grad`` holding ``data``, ``attack``, ``aggregate``, ``optimizer``)
    and compiles to the same program as with every scope a null
    context, once metadata is stripped."""
    assert set(robust_dp_steps["phases"]) == {"grad", "data", "attack", "aggregate",
                                              "optimizer"}
    assert robust_dp_steps["bare_phases"] == []
    assert robust_dp_steps["same_program"] is True


@pytest.mark.parametrize("step", [0, 1], ids=["one_step", "two_steps"])
def test_robust_dp_step_matches_reference(robust_dp_steps, step):
    """Four workers on four virtual devices, fused stacked WFAgg, one
    IPM-0.5 worker.  Tolerances: per-worker losses to float32 rounding
    (rtol 1e-5; readings <= 2e-7); the accept decisions exactly; Adam's
    first moment, a running mean of the aggregated gradient, to 2e-5
    (readings <= 4e-6); the parameters' change to 1e-3, and the key
    bias's to 1e-2: Adam divides each coordinate by its own magnitude,
    so a coordinate whose gradient is rounding noise (the key bias, to
    which the softmax is invariant) moves by +-lr with either sign
    (readings 2e-4 and 3.4e-3)."""
    s = robust_dp_steps["steps"][step]
    np.testing.assert_allclose(s["losses"], s["ref_losses"], rtol=1e-5)
    w, rw = np.asarray(s["weights"]), np.asarray(s["ref_weights"])
    np.testing.assert_array_equal(w > 0, rw > 0)
    np.testing.assert_allclose(w, rw, rtol=1e-6)
    assert s["n_accepted"] == int((rw > 0).sum())
    assert max(s["adam_m"].values()) <= 2e-5, s["adam_m"]
    for leaf, gap in s["param_change"].items():
        assert gap <= (1e-2 if "bk" in leaf else 1e-3), (leaf, gap)
