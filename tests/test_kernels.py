"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests.

Every Pallas kernel is executed in interpret=True mode (the kernel body
runs in Python on CPU) and compared against its pure-jnp oracle in ref.py.
The WFAgg round kernel's phases run alone here over an identity slate
(one node, its K candidates as the model matrix): phase 0 against
``robust_stats_ref``'s accumulators and ``pairwise_dist_ref``, phase 1
against ``weighted_agg_ref``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import trust
from repro.kernels.robust_stats.ops import (
    wfagg_round_indexed_combine, wfagg_round_indexed_stats)
from repro.kernels.robust_stats.ref import (
    pairwise_dist_ref, robust_stats_ref, weighted_agg_ref)

KS = [4, 5, 8, 9, 16, 20, 32]
DS = [128, 777, 2048]
BLOCKS = [256, 512]
# phase 0's accumulators: the median reaches them through dist2, dotmed
# and mednorm2
ACCS = ("dist2", "dotmed", "norm2", "mednorm2")


def _rand(key, shape, dtype, scale=3.0):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


def _slate(K):
    return jnp.arange(K, dtype=jnp.int32)[None, :]


def _stats(u, block_d=256, need_gram=False):
    """Phase 0 over one node's (K, D) candidates, the node axis dropped."""
    got = wfagg_round_indexed_stats(u, _slate(u.shape[0]),
                                    need_gram=need_gram, block_d=block_d)
    return jax.tree.map(lambda x: x[0], got)


def _combine(local, u, w, alpha, block_d=256):
    """Phase 1 over one node's candidates with the coefficients
    ``core.trust`` derives from trust weights ``w``."""
    wcomb, lcoef = trust.combine_coefficients(
        w, alpha, jnp.ones(w.shape, bool), False)
    return wfagg_round_indexed_combine(
        local[None], u, _slate(u.shape[0]), wcomb[None], lcoef[None],
        block_d=block_d)[0]


@pytest.mark.parametrize("K", KS)
@pytest.mark.parametrize("D", DS)
def test_robust_stats_matches_oracle(K, D):
    u = _rand(jax.random.PRNGKey(K * 1000 + D), (K, D), jnp.float32)
    got = _stats(u)
    ref = robust_stats_ref(u, beta=0.1)
    for name in got._fields:
        g = getattr(got, name)
        if g is None:  # no temporal tail without prev, no Gram, no centers
            assert name not in ACCS, name
            continue
        np.testing.assert_allclose(
            g, getattr(ref, name), rtol=3e-5, atol=3e-5, err_msg=name
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_robust_stats_dtypes(dtype):
    u = _rand(jax.random.PRNGKey(7), (8, 512), dtype)
    got = _stats(u)
    ref = robust_stats_ref(u.astype(jnp.float32), beta=0.1)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    for name in ("dotmed", "mednorm2"):
        np.testing.assert_allclose(getattr(got, name), getattr(ref, name),
                                   rtol=tol, atol=tol * 512, err_msg=name)
    np.testing.assert_allclose(got.dist2, ref.dist2, rtol=tol, atol=tol * 512)


@pytest.mark.parametrize("block_d", BLOCKS)
def test_robust_stats_block_invariance(block_d):
    """Kernel output must not depend on the VMEM block size."""
    u = _rand(jax.random.PRNGKey(3), (16, 1024), jnp.float32)
    a = _stats(u, block_d=block_d)
    b = _stats(u, block_d=1024)
    for name in a._fields:
        if getattr(a, name) is None:
            continue
        np.testing.assert_allclose(getattr(a, name), getattr(b, name), rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("K", [4, 8, 16, 31])
@pytest.mark.parametrize("D", [128, 1000])
def test_pairwise_matches_oracle(K, D):
    u = _rand(jax.random.PRNGKey(K + D), (K, D), jnp.float32)
    st_ = _stats(u, need_gram=True)
    got = trust.sq_dists_from_gram(st_.gram, st_.norm2)
    ref = pairwise_dist_ref(u)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-2)
    assert np.all(np.diag(np.asarray(got)) == 0.0)


@pytest.mark.parametrize("K", [4, 8, 16])
@pytest.mark.parametrize("alpha", [0.0, 0.5, 0.8, 1.0])
def test_weighted_agg_matches_oracle(K, alpha):
    key = jax.random.PRNGKey(K)
    k1, k2, k3 = jax.random.split(key, 3)
    u = _rand(k1, (K, 700), jnp.float32)
    local = _rand(k2, (700,), jnp.float32)
    w = jnp.abs(_rand(k3, (K,), jnp.float32))
    got = _combine(local, u, w, alpha)
    ref = weighted_agg_ref(local, u, w, alpha)
    np.testing.assert_allclose(got, ref, rtol=3e-5, atol=3e-5)


def test_weighted_agg_zero_weights_returns_local():
    u = _rand(jax.random.PRNGKey(0), (8, 300), jnp.float32)
    local = _rand(jax.random.PRNGKey(1), (300,), jnp.float32)
    got = _combine(local, u, jnp.zeros((8,)), alpha=0.8)
    np.testing.assert_allclose(got, local, rtol=1e-6, atol=1e-6)


# ------------------------- hypothesis property tests -------------------------

@settings(max_examples=25, deadline=None)
@given(
    K=st.integers(min_value=3, max_value=12),
    D=st.integers(min_value=1, max_value=300),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_median_permutation_invariance(K, D, seed):
    """The fused stats must be invariant to candidate order (the median,
    through mednorm2) and equivariant (row-permuted) for the
    per-candidate statistics."""
    u = np.asarray(_rand(jax.random.PRNGKey(seed), (K, D), jnp.float32))
    perm = np.random.default_rng(seed).permutation(K)
    a = _stats(jnp.asarray(u))
    b = _stats(jnp.asarray(u[perm]))
    np.testing.assert_allclose(a.mednorm2, b.mednorm2, rtol=1e-5, atol=1e-5)
    for name in ("dist2", "dotmed"):
        np.testing.assert_allclose(np.asarray(getattr(a, name))[perm],
                                   getattr(b, name), rtol=1e-4, atol=1e-4,
                                   err_msg=name)


@settings(max_examples=20, deadline=None)
@given(
    K=st.integers(min_value=3, max_value=10),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    shift=st.floats(min_value=-5.0, max_value=5.0, allow_nan=False),
)
def test_median_translation_equivariance(K, seed, shift):
    """median(u + c) == median(u) + c: distances to the median do not
    move, and the median's norm is the shifted oracle median's."""
    u = _rand(jax.random.PRNGKey(seed), (K, 256), jnp.float32)
    a = _stats(u)
    b = _stats(u + shift)
    np.testing.assert_allclose(a.dist2, b.dist2, rtol=1e-4, atol=1e-4)
    med = np.asarray(robust_stats_ref(u).med, np.float64) + shift
    np.testing.assert_allclose(b.mednorm2, np.sum(med * med), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# chunked online-softmax attention vs dense reference
# ---------------------------------------------------------------------------

def test_sdpa_chunked_matches_dense_causal():
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import layers as L
    B, H, S, hd = 2, 4, 512, 32
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, S, hd), jnp.float32)
    k = jax.random.normal(kk, (B, H, S, hd), jnp.float32)
    v = jax.random.normal(kv, (B, H, S, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    mask = (pos[:, None, :] <= pos[:, :, None])[:, None, :, :]
    scale = 1.0 / np.sqrt(hd)
    ref = L._sdpa(q, k, v, mask, scale)

    def mask_fn(off, C):
        kpos_c = off + jnp.arange(C)
        return (kpos_c[None, None, None, :] <= pos[:, None, :, None])

    out = L._sdpa_chunked(q, k, v, scale, mask_fn, chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sdpa_chunked_ragged_and_gradient():
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import layers as L
    B, H, Sq, Sk, hd = 1, 2, 64, 300, 16  # Sk not a chunk multiple
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, Sq, hd), jnp.float32)
    k = jax.random.normal(kk, (B, H, Sk, hd), jnp.float32)
    v = jax.random.normal(kv, (B, H, Sk, hd), jnp.float32)
    scale = 0.25

    ref = L._sdpa(q, k, v, None, scale)
    out = L._sdpa_chunked(q, k, v, scale, None, chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # gradients flow through the checkpointed scan
    g_ref = jax.grad(lambda q: L._sdpa(q, k, v, None, scale).sum())(q)
    g_out = jax.grad(
        lambda q: L._sdpa_chunked(q, k, v, scale, None, chunk=128).sum())(q)
    np.testing.assert_allclose(np.asarray(g_out), np.asarray(g_ref),
                               rtol=5e-5, atol=5e-5)


def test_rmsnorm_custom_vjp_matches_autodiff():
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.layers import _rmsnorm
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 64), jnp.float32)
    s = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,), jnp.float32)

    def ref(x, s):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, -1, keepdims=True)
        return xf * jax.lax.rsqrt(ms + 1e-5) * s

    o1 = _rmsnorm(x, s, 1e-5)
    o2 = ref(x, s)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5)
    g1 = jax.grad(lambda x, s: _rmsnorm(x, s, 1e-5).sum(), argnums=(0, 1))(x, s)
    g2 = jax.grad(lambda x, s: ref(x, s).sum(), argnums=(0, 1))(x, s)
    np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# flash-attention Pallas kernel (interpret mode) vs dense oracle
# ---------------------------------------------------------------------------

import itertools as _it

import pytest as _pytest


@_pytest.mark.parametrize("B,H,Sq,Sk,hd,causal,dtype", [
    (1, 2, 128, 128, 64, True, "float32"),
    (2, 1, 256, 256, 32, True, "float32"),
    (1, 1, 128, 384, 64, True, "float32"),    # decode-style Sq < Sk
    (1, 2, 130, 200, 32, True, "float32"),    # ragged (padding masked)
    (1, 1, 128, 256, 64, False, "float32"),
    (1, 2, 128, 128, 64, True, "bfloat16"),
])
def test_flash_attention_kernel_matches_ref(B, H, Sq, Sk, hd, causal, dtype):
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels.flash_attn.ops import flash_attention
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (B, H, Sk, hd), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (B, H, Sk, hd), jnp.float32).astype(dt)
    scale = 1.0 / np.sqrt(hd)
    out = flash_attention(q, k, v, scale, causal=causal, block_q=64, block_k=64)
    ref = flash_attention(q, k, v, scale, causal=causal, use_kernel=False)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_head_padding_is_exact():
    """pad_heads_to is a sharding-layout change only: outputs must be
    bit-comparable with the unpadded path."""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.registry import get_config
    from repro.models import layers as L
    cfg = dataclasses.replace(
        get_config("yi-6b").reduced(), n_heads=7, n_kv_heads=7, d_model=224,
        head_dim=32, vocab_size=64)
    cfgp = dataclasses.replace(cfg, pad_heads_to=8)
    key = jax.random.PRNGKey(0)
    p = L.init_attention(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 224), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    o1, _ = L.attention_fwd(cfg, p, x, pos)
    o2, _ = L.attention_fwd(cfgp, p, x, pos)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-6)
