"""Shared neural-net layers: norms, RoPE, GQA/MLA attention, SwiGLU, MoE.

Functional style: ``init_*`` builds a param dict, ``*_fwd`` applies it.
All forward functions accept an optional KV-cache for decode and annotate
activations with logical sharding axes (no-ops outside a mesh context).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.logical import shard

Array = jax.Array
Params = Dict[str, Any]


def _dense_init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(fan_in)
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, d: int) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm(x: Array, scale: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    return (xf * inv).astype(x.dtype) * scale.astype(x.dtype)


def _rmsnorm_fwd(x, scale, eps):
    return _rmsnorm(x, scale, eps), (x, scale)


def _rmsnorm_bwd(eps, res, ct):
    """Exact gradient, computed in f32, RETURNED in the activation dtype.

    Letting autodiff differentiate the f32-upcast statistic makes the
    f32 cotangent leak into the residual-gradient stream (every backward
    TP all-reduce and elementwise chain doubles — EXPERIMENTS.md Section
    Perf); casting d_x back to x.dtype keeps the stream bf16 while the
    norm math itself stays f32-exact.
    """
    x, scale = res
    xf = x.astype(jnp.float32)
    ctf = ct.astype(jnp.float32)
    sf = scale.astype(jnp.float32)
    D = x.shape[-1]
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    xhat = xf * inv
    d_scale = jnp.sum(ctf * xhat, axis=tuple(range(ct.ndim - 1)))
    g = ctf * sf
    d_x = inv * (g - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
    return d_x.astype(x.dtype), d_scale.astype(scale.dtype)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def norm_fwd(cfg: ArchConfig, p: Params, x: Array) -> Array:
    """Reduction statistics in f32, application + cotangents in the
    activation dtype (see _rmsnorm_bwd)."""
    dt = x.dtype
    if cfg.norm == "layernorm":
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + cfg.norm_eps)
        return ((xf - mu) * inv).astype(dt) * p["scale"].astype(dt) \
            + p["bias"].astype(dt)
    return _rmsnorm(x, p["scale"], float(cfg.norm_eps))


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding on the last dim.  x: (..., S, H, hd), positions (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA + optional ring-buffer sliding-window cache)
# ---------------------------------------------------------------------------

def init_attention(cfg: ArchConfig, key: Array, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    hd, H, Hkv = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    if cfg.use_mla:
        r, rd = cfg.kv_lora_rank, cfg.qk_rope_dim
        p = {
            "wq": _dense_init(ks[0], (d, H * (hd + rd))),
            "w_dkv": _dense_init(ks[1], (d, r)),
            "w_kr": _dense_init(ks[2], (d, rd)),
            "w_uk": _dense_init(ks[3], (r, H * hd)),
            "w_uv": _dense_init(ks[4], (r, H * hd)),
            "wo": _dense_init(ks[5], (H * hd, d)),
            "kv_norm": jnp.ones((r,), jnp.float32),
        }
        return p
    p = {
        "wq": _dense_init(ks[0], (d, H * hd)),
        "wk": _dense_init(ks[1], (d, Hkv * hd)),
        "wv": _dense_init(ks[2], (d, Hkv * hd)),
        "wo": _dense_init(ks[3], (H * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((Hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((Hkv * hd,), jnp.float32)
    return p


def init_kv_cache(cfg: ArchConfig, batch: int, capacity: int, dtype) -> Params:
    hd, Hkv = cfg.head_dim_, cfg.n_kv_heads
    if cfg.use_mla:
        return {
            "ckv": jnp.zeros((batch, capacity, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, capacity, cfg.qk_rope_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, Hkv, capacity, hd), dtype),
        "v": jnp.zeros((batch, Hkv, capacity, hd), dtype),
    }


def _sdpa(q: Array, k: Array, v: Array, mask: Optional[Array], scale: float) -> Array:
    """q (B,H,Sq,hd), k/v (B,H,Sk,hd) -> (B,H,Sq,hd)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


# KV lengths > this use the chunked online-softmax path (never materializes
# the (Sq, Sk) score matrix — EXPERIMENTS.md Section Perf iteration).  At
# 4k the dense path + remat is cheaper (chunk recompute adds ~12% HBM
# traffic for no capacity win); at 32k the dense scores cannot fit.
SDPA_CHUNK_THRESHOLD = 8192
SDPA_CHUNK = 1024


def _use_flash_kernel() -> bool:
    """Opt-in switch for the Pallas flash-attention kernel (kernels/
    flash_attn).  Default off: on this CPU container interpret-mode
    execution of real sizes is impractical, and the chunked-scan XLA path
    is the measured fallback; on a TPU pod set REPRO_FLASH_KERNEL=1."""
    import os
    return os.environ.get("REPRO_FLASH_KERNEL", "0") == "1"


def _sdpa_chunked(q: Array, k: Array, v: Array, scale: float,
                  mask_chunk_fn, chunk: int = SDPA_CHUNK) -> Array:
    """Flash-style attention: lax.scan over KV chunks with a running
    (max, denominator, accumulator).  ``mask_chunk_fn(offset, C)`` returns
    the boolean mask block (broadcastable to (B, 1|H, Sq, C)) for KV slots
    [offset, offset+C) — masks are built per chunk from positions, so the
    dense (Sq, Sk) mask never exists either.  The scan body is
    jax.checkpoint'ed: backward recomputes each chunk's scores instead of
    storing softmax weights (peak memory O(Sq x chunk), not O(Sq x Sk)).
    """
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    nc = -(-Sk // chunk)
    pad = nc * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ks = jnp.moveaxis(k.reshape(B, H, nc, chunk, hd), 2, 0)
    vs = jnp.moveaxis(v.reshape(B, H, nc, chunk, hd), 2, 0)

    def body(carry, xs):
        m, l, acc = carry
        ci, kc, vc = xs
        off = ci * chunk
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kc).astype(jnp.float32) * scale
        valid = (off + jnp.arange(chunk)) < Sk            # strip padding
        msk = valid[None, None, None, :]
        if mask_chunk_fn is not None:
            msk = msk & mask_chunk_fn(off, chunk)
        s = jnp.where(msk, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc).astype(jnp.float32)
        return (m_new, l, acc), None

    init = (
        jnp.full((B, H, Sq), -1e30, jnp.float32),
        jnp.zeros((B, H, Sq), jnp.float32),
        jnp.zeros((B, H, Sq, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), init, (jnp.arange(nc), ks, vs))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _repeat_kv(x: Array, n_rep: int) -> Array:
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=1)


def attention_fwd(
    cfg: ArchConfig,
    p: Params,
    x: Array,
    positions: Array,
    *,
    causal: bool = True,
    cache: Optional[Params] = None,
    cache_index: Optional[Array] = None,
    kv_source: Optional[Array] = None,
    use_rope: bool = True,
) -> Tuple[Array, Optional[Params]]:
    """GQA attention.

    Modes:
      train/prefill: cache=None -> full (causal) self-attention.
      decode:        cache given -> append x's K/V at ``cache_index`` (ring
                     buffer modulo capacity, i.e. sliding window when the
                     capacity < total positions) and attend to the cache.
      cross:         kv_source given -> K/V from kv_source, no cache write.
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    hd, H, Hkv = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    dt = x.dtype
    src = kv_source if kv_source is not None else x

    q = x @ p["wq"].astype(dt)
    k = src @ p["wk"].astype(dt)
    v = src @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, src.shape[1], Hkv, hd)
    v = v.reshape(B, src.shape[1], Hkv, hd)
    q = shard(q, "batch", "seq", "heads")
    k = shard(k, "batch", "seq", "kv_heads")
    v = shard(v, "batch", "seq", "kv_heads")

    if use_rope and kv_source is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    q = q.transpose(0, 2, 1, 3)  # (B,H,S,hd)
    k = k.transpose(0, 2, 1, 3)  # (B,Hkv,Sk,hd)
    v = v.transpose(0, 2, 1, 3)

    new_cache = None
    mask = None
    mask_chunk_fn = None
    if cache is not None:
        cap = cache["k"].shape[2]
        slot = jnp.mod(cache_index, cap)
        # dynamic_update_slice needs S contiguous writes; decode has S==1.
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
        new_cache = {"k": ck, "v": cv}
        k, v = ck.astype(dt), cv.astype(dt)
        n_valid = jnp.minimum(cache_index + S, cap)
        # Before the ring buffer wraps, slot j holds absolute position j, so
        # prefill-with-cache (S > 1) still needs the causal constraint.  Once
        # wrapped, every valid slot is in the query's past by construction.
        qpos = cache_index + jnp.arange(S)
        no_wrap = (cache_index + S) <= cap

        def _cache_mask(off, C):
            slots_c = off + jnp.arange(C)
            valid = slots_c[None, None, None, :] < n_valid
            causal_c = jnp.where(no_wrap, slots_c[None, :] <= qpos[:, None], True)
            return valid & causal_c[None, None, :, :]

        mask_chunk_fn = _cache_mask
        mask = _cache_mask(0, cap)
    elif causal:
        def _causal_mask(off, C):
            kpos_c = jax.lax.dynamic_slice_in_dim(
                jnp.pad(positions, ((0, 0), (0, (-positions.shape[1]) % C))),
                off, C, axis=1)
            return (kpos_c[:, None, None, :] <= positions[:, None, :, None])

        mask_chunk_fn = _causal_mask
        mask = (positions[:, None, :] <= positions[:, :, None])[:, None, :, :]

    k = _repeat_kv(k, H // Hkv)
    v = _repeat_kv(v, H // Hkv)
    Hp = cfg.pad_heads_to
    if Hp and H < Hp:
        # head-padding (EXPERIMENTS.md Section Perf, arctic/llava): 56 query
        # heads do not divide a 16-way model axis, so every attention
        # activation would replicate across TP shards (involuntary
        # rematerialization).  Zero-pad the head axis AFTER GQA expansion —
        # padded heads produce zero outputs (v rows are zero) and are
        # sliced off before wo, so the math is exact at +Hp/H-1 compute.
        padw = ((0, 0), (0, Hp - H), (0, 0), (0, 0))
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        q = shard(q, "batch", "heads", None, None)
        k = shard(k, "batch", "heads", None, None)
        v = shard(v, "batch", "heads", None, None)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    # chunked path only when BOTH dims are large: for single-token decode
    # the dense (B,H,1,Sk) scores are tiny and the 512-iteration chunk scan
    # just adds loop overhead (zamba2 x long_500k regression, EXPERIMENTS).
    if k.shape[2] >= SDPA_CHUNK_THRESHOLD and q.shape[2] >= 128:
        if _use_flash_kernel() and cache is None and causal and kv_source is None:
            # Pallas flash kernel (kernels/flash_attn): TPU fast path for the
            # plain-causal train/prefill case; ring-buffer cache masks stay
            # on the chunked-scan path.
            from repro.kernels.flash_attn.ops import flash_attention
            out = flash_attention(q, k, v, float(1.0 / hd ** 0.5), causal=True)
        else:
            out = _sdpa_chunked(q, k, v, scale, mask_chunk_fn)
    else:
        out = _sdpa(q, k, v, mask, scale)
    if Hp and H < Hp:
        out = out[:, :H]
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    out = out @ p["wo"].astype(dt)
    return shard(out, "batch", "seq", "embed"), new_cache


def mla_attention_fwd(
    cfg: ArchConfig,
    p: Params,
    x: Array,
    positions: Array,
    *,
    cache: Optional[Params] = None,
    cache_index: Optional[Array] = None,
) -> Tuple[Array, Optional[Params]]:
    """Multi-head Latent Attention (DeepSeek-V2).

    Train/prefill: materialize K/V from the compressed latent.
    Decode: cache only (c_kv, k_rope) — the paper's KV-compression win —
    and run the *absorbed* form: q is projected into the latent space so
    attention scores are inner products in r + rope_dim dims.
    """
    B, S, d = x.shape
    hd, H = cfg.head_dim_, cfg.n_heads
    r, rd = cfg.kv_lora_rank, cfg.qk_rope_dim
    dt = x.dtype
    scale = 1.0 / jnp.sqrt(hd + rd).astype(jnp.float32)

    q = (x @ p["wq"].astype(dt)).reshape(B, S, H, hd + rd)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    ckv = x @ p["w_dkv"].astype(dt)  # (B,S,r)
    # RMS-normalize the latent (deepseek uses a norm on the compressed kv)
    ckv = ckv * jax.lax.rsqrt(jnp.mean(ckv.astype(jnp.float32) ** 2, -1, keepdims=True) + 1e-6).astype(dt)
    ckv = ckv * p["kv_norm"].astype(dt)
    krope = (x @ p["w_kr"].astype(dt)).reshape(B, S, 1, rd)
    krope = rope(krope, positions, cfg.rope_theta).reshape(B, S, rd)

    if cache is None:
        # materialized path
        k_nope = (ckv @ p["w_uk"].astype(dt)).reshape(B, S, H, hd)
        v = (ckv @ p["w_uv"].astype(dt)).reshape(B, S, H, hd)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(krope[:, :, None, :], (B, S, H, rd))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        qpos, kpos = positions[:, :, None], positions[:, None, :]
        mask = (kpos <= qpos)[:, None, :, :]
        out = _sdpa(
            qq.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), mask, scale
        )
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        return (out @ p["wo"].astype(dt)), None

    # absorbed decode path
    cap = cache["ckv"].shape[1]
    slot = jnp.mod(cache_index, cap)
    cckv = jax.lax.dynamic_update_slice(cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, slot, 0))
    ckr = jax.lax.dynamic_update_slice(cache["krope"], krope.astype(cache["krope"].dtype), (0, slot, 0))
    new_cache = {"ckv": cckv, "krope": ckr}
    n_valid = jnp.minimum(cache_index + S, cap)
    slots = jnp.arange(cap)
    qpos = cache_index + jnp.arange(S)
    no_wrap = (cache_index + S) <= cap
    causal_c = jnp.where(no_wrap, slots[None, :] <= qpos[:, None], True)  # (S,C)
    valid = (slots[None, None, None, :] < n_valid) & causal_c[None, None, :, :]

    w_uk = p["w_uk"].astype(dt).reshape(r, H, hd)
    q_eff = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)  # (B,S,H,r)
    scores = jnp.einsum("bshr,bcr->bhsc", q_eff, cckv.astype(dt)) + jnp.einsum(
        "bshr,bcr->bhsc", q_rope, ckr.astype(dt)
    )
    scores = scores.astype(jnp.float32) * scale
    scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx = jnp.einsum("bhsc,bcr->bshr", w, cckv.astype(dt))  # (B,S,H,r)
    w_uv = p["w_uv"].astype(dt).reshape(r, H, hd)
    out = jnp.einsum("bshr,rhd->bshd", ctx, w_uv).reshape(B, S, H * hd)
    return (out @ p["wo"].astype(dt)), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(cfg: ArchConfig, key: Array, d: Optional[int] = None, ff: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    ff = ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k1, (d, ff)),
        "w_up": _dense_init(k2, (d, ff)),
        "w_down": _dense_init(k3, (ff, d)),
    }


def mlp_fwd(p: Params, x: Array) -> Array:
    dt = x.dtype
    h = jax.nn.silu(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
    h = shard(h, "batch", "seq", "ff")
    return h @ p["w_down"].astype(dt)


# ---------------------------------------------------------------------------
# MoE (scatter-based capacity dispatch, GShard-style but without the
# (T, E, C) one-hot dispatch tensor)
# ---------------------------------------------------------------------------

def init_moe(cfg: ArchConfig, key: Array) -> Params:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, E), scale=0.02),
        "w_gate": _dense_init(ks[1], (E, d, ff)),
        "w_up": _dense_init(ks[2], (E, d, ff)),
        "w_down": _dense_init(ks[3], (E, ff, d)),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(cfg, ks[4], ff=cfg.n_shared_experts * ff)
    if cfg.moe_dense_residual:
        p["dense_residual"] = init_mlp(cfg, ks[4], ff=cfg.dense_residual_ff or ff)
    return p


def moe_fwd(cfg: ArchConfig, p: Params, x: Array) -> Tuple[Array, Array]:
    """x (B, S, d) -> (out, aux_loss).

    GROUPED top-k capacity dispatch (GShard-style groups = batch rows):
    each batch row dispatches its S tokens into its own (E, C_row) buffer
    with C_row = ceil(S/E * k * capacity_factor).  The scatter/gather is
    LOCAL to the row, so the dispatch buffer shards as (batch->data,
    expert->model) with no cross-shard scatter — the global-buffer
    formulation made GSPMD replicate the (E, C, d) buffer per data group
    and all-reduce it (6.6 TB all-gather + 12.7 TB all-reduce per arctic
    step; EXPERIMENTS.md Section Perf).  Per-row capacity drops tokens on
    per-row imbalance, the standard GShard trade-off.
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    dt = x.dtype

    logits = (x @ p["router"].astype(dt)).astype(jnp.float32)      # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                            # (B,S,k)
    gate = (gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)).astype(dt)

    # load-balance auxiliary loss (Switch/GShard form)
    me = jnp.mean(probs, axis=(0, 1))                              # (E,)
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(me * ce) * cfg.router_aux_coef

    capacity = max(1, int(cfg.capacity_factor * k * S / E))

    def dispatch_row(xr, er):
        # xr (S,d), er (S,k) -> per-row expert buffer (E,C,d) + addressing
        e_flat = er.T.reshape(-1)                                  # (k*S,) top-1 first
        onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos_flat = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
        within = pos_flat < capacity
        pos_safe = jnp.where(within, pos_flat, capacity)           # OOB -> dropped
        x_rep = jnp.tile(xr, (k, 1))                               # (k*S, d)
        buf = jnp.zeros((E, capacity, d), dt)
        buf = buf.at[e_flat, pos_safe].add(
            x_rep * within[:, None].astype(dt), mode="drop")
        return buf, e_flat, pos_safe, within

    buf, e_flat, pos_safe, within = jax.vmap(dispatch_row)(x, idx)  # (B,E,C,d)
    buf = shard(buf, "batch", "expert", None, None)

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(dt))
    h = shard(h, "batch", "expert", None, None)
    yb = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(dt))
    yb = shard(yb, "batch", "expert", None, None)

    def combine_row(ybr, e_flat_r, pos_r, within_r, gate_r):
        y_rep = ybr.at[e_flat_r, pos_r].get(mode="fill", fill_value=0)  # (k*S,d)
        y_rep = y_rep * within_r[:, None].astype(dt)
        return (y_rep.reshape(k, S, d) * gate_r.T[:, :, None]).sum(axis=0)

    y = jax.vmap(combine_row)(yb, e_flat, pos_safe, within, gate)  # (B,S,d)

    out = y
    if cfg.n_shared_experts:
        out = out + mlp_fwd(p["shared"], x.reshape(B * S, d)).reshape(B, S, d)
    if cfg.moe_dense_residual:
        out = out + mlp_fwd(p["dense_residual"], x.reshape(B * S, d)).reshape(B, S, d)
    return out, aux


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embedding(cfg: ArchConfig, key: Array) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"embed": 0.02 * jax.random.normal(k1, (cfg.vocab_size, cfg.d_model))}
    if not cfg.tie_embeddings:
        p["unembed"] = _dense_init(k2, (cfg.d_model, cfg.vocab_size))
    return p


def embed_fwd(cfg: ArchConfig, p: Params, tokens: Array, dtype) -> Array:
    out = jnp.take(p["embed"].astype(dtype), tokens, axis=0)
    return shard(out, "batch", "seq", "embed")


def unembed_fwd(cfg: ArchConfig, p: Params, h: Array) -> Array:
    dt = h.dtype
    if cfg.tie_embeddings:
        logits = h @ p["embed"].astype(dt).T
    else:
        logits = h @ p["unembed"].astype(dt)
    return shard(logits, "batch", "seq", "vocab")
