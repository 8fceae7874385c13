"""Unified config-driven model: every assigned architecture family.

Families:
  dense / moe / vlm       decoder-only LM (GQA or MLA attention, dense or
                          MoE FFN, optional modal-embedding prefix)
  ssm                     Mamba-1 stack (attention-free)
  hybrid                  Mamba-2 stack with a shared transformer block
                          invoked every `shared_attn_every` layers (Zamba2)
  encdec / audio          encoder-decoder backbone (Seamless) consuming
                          stub frame embeddings on the encoder side

Layer stacks are scanned (params stacked on a leading L axis via
vmap(init)) so compile time stays bounded for 27-64 layer configs, with
optional remat around the scanned body.

Public API:
  init_params(cfg, key)                  -> params pytree
  forward(cfg, params, batch)            -> (logits, aux_loss)
  loss_fn(cfg, params, batch)            -> (loss, metrics)
  init_cache(cfg, batch, capacity, dtype)-> decode cache pytree
  decode_step(cfg, params, cache, batch) -> (logits, new_cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.logical import shard
from repro.models import layers as L
from repro.models import ssm as S

Array = jax.Array
Params = Dict[str, Any]

MODAL_EMBED_DIM = 1024  # stubbed ViT/conv frontend output width


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def init_block(cfg: ArchConfig, key: Array, kind: str, cross: bool = False) -> Params:
    ks = jax.random.split(key, 6)
    p = {
        "ln1": L.init_norm(cfg, cfg.d_model),
        "attn": L.init_attention(cfg, ks[0]),
        "ln2": L.init_norm(cfg, cfg.d_model),
    }
    if kind == "moe":
        p["ffn"] = L.init_moe(cfg, ks[1])
    else:
        p["ffn"] = L.init_mlp(cfg, ks[1])
    if cross:
        p["ln_x"] = L.init_norm(cfg, cfg.d_model)
        p["xattn"] = L.init_attention(cfg, ks[2])
    return p


def block_fwd(
    cfg: ArchConfig,
    p: Params,
    h: Array,
    positions: Array,
    *,
    kind: str,
    causal: bool = True,
    cache: Optional[Params] = None,
    cache_index=None,
    enc_out: Optional[Array] = None,
) -> Tuple[Array, Optional[Params], Array]:
    a_in = L.norm_fwd(cfg, p["ln1"], h)
    if cfg.use_mla:
        attn_out, new_cache = L.mla_attention_fwd(
            cfg, p["attn"], a_in, positions, cache=cache, cache_index=cache_index
        )
    else:
        attn_out, new_cache = L.attention_fwd(
            cfg, p["attn"], a_in, positions, causal=causal, cache=cache, cache_index=cache_index
        )
    h = h + attn_out
    if enc_out is not None:
        x_in = L.norm_fwd(cfg, p["ln_x"], h)
        x_out, _ = L.attention_fwd(
            cfg, p["xattn"], x_in, positions, causal=False, kv_source=enc_out, use_rope=False
        )
        h = h + x_out
    f_in = L.norm_fwd(cfg, p["ln2"], h)
    if kind == "moe":
        f_out, aux = L.moe_fwd(cfg, p["ffn"], f_in)
    else:
        f_out, aux = L.mlp_fwd(p["ffn"], f_in), jnp.zeros((), jnp.float32)
    return h + f_out, new_cache, aux


def init_mamba_block(cfg: ArchConfig, key: Array) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln": L.init_norm(cfg, cfg.d_model), "mixer": S.init_mamba(cfg, k1)}


def mamba_block_fwd(cfg: ArchConfig, p: Params, h: Array, state=None):
    m_in = L.norm_fwd(cfg, p["ln"], h)
    out, new_state = S.mamba_fwd(cfg, p["mixer"], m_in, state)
    return h + out, new_state


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(fn, key: Array, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(cfg: ArchConfig, key: Array) -> Params:
    ks = jax.random.split(key, 12)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {"embedding": L.init_embedding(cfg, ks[0]), "final_norm": L.init_norm(cfg, cfg.d_model)}

    if cfg.family == "ssm":
        p["layers"] = _stack_init(lambda k: init_mamba_block(cfg, k), ks[1], cfg.n_layers)
    elif cfg.family == "hybrid":
        p["layers"] = _stack_init(lambda k: init_mamba_block(cfg, k), ks[1], cfg.n_layers)
        k1, k2, k3 = jax.random.split(ks[2], 3)
        p["shared_attn"] = {
            "in_proj": L._dense_init(k1, (2 * cfg.d_model, cfg.d_model)),
            "block": init_block(cfg, k2, "dense"),
        }
    elif cfg.is_encoder_decoder:
        p["enc_in_proj"] = L._dense_init(ks[3], (cfg.d_model, cfg.d_model))
        p["enc_layers"] = _stack_init(
            lambda k: init_block(cfg, k, "dense"), ks[4], cfg.n_enc_layers
        )
        p["enc_norm"] = L.init_norm(cfg, cfg.d_model)
        p["layers"] = _stack_init(
            lambda k: init_block(cfg, k, "dense", cross=True), ks[1], cfg.n_layers
        )
    else:
        kind = "moe" if cfg.n_experts else "dense"
        n_prefix = cfg.first_dense_layers if cfg.n_experts else 0
        if n_prefix:
            p["prefix_layers"] = [
                init_block(cfg, k, "dense") for k in jax.random.split(ks[5], n_prefix)
            ]
        p["layers"] = _stack_init(
            lambda k: init_block(cfg, k, kind), ks[1], cfg.n_layers - n_prefix
        )
        if cfg.family == "vlm" or cfg.modality == "vision":
            p["projector"] = {
                "w1": L._dense_init(ks[6], (MODAL_EMBED_DIM, cfg.d_model)),
                "w2": L._dense_init(ks[7], (cfg.d_model, cfg.d_model)),
            }
    return jax.tree.map(lambda x: x.astype(dt) if x.dtype == jnp.float32 else x, p)


# ---------------------------------------------------------------------------
# trunk helpers
# ---------------------------------------------------------------------------

def _scan_blocks(cfg, stacked, h, positions, kind, caches=None, cache_index=None, enc_out=None,
                 causal=True):
    """Scan h through stacked transformer blocks; threads optional caches."""

    def body(carry, xs):
        h = carry
        lp, cache = xs
        h2, new_cache, aux = block_fwd(
            cfg, lp, h, positions, kind=kind, causal=causal, cache=cache,
            cache_index=cache_index, enc_out=enc_out,
        )
        return h2, (new_cache, aux)

    fn = jax.checkpoint(body) if (cfg.remat and caches is None) else body
    xs = (stacked, caches)
    h, (new_caches, auxs) = jax.lax.scan(fn, h, xs)
    return h, new_caches, auxs.sum()


def _scan_mamba(cfg, stacked, h, states=None):
    def body(carry, xs):
        lp, st = xs
        h2, new_st = mamba_block_fwd(cfg, lp, carry, st)
        return h2, new_st

    fn = jax.checkpoint(body) if (cfg.remat and states is None) else body
    h, new_states = jax.lax.scan(fn, h, (stacked, states))
    return h, new_states


def _shared_attn_apply(cfg, p_sh, h, h0, positions, cache=None, cache_index=None):
    """Zamba-style shared block: concat(h, h0) -> proj -> transformer block."""
    x = jnp.concatenate([h, h0], axis=-1) @ p_sh["in_proj"].astype(h.dtype)
    out, new_cache, _ = block_fwd(
        cfg, p_sh["block"], x, positions, kind="dense", cache=cache, cache_index=cache_index
    )
    return h + out, new_cache


def _hybrid_trunk(cfg, params, h, positions, caches=None, cache_index=None):
    """Scan over G groups: shared attention + `every` mamba layers."""
    Lc, every = cfg.n_layers, cfg.shared_attn_every
    assert Lc % every == 0, (Lc, every)
    G = Lc // every
    grouped = jax.tree.map(lambda x: x.reshape((G, every) + x.shape[1:]), params["layers"])
    h0 = h
    p_sh = params["shared_attn"]

    def body(carry, xs):
        h = carry
        gp, g_caches = xs
        attn_cache = g_caches["attn"] if g_caches is not None else None
        m_states = g_caches["mamba"] if g_caches is not None else None
        h, new_attn = _shared_attn_apply(cfg, p_sh, h, h0, positions, attn_cache, cache_index)
        h, new_m = _scan_mamba(cfg, gp, h, m_states)
        return h, {"attn": new_attn, "mamba": new_m}

    fn = jax.checkpoint(body) if (cfg.remat and caches is None) else body
    h, new_caches = jax.lax.scan(fn, h, (grouped, caches))
    return h, new_caches


# ---------------------------------------------------------------------------
# forward (train / single-shot)
# ---------------------------------------------------------------------------

def forward(cfg: ArchConfig, params: Params, batch: Dict[str, Array]) -> Tuple[Array, Array]:
    """Full-sequence forward.  Returns (logits, aux_loss)."""
    dt = _dtype(cfg)
    aux = jnp.zeros((), jnp.float32)

    if cfg.is_encoder_decoder:
        frames = batch["frames"].astype(dt)
        enc_h = frames @ params["enc_in_proj"].astype(dt)
        enc_pos = jnp.broadcast_to(jnp.arange(frames.shape[1]), frames.shape[:2])
        enc_h, _, _ = _scan_blocks(cfg, params["enc_layers"], enc_h, enc_pos, "dense", causal=False)
        enc_out = L.norm_fwd(cfg, params["enc_norm"], enc_h)

        tokens = batch["tokens"]
        h = L.embed_fwd(cfg, params["embedding"], tokens, dt)
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        h, _, _ = _scan_blocks(cfg, params["layers"], h, pos, "dense", enc_out=enc_out)
    else:
        tokens = batch["tokens"]
        h = L.embed_fwd(cfg, params["embedding"], tokens, dt)
        if "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(dt)
            proj = params["projector"]
            pe = jax.nn.gelu(pe @ proj["w1"].astype(dt)) @ proj["w2"].astype(dt)
            h = jnp.concatenate([pe, h], axis=1)
        Bb, Ss = h.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(Ss), (Bb, Ss))
        h = shard(h, "batch", "seq", "embed")

        if cfg.family == "ssm":
            h, _ = _scan_mamba(cfg, params["layers"], h)
        elif cfg.family == "hybrid":
            h, _ = _hybrid_trunk(cfg, params, h, pos)
        else:
            for lp in params.get("prefix_layers", []):
                h, _, a = block_fwd(cfg, lp, h, pos, kind="dense")
                aux = aux + a
            kind = "moe" if cfg.n_experts else "dense"
            h, _, a = _scan_blocks(cfg, params["layers"], h, pos, kind)
            aux = aux + a

    h = L.norm_fwd(cfg, params["final_norm"], h)
    logits = L.unembed_fwd(cfg, params["embedding"], h)
    return logits, aux


def _chunked_ce(cfg: ArchConfig, params: Params, h: Array, labels: Array, mask: Array) -> Array:
    """Cross-entropy without materializing (B, S, V) logits: lax.map over
    sequence chunks (vocab up to 256k makes full logits the peak tensor)."""
    B, Ss, d = h.shape
    C = min(cfg.loss_chunk, Ss)
    nC = -(-Ss // C)
    pad = nC * C - Ss          # the ragged last chunk: zero mask, no loss
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = h.reshape(B, nC, C, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nC, C).transpose(1, 0, 2)
    mc = mask.reshape(B, nC, C).transpose(1, 0, 2)

    def chunk_loss(args):
        hx, lx, mx = args
        logits = L.unembed_fwd(cfg, params["embedding"], hx).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mx), jnp.sum(mx)

    losses, counts = jax.lax.map(chunk_loss, (hc, lc, mc))
    return losses.sum() / jnp.maximum(counts.sum(), 1.0)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, Array]) -> Tuple[Array, Dict[str, Array]]:
    """Next-token cross-entropy (+ MoE aux).  VLM: loss on text positions only."""
    dt = _dtype(cfg)
    n_modal = batch["patch_embeds"].shape[1] if "patch_embeds" in batch else 0

    if cfg.loss_chunk and not cfg.is_encoder_decoder:
        # recompute trunk output h, then chunked CE over the sequence
        logits = None
        # forward trunk without unembedding
        tokens = batch["tokens"]
        h = L.embed_fwd(cfg, params["embedding"], tokens, dt)
        if n_modal:
            pe = batch["patch_embeds"].astype(dt)
            proj = params["projector"]
            pe = jax.nn.gelu(pe @ proj["w1"].astype(dt)) @ proj["w2"].astype(dt)
            h = jnp.concatenate([pe, h], axis=1)
        Bb, Ss = h.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(Ss), (Bb, Ss))
        h = shard(h, "batch", "seq", "embed")
        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "ssm":
            h, _ = _scan_mamba(cfg, params["layers"], h)
        elif cfg.family == "hybrid":
            h, _ = _hybrid_trunk(cfg, params, h, pos)
        else:
            for lp in params.get("prefix_layers", []):
                h, _, a = block_fwd(cfg, lp, h, pos, kind="dense")
                aux = aux + a
            kind = "moe" if cfg.n_experts else "dense"
            h, _, a = _scan_blocks(cfg, params["layers"], h, pos, kind)
            aux = aux + a
        h = L.norm_fwd(cfg, params["final_norm"], h)
        # shift: predict token t+1 from position t
        labels_full = jnp.concatenate(
            [jnp.zeros((Bb, n_modal), tokens.dtype), batch["tokens"]], axis=1
        ) if n_modal else batch["tokens"]
        h_in = h[:, :-1]
        lab = labels_full[:, 1:]
        mask = jnp.ones_like(lab, jnp.float32)
        if n_modal:
            posn = jnp.arange(lab.shape[1])
            mask = mask * (posn[None, :] >= n_modal - 1)
        ce = _chunked_ce(cfg, params, h_in, lab, mask)
        return ce + aux, {"ce": ce, "aux": aux}

    logits, aux = forward(cfg, params, batch)
    tokens = batch["tokens"]
    if n_modal:
        logits_text = logits[:, n_modal:]
    else:
        logits_text = logits
    lg = logits_text[:, :-1].astype(jnp.float32)
    lab = tokens[:, 1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, lab[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _cache_capacity(cfg: ArchConfig, total_len: int) -> int:
    if cfg.sliding_window:
        return min(cfg.sliding_window, total_len)
    return total_len


def init_cache(cfg: ArchConfig, batch: int, total_len: int, dtype=None,
               enc_len: int = 0) -> Params:
    """Decode cache for a context of ``total_len`` positions."""
    dt = dtype or _dtype(cfg)
    cap = _cache_capacity(cfg, total_len)
    cache: Params = {"idx": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        cache["layers"] = jax.vmap(lambda _: S.init_ssm_state(cfg, batch, dt))(
            jnp.arange(cfg.n_layers)
        )
    elif cfg.family == "hybrid":
        G = cfg.n_layers // cfg.shared_attn_every
        cache["layers"] = {
            "attn": jax.vmap(lambda _: L.init_kv_cache(cfg, batch, cap, dt))(jnp.arange(G)),
            "mamba": jax.vmap(
                lambda _: jax.vmap(lambda __: S.init_ssm_state(cfg, batch, dt))(
                    jnp.arange(cfg.shared_attn_every)
                )
            )(jnp.arange(G)),
        }
    elif cfg.is_encoder_decoder:
        cache["enc_out"] = jnp.zeros((batch, enc_len, cfg.d_model), dt)
        cache["layers"] = jax.vmap(lambda _: L.init_kv_cache(cfg, batch, cap, dt))(
            jnp.arange(cfg.n_layers)
        )
    else:
        n_prefix = cfg.first_dense_layers if cfg.n_experts else 0
        if n_prefix:
            cache["prefix"] = [L.init_kv_cache(cfg, batch, cap, dt) for _ in range(n_prefix)]
        cache["layers"] = jax.vmap(lambda _: L.init_kv_cache(cfg, batch, cap, dt))(
            jnp.arange(cfg.n_layers - n_prefix)
        )
    return cache


def decode_step(cfg: ArchConfig, params: Params, cache: Params, tokens: Array
                ) -> Tuple[Array, Params]:
    """One-token decode: tokens (B, 1) -> (logits (B, 1, V), new cache)."""
    dt = _dtype(cfg)
    idx = cache["idx"]
    B = tokens.shape[0]
    pos = jnp.broadcast_to(idx[None, None], (B, 1))
    h = L.embed_fwd(cfg, params["embedding"], tokens, dt)
    new_cache: Params = {"idx": idx + 1}
    kind = "moe" if cfg.n_experts else "dense"

    if cfg.family == "ssm":
        def body(carry, xs):
            lp, st = xs
            h2, new_st = mamba_block_fwd(cfg, lp, carry, st)
            return h2, new_st
        h, new_states = jax.lax.scan(body, h, (params["layers"], cache["layers"]))
        new_cache["layers"] = new_states
    elif cfg.family == "hybrid":
        h, new_c = _hybrid_trunk(cfg, params, h, pos, caches=cache["layers"], cache_index=idx)
        new_cache["layers"] = new_c
    elif cfg.is_encoder_decoder:
        enc_out = cache["enc_out"].astype(dt)
        h, new_c, _ = _scan_blocks(
            cfg, params["layers"], h, pos, "dense",
            caches=cache["layers"], cache_index=idx, enc_out=enc_out,
        )
        new_cache["enc_out"] = cache["enc_out"]
        new_cache["layers"] = new_c
    else:
        if "prefix" in cache:
            new_prefix = []
            for lp, c in zip(params["prefix_layers"], cache["prefix"]):
                h, nc, _ = block_fwd(cfg, lp, h, pos, kind="dense", cache=c, cache_index=idx)
                new_prefix.append(nc)
            new_cache["prefix"] = new_prefix
        h, new_c, _ = _scan_blocks(
            cfg, params["layers"], h, pos, kind, caches=cache["layers"], cache_index=idx
        )
        new_cache["layers"] = new_c

    h = L.norm_fwd(cfg, params["final_norm"], h)
    logits = L.unembed_fwd(cfg, params["embedding"], h)
    return logits, new_cache
