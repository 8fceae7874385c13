"""Plain reference of Qwen1.5 (``hf:Qwen/Qwen1.5-0.5B``) and of the
robust data-parallel training step with WFAgg, for the output check of
the trainer's cells.  Straightforward ``jax.numpy`` at float32 with
every matmul at ``HIGHEST`` precision; it imports nothing of the
program and takes nothing the program made: the initial weights come
from this module's ``init`` and the tokens from the benchmark.

The decoder: token embedding -> L x [RMSNorm -> causal self-attention
(QKV bias, rotate-half RoPE at ``rope_theta``, scores over sqrt(head
dim)) -> residual -> RMSNorm -> SwiGLU MLP -> residual] -> RMSNorm ->
logits by the tied embedding; RMSNorm is x / sqrt(mean(x^2) + eps) *
scale.  A worker's loss is the mean next-token cross-entropy over the
S - 1 predicted positions of its sequences.  No dropout (the published
attention dropout is 0); every key/value head is its own, as published.

One step, on K workers' batches: every worker's gradient of its own
loss; the malicious workers' candidates replaced by the IPM attack,
-eps times the mean of the benign candidates; WFAgg over the K
candidates (arXiv 2409.17754 Alg. 1-4, one receiver): the distance
filter keeps the K - f - 1 candidates closest in L2 to the
coordinate-wise median (mean of the two middle values), the similarity
filter the K - f - 1 of smallest cosine distance to it, ties by index;
the temporal filter accepts a candidate whose squared distance and
cosine distance to the same worker's previous candidate lie within the
EWMA mean +- std of its last ``window`` metrics once more than
``transient`` steps have passed; a candidate weighs tau1 D + tau2 C +
tau3 T where two filters or more accept it, else 0; the aggregate is
the weight-normalized mean of the candidates, their uniform mean where
every weight is 0; then AdamW (decoupled weight decay, warmup then
cosine decay to a floor of the peak rate), and the history takes the
step's metrics.  With no previous candidate (the first step) the
metrics are taken against zeros, cosine distance 1; the transient keeps
them out of every decision while ``window <= transient``.

It computes in blocks, so that a step at the published widths fits one
chip at float32: worker by worker; per worker the forward layer by
layer, keeping each layer's input, the loss over chunks of
``LOSS_CHUNK`` positions and the backward layer by layer, each layer's
vjp recomputing its forward; WFAgg's statistics, the combine and the
optimizer leaf by leaf and in blocks of rows; the previous step's
candidates on the host.

``fp8=True`` is the output check's control: the inputs of every matmul,
forward and backward, rounded to float8 e4m3 with one scale per tensor
(its largest magnitude maps to 448), below the configuration's bfloat16
compute.  ``fault`` plants one of the faults the check must catch.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LOSS_CHUNK = 512            # positions a head chunk computes logits for
BLOCK = 1 << 22             # elements of one leaf block of the statistics
FAULTS = ("half_batch", "no_exchange", "altered_weight")

# a layer's leaves in the program's parameter pytree (layers stacked on
# a leading axis): (group, name)
LAYER_LEAVES = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
                ("attn", "bq"), ("attn", "bk"), ("attn", "bv"),
                ("ffn", "w_gate"), ("ffn", "w_up"), ("ffn", "w_down"),
                ("ln1", "scale"), ("ln2", "scale"))


class Dims(NamedTuple):
    d: int
    heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    theta: float
    eps: float


def dims(cfg) -> Dims:
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the reference has one key/value head per query head")
    return Dims(cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
                cfg["intermediate_size"], cfg["vocab_size"],
                cfg["num_hidden_layers"], float(cfg["rope_theta"]),
                float(cfg["rms_norm_eps"]))


def layer_shapes(dm: Dims) -> Dict[tuple, tuple]:
    q = dm.heads * dm.head_dim
    return {("attn", "wq"): (dm.d, q), ("attn", "wk"): (dm.d, q),
            ("attn", "wv"): (dm.d, q), ("attn", "wo"): (q, dm.d),
            ("attn", "bq"): (q,), ("attn", "bk"): (q,), ("attn", "bv"): (q,),
            ("ffn", "w_gate"): (dm.d, dm.ff), ("ffn", "w_up"): (dm.d, dm.ff),
            ("ffn", "w_down"): (dm.ff, dm.d),
            ("ln1", "scale"): (dm.d,), ("ln2", "scale"): (dm.d,)}


def init(cfg, key) -> Dict[str, Any]:
    """Weights in the program's pytree layout, from ``key``: every
    matrix and the embedding normal(0, initializer_range), biases 0,
    norm scales 1."""
    dm, std = dims(cfg), float(cfg["initializer_range"])
    layers: Dict[str, Dict[str, jax.Array]] = {}
    for i, (grp, name) in enumerate(LAYER_LEAVES):
        shape = (dm.layers,) + layer_shapes(dm)[(grp, name)]
        if grp.startswith("ln"):
            x = jnp.ones(shape, jnp.float32)
        elif name.startswith("b"):
            x = jnp.zeros(shape, jnp.float32)
        else:
            x = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        layers.setdefault(grp, {})[name] = x
    embed = std * jax.random.normal(jax.random.fold_in(key, 100), (dm.vocab, dm.d),
                                    jnp.float32)
    return {"embedding": {"embed": embed},
            "final_norm": {"scale": jnp.ones((dm.d,), jnp.float32)},
            "layers": layers}


def leaf_norms(params) -> Dict[str, jax.Array]:
    """{leaf name: L2 norm} of a program-layout pytree, one entry per
    layer of each stacked leaf (``layers.<l>.<group>.<name>``)."""
    out = {"embedding.embed": jnp.linalg.norm(params["embedding"]["embed"]),
           "final_norm.scale": jnp.linalg.norm(params["final_norm"]["scale"])}
    for grp, name in LAYER_LEAVES:
        x = params["layers"][grp][name]
        per_layer = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        for l in range(x.shape[0]):
            out[f"layers.{l}.{grp}.{name}"] = per_layer[l]
    return out


def _split(params) -> Dict[str, jax.Array]:
    """Program layout -> {leaf name: array}, one array per layer."""
    out = {"embedding.embed": params["embedding"]["embed"],
           "final_norm.scale": params["final_norm"]["scale"]}
    for grp, name in LAYER_LEAVES:
        x = params["layers"][grp][name]
        for l in range(x.shape[0]):
            out[f"layers.{l}.{grp}.{name}"] = x[l]
    return out


def _layer(flat: Dict[str, jax.Array], l: int) -> Dict[str, jax.Array]:
    return {f"{g}.{n}": flat[f"layers.{l}.{g}.{n}"] for g, n in LAYER_LEAVES}


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------

def _fp8(x):
    """x rounded to float8 e4m3 under one power-of-two scale that maps
    its largest magnitude to at most 448.  The values keep 4 significant
    bits, so bfloat16 holds them exactly and a matmul of two of them at
    the default precision is exact in float32 on a TPU as on a CPU."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 2.0 ** jnp.floor(jnp.log2(448.0 / amax)), 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm8_exact(spec, a8, b8):
    return jnp.einsum(spec, a8, b8, precision=jax.lax.Precision.DEFAULT)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec, a, b):
    return _mm8_exact(spec, _fp8(a), _fp8(b))


def _mm8_fwd(spec, a, b):
    a8, b8 = _fp8(a), _fp8(b)
    return _mm8_exact(spec, a8, b8), (a8, b8)


def _mm8_bwd(spec, res, ct):
    _, vjp = jax.vjp(lambda x, y: _mm8_exact(spec, x, y), *res)
    return vjp(_fp8(ct))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(spec, a, b, fp8: bool):
    if fp8:
        return _mm8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _block(dm: Dims, fp8: bool, lp, h):
    B, S, _ = h.shape
    x = _rms(h, lp["ln1.scale"], dm.eps)
    proj = lambda w, b: (_mm("bsd,dq->bsq", x, lp[w], fp8) + lp[b]).reshape(  # noqa: E731
        B, S, dm.heads, dm.head_dim)
    q = _rope(proj("attn.wq", "attn.bq"), dm.theta)
    k = _rope(proj("attn.wk", "attn.bk"), dm.theta)
    v = proj("attn.wv", "attn.bv")
    scores = _mm("bqhd,bkhd->bhqk", q, k, fp8) / math.sqrt(dm.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = _mm("bhqk,bkhd->bqhd", probs, v, fp8).reshape(B, S, dm.heads * dm.head_dim)
    h = h + _mm("bsq,qd->bsd", att, lp["attn.wo"], fp8)
    x = _rms(h, lp["ln2.scale"], dm.eps)
    gate = _mm("bsd,df->bsf", x, lp["ffn.w_gate"], fp8)
    up = _mm("bsd,df->bsf", x, lp["ffn.w_up"], fp8)
    return h + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lp["ffn.w_down"], fp8)


def forward(params, tokens, cfg, fp8: bool = False):
    """tokens (B, S) -> logits (B, S, vocab) of a program-layout pytree,
    whole (no blocks); the blocked step below computes the same."""
    dm, flat = dims(cfg), _split(params)
    h = flat["embedding.embed"][tokens]
    for l in range(dm.layers):
        h = _block(dm, fp8, _layer(flat, l), h)
    h = _rms(h, flat["final_norm.scale"], dm.eps)
    return _mm("bsd,vd->bsv", h, flat["embedding.embed"], fp8)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_jit(dm, fp8, lp, h):
    return _block(dm, fp8, lp, h)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_vjp(dm, fp8, lp, h, dh):
    _, vjp = jax.vjp(lambda p, x: _block(dm, fp8, p, x), lp, h)
    return vjp(dh)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(7, 8))
def _head(dm, fp8, embed, scale, h, labels, weight, d_embed, d_scale):
    """Loss of one chunk of positions (cross-entropy times ``weight``,
    summed) and its gradients, added to the running ones."""
    def chunk_loss(e, s, x):
        logits = _mm("bcd,vd->bcv", _rms(x, s, dm.eps), e, fp8)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * weight)

    loss, (de, ds, dx) = jax.value_and_grad(chunk_loss, argnums=(0, 1, 2))(embed, scale, h)
    return loss, d_embed + de, d_scale + ds, dx


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_bwd(d_embed, tokens, dh):
    return d_embed.at[tokens.reshape(-1)].add(dh.reshape(-1, dh.shape[-1]))


def worker_grad(dm: Dims, fp8: bool, flat, tokens):
    """(loss, {leaf name: gradient}) of one worker's batch ``tokens``
    (B, S), worker-local, in blocks."""
    B, S = tokens.shape
    embed = flat["embedding.embed"]
    hs = [embed[tokens]]
    layers = [_layer(flat, l) for l in range(dm.layers)]
    for lp in layers:
        hs.append(_block_jit(dm, fp8, lp, hs[-1]))
    n = S - 1
    C = min(LOSS_CHUNK, n)
    n_chunks = -(-n // C)
    pad = n_chunks * C - n
    h_in = jnp.pad(hs[-1][:, :n], ((0, 0), (0, pad), (0, 0)))
    labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad)))
    weight = jnp.pad(jnp.full((B, n), 1.0 / (B * n), jnp.float32), ((0, 0), (0, pad)))
    d_embed = jnp.zeros_like(embed)
    d_scale = jnp.zeros_like(flat["final_norm.scale"])
    loss, dhs = 0.0, []
    for c in range(n_chunks):
        sl = slice(c * C, (c + 1) * C)
        part, d_embed, d_scale, dx = _head(dm, fp8, embed, flat["final_norm.scale"],
                                           h_in[:, sl], labels[:, sl], weight[:, sl],
                                           d_embed, d_scale)
        loss += float(part)
        dhs.append(dx)
    dh = jnp.concatenate(dhs, axis=1)[:, :n]
    dh = jnp.pad(dh, ((0, 0), (0, 1), (0, 0)))      # the last position predicts nothing
    grads = {"embedding.embed": None, "final_norm.scale": d_scale}
    for l in reversed(range(dm.layers)):
        dlp, dh = _block_vjp(dm, fp8, layers[l], hs[l], dh)
        for key, g in dlp.items():
            grads[f"layers.{l}.{key}"] = g
    grads["embedding.embed"] = _embed_bwd(d_embed, tokens, dh)
    return loss, grads


# ---------------------------------------------------------------------------
# WFAgg and the optimizer, leaf by leaf
# ---------------------------------------------------------------------------

def _blocks(shape) -> List[slice]:
    """Row slices of a leaf, each of at most about ``BLOCK`` elements."""
    size = int(np.prod(shape))
    if size <= BLOCK or len(shape) < 2:
        return [slice(None)]
    rows = max(1, -(-shape[0] // -(-size // BLOCK)))
    return [slice(r, min(r + rows, shape[0])) for r in range(0, shape[0], rows)]


@jax.jit
def _ipm(benign: Sequence[jax.Array], eps):
    return -eps * sum(benign) / len(benign)


@jax.jit
def _stats(cands: Sequence[jax.Array], prevs: Optional[Sequence[jax.Array]]):
    x = jnp.stack([c.reshape(-1) for c in cands])
    K = x.shape[0]
    srt = jnp.sort(x, axis=0)
    med = 0.5 * (srt[(K - 1) // 2] + srt[K // 2])
    out = {"dist2": jnp.sum((x - med) ** 2, axis=1), "dotmed": jnp.sum(x * med, axis=1),
           "norm2": jnp.sum(x * x, axis=1), "mednorm2": jnp.sum(med * med)}
    if prevs is not None:
        p = jnp.stack([q.reshape(-1) for q in prevs])
        out.update(prev_dist2=jnp.sum((x - p) ** 2, axis=1),
                   prev_dot=jnp.sum(x * p, axis=1), prev_norm2=jnp.sum(p * p, axis=1))
    return out


@functools.partial(jax.jit, donate_argnums=(2, 3, 4))
def _adamw(cands: Sequence[jax.Array], coef, p, m, v, lr, t, opt):
    g = sum(c * coef[k] for k, c in enumerate(cands))
    b1, b2, eps, wd = opt
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
    return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p), m, v


def _smallest(scores: np.ndarray, n: int) -> np.ndarray:
    mask = np.zeros(scores.shape, bool)
    mask[np.argsort(scores, kind="stable")[:n]] = True
    return mask


def _ewma(hist: np.ndarray, count: int, decay: float):
    ages = np.arange(hist.shape[0])
    w = np.where(ages < count, decay ** ages, 0.0)
    w = w / w.sum()
    mu = w @ hist
    return mu, np.sqrt(w @ (hist - mu) ** 2)


def _cos_dist(dot, n2a, n2b):
    den = np.sqrt(n2a * n2b)
    return 1.0 - dot / np.where(den > 0, den, 1.0)


def learning_rate(opt: dict, step: int) -> float:
    if step < opt["warmup"]:
        return opt["lr"] * step / opt["warmup"]
    frac = min(max((step - opt["warmup"]) / max(opt["total_steps"] - opt["warmup"], 1),
                   0.0), 1.0)
    return opt["lr"] * (opt["lr_floor"]
                        + (1 - opt["lr_floor"]) * 0.5 * (1 + math.cos(math.pi * frac)))


class RefTrainer:
    """The reference's robust-DP training from ``params0`` (program
    layout), on the default device: ``step(tokens)`` per step, then
    ``norms()``."""

    def __init__(self, cfg, mix, params0, fp8: bool = False,
                 fault: Optional[str] = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
        self.cfg, self.dm, self.fp8, self.fault = cfg, dims(cfg), fp8, fault
        self.K = int(mix["workers"])
        self.malicious = [int(i) for i in mix["malicious_workers"]]
        self.eps_ipm = float(mix["attack"].split("_")[1])
        self.w, self.opt = cfg["wfagg"], cfg["optimizer"]
        self.p = dict(_split(params0))
        self.m = {k: jnp.zeros_like(x) for k, x in self.p.items()}
        self.v = {k: jnp.zeros_like(x) for k, x in self.p.items()}
        self.step_count = 0
        self.prev: Optional[List[Dict[str, np.ndarray]]] = None   # host
        W = int(self.w["window"])
        self.hist_s, self.hist_b = np.zeros((W, self.K)), np.zeros((W, self.K))
        self.count = 0
        self.cands: List[Dict[str, jax.Array]] = []

    def _candidates(self, worker_tokens):
        losses, cands = [], []
        for tok in worker_tokens:                    # worker by worker
            tok = jnp.asarray(tok)
            if self.fault == "half_batch":
                B, S = tok.shape
                tok = tok[:, :S // 2] if B == 1 else tok[:B // 2]
            loss, g = worker_grad(self.dm, self.fp8, self.p, tok)
            losses.append(loss)
            cands.append(g)
        benign = [k for k in range(self.K) if k not in self.malicious]
        for name in self.p:
            bad = _ipm([cands[k][name] for k in benign], self.eps_ipm)
            for k in self.malicious:
                cands[k][name] = bad
        return np.asarray(losses), cands

    def _wfagg(self, cands):
        """Statistics leaf by leaf and block by block -> (weights,
        combine coefficients, this step's temporal metrics)."""
        K, w = self.K, self.w
        acc = {}
        for name in self.p:
            for rows in _blocks(self.p[name].shape):
                prevs = None if self.prev is None else [
                    jnp.asarray(self.prev[k][name][rows]) for k in range(K)]
                part = _stats([c[name][rows] for c in cands], prevs)
                for key, val in part.items():
                    acc[key] = acc.get(key, 0.0) + np.asarray(val, np.float64)
        keep = K - int(w["f"]) - 1
        mask_d = _smallest(acc["dist2"], keep)
        mask_c = _smallest(_cos_dist(acc["dotmed"], acc["norm2"], acc["mednorm2"]), keep)
        if self.prev is None:
            s, b = acc["norm2"], np.ones(K)
        else:
            s = acc["prev_dist2"]
            b = np.where(acc["prev_norm2"] > 0,
                         _cos_dist(acc["prev_dot"], acc["norm2"], acc["prev_norm2"]), 1.0)
        if self.step_count > int(w["transient"]) and self.count > 0:
            mu_s, sd_s = _ewma(self.hist_s, self.count, float(w["ewma_decay"]))
            mu_b, sd_b = _ewma(self.hist_b, self.count, float(w["ewma_decay"]))
            mask_t = (np.abs(s - mu_s) <= sd_s) & (np.abs(b - mu_b) <= sd_b)
        else:
            mask_t = np.zeros(K, bool)
        votes = mask_d.astype(int) + mask_c.astype(int) + mask_t.astype(int)
        weights = np.where(votes >= 2, w["tau1"] * mask_d + w["tau2"] * mask_c
                           + w["tau3"] * mask_t, 0.0)
        if self.fault == "altered_weight":
            weights[self.malicious[0]] = max(weights.max(), w["tau1"] + w["tau2"])
        coef = weights / weights.sum() if weights.sum() > 0 else np.full(K, 1.0 / K)
        if self.fault == "no_exchange":
            # the copy on the malicious worker's chip, which applies its
            # own candidate: never the aggregate unless it alone is accepted
            coef = np.eye(K)[self.malicious[0]]
        return weights, coef, s, b

    def step(self, worker_tokens) -> Dict[str, np.ndarray]:
        """One step on the K workers' batches (each (B, S)).  The last
        step's candidates move to the host first: this step's temporal
        metrics compare against them."""
        if self.cands:
            self.prev = [{k: np.asarray(x) for k, x in c.items()} for c in self.cands]
            self.cands = []
        with jax.default_matmul_precision("highest"):
            losses, cands = self._candidates(worker_tokens)
            weights, coef, s, b = self._wfagg(cands)
            t = self.step_count + 1
            lr = learning_rate(self.opt, self.step_count)
            o = self.opt
            opt = (float(o["b1"]), float(o["b2"]), float(o["eps"]), float(o["weight_decay"]))
            coef_d = jnp.asarray(coef, jnp.float32)
            for name in self.p:
                self.p[name], self.m[name], self.v[name] = _adamw(
                    [c[name] for c in cands], coef_d, self.p[name], self.m[name],
                    self.v[name], lr, float(t), opt)
        self.hist_s = np.concatenate([s[None], self.hist_s[:-1]])
        self.hist_b = np.concatenate([b[None], self.hist_b[:-1]])
        self.count = min(self.count + 1, self.hist_s.shape[0])
        self.step_count = t
        self.prev = None
        self.cands = cands
        return {"losses": losses, "weights": weights}

    def norms(self, params0) -> Dict[str, Any]:
        """What the check compares: per-leaf norms of the parameters'
        change, of Adam's first moment and of every worker's last
        candidate, and the WFAgg-T history."""
        p0 = _split(params0)
        norm = jax.jit(jnp.linalg.norm)
        return {
            "param_change": {k: float(norm(self.p[k] - p0[k])) for k in self.p},
            "adam_m": {k: float(norm(x)) for k, x in self.m.items()},
            "candidates": {f"worker{i}.{k}": float(norm(x))
                           for i, c in enumerate(self.cands) for k, x in c.items()},
            "hist_s": self.hist_s, "hist_b": self.hist_b,
        }
