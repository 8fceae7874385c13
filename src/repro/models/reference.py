"""Plain float32 reference of the Qwen1.5 decoder and of one robust
data-parallel training step with WFAgg, for checking the program
(``models.model`` and ``train.trainer`` ``robust_dp``) against.

Written from the published descriptions, in straightforward
``jax.numpy`` at float32 with every matmul at ``Precision.HIGHEST``: no
kernels, no ``shard_map``, no remat, no chunked loss, nothing of the
program but the layout of its parameter pytree (so that one set of
weights feeds both).

The decoder (Qwen1.5, ``hf:Qwen/Qwen1.5-0.5B``): token embedding ->
``n_layers`` x [RMSNorm -> causal self-attention with QKV bias and
rotary position embedding (rotate-half, ``rope_theta``) -> residual ->
RMSNorm -> SwiGLU MLP -> residual] -> RMSNorm -> logits by the tied
embedding.  RMSNorm is ``x / sqrt(mean(x^2) + eps) * scale``.  The loss
is the mean next-token cross-entropy over the ``S - 1`` predicted
positions of every sequence.  Departure: every key/value head is its
own (``n_kv_heads == n_heads``, as in Qwen1.5-0.5B); no dropout
(the published attention dropout is 0).

The robust-DP step (arXiv 2409.17754 Alg. 1-4 with the all-reduce
conventions of the program's ``robust_allreduce_stacked``): each of K
workers takes the gradient of its own batch; the malicious workers'
candidates are replaced by the IPM attack, ``-eps`` times the mean of
the benign candidates; WFAgg over the K candidates of ONE receiver
(N = 1): the distance filter keeps the ``K - f - 1`` candidates closest
in L2 to the coordinate-wise median (the mean of the two middle values
for even K), the similarity filter the ``K - f - 1`` of smallest cosine
distance to it (ties by index), the temporal filter accepts a candidate
whose squared distance and cosine distance to that worker's previous
candidate lie within the EWMA mean +- std of its last ``window``
metrics once more than ``transient`` steps have passed; a candidate
weighs ``tau1 D + tau2 C + tau3 T`` when at least two filters accept
it, else 0; the aggregate is the weight-normalized mean of the
candidates, or their uniform mean when every weight is 0 (an
all-reduce has no local model to fall back to; the paper's Eq. 3 with
alpha = 1).  The history then takes this step's metrics.  Departure: on
the first step there is no previous candidate; its metrics are taken
against zeros (cosine distance 1), which only fills history that the
transient keeps out of every decision while ``window <= transient``.
AdamW (decoupled weight decay) with a linear warmup then cosine decay
to ``floor`` of the peak rate.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------

def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x (B, S, H, hd) at positions 0..S-1: rotate-half RoPE."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def attention(cfg, p, x):
    B, S, _ = x.shape
    H = cfg.n_heads
    hd = cfg.head_dim_
    q = (_mm(x, p["wq"]) + p["bq"]).reshape(B, S, H, hd)
    k = (_mm(x, p["wk"]) + p["bk"]).reshape(B, S, H, hd)
    v = (_mm(x, p["wv"]) + p["bv"]).reshape(B, S, H, hd)
    q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    return _mm(out.reshape(B, S, H * hd), p["wo"])


def mlp(p, x):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]), p["w_down"])


def block(cfg, lp, h):
    h = h + attention(cfg, lp["attn"], rms_norm(h, lp["ln1"]["scale"], cfg.norm_eps))
    return h + mlp(lp["ffn"], rms_norm(h, lp["ln2"]["scale"], cfg.norm_eps))


def forward(cfg, params: Params, tokens) -> jax.Array:
    """tokens (B, S) int -> logits (B, S, vocab), float32."""
    emb = params["embedding"]["embed"]
    h = emb[tokens]
    for l in range(cfg.n_layers):
        h = block(cfg, jax.tree.map(lambda x: x[l], params["layers"]), h)
    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    return _mm(h, emb.T)


def loss(cfg, params: Params, tokens) -> jax.Array:
    """Mean next-token cross-entropy over positions 0..S-2."""
    logits = forward(cfg, params, tokens)[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def loss_and_grad(cfg, params: Params, tokens) -> Tuple[jax.Array, Params]:
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(cfg, p, tokens))(params)


_loss_and_grad_jit = jax.jit(loss_and_grad, static_argnums=0)


# ---------------------------------------------------------------------------
# the robust-DP step
# ---------------------------------------------------------------------------

class StepConfig(NamedTuple):
    malicious: Tuple[int, ...]   # indices of the workers that attack
    ipm_eps: float = 0.5
    f: int = 1
    tau1: float = 0.4
    tau2: float = 0.4
    tau3: float = 0.2
    window: int = 3
    transient: int = 3
    ewma_decay: float = 0.5
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    lr_floor: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    adam_eps: float = 1e-8
    weight_decay: float = 0.1


class StepState(NamedTuple):
    params: Params
    m: Params
    v: Params
    step: int                  # steps taken so far
    prev: jax.Array            # (K, P) each worker's previous candidate
    hist_s: jax.Array          # (window, K), most recent first
    hist_b: jax.Array
    count: int                 # history entries recorded, at most window


def init_state(params: Params, K: int, sc: StepConfig) -> StepState:
    zeros = jax.tree.map(jnp.zeros_like, params)
    P = sum(x.size for x in jax.tree.leaves(params))
    return StepState(params, zeros, zeros, 0, jnp.zeros((K, P), jnp.float32),
                     jnp.zeros((sc.window, K)), jnp.zeros((sc.window, K)), 0)


def learning_rate(sc: StepConfig, step: int) -> float:
    if step < sc.warmup:
        return sc.lr * step / sc.warmup
    frac = min(max((step - sc.warmup) / max(sc.total_steps - sc.warmup, 1), 0.0), 1.0)
    return sc.lr * (sc.lr_floor + (1 - sc.lr_floor) * 0.5 * (1 + math.cos(math.pi * frac)))


def _flat(tree) -> jax.Array:
    return jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(tree)])


def _unflat(vec, like):
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for x in leaves:
        out.append(vec[off:off + x.size].reshape(x.shape))
        off += x.size
    return jax.tree.unflatten(treedef, out)


def _smallest(scores, n):
    """Bool mask of the ``n`` smallest scores, ties by index."""
    order = jnp.argsort(scores, stable=True)
    return jnp.zeros(scores.shape, bool).at[order[:n]].set(True)


def _cos_dist(x, y):
    """1 - cos between rows of x and y; a zero vector's cosine is 0."""
    den = jnp.sqrt(jnp.sum(x * x, -1) * jnp.sum(y * y, -1))
    return 1.0 - jnp.sum(x * y, -1) / jnp.where(den > 0, den, 1.0)


def _ewma(hist, count, decay):
    ages = jnp.arange(hist.shape[0])
    w = jnp.where(ages < count, decay ** ages, 0.0)
    w = w / w.sum()
    mu = w @ hist
    return mu, jnp.sqrt(w @ (hist - mu) ** 2)


def wfagg(cands, prev, hist_s, hist_b, count: int, step: int, sc: StepConfig):
    """cands, prev (K, P) -> (aggregate (P,), weights (K,), masks
    (mask_d, mask_c, mask_t), this step's metrics (s, b))."""
    K = cands.shape[0]
    srt = jnp.sort(cands, axis=0)
    med = 0.5 * (srt[(K - 1) // 2] + srt[K // 2])
    keep = K - sc.f - 1
    mask_d = _smallest(jnp.sum((cands - med) ** 2, -1), keep)
    mask_c = _smallest(_cos_dist(cands, med[None]), keep)
    s = jnp.sum((cands - prev) ** 2, -1)
    b = jnp.where(jnp.sum(prev * prev, -1) > 0, _cos_dist(cands, prev), 1.0)
    if step > sc.transient and count > 0:
        mu_s, sd_s = _ewma(hist_s, count, sc.ewma_decay)
        mu_b, sd_b = _ewma(hist_b, count, sc.ewma_decay)
        mask_t = (jnp.abs(s - mu_s) <= sd_s) & (jnp.abs(b - mu_b) <= sd_b)
    else:
        mask_t = jnp.zeros((K,), bool)
    votes = mask_d.astype(int) + mask_c.astype(int) + mask_t.astype(int)
    w = jnp.where(votes >= 2, sc.tau1 * mask_d + sc.tau2 * mask_c + sc.tau3 * mask_t, 0.0)
    coef = jnp.where(w.sum() > 0, w / jnp.where(w.sum() > 0, w.sum(), 1.0), 1.0 / K)
    agg = jnp.einsum("k,kp->p", coef, cands, precision=HIGHEST)
    return agg, w, (mask_d, mask_c, mask_t), (s, b)


def robust_dp_step(cfg, sc: StepConfig, st: StepState,
                   worker_tokens: Sequence[jax.Array]):
    """One step on K workers' batches ``worker_tokens[k]`` (B, S).
    Returns (new state, info with the per-worker losses and WFAgg's
    weights and masks)."""
    K = len(worker_tokens)
    losses, cands = [], []
    for k in range(K):                           # worker by worker
        l, g = _loss_and_grad_jit(cfg, st.params, worker_tokens[k])
        losses.append(l)
        cands.append(_flat(g))
    cands = jnp.stack(cands)
    bad = jnp.zeros((K,), bool).at[jnp.asarray(sc.malicious, int)].set(True)
    benign_mean = jnp.mean(cands[~bad], axis=0)
    cands = jnp.where(bad[:, None], -sc.ipm_eps * benign_mean, cands)
    agg, w, masks, (s, b) = wfagg(cands, st.prev, st.hist_s, st.hist_b,
                                  st.count, st.step, sc)
    grads = _unflat(agg, st.params)
    t = st.step + 1
    lr = learning_rate(sc, st.step)
    m = jax.tree.map(lambda m, g: sc.b1 * m + (1 - sc.b1) * g, st.m, grads)
    v = jax.tree.map(lambda v, g: sc.b2 * v + (1 - sc.b2) * g * g, st.v, grads)

    def update(p, m, v):
        mh, vh = m / (1 - sc.b1 ** t), v / (1 - sc.b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + sc.adam_eps) + sc.weight_decay * p)

    params = jax.tree.map(update, st.params, m, v)
    hist_s = jnp.concatenate([s[None], st.hist_s[:-1]])
    hist_b = jnp.concatenate([b[None], st.hist_b[:-1]])
    new = StepState(params, m, v, t, cands, hist_s, hist_b,
                    min(st.count + 1, sc.window))
    info = {"losses": jnp.stack(losses), "weights": w, "mask_d": masks[0],
            "mask_c": masks[1], "mask_t": masks[2]}
    return new, info
