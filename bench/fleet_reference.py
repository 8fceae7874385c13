"""Plain reference of the gossip fleet's rounds: local training, the
IPM attack, the lossy transport and one WFAgg aggregation per node, with
the WFAgg-T history, written from the paper (arXiv 2409.17754, Alg. 1-4,
Eq. 3) and the fleet's documented round semantics.  It imports nothing
of the program and takes nothing the program made: the initial models
come from the benchmark's own ``init`` and the images from its own copy
of the synthetic data generator.

One round, for every node n (all from the state the round starts with):

1. WFAgg-T history and the transport's served-lag table are re-keyed to
   this round's slate by neighbor identity (a neighbor new to the slate
   starts from zeros).
2. Local training: ``batches_per_round`` steps of momentum SGD on the
   node's own images (key: seed, node, 1000 * round + batch).
3. IPM: every Byzantine row is replaced by ``-eps`` times the mean of
   the benign rows.
4. Transport (chaos mixes): a dropped delivery re-serves the edge's last
   delivered payload one round older, valid while its age is within the
   staleness budget and the round count; the candidate rows come from
   the stack [this round's models | ring of the last L rounds].
5. Sanitizer: non-finite candidate rows are zeroed and their edges
   demoted to invalid.
6. WFAgg: over the valid candidates, the coordinate-wise median (mean of
   the two middle values); distance filter keeps the v - f - 1 closest
   in L2 to the median, similarity filter the v - f - 1 of smallest
   cosine distance to it (stable order, ties by slot); the temporal
   filter accepts a candidate whose squared distance and cosine distance
   to the edge's previous payload lie within the EWMA mean +- std of the
   edge's last W metrics, once the round count exceeds the transient;
   a candidate weighs tau1*D + tau2*C + tau3*T when at least two filters
   accept it, else 0; the node's new model is (1 - alpha) * own +
   alpha * weighted mean, or its own model when every weight is 0.
7. History: this round's metrics are pushed on every slot (an edge of a
   chaos mix with no valid delivery pushes the pre-round EWMA means).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12
# nodes per jitted call, which bounds the reference's device memory: a
# call holds (AGG_BLOCK, K, d) gathered candidates, or TRAIN_BLOCK
# nodes' activations for one batch
AGG_BLOCK = 64
TRAIN_BLOCK = 256


# ---------------------------------------------------------------------------
# the synthetic images (a copy of the program's generator)
# ---------------------------------------------------------------------------

def templates(dseed: int, cfg) -> jax.Array:
    """10 class templates: seeded normal images smoothed by a 3x3 box
    filter with zero padding."""
    h, w, c = cfg["image"]
    t = jax.random.normal(jax.random.PRNGKey(dseed + 17),
                          (cfg["n_classes"], h, w, c))
    p = jnp.pad(t, ((0, 0), (1, 1), (1, 1), (0, 0)))
    s = sum(p[:, i:i + h, j:j + w, :] for i in range(3) for j in range(3))
    return s / 9.0


def images(key, tpl, batch: int, noise: float, n_classes: int):
    k1, k2 = jax.random.split(key)
    labels = jax.random.randint(k1, (batch,), 0, n_classes)
    x = tpl[labels] + noise * jax.random.normal(k2, (batch,) + tpl.shape[1:])
    return x, labels


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

class RefState(NamedTuple):
    params: Any          # {layer: {"w", "b"}}, leading axis N
    momentum: Any
    prev: jax.Array      # (N, d) last round's sanitized models (clean mixes)
    hist_s: jax.Array    # (N, W, K)
    hist_b: jax.Array
    count: jax.Array     # (N,)
    t: jax.Array         # (N,)
    ring: Optional[jax.Array]    # (L, N, d) chaos mixes
    served: Optional[jax.Array]  # (N, K) int32 chaos mixes
    slate_idx: jax.Array         # (N, K) last round's slate
    slate_valid: jax.Array


def _flatten(params):
    leaves = [params[n][k] for n in sorted(params) for k in sorted(params[n])]
    N = leaves[0].shape[0]
    return jnp.concatenate([x.reshape(N, -1) for x in leaves], axis=1)


def _unflatten(flat, like):
    out, off = {}, 0
    for n in sorted(like):
        out[n] = {}
        for k in sorted(like[n]):
            shape = like[n][k].shape
            size = int(np.prod(shape[1:]))
            out[n][k] = flat[:, off:off + size].reshape(shape)
            off += size
    return out


def _rekey(x, prev_idx, prev_valid, idx, valid):
    """Slot-keyed (..., K) tables re-keyed to a new slate by identity."""
    match = ((idx[:, :, None] == prev_idx[:, None, :])
             & valid[:, :, None] & prev_valid[:, None, :])   # (N, Knew, Kold)
    if x.ndim == 3:   # (N, W, K)
        return jnp.sum(jnp.where(match[:, None], x[:, :, None, :], 0), axis=-1)
    return jnp.sum(jnp.where(match, x[:, None, :], 0), axis=-1)


def _rank(scores):
    """Position of each entry in a stable ascending sort (NaN last)."""
    order = jnp.argsort(scores, axis=-1, stable=True)
    return jnp.argsort(order, axis=-1, stable=True)


def _ewma(hist, count, decay):
    W = hist.shape[1]
    ages = jnp.arange(W, dtype=jnp.float32)
    w = jnp.where(ages[None, :] < count[:, None].astype(jnp.float32),
                  decay ** ages[None, :], 0.0)
    w = w / jnp.maximum(w.sum(axis=1, keepdims=True), _EPS)
    mu = jnp.einsum("nw,nwk->nk", w, hist.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
    var = jnp.einsum("nw,nwk->nk", w, (hist - mu[:, None, :]) ** 2,
                     precision=jax.lax.Precision.HIGHEST)
    return mu, jnp.sqrt(jnp.maximum(var, 0.0))


class FleetReference:
    """Follows a fleet mix round by round from the benchmark's initial
    models.  ``dtype`` float32 is the reference; bfloat16 is the control.
    ``fault`` plants one of the faults the output check must catch:
    ``half_batch`` (each step's loss averaged over half its images),
    ``no_exchange`` (every node keeps its own trained model) or
    ``altered_answer`` (one benign node's aggregate replaced by its own
    trained model)."""

    def __init__(self, model, cfg: Dict[str, Any], mix, traffic, dseed: int,
                 dtype=jnp.float32, fault: Optional[str] = None):
        self.model, self.cfg, self.mix, self.traffic = model, cfg, mix, traffic
        self.dseed, self.dtype, self.fault = dseed, dtype, fault
        self.prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        self.chaos = traffic.drop is not None
        self._train = jax.jit(self._train_nodes)
        self._agg = jax.jit(self._aggregate_nodes)

    # -- local training -----------------------------------------------------
    def _train_nodes(self, params, mom, node_ids, rnd):
        cfg, dtype = self.cfg, self.dtype
        B = cfg["batch_size"]
        used = B // 2 if self.fault == "half_batch" else B
        tpl = templates(self.dseed, cfg)

        def one_node(p, m, node):
            def step(carry, b):
                p, m = carry
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(self.dseed), node),
                    rnd * 1000 + b)
                x, y = images(key, tpl, B, cfg["image_noise"], cfg["n_classes"])
                x, y = x[:used].astype(dtype), y[:used]

                def loss(pp):
                    z = self.model.forward(pp, x, self.prec)
                    lz = jax.nn.logsumexp(z, axis=-1)
                    return jnp.mean(lz - jnp.take_along_axis(
                        z, y[:, None], axis=-1)[:, 0])

                g = jax.grad(loss)(p)
                m = jax.tree.map(lambda a, b_: (cfg["momentum"] * a + b_).astype(dtype), m, g)
                p = jax.tree.map(lambda a, b_: (a - cfg["lr"] * b_).astype(dtype), p, m)
                return (p, m), None

            (p, m), _ = jax.lax.scan(step, (p, m), jnp.arange(cfg["batches_per_round"]))
            return p, m

        return jax.vmap(one_node)(params, mom, node_ids)

    # -- one WFAgg aggregation per node of a block ------------------------------
    def _aggregate_nodes(self, local, models, prev, idx, pidx, valid,
                         hist_s, hist_b, count, t):
        cfg, dt = self.cfg, self.dtype
        X = models[idx]                                  # (B, K, d)
        P = prev[pidx]
        K = idx.shape[1]
        nv = valid.sum(axis=1)
        srt = jnp.sort(jnp.where(valid[..., None], X, jnp.inf), axis=1)
        lo = jnp.clip((nv - 1) // 2, 0, K - 1)
        hi = jnp.clip(nv // 2, 0, K - 1)
        take = lambda j: jnp.take_along_axis(srt, j[:, None, None], axis=1)[:, 0]  # noqa: E731
        med = jnp.where((nv > 0)[:, None], 0.5 * (take(lo) + take(hi)), 0).astype(dt)
        dist2 = jnp.sum((X - med[:, None]) ** 2, axis=-1)
        dotmed = jnp.sum(X * med[:, None], axis=-1)
        norm2 = jnp.sum(X * X, axis=-1)
        mednorm2 = jnp.sum(med * med, axis=-1)
        cos_med = 1 - dotmed / jnp.sqrt(jnp.maximum(norm2 * mednorm2[:, None], 1e-24))
        s_t = jnp.sum((X - P) ** 2, axis=-1)
        pnorm2 = jnp.sum(P * P, axis=-1)
        b_t = 1 - jnp.sum(X * P, axis=-1) / jnp.sqrt(jnp.maximum(norm2 * pnorm2, 1e-24))

        keep = (nv - cfg["f"] - 1)[:, None]
        inf = jnp.asarray(jnp.inf, dist2.dtype)
        mask_d = _rank(jnp.where(valid, dist2, inf)) < jnp.clip(keep, 0, K)
        mask_c = _rank(jnp.where(valid, cos_med, inf)) < jnp.clip(keep, 0, K)
        mu_s, sd_s = _ewma(hist_s, count, cfg["ewma_decay"])
        mu_b, sd_b = _ewma(hist_b, count, cfg["ewma_decay"])
        active = ((t > cfg["transient"]) & (count > 0))[:, None]
        s32, b32 = s_t.astype(jnp.float32), b_t.astype(jnp.float32)
        mask_t = (active & valid & (s32 >= mu_s - sd_s) & (s32 <= mu_s + sd_s)
                  & (b32 >= mu_b - sd_b) & (b32 <= mu_b + sd_b))
        score = (cfg["tau1"] * mask_d + cfg["tau2"] * mask_c
                 + cfg["tau3"] * mask_t).astype(jnp.float32)
        floor = min(cfg["tau1"] + cfg["tau2"], cfg["tau1"] + cfg["tau3"],
                    cfg["tau2"] + cfg["tau3"])
        w = jnp.where(score < floor - 1e-9, 0.0, score) * valid
        wsum = w.sum(axis=1, keepdims=True)
        wn = (w / jnp.maximum(wsum, _EPS)).astype(dt)
        a = jnp.where(wsum > 0, cfg["alpha"], 0.0).astype(dt)
        out = (1 - a) * local + a * jnp.einsum("bk,bkd->bd", wn, X,
                                                precision=self.prec)
        return (out.astype(dt), s32, b32, mu_s, mu_b, (w > 0) & valid)

    # -- whole rounds ---------------------------------------------------------
    def init_state(self, params0) -> RefState:
        mix, tr = self.mix, self.traffic
        N, K, W = mix.nodes, mix.width, self.cfg["window"]
        params = jax.tree.map(lambda x: x.astype(self.dtype), params0)
        d = _flatten(params).shape[1]
        z = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
        return RefState(
            params=params, momentum=jax.tree.map(jnp.zeros_like, params),
            prev=jnp.zeros((N, d), self.dtype), hist_s=z(N, W, K), hist_b=z(N, W, K),
            count=jnp.zeros((N,), jnp.int32), t=jnp.zeros((N,), jnp.int32),
            ring=(jnp.zeros((mix.ring_depth, N, d), self.dtype) if self.chaos else None),
            served=(jnp.zeros((N, K), jnp.int32) if self.chaos else None),
            slate_idx=jnp.asarray(tr.idx[0]), slate_valid=jnp.asarray(tr.valid[0]))

    def _blocks(self, n, size):
        return [(s, min(n, s + size)) for s in range(0, n, size)]

    def round(self, st: RefState, r: int):
        """Round ``r`` (0-based; its data keys use r) of the mix's
        schedule.  Returns (new state, accepted (N, K), effective valid)."""
        mix, tr, cfg = self.mix, self.traffic, self.cfg
        s = r % mix.schedule_rounds
        N, L = mix.nodes, mix.ring_depth
        idx = jnp.asarray(tr.idx[s])
        valid = jnp.asarray(tr.valid[s])
        mal = jnp.asarray(tr.mal[s])
        hist_s = _rekey(st.hist_s, st.slate_idx, st.slate_valid, idx, valid)
        hist_b = _rekey(st.hist_b, st.slate_idx, st.slate_valid, idx, valid)
        served = (_rekey(st.served, st.slate_idx, st.slate_valid, idx, valid)
                  if self.chaos else None)

        parts = [self._train(jax.tree.map(lambda x: x[a:b], st.params),
                             jax.tree.map(lambda x: x[a:b], st.momentum),
                             jnp.arange(a, b), r)
                 for a, b in self._blocks(N, TRAIN_BLOCK)]
        params = jax.tree.map(lambda *xs: jnp.concatenate(xs), *[p for p, _ in parts])
        momentum = jax.tree.map(lambda *xs: jnp.concatenate(xs), *[m for _, m in parts])
        flat = _flatten(params)

        n_benign = jnp.maximum(N - mal.sum(), 1).astype(flat.dtype)
        mu = jnp.sum(jnp.where(mal[:, None], 0, flat), axis=0) / n_benign
        eps = {"ipm_100": 100.0, "ipm_0.5": 0.5}[mix.attack]
        flat = jnp.where(mal[:, None], (-eps * mu)[None, :].astype(flat.dtype), flat)

        if self.chaos:
            relag = jnp.minimum(served + 1, L)
            drop = jnp.asarray(tr.drop[s]) & valid
            lag = jnp.where(drop, relag, 0)
            ok = (lag <= mix.staleness_budget) & (lag <= r)
            eff_idx = lag * N + idx
            pidx = relag * N + idx
            full = jnp.concatenate([flat, st.ring.reshape(L * N, -1)])
            eff_valid = valid & ok
        else:
            eff_idx, pidx, full, eff_valid = idx, idx, flat, valid
        finite = jnp.isfinite(full).all(axis=1)
        models = jnp.where(finite[:, None], full, 0)
        eff_valid = eff_valid & finite[eff_idx]
        prev = models if self.chaos else jnp.where(
            jnp.isfinite(st.prev).all(axis=1)[:, None], st.prev, 0)

        outs = [self._agg(flat[a:b], models, prev, eff_idx[a:b], pidx[a:b],
                          eff_valid[a:b], hist_s[a:b], hist_b[a:b],
                          st.count[a:b], st.t[a:b])
                for a, b in self._blocks(N, AGG_BLOCK)]
        new_flat, s_t, b_t, mu_s, mu_b, accepted = (
            jnp.concatenate(xs) for xs in zip(*outs))
        if self.fault == "no_exchange":
            new_flat = flat
        elif self.fault == "altered_answer":
            victim = int(np.flatnonzero(~tr.malicious)[0])
            new_flat = new_flat.at[victim].set(flat[victim])

        if self.chaos:
            s_t = jnp.where(eff_valid, s_t, mu_s)
            b_t = jnp.where(eff_valid, b_t, mu_b)
        W = hist_s.shape[1]
        hist_s = jnp.roll(hist_s, 1, axis=1).at[:, 0].set(s_t)
        hist_b = jnp.roll(hist_b, 1, axis=1).at[:, 0].set(b_t)
        new = RefState(
            params=_unflatten(new_flat, params), momentum=momentum,
            prev=models, hist_s=hist_s, hist_b=hist_b,
            count=jnp.minimum(st.count + 1, W), t=st.t + 1,
            ring=(jnp.concatenate([flat[None], st.ring[:-1]]) if self.chaos else None),
            served=(jnp.where(eff_valid, lag, relag) if self.chaos else None),
            slate_idx=idx, slate_valid=valid)
        return new, accepted, eff_valid
