"""The stacked robust all-reduce's round kernel route on 4 virtual CPU
devices, where it runs sliced over the parameters
(``robust_allreduce._stacked_sliced_round``), against the same calls on
one device, where it is one launch.

Four steps, with WFAgg-T history (transient 1, so the temporal filter
votes from the third step on) and without, one IPM-0.5 worker of K = 4.  The leaves
give every device a slice of 1,922 columns, not a multiple of 1,024:
two split on their first dim, one on its second, and one (3 x 5) that
no dim of splits four ways and rides flattened and zero-padded.

Run by ``tests/test_sliced_round.py`` in a process of its own, with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Prints one JSON
line: for each method, with and without history, each step's masks,
weights and relative gaps, and which route each side took.
"""
from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.wfagg import WFAggConfig
from repro.distributed import robust_allreduce as ra
from repro.distributed.logical import use_sharding
from repro.launch.mesh import make_test_mesh

K, STEPS, AXES = 4, 4, ("data",)
SHAPES = {"a": (8, 300), "b": (40, 130), "c": (3, 5), "d": (6, 12)}
MALICIOUS = np.array([False, False, True, False])


def candidates(step: int):
    """(K, *shape) leaves: a shared signal that drifts with the step, a
    worker's own noise, and worker 2's IPM-0.5 row."""
    key = jax.random.PRNGKey(step)
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        base = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(99), i), shape)
        signal = base + 0.3 * jax.random.normal(k1, shape)
        out[name] = (signal[None] + 0.5 * jax.random.normal(k2, (K,) + shape))
    return ra.apply_stacked_attack(out, jnp.asarray(MALICIOUS), "ipm_0.5",
                                   jax.random.PRNGKey(7))


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def run(method: str, temporal: bool):
    cfg = ra.RobustAggConfig(method=method, layout="stacked", backend="fused",
                             wfagg=WFAggConfig(f=1, transient=1, window=3,
                                               use_temporal=temporal))
    like = {n: jnp.zeros(s, jnp.float32) for n, s in SHAPES.items()}
    mesh = make_test_mesh(data=K, model=1)
    ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    cand_sh = {n: ns(P("data")) for n in SHAPES}
    prev_sh = {n: ns(ra.stacked_prev_spec(cfg, s, P(), AXES, mesh))
               for n, s in SHAPES.items()}
    st_sh = ra.TreeAggState(prev=prev_sh, hist_s=ns(P()), hist_b=ns(P()),
                            count=ns(P()), t=ns(P()))
    out_sh = {n: ns(P()) for n in SHAPES}

    def agg(g, st):
        return ra.robust_allreduce_stacked(g, cfg, st)

    one = jax.jit(agg)
    sliced = jax.jit(agg, in_shardings=(cand_sh, st_sh),
                     out_shardings=(out_sh, st_sh, None))
    st1 = ra.init_tree_agg_state(cfg, K, like) if temporal else None
    st4 = jax.device_put(st1, st_sh) if temporal else None
    if not temporal:
        sliced = jax.jit(agg, in_shardings=(cand_sh, None),
                         out_shardings=(out_sh, None, None))
    g0 = candidates(0)
    one_hlo = one.lower(g0, st1).as_text()
    with use_sharding(mesh, {}):
        hlo = sliced.lower(jax.device_put(g0, cand_sh), st4).as_text()
    steps = []
    for step in range(STEPS):
        g = candidates(step)
        o1, st1, i1 = one(g, st1)
        with use_sharding(mesh, {}):
            o4, st4, i4 = sliced(jax.device_put(g, cand_sh), st4)
        steps.append({
            **{k: np.asarray(i4[k]).tolist() for k in ("mask_d", "mask_c", "mask_t", "weights")},
            **{"one_" + k: np.asarray(i1[k]).tolist()
               for k in ("mask_d", "mask_c", "mask_t", "weights")},
            "out": max(rel(o4[n], o1[n]) for n in SHAPES),
        })
        if temporal:
            steps[-1].update(
                prev=max(rel(st4.prev[n], st1.prev[n]) for n in SHAPES),
                hist_s=rel(st4.hist_s, st1.hist_s),
                hist_b=rel(st4.hist_b, st1.hist_b),
                count=[int(st4.count), int(st1.count)])
    # which route each side took: the sliced round's statistics launch,
    # or the one launch
    return {"steps": steps,
            "sliced_route": "@wfagg_round_indexed_stats" in hlo,
            "one_route": ("@wfagg_round_indexed_stats" not in one_hlo
                          and "@wfagg_round_indexed(" in one_hlo)}


def main() -> int:
    assert jax.device_count() == K, jax.devices()
    print(json.dumps({f"{m}.{h}": run(m, h == "history")
                      for m in ("wfagg", "alt_wfagg")
                      for h in ("history", "no_history")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
