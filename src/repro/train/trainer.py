"""Training step builders.

Two execution modes (DESIGN.md Section 3):

* ``robust_dp`` — the paper's technique as a first-class distributed
  feature: partial-manual shard_map over the candidate axes ('data', and
  'pod' when multi-pod).  Each worker computes its own gradient (GSPMD
  tensor-parallel over 'model'), Byzantine workers optionally poison it
  (integration tests / demos), and `robust_allreduce` replaces the mean
  all-reduce.  Params are replicated across candidates, TP-sharded over
  'model'.

* ``gspmd`` — conventional jit data-parallel training (mean aggregation,
  FSDP+TP param sharding).  Used for the >=100B arch whose K full
  gradient candidates cannot coexist in pod HBM (arctic-480b), and as the
  non-robust performance baseline.

Both name their phases on the device trace (``repro.obs.profile.phase``):
``grad`` (with ``data`` nested in it), ``attack``, ``aggregate`` and
``optimizer``.  The scopes change metadata only; the compiled step is
the same program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape
from repro.core.topology import spaced_malicious
from repro.distributed import sharding as shd
from repro.distributed.logical import use_sharding
from repro.distributed.robust_allreduce import (
    AggState,
    RobustAggConfig,
    TreeAggState,
    apply_distributed_attack,
    apply_stacked_attack,
    init_agg_state,
    init_tree_agg_state,
    robust_allreduce,
    robust_allreduce_stacked,
    stacked_prev_spec,
)
from repro.models import model as M
from repro.obs.profile import phase
from repro.optim.optimizers import make_optimizer, warmup_cosine

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    mode: str = "robust_dp"                    # robust_dp | gspmd
    agg: RobustAggConfig = RobustAggConfig()
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    attack: str = "none"
    n_malicious: int = 0
    multi_pod: bool = False
    donate: bool = True
    # FSDP-shard params + optimizer state over the data axes (stacked
    # layout only): costs one param all-gather per step at the grad
    # shard_map boundary, divides train-state HBM by the data size — the
    # change that lets >30B robust_dp archs hold Adam state at all
    # (EXPERIMENTS.md Section Perf, pair C).
    fsdp_params: bool = False
    # split each worker's local batch into m microbatches accumulated in a
    # scan: activation peak /m, gradient semantics identical (the
    # candidate gradient is the mean over its own microbatches).
    microbatches: int = 1

    def candidate_axes(self) -> Tuple[str, ...]:
        return ("pod", "data") if self.multi_pod else ("data",)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    agg_state: Optional[AggState]
    step: Array


def _n_candidates(mesh: Mesh, tc: TrainConfig) -> int:
    n = mesh.shape["data"]
    if tc.multi_pod:
        n *= mesh.shape["pod"]
    return int(n)


def init_train_state(cfg: ArchConfig, tc: TrainConfig, key: Array,
                     mesh: Optional[Mesh] = None, abstract: bool = False) -> TrainState:
    """Materialize (or eval_shape when abstract=True) the train state."""
    opt = make_optimizer(cfg.optimizer)
    K = _n_candidates(mesh, tc) if mesh is not None else 1

    def build(key):
        params = M.init_params(cfg, key)
        opt_state = opt.init(params)
        agg_state = None
        if tc.mode == "robust_dp" and tc.agg.method in ("wfagg", "alt_wfagg") \
                and tc.agg.wfagg.use_temporal:
            if tc.agg.layout == "stacked":
                agg_state = init_tree_agg_state(tc.agg, K, params)
            else:
                agg_state = init_agg_state(tc.agg, K)
        return TrainState(params, opt_state, agg_state, jnp.zeros((), jnp.int32))

    if abstract:
        return jax.eval_shape(build, key)
    return build(key)


def state_shardings(cfg: ArchConfig, tc: TrainConfig, mesh: Mesh,
                    state_shape: TrainState) -> TrainState:
    """NamedShardings for the train state under the chosen mode."""
    data_axes = tc.candidate_axes()
    fsdp = tc.mode == "gspmd" or (tc.fsdp_params and tc.agg.layout == "stacked")
    pspecs = shd.param_specs(cfg, state_shape.params, fsdp=fsdp, data_axes=data_axes, mesh=mesh)
    # optimizer state mirrors param sharding where shapes match; replicate
    # the rest (Adafactor row/col factors, scalars).
    flat_p = {id(l): s for l, s in zip(jax.tree.leaves(state_shape.params),
                                       jax.tree.leaves(pspecs))}
    p_shapes = {tuple(l.shape): s for l, s in zip(jax.tree.leaves(state_shape.params),
                                                  jax.tree.leaves(pspecs))}

    def opt_spec(leaf):
        return p_shapes.get(tuple(leaf.shape), P())

    ospecs = jax.tree.map(opt_spec, state_shape.opt_state)
    if state_shape.agg_state is None:
        aspecs = None
    elif isinstance(state_shape.agg_state, TreeAggState):
        # prev: where the aggregation reads it (stacked_prev_spec)
        prev_p = shd.param_specs(cfg, state_shape.params, fsdp=False,
                                 data_axes=data_axes, mesh=mesh)
        prev_specs = jax.tree.map(
            lambda sp, leaf: stacked_prev_spec(tc.agg, leaf.shape, sp,
                                               data_axes, mesh),
            prev_p, state_shape.params)
        aspecs = TreeAggState(prev=prev_specs,
                              hist_s=P(), hist_b=P(), count=P(), t=P())
    else:
        aspecs = jax.tree.map(lambda _: P(), state_shape.agg_state)
    ns = lambda spec: NamedSharding(mesh, spec)
    return TrainState(
        params=jax.tree.map(ns, pspecs),
        opt_state=jax.tree.map(ns, ospecs),
        agg_state=jax.tree.map(ns, aspecs) if aspecs is not None else None,
        step=ns(P()),
    )


def batch_shardings(tc: TrainConfig, mesh: Mesh, batch_shape: Any) -> Any:
    specs = shd.batch_specs(batch_shape, data_axes=tc.candidate_axes(), mesh=mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def _pin_state(cfg: ArchConfig, tc: TrainConfig, mesh: Mesh,
               state: TrainState) -> TrainState:
    """Constrain a step's output state to ``state_shardings``: left to
    GSPMD, small leaves (the WFAgg-T history) come out sharded unlike
    the input, and the next step recompiles for the new shardings."""
    return jax.tree.map(jax.lax.with_sharding_constraint, state,
                        state_shardings(cfg, tc, mesh, state))


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_train_step(cfg: ArchConfig, tc: TrainConfig, mesh: Mesh) -> Callable:
    """Returns jitted fn(state, batch) -> (state, metrics)."""
    opt = make_optimizer(cfg.optimizer)
    lr_fn = warmup_cosine(tc.lr, tc.warmup, tc.total_steps)
    axes = tc.candidate_axes()
    K = _n_candidates(mesh, tc)
    malicious = jnp.asarray(spaced_malicious(K, tc.n_malicious))
    rules = shd.activation_rules(tc.mode, tc.multi_pod)

    def loss_of(params, batch):
        if tc.attack == "label_flip":
            # data poisoning analog for LM batches: flip target ids
            batch = dict(batch, tokens=(cfg.vocab_size - 1) - batch["tokens"])
            # only malicious nodes flip; handled by caller via lax.cond-free
            # select in robust_dp mode (see _node_step)
        return M.loss_fn(cfg, params, batch)

    if tc.mode == "robust_dp":
        stacked = tc.agg.layout == "stacked"
        axis_spec = axes if len(axes) > 1 else axes[0]

        def _node_step(params, opt_state, agg_state, step, batch):
            # batch here is this node's LOCAL slice (manual over candidate axes)
            if tc.attack == "label_flip" and tc.n_malicious > 0:
                from repro.distributed.robust_allreduce import my_index
                me = my_index(axes)
                bad = malicious[me]
                batch = dict(
                    batch,
                    tokens=jnp.where(bad, (cfg.vocab_size - 1) - batch["tokens"],
                                     batch["tokens"]),
                )
            with phase("grad"):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: M.loss_fn(cfg, p, batch), has_aux=True
                )(params)
                flat, unravel = ravel_pytree(grads)
            attacking = tc.attack not in ("none", "label_flip") and tc.n_malicious > 0
            if attacking:
                with phase("attack"):
                    akey = jax.random.fold_in(jax.random.PRNGKey(tc.agg.seed + 1),
                                              step)
                    flat = apply_distributed_attack(flat, axes, malicious,
                                                    tc.attack, akey)
            with phase("aggregate"):
                agg_flat, new_agg, info = robust_allreduce(flat, axes, tc.agg,
                                                           agg_state)
                grads = unravel(agg_flat)
            with phase("optimizer"):
                gn = jnp.sqrt(jnp.sum(agg_flat.astype(jnp.float32) ** 2))
                lr = lr_fn(step)
                updates, new_opt = opt.update(grads, opt_state, params, lr)
                new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                          params, updates)
            mean_loss = jax.lax.pmean(loss, axes)
            out_metrics = {
                "loss": mean_loss,
                "lr": lr,
                "grad_norm": gn,
                "n_accepted": info.get("n_accepted", jnp.asarray(K)),
                "weights": info.get("weights", jnp.ones((K,), jnp.float32)),
            }
            return new_params, new_opt, new_agg, step + 1, out_metrics

        # ------------- layout='flat': the paper-shaped baseline -------------
        def flat_step_fn(state: TrainState, batch):
            has_agg = state.agg_state is not None
            agg_in = state.agg_state if has_agg else jnp.zeros((), jnp.float32)
            bspecs = shd.batch_specs(batch, data_axes=axes, mesh=mesh)

            def wrapped(params, opt_state, agg_state, step, batch):
                agg = agg_state if has_agg else None
                p, o, a, s, m = _node_step(params, opt_state, agg, step, batch)
                a = a if a is not None else jnp.zeros((), jnp.float32)
                return p, o, a, s, m

            out = jax.shard_map(
                wrapped,
                mesh=mesh,
                in_specs=(P(), P(), P(), P(), bspecs),
                out_specs=(P(), P(), P(), P(), P()),
                axis_names=set(axes),
                check_vma=False,
            )(state.params, state.opt_state, agg_in, state.step, batch)
            p, o, a, s, m = out
            return TrainState(p, o, a if has_agg else None, s), m

        # --------- layout='stacked': sharded-gradient fast path -------------
        # shard_map computes ONLY per-worker (loss, grads), returned with a
        # leading candidate axis sharded over the data axes; attacks,
        # robust aggregation and the optimizer run OUTSIDE in pure GSPMD,
        # where every gradient leaf keeps its TP sharding (manual
        # collectives in partial-manual regions force auto-axis
        # replication — measured in EXPERIMENTS.md Section Perf).
        pspecs_tp = shd.param_specs(cfg, jax.eval_shape(
            lambda: M.init_params(cfg, jax.random.PRNGKey(0))),
            fsdp=False, data_axes=axes, mesh=mesh)
        stacked_specs = jax.tree.map(lambda sp: P(axis_spec, *tuple(sp)), pspecs_tp)

        def grad_worker(params, step, batch):
            if tc.attack == "label_flip" and tc.n_malicious > 0:
                from repro.distributed.robust_allreduce import my_index
                me = my_index(axes)
                bad = malicious[me]
                batch = dict(
                    batch,
                    tokens=jnp.where(bad, (cfg.vocab_size - 1) - batch["tokens"],
                                     batch["tokens"]),
                )
            mb = tc.microbatches
            if mb > 1:
                batch_r = jax.tree.map(
                    lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]),
                    batch)

                def one_mb(carry, mbatch):
                    acc, lsum = carry
                    (loss, _), g = jax.value_and_grad(
                        lambda p: M.loss_fn(cfg, p, mbatch), has_aux=True
                    )(params)
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(a.dtype) / mb, acc, g)
                    return (acc, lsum + loss / mb), None

                acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
                (grads, loss), _ = jax.lax.scan(
                    one_mb, (acc0, jnp.zeros((), jnp.float32)), batch_r)
            else:
                (loss, _), grads = jax.value_and_grad(
                    lambda p: M.loss_fn(cfg, p, batch), has_aux=True
                )(params)
            return jax.tree.map(lambda g: g[None], grads), loss[None]

        def stacked_step_fn(state: TrainState, batch):
            has_agg = state.agg_state is not None
            bspecs = shd.batch_specs(batch, data_axes=axes, mesh=mesh)
            with phase("grad"):
                grads_stacked, losses = jax.shard_map(
                    grad_worker,
                    mesh=mesh,
                    in_specs=(P(), P(), bspecs),
                    out_specs=(jax.tree.map(lambda _: P(axis_spec), state.params),
                               P(axis_spec)),
                    axis_names=set(axes),
                    check_vma=False,
                )(state.params, state.step, batch)
                # pin the stacked candidate layout: (K over data axes, TP inner)
                grads_stacked = jax.tree.map(
                    lambda g, sp: jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, sp)),
                    grads_stacked, stacked_specs)

            if tc.attack not in ("none", "label_flip") and tc.n_malicious > 0:
                with phase("attack"):
                    akey = jax.random.fold_in(jax.random.PRNGKey(tc.agg.seed + 1),
                                              state.step)
                    grads_stacked = apply_stacked_attack(
                        grads_stacked, malicious, tc.attack, akey)

            with phase("aggregate"):
                agg = state.agg_state if has_agg else None
                grads, new_agg, info = robust_allreduce_stacked(
                    grads_stacked, tc.agg, agg)

            with phase("optimizer"):
                lr = lr_fn(state.step)
                updates, new_opt = opt.update(grads, state.opt_state,
                                              state.params, lr)
                new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                          state.params, updates)
                gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                  for g in jax.tree.leaves(grads)))
            m = {
                "loss": jnp.mean(losses),
                "losses": losses,
                "lr": lr,
                "grad_norm": gn,
                "n_accepted": info.get("n_accepted", jnp.asarray(K)),
                "weights": info.get("weights", jnp.ones((K,), jnp.float32)),
            }
            return TrainState(new_params, new_opt,
                              new_agg if has_agg else None,
                              state.step + 1), m

        step_fn = stacked_step_fn if stacked else flat_step_fn

        def jit_step(state, batch):
            with use_sharding(mesh, rules):
                new_state, m = step_fn(state, batch)
            return _pin_state(cfg, tc, mesh, new_state), m

        return jax.jit(jit_step, donate_argnums=(0,) if tc.donate else ())

    # ------------------------------ gspmd mode ------------------------------
    assert tc.agg.method == "mean", "gspmd mode supports mean aggregation only"

    def gspmd_step(state: TrainState, batch):
        with use_sharding(mesh, rules):
            with phase("grad"):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: M.loss_fn(cfg, p, batch), has_aux=True
                )(state.params)
            with phase("optimizer"):
                lr = lr_fn(state.step)
                updates, new_opt = opt.update(grads, state.opt_state,
                                              state.params, lr)
                new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                          state.params, updates)
                gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                  for g in jax.tree.leaves(grads)))
            m = {"loss": loss, "lr": lr, "grad_norm": gn,
                 "n_accepted": jnp.asarray(K), "weights": jnp.ones((K,), jnp.float32)}
            new_state = TrainState(new_params, new_opt, None, state.step + 1)
        return _pin_state(cfg, tc, mesh, new_state), m

    return jax.jit(gspmd_step, donate_argnums=(0,) if tc.donate else ())
