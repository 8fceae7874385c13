"""Ahead-of-time compiles of the indexed Pallas kernels for a described
TPU v5e, at the paper fleet's real width (N=20 nodes, K=8 neighbors,
LeNet d=44,426) and, for the round kernel, at the 1,024-node lossy
fleet's (K=30 slots, a 4,100-row stacked matrix) and the robust-DP
trainer's (N=1, K=4 replicas); and the trainer's whole step on a
described 2x2 mesh.  No chip is attached: Mosaic compiles for the
described device and refuses what the chip would refuse (block shapes
off the (8, 128) tiling, unsupported vector ops, a program that does
not fit HBM), which interpret mode cannot show.  Each compile must
contain the Mosaic custom call.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.wfagg import WFAggConfig, alt_wfagg_config
from repro.kernels.robust_stats.ops import (
    wfagg_round_indexed, wfagg_round_indexed_combine,
    wfagg_round_indexed_stats)

N, K, D = 20, 8, 44_426


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("cfg", [WFAggConfig(), alt_wfagg_config()],
                         ids=["wfagg", "alt_wfagg"])
def test_round_kernel_compiles(one_chip, cfg):
    """The one-launch round with matrix-form temporal state read through
    its own table (the chaos transport's shape) and WFAgg-T bands, at
    the tile the rule gives the paper fleet (T = 22,528, the flush's
    partial sums counted)."""
    from repro.kernels.robust_stats.kernel import round_tile_width

    assert round_tile_width(K, D, True) == 22_528
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    _assert_mosaic(
        lambda loc, m, i, v, p, tb, pi: wfagg_round_indexed(
            loc, m, i, v, cfg, prev=p, tbands=tb, prev_idx=pi,
            interpret=False),
        s((N, D)), s((N, D)), s((N, K), jnp.int32), s((N, K)),
        s((2 * N, D)), s((N, 4 * K)), s((N, K), jnp.int32))


@pytest.mark.parametrize("cfg", [WFAggConfig(), alt_wfagg_config()],
                         ids=["wfagg", "alt_wfagg"])
def test_round_kernel_compiles_er1024(one_chip, cfg):
    """The round at the 1,024-node cell's shape: K = 30 (the manual row
    gathers and the tile rule's T = 4,096 at that K), the transport's
    stacked (4N + 4, d) matrix as both candidates and ``prev``, read
    through ``prev_idx``, and the WFAgg-T bands."""
    from repro.kernels.robust_stats.kernel import round_tile_width

    n, k, m = 1024, 30, 4 * 1024 + 4
    assert round_tile_width(k, D, True) == 4_096
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    _assert_mosaic(
        lambda loc, mat, i, v, tb, pi: wfagg_round_indexed(
            loc, mat, i, v, cfg, prev=mat, tbands=tb, prev_idx=pi,
            interpret=False),
        s((n, D)), s((m, D)), s((n, k), jnp.int32), s((n, k)),
        s((n, 4 * k)), s((n, k), jnp.int32))


@pytest.mark.parametrize("temporal", [True, False],
                         ids=["prev_tbands", "no_prev"])
def test_round_kernel_compiles_trainer(one_chip, temporal):
    """The round as the robust-DP trainer's stacked WFAgg step runs it:
    N = 1, an identity table over K = 4 replica gradients, matrix-form
    ``prev``, alpha = 1 with the uniform-mean fallback, at a P where the
    tile rule picks the widest tile its budget allows at K = 4, the
    flush's partial sums counted (T = 52,224 with ``prev``, 86,016
    without)."""
    from repro.kernels.robust_stats.kernel import round_tile_width

    k, p = 4, 51 * 84 * 1024 * 24 - 1000
    assert round_tile_width(k, p, temporal) == (52_224 if temporal
                                                else 86_016)
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    cfg = WFAggConfig(f=1, use_temporal=temporal)
    args = [s((1, p)), s((k, p)), s((1, k), jnp.int32)]
    if temporal:
        args += [s((k, p)), s((1, 4 * k))]
    _assert_mosaic(
        lambda loc, f, i, p=None, tb=None: wfagg_round_indexed(
            loc, f, i, None, cfg, prev=p, tbands=tb, alpha=1.0,
            mean_fallback=True, interpret=False),
        *args)


@pytest.fixture(scope="module")
def trainer_step_four_chips(topo):
    """The robust-DP trainer's whole step, as the four-chip bring-up
    check runs it (qwen1.5-0.5b widths at 8 layers, stacked WFAgg with
    the fused round, a (data=4, model=1) mesh, donated state), compiled
    for a described v5e 2x2: (compiled text, parameter shapes)."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    import repro.kernels.common as kcommon
    from repro.configs.registry import get_config
    from repro.distributed.robust_allreduce import RobustAggConfig
    from repro.data.synthetic import TokenStream
    from repro.train import trainer as tr

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b"), n_layers=8)
    tc = tr.TrainConfig(
        agg=RobustAggConfig(method="wfagg", layout="stacked",
                            wfagg=WFAggConfig(f=1), backend="fused"),
        warmup=1, total_steps=3, attack="ipm_100", n_malicious=1)
    shape = tr.init_train_state(cfg, tc, jax.random.PRNGKey(0), mesh,
                                abstract=True)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        shape, tr.state_shardings(cfg, tc, mesh, shape))
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=128,
                         batch_size=8)
    bshape = jax.eval_shape(stream.batch, 0)
    batch = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        bshape, tr.batch_shardings(tc, mesh, bshape))
    # the described chips are not the default backend: pin the Mosaic path
    with pytest.MonkeyPatch.context() as mp, mesh:
        mp.setattr(kcommon, "default_interpret", lambda: False)
        compiled = tr.build_train_step(cfg, tc, mesh).lower(
            state, batch).compile()
    return compiled.as_text(), [tuple(p.shape) for p in
                                jax.tree.leaves(shape.params)]


def test_trainer_step_compiles_on_four_chips(trainer_step_four_chips):
    """The step compiles with the Mosaic round kernel in it and fits
    the chips' HBM."""
    assert "tpu_custom_call" in trainer_step_four_chips[0]


def test_trainer_step_reads_only_its_slice(trainer_step_four_chips):
    """Each chip aggregates its 1/4 slice of every candidate: no
    all-gather or all-to-all of the step yields K whole candidates or
    K whole ``prev`` rows, flattened ((K, P)) or leaf by leaf
    ((K, *leaf)), nor anything larger than the largest leaf; and every
    Pallas launch is the round kernel's (the benchmark reads the
    ``wfagg_round_indexed`` prefix)."""
    import math
    import re

    hlo, leaves = trainer_step_four_chips
    k = 4
    p_total = sum(math.prod(s) for s in leaves)
    whole = {(k,) + s for s in leaves} | {(k, math.prod(s)) for s in leaves}
    whole.add((k, p_total))
    largest = max(math.prod(s) for s in leaves)
    moved = re.findall(
        r"= (\(?[^=]*?) (all-gather|all-to-all)(?:-start)?\(", hlo)
    assert moved, "the step moves no candidate"
    for result, op in moved:
        for dims in re.findall(r"f32\[([\d,]*)\]", result):
            shape = tuple(int(x) for x in dims.split(",") if x)
            squeezed = tuple(x for x in shape if x != 1)
            assert squeezed not in whole, (op, shape)
            assert math.prod(shape) <= largest, (op, shape)
    launches = re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert launches and all(
        n.startswith("wfagg_round_indexed") for n in launches), launches


@pytest.mark.parametrize("need_gram", [False, True], ids=["stats", "gram"])
def test_stats_kernel_compiles(one_chip, need_gram):
    """The round kernel's statistics phase alone (the d-sharded round's
    first launch), with a ``valid`` mask, a matrix-form ``prev`` and,
    for Alt-WFAgg, the Gram."""
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    _assert_mosaic(
        lambda m, i, v, p: wfagg_round_indexed_stats(
            m, i, p, valid=v, need_gram=need_gram, interpret=False),
        s((N, D)), s((N, K), jnp.int32), s((N, K)), s((N, D)))


def test_combine_kernel_compiles(one_chip):
    """The round kernel's combine phase alone (the d-sharded round's
    second launch), its coefficients an input."""
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    _assert_mosaic(
        lambda loc, m, i, w, lc: wfagg_round_indexed_combine(
            loc, m, i, w, lc, interpret=False),
        s((N, D)), s((N, D)), s((N, K), jnp.int32), s((N, K)), s((N,)))
