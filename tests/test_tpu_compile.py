"""Ahead-of-time compiles of the indexed Pallas kernels for a described
TPU v5e, at the paper fleet's real width (N=20 nodes, K=8 neighbors,
LeNet d=44,426).  No chip is attached: Mosaic compiles for the described
device and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, unsupported vector ops), which interpret mode cannot
show.  Each compile must contain the Mosaic custom call.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.wfagg import WFAggConfig, alt_wfagg_config
from repro.kernels.robust_stats.ops import (
    robust_stats_indexed, wfagg_round_indexed)
from repro.kernels.weighted_agg.ops import weighted_agg_indexed

N, K, D = 20, 8, 44_426


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("cfg", [WFAggConfig(), alt_wfagg_config()],
                         ids=["wfagg", "alt_wfagg"])
def test_round_kernel_compiles(one_chip, cfg):
    """The one-launch round with matrix-form temporal state read through
    its own table (the chaos transport's shape) and WFAgg-T bands."""
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    _assert_mosaic(
        lambda loc, m, i, v, p, tb, pi: wfagg_round_indexed(
            loc, m, i, v, cfg, prev=p, tbands=tb, prev_idx=pi,
            interpret=False),
        s((N, D)), s((N, D)), s((N, K), jnp.int32), s((N, K)),
        s((2 * N, D)), s((N, 4 * K)), s((N, K), jnp.int32))


@pytest.mark.parametrize("need_gram", [False, True], ids=["stats", "gram"])
def test_stats_kernel_compiles(one_chip, need_gram):
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    _assert_mosaic(
        lambda m, i, v, p: robust_stats_indexed(
            m, i, v, prev=p, need_gram=need_gram, interpret=False),
        s((N, D)), s((N, K), jnp.int32), s((N, K)), s((N, D)))


def test_combine_kernel_compiles(one_chip):
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    _assert_mosaic(
        lambda loc, m, i, w: weighted_agg_indexed(loc, m, i, w,
                                                  interpret=False),
        s((N, D)), s((N, D)), s((N, K), jnp.int32), s((N, K)))
