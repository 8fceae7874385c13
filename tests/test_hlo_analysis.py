"""Validate the trip-count-aware HLO analyzer against analytic ground truth.

The whole §Roofline pipeline rests on this module, so we check:
  * dot FLOPs exact on a plain matmul;
  * scan(L) total ~= L x per-iteration cost (the thing raw cost_analysis
    misses);
  * scanned == unrolled totals to within fusion noise;
  * collective wire bytes inside a scan get multiplied by the trip count.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis as ha


def _cost(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return ha.analyze(compiled.as_text(), n_devices=1)


def test_matmul_flops_exact():
    xs = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    ws = jax.ShapeDtypeStruct((512, 384), jnp.float32)
    c = _cost(lambda x, w: x @ w, xs, ws)
    assert c.flops == pytest.approx(2 * 256 * 512 * 384, rel=0.05)


def test_scan_trip_count_multiplies():
    L, B, D = 24, 128, 256

    def f(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), ()
        h, _ = jax.lax.scan(body, x, ws)
        return h

    xs = jax.ShapeDtypeStruct((B, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    c = _cost(f, xs, ws)
    expected = L * 2 * B * D * D
    assert c.flops == pytest.approx(expected, rel=0.1)
    assert c.n_while == 1
    assert c.trip_counts == [L]


def test_scanned_matches_unrolled():
    L, B, D = 8, 64, 128

    def scanned(x, ws):
        h, _ = jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), ()), x, ws)
        return h

    def unrolled(x, ws):
        h = x
        for i in range(L):
            h = jnp.tanh(h @ ws[i])
        return h

    xs = jax.ShapeDtypeStruct((B, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    cs, cu = _cost(scanned, xs, ws), _cost(unrolled, xs, ws)
    assert cs.flops == pytest.approx(cu.flops, rel=0.15)
    # bytes: scanned re-reads weights per iteration either way
    assert cs.bytes == pytest.approx(cu.bytes, rel=0.5)


def test_grad_of_scan_counts_backward_pass():
    L, B, D = 16, 32, 64

    def loss(x, ws):
        h, _ = jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), ()), x, ws)
        return h.sum()

    xs = jax.ShapeDtypeStruct((B, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    c = _cost(jax.grad(loss, argnums=(0, 1)), xs, ws)
    # fwd (2BDD) + two backward matmuls (2x 2BDD) per layer
    expected = 3 * L * 2 * B * D * D
    assert c.flops == pytest.approx(expected, rel=0.25)
    assert c.n_while >= 2  # fwd scan + bwd scan


def test_collectives_inside_scan_multiplied(monkeypatch):
    if jax.device_count() < 4:
        pytest.skip("needs forced host devices")
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    N, C, D = 4, 6, 1024

    def f(x):
        def body(acc, chunk):
            g = jax.lax.all_gather(chunk, "data")  # (4, D) f32
            return acc + g.sum(), None
        acc, _ = jax.lax.scan(body, 0.0, x)
        return acc

    sf = jax.shard_map(f, mesh=mesh, in_specs=(P(None, None),),
                       out_specs=P(), check_vma=False)
    xs = jax.ShapeDtypeStruct((C, D), jnp.float32)
    compiled = jax.jit(sf).lower(xs).compile()
    c = ha.analyze(compiled.as_text(), n_devices=4)
    per_gather_wire = 4 * D * 4 * (4 - 1) / 4  # out_bytes*(S-1)/S
    assert c.wire_bytes == pytest.approx(C * per_gather_wire, rel=0.3)


def test_indexed_wfagg_round_is_gossip_tensor_free():
    """The gather-free (fused, neighbor-indexed) DFL round must not
    allocate ANY (N, K, d)-shaped f32 buffer — the K-fold gossip tensor,
    its padded variants AND the per-edge temporal state are all gone.
    The reference backend still materializes them (sanity check that the
    scanner actually catches the gather).  Asserted through the shared
    ``repro.analysis.scan_nkd_buffers`` — the same scanner behind the
    ``no-nkd-buffer`` rule in ``python -m repro.analysis``."""
    from repro.analysis import scan_nkd_buffers
    from repro.core.topology import paper_topology
    from repro.data.synthetic import SyntheticImages
    from repro.dfl.engine import DFLConfig, build_round_fn, init_dfl_state

    topo = paper_topology()
    data = SyntheticImages()
    N, K = topo.n_nodes, topo.degree
    hits = {}
    for backend in ("fused", "reference"):
        cfg = DFLConfig(aggregator="wfagg", attack="ipm_100", model="mlp",
                        wfagg_backend=backend)
        state = init_dfl_state(cfg, topo)
        fn = build_round_fn(cfg, topo, data)
        hlo = fn.lower(state).compile().as_text()
        hits[backend] = scan_nkd_buffers(hlo, N, K)
    assert hits["fused"] == [], hits["fused"]
    assert hits["reference"], "reference round should materialize the gather"


def test_dynamic_update_slice_counts_update_only():
    cap, D = 65536, 512

    def f(buf, upd, idx):
        return jax.lax.dynamic_update_slice(buf, upd, (idx, 0))

    bs = jax.ShapeDtypeStruct((cap, D), jnp.float32)
    us = jax.ShapeDtypeStruct((1, D), jnp.float32)
    isx = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = jax.jit(f, donate_argnums=(0,)).lower(bs, us, isx).compile()
    c = ha.analyze(compiled.as_text(), n_devices=1)
    # traffic should be ~the update (2*2KB), not the 128MB buffer
    assert c.bytes < 1e6
