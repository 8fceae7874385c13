"""Tier-1 coverage for the ``repro.analysis`` computation linter.

Two layers:
  * every rule's doctored-fixture self-test (the same code behind
    ``python -m repro.analysis --self-test``) runs as a pytest case, so
    a rule that stops firing breaks CI even if nobody runs the CLI;
  * cheap unit tests of the text-level scanners and the entry-point
    registry's well-formedness that don't build any real round.
"""
import inspect

import pytest

from repro.analysis import (
    RULES,
    RULES_BY_ID,
    parse_suppressions,
    scan_gather_model_dim,
    scan_nkd_buffers,
)
from repro.analysis import selftest


_SELFTESTS = [
    fn for name, fn in sorted(vars(selftest).items())
    if name.startswith("test_") and inspect.isfunction(fn)
]


@pytest.mark.parametrize("check", _SELFTESTS, ids=lambda f: f.__name__)
def test_rule_selftest(check):
    """Each rule fires on its doctored fixture and stays quiet on the
    clean twin (SystemExit signals a broken rule)."""
    check()


def test_every_rule_has_a_selftest():
    covered = {name.replace("test_", "").replace("_", "-")
               for name in (f.__name__ for f in _SELFTESTS)}
    missing = [r.id for r in RULES if r.id not in covered]
    assert not missing, f"rules without a firing self-test: {missing}"


def test_rule_registry_well_formed():
    assert len({r.id for r in RULES}) == len(RULES)
    for r in RULES:
        assert r.severity in ("error", "warning", "info"), r.id
        assert r.layer in ("jaxpr", "hlo", "pallas", "runtime", "config"), r.id
        assert RULES_BY_ID[r.id] is r


def test_entry_registry_well_formed():
    # Import deferred: entry_points() builds nothing until called, but the
    # module pulls in the dfl engine, so keep it out of collection cost.
    from repro.analysis.entry_points import entry_points

    entries = entry_points()
    assert set(entries) >= {
        "one_launch_round", "reference_round",
        "dynamic_scan", "stacked_mode_b",
    }
    for name, ep in entries.items():
        assert ep.name == name
        assert ep.expected_launches is None or ep.expected_launches >= 0
        unknown = ep.suppress - {r.id for r in RULES}
        assert not unknown, f"{name} suppresses unknown rules: {unknown}"


def test_scan_nkd_buffers_text_level():
    hlo = (
        "ENTRY main {\n"
        "  %a = f32[10,4,50890]{2,1,0} broadcast()\n"
        "  %b = f32[10,4,64]{2,1,0} broadcast()\n"
        "  %c = f32[10,4,4]{2,1,0} broadcast()\n"
        "}\n"
    )
    assert scan_nkd_buffers(hlo, 10, 4) == [4, 64, 50890]
    # min_d spares the (N, K, K) Alt-WFAgg Gram and small scratch
    assert scan_nkd_buffers(hlo, 10, 4, min_d=65) == [50890]
    assert scan_nkd_buffers(hlo, 7, 3) == []


def test_scan_gather_model_dim_text_level():
    hlo = (
        "ENTRY main {\n"
        '  %g = f32[4,50890]{1,0} gather(%o, %i), offset_dims={1}\n'
        '  %s = f32[4,8]{1,0} gather(%o2, %i2), offset_dims={1}\n'
        "}\n"
    )
    assert len(scan_gather_model_dim(hlo, min_d=25445)) == 1
    assert len(scan_gather_model_dim(hlo, min_d=8)) == 2
    assert scan_gather_model_dim(hlo, min_d=60000) == []


def test_parse_suppressions():
    sup = parse_suppressions(["no-nkd-buffer@reference_round",
                              "no-nkd-buffer@other",
                              "unknown-trip-count"])
    assert sup["unknown-trip-count"] is None  # all entries
    assert sup["no-nkd-buffer"] == {"reference_round", "other"}
    with pytest.raises(ValueError):
        parse_suppressions(["not-a-rule"])


def test_block_shapes_read_from_pallas_block_objects():
    """Block dims arrive as Pallas ``Blocked``/``Squeezed`` objects; the
    VMEM model must read their sizes (a squeezed dim counts 1)."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.artifacts import collect_pallas_calls
    from repro.kernels.robust_stats.ops import wfagg_round_indexed_combine

    N, K, d, T = 4, 3, 512, 256
    f32 = jnp.float32
    jaxpr = jax.make_jaxpr(lambda loc, m, i, w, lc: wfagg_round_indexed_combine(
        loc, m, i, w, lc, block_d=T, interpret=True))(
        jax.ShapeDtypeStruct((N, d), f32), jax.ShapeDtypeStruct((N, d), f32),
        jax.ShapeDtypeStruct((N, K), jnp.int32),
        jax.ShapeDtypeStruct((N, K), f32), jax.ShapeDtypeStruct((N,), f32))
    (info,) = collect_pallas_calls(jaxpr.jaxpr)
    shapes = [b.block_shape for b in info.blocks]
    assert (1, 1, K) in shapes and (1, 1, T) in shapes, shapes
    # the two-slot (2, K, 1, T) landing tile; its DMA semaphores cost 0
    assert info.scratch_bytes == 2 * K * T * 4


@pytest.mark.parametrize("n,k,d", [(1024, 30, 44_426), (1, 8, 7_000_000_000)],
                         ids=["er1024", "d7e9"])
def test_round_kernel_modelled_under_vmem_ceiling(n, k, d):
    """The round kernel's VMEM follows its tile rule, not d: at the
    1,024-node fleet's shape and at a 7B-parameter d the modelled
    per-step residency stays under the 16 MiB ceiling.  The candidate and
    ``prev`` matrices stay in HBM (``pl.ANY``, priced at 0) and the DMA
    semaphores cost no VMEM; the double-buffered landing tiles and the
    flush's lane-wide partial sums do."""
    from repro.analysis.vmem import DEFAULT_VMEM_CEILING, round_kernel_residency
    from repro.kernels.robust_stats.kernel import round_tile_width

    res = round_kernel_residency(d, n=n, k=k)
    T = round_tile_width(k, d, True)
    assert res["block_d"] == T and T % 1024 == 0
    n_t = -(-d // T)
    assert res["grid"] == [n, 2, n_t]
    assert res["grid_steps"] == n * 2 * n_t
    landing = 2 * (2 * k * T * 4)                   # models + prev, 2 slots
    # the flush's partial sums: mednorm2 and six per candidate, each one
    # 1024-lane chunk wide
    partials = (1 + 6 * k) * 1024 * 4
    assert landing + partials <= res["scratch_bytes"] < (
        landing + partials + 4096)
    assert res["vmem_bytes"] <= DEFAULT_VMEM_CEILING, res
    if d == 44_426:
        assert T == 4096 and n_t == 11           # 45,056 lanes, as before
