"""Per-config VMEM-budget headroom over the model-shape registry.

The round kernel tiles the model dimension, so its per-grid-step VMEM
residency is set by (K, T), NOT by d — that independence is exactly the
scaling claim (LeNet to yi-6b through one kernel), and this report
makes it checkable instead of folklore: for every registered
architecture, trace ``wfagg_round_indexed`` abstractly at the compiled-
TPU tile rule (``round_tile_width``) and price the launch with the same
:class:`~repro.analysis.artifacts.PallasCallInfo` model the vmem-budget
rule uses (the candidate and ``prev`` matrices stay in HBM and cost no
VMEM; the landing tiles are scratch).  Tracing uses ShapeDtypeStructs
only — a 480B-parameter config costs the same milliseconds as LeNet.

``launch/dryrun.py`` embeds one of these records per dry-run artifact;
``python -m repro.analysis --configs`` emits the whole sweep.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

# ~16 MiB/core of scoped VMEM
DEFAULT_VMEM_CEILING = 16 * 1024 * 1024


def round_kernel_residency(d: int, n: int = 10, k: int = 8,
                           block_d: Optional[int] = None,
                           temporal: bool = True) -> Dict[str, Any]:
    """Trace the one-launch round kernel at ``(n, k, d)`` and return its
    grid, tile width, grid steps a round and modelled per-grid-step VMEM
    bytes (no arrays allocated).  ``block_d=None`` is the compiled-TPU
    tile rule."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.artifacts import collect_pallas_calls
    from repro.core import wfagg as wf
    from repro.kernels.robust_stats.kernel import round_tile_width
    from repro.kernels.robust_stats.ops import wfagg_round_indexed

    if block_d is None:
        block_d = round_tile_width(k, d, temporal)
    cfg = wf.WFAggConfig(f=1)
    f32 = jnp.float32
    local = jax.ShapeDtypeStruct((n, d), f32)
    idx = jax.ShapeDtypeStruct((n, k), jnp.int32)
    valid = jax.ShapeDtypeStruct((n, k), jnp.bool_)
    prev = jax.ShapeDtypeStruct((n, d), f32) if temporal else None
    tbands = jax.ShapeDtypeStruct((n, 4, k), f32) if temporal else None

    def fn(m, i, v, *rest):
        p, tb = rest if temporal else (None, None)
        return wfagg_round_indexed(m, m, i, v, cfg, prev=p, tbands=tb,
                                   block_d=block_d, interpret=False)

    args = (local, idx, valid) + ((prev, tbands) if temporal else ())
    jaxpr = jax.make_jaxpr(fn)(*args)
    calls = collect_pallas_calls(jaxpr.jaxpr)
    if not calls:
        raise RuntimeError("round op traced to zero pallas_call eqns")
    info = calls[0]
    return {
        "kernel": info.name,
        "grid": list(info.grid),
        "grid_steps": math.prod(info.grid),
        "block_d": block_d,
        "block_bytes": info.block_bytes,
        "scratch_bytes": info.scratch_bytes,
        "vmem_bytes": info.vmem_bytes(),
    }


def config_vmem_report(arch: Optional[str] = None, n: int = 10, k: int = 8,
                       ceiling: int = DEFAULT_VMEM_CEILING) -> List[Dict[str, Any]]:
    """vmem-budget headroom records for ``arch`` (or every registered
    architecture, LeNet to yi-6b, when None)."""
    from repro.configs.registry import ALL_ARCHS, get_config

    names = [arch] if arch else sorted(ALL_ARCHS)
    records = []
    for name in names:
        cfg = get_config(name)
        d = int(cfg.param_count())
        res = round_kernel_residency(d, n=n, k=k)
        vmem = res["vmem_bytes"]
        records.append({
            "arch": name,
            "d": d,
            **res,
            "ceiling": ceiling,
            "headroom_bytes": ceiling - vmem,
            "headroom_frac": round(1.0 - vmem / ceiling, 4),
            "ok": vmem <= ceiling,
        })
    return records
