"""Pallas TPU kernel: fused robust statistics over K candidates.

Tiling: the candidate matrix (K, D) streams HBM->VMEM in (K, T) blocks
(T a multiple of 128 lanes; K <= 32 candidates sit on the sublane axis).
Inside a block we run an odd-even-transposition sorting network over the
K axis — K is static and small, so the network fully unrolls into ~K^2/2
vectorized min/max pairs on (T,)-shaped vregs, which the VPU executes at
full lane width.  The median/trimmed-mean reductions and all per-candidate
partial statistics (distance-to-median, dot-with-median, norms) come out
of the same VMEM-resident block, so the whole WFAgg filter bank costs ONE
HBM read of the candidates.

Temporal extension: when the previous-round candidates ``prev (K, D)``
are supplied, the same VMEM-resident block also accumulates the WFAgg-T
metrics — s_t = ||u - prev||^2 plus the dot/norm terms of b_t — so the
full WFAgg-D/C/T filter bank still costs one read of the candidates (plus
the unavoidable one read of ``prev``).

Grids:
  single  1-D over D/T blocks, candidates (K, D)
  batched 2-D over (node, D/T block), candidates (N, K, D) — all N
          per-node gossip aggregations in ONE kernel launch.  The D axis
          is the innermost grid dimension, so each node's revisited (K,)
          accumulator blocks are initialized at its first D block and
          complete before the grid moves to the next node.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret
from repro.kernels.robust_stats.ref import RobustStats

Array = jax.Array


def sort_rows(x: Array) -> list:
    """Odd-even transposition sort along axis 0 of a (K, T) tile (static
    K, fully unrolled); returns the K sorted rows, each (1, T)."""
    K = x.shape[0]
    rows = [x[r:r + 1] for r in range(K)]
    for p in range(K):
        for r in range(p % 2, K - 1, 2):
            a, b = rows[r], rows[r + 1]
            rows[r], rows[r + 1] = jnp.minimum(a, b), jnp.maximum(a, b)
    return rows


def _row_view(x: Array) -> Array:
    """(R, D) -> (R, 1, D).  Mosaic requires a block's last two dims to be
    (8, 128)-aligned or whole; a one-row block of the (1, D) trailing view
    is whole on the sublane axis, so each grid step can DMA a single row
    picked by a data-dependent index map."""
    return x.reshape(x.shape[0], 1, x.shape[-1])


def _valid_median(u: Array, vcol: Array) -> Array:
    """Valid-masked median (1, T) of a resident (K, T) tile: invalid rows
    sort to +inf, the two dynamic middles of the v valid rows are one-hot
    selected, and the degree-0 guard zeroes the empty median (an
    all-invalid row would otherwise pick +inf and 0 * inf would poison
    dotmed with NaNs).  Zero is the safe empty median: every accumulated
    statistic stays finite and the caller's valid mask rejects all slots,
    so the node keeps its local model."""
    rows = sort_rows(jnp.where(vcol, u, jnp.inf))
    v = jnp.sum(vcol.astype(jnp.int32))
    # truncating division: lo is wrong only at v == 0, which the guard zeroes
    lo, hi = jax.lax.div(v - 1, 2), jax.lax.div(v, 2)
    med = 0.5 * (sum(jnp.where(lo == r, row, 0.0) for r, row in enumerate(rows))
                 + sum(jnp.where(hi == r, row, 0.0) for r, row in enumerate(rows)))
    return jnp.where(v > 0, med, jnp.zeros_like(med))


def _row_sums(x: Array) -> Array:
    """Per-candidate sums of a (K, T) tile as a (1, K) row."""
    from repro.core import trust  # deferred: see _wfagg_round_indexed_kernel
    return trust.col_to_row(jnp.sum(x, axis=1, keepdims=True))


def _robust_stats_kernel(*refs, n_trim: int, has_prev: bool,
                         emit_center: bool, d_axis: int):
    """Shared kernel body for the single (d_axis=0) and batched (d_axis=1)
    launches.  Block shapes may carry a leading node axis of size 1; every
    read/write goes through a reshape so one body serves both layouts.
    ``emit_center=False`` drops the streaming (1, D) median/trimmed-mean
    outputs — the WFAgg filter bank only consumes the O(K) accumulators,
    so skipping those writes keeps the fused path at one read + no
    d-sized writes."""
    if has_prev:
        u_ref, prev_ref = refs[0], refs[1]
        outs = refs[2:]
    else:
        u_ref, prev_ref = refs[0], None
        outs = refs[1:]
    if emit_center:
        med_ref, trim_ref = outs[:2]
        acc_refs = outs[2:]
    else:
        med_ref = trim_ref = None
        acc_refs = outs
    dist2_ref, dotmed_ref, norm2_ref, mednorm2_ref = acc_refs[:4]

    u = u_ref[...].astype(jnp.float32)
    u = u.reshape(u.shape[-2], u.shape[-1])            # (K, T)
    K = u.shape[0]

    srt = sort_rows(u)
    if K % 2 == 1:
        med = srt[K // 2]
    else:
        med = 0.5 * (srt[K // 2 - 1] + srt[K // 2])
    if emit_center:
        kept = srt[n_trim:K - n_trim] if n_trim > 0 else srt
        trim = sum(kept) / len(kept)
        med_ref[...] = med.reshape(med_ref.shape).astype(med_ref.dtype)
        trim_ref[...] = trim.reshape(trim_ref.shape).astype(trim_ref.dtype)

    diff = u - med
    p_dist2 = _row_sums(diff * diff)                 # (1, K)
    p_dot = _row_sums(u * med)
    p_norm2 = _row_sums(u * u)
    p_med2 = jnp.sum(med * med, keepdims=True)       # (1, 1)

    @pl.when(pl.program_id(d_axis) == 0)
    def _init():
        for ref in acc_refs:
            ref[...] = jnp.zeros_like(ref)

    dist2_ref[...] += p_dist2.reshape(dist2_ref.shape)
    dotmed_ref[...] += p_dot.reshape(dotmed_ref.shape)
    norm2_ref[...] += p_norm2.reshape(norm2_ref.shape)
    mednorm2_ref[...] += p_med2.reshape(mednorm2_ref.shape)

    if has_prev:
        pdist2_ref, pdot_ref, pnorm2_ref = acc_refs[4:]
        pv = prev_ref[...].astype(jnp.float32)
        pv = pv.reshape(pv.shape[-2], pv.shape[-1])
        dprev = u - pv
        pdist2_ref[...] += _row_sums(dprev * dprev).reshape(pdist2_ref.shape)
        pdot_ref[...] += _row_sums(u * pv).reshape(pdot_ref.shape)
        pnorm2_ref[...] += _row_sums(pv * pv).reshape(pnorm2_ref.shape)


def robust_stats_pallas(
    updates: Array,
    prev: Array | None = None,
    *,
    n_trim: int,
    block_d: int = 1024,
    interpret: bool | None = None,
    emit_center: bool = True,
):
    """Launch the fused robust-stats kernel.  D must be a multiple of block_d.

    Returns ([med, trim,] dist2, dotmed, norm2, mednorm2[, prev_dist2,
    prev_dot, prev_norm2]) — med/trim only with ``emit_center``, the
    temporal tail only when ``prev`` is given.
    """
    K, D = updates.shape
    assert D % block_d == 0, (D, block_d)
    has_prev = prev is not None
    grid = (D // block_d,)
    kernel = functools.partial(
        _robust_stats_kernel, n_trim=n_trim, has_prev=has_prev,
        emit_center=emit_center, d_axis=0
    )
    d_spec = pl.BlockSpec((1, block_d), lambda i: (0, i))
    k_spec = pl.BlockSpec((1, K), lambda i: (0, 0))
    out_shapes, out_specs = [], []
    if emit_center:
        out_shapes += [jax.ShapeDtypeStruct((1, D), jnp.float32)] * 2  # med, trim
        out_specs += [d_spec, d_spec]
    out_shapes += [
        jax.ShapeDtypeStruct((1, K), jnp.float32),   # dist2
        jax.ShapeDtypeStruct((1, K), jnp.float32),   # dotmed
        jax.ShapeDtypeStruct((1, K), jnp.float32),   # norm2
        jax.ShapeDtypeStruct((1, 1), jnp.float32),   # mednorm2
    ]
    out_specs += [k_spec, k_spec, k_spec,
                  pl.BlockSpec((1, 1), lambda i: (0, 0))]
    in_specs = [pl.BlockSpec((K, block_d), lambda i: (0, i))]
    args = [updates]
    if has_prev:
        assert prev.shape == updates.shape, (prev.shape, updates.shape)
        in_specs.append(pl.BlockSpec((K, block_d), lambda i: (0, i)))
        args.append(prev)
        out_shapes += [jax.ShapeDtypeStruct((1, K), jnp.float32)] * 3
        out_specs += [k_spec] * 3
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        interpret=resolve_interpret(interpret),
    )(*args)


def _flush_indexed_stats(u: Array, valid_row: Array, acc_refs,
                         is_first_d, need_gram: bool, pv: Array | None):
    """Accumulate one D block of the indexed statistics off the resident
    (K, T) candidate tile ``u`` (and ``pv``, the matching previous-round
    tile): the shared phase-0 flush of the indexed stats kernel and the
    round kernel.  The median honors the node's valid row: invalid
    (padded) rows sort to +inf and the median picks the dynamic middle of
    the v valid rows.  Per-candidate statistics are computed on the RAW
    rows (padded slots hold the node's own finite model), so they stay
    finite and the caller's mask logic drops them by ``valid``.
    ``acc_refs`` are (dist2, dotmed, norm2, mednorm2[, gram][, prev_dist2,
    prev_dot, prev_norm2]) — (1, K) rows, a (1, 1) and a (K, K)."""
    from repro.core import trust  # deferred: see _wfagg_round_indexed_kernel
    dist2_ref, dotmed_ref, norm2_ref, mednorm2_ref = acc_refs[:4]
    vcol = trust.row_to_col(valid_row) > 0.0             # (K, 1)
    med = _valid_median(u, vcol)        # degree-0 guard: empty median = 0

    diff = u - med
    p_dist2 = _row_sums(diff * diff)
    p_dot = _row_sums(u * med)
    p_norm2 = _row_sums(u * u)
    p_med2 = jnp.sum(med * med, keepdims=True)

    @pl.when(is_first_d)
    def _init():
        for ref in acc_refs:
            ref[...] = jnp.zeros_like(ref)

    dist2_ref[...] += p_dist2
    dotmed_ref[...] += p_dot
    norm2_ref[...] += p_norm2
    mednorm2_ref[...] += p_med2

    if need_gram:
        # the (K, K) candidate Gram comes free off the resident tile
        # (MXU matmul) — no extra pass for the Alt-WFAgg filters, and
        # nothing quadratic in the TOTAL node count M
        acc_refs[4][...] += jax.lax.dot_general(
            u, u, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    if pv is not None:
        pdist2_ref, pdot_ref, pnorm2_ref = acc_refs[5 if need_gram else 4:]
        dprev = u - pv
        pdist2_ref[...] += _row_sums(dprev * dprev)
        pdot_ref[...] += _row_sums(u * pv)
        pnorm2_ref[...] += _row_sums(pv * pv)


def _robust_stats_indexed_kernel(*refs, K: int, has_prev: bool,
                                 need_gram: bool):
    """Gather-free body: grid (node, D block, neighbor slot).  Each step
    DMAs ONE neighbor row block — models[neighbor_idx[n, k], d-block],
    resolved by the scalar-prefetch index map — into a VMEM scratch row;
    at the last slot the full (K, T) candidate tile is resident and the
    stats flush (``_flush_indexed_stats``), so the (N, K, d) gossip
    tensor never exists in HBM."""
    refs = list(refs[1:])  # refs[0]: the prefetched table, read by index maps
    valid_ref = refs.pop(0)
    u_ref = refs.pop(0)
    prev_ref = refs.pop(0) if has_prev else None
    scratch_p = refs.pop() if has_prev else None
    scratch_u = refs.pop()
    acc_refs = refs

    # program ids are read outside the pl.when bodies
    k = pl.program_id(2)
    is_last = k == K - 1
    is_first_d = pl.program_id(1) == 0

    scratch_u[pl.ds(k, 1), :] = u_ref[...].astype(jnp.float32)
    if has_prev:
        scratch_p[pl.ds(k, 1), :] = prev_ref[...].astype(jnp.float32)

    @pl.when(is_last)
    def _flush():
        _flush_indexed_stats(
            scratch_u[...], valid_ref[...], acc_refs, is_first_d, need_gram,
            scratch_p[...] if has_prev else None)


def _prev_spec(prev: Array, models: Array, neighbor_idx: Array,
               prev_idx: Array | None, block_d: int, phase_axis: bool):
    """(BlockSpec, row-view array, index table) for the ``prev`` input of
    the indexed launches.  ``prev`` is per-edge (N, K, D) — viewed as
    (N*K, 1, D) rows — or a previous-round matrix (M', D) read through
    ``neighbor_idx`` or, with ``prev_idx``, through its own (N, K) table
    (the two tables then ride the scalar prefetch as one (N, 2K) block).
    With ``phase_axis`` (the round kernel's (node, phase, D block, slot)
    grid) the map is pinned to one block in phase 1, which reads no prev,
    so the combine re-walk fetches nothing new."""
    N, K = neighbor_idx.shape
    D = models.shape[-1]
    table = neighbor_idx
    if prev.ndim == 2:
        assert prev.shape[-1] == D, (prev.shape, models.shape)
        if prev_idx is not None:
            assert prev_idx.shape == (N, K), (prev_idx.shape, (N, K))
            table = jnp.concatenate([neighbor_idx, prev_idx], axis=1)
            off = K
        else:
            assert prev.shape == models.shape, (prev.shape, models.shape)
            off = 0
        view = _row_view(prev)
        if phase_axis:
            imap = lambda n, p, i, k, ir: (ir[n, off + k * (1 - p)], 0,
                                           i * (1 - p))
        else:
            imap = lambda n, i, k, ir: (ir[n, off + k], 0, i)
    else:
        if prev_idx is not None:
            raise ValueError("prev_idx requires a matrix-form prev")
        assert prev.shape == (N, K, D), (prev.shape, (N, K, D))
        view = prev.reshape(N * K, 1, D)
        if phase_axis:
            imap = lambda n, p, i, k, ir: (n * K + k * (1 - p), 0,
                                           i * (1 - p))
        else:
            imap = lambda n, i, k, ir: (n * K + k, 0, i)
    return pl.BlockSpec((None, 1, block_d), imap), view, table


def robust_stats_indexed_pallas(
    models: Array,        # (M, D) model matrix (row per node)
    neighbor_idx: Array,  # (N, K) int32 rows into ``models``
    valid: Array,         # (N, K) float32, 1.0 on real edges
    prev: Array | None = None,   # (N, K, D) per-edge, or (M, D) matrix
    prev_idx: Array | None = None,  # (N, K) rows into matrix ``prev``
    *,
    block_d: int = 1024,
    interpret: bool | None = None,
    need_gram: bool = False,
):
    """Gather-free robust-stats launch over a 3-D (node, D block, slot)
    grid via ``PrefetchScalarGridSpec``: the neighbor table rides in SMEM
    and the models input's index map reads it, so each grid step streams
    one neighbor row block straight from the (M, D) matrix.  ``prev`` may
    be per-edge (N, K, D) or a previous-round model matrix (M, D) read
    through the same index table — or, with ``prev_idx``, through its OWN
    (N, K) table (fault-injected transport: the payload an edge served
    last round need not be the row it reads this round).
    ``need_gram`` also accumulates each node's (K, K) candidate Gram off
    the same resident tile (Alt-WFAgg).
    Returns (dist2, dotmed, norm2, mednorm2[, gram][, prev_dist2,
    prev_dot, prev_norm2]) shaped like the batched launch ((N, 1, K) /
    (N, 1, 1) / (N, K, K)).
    """
    M, D = models.shape
    N, K = neighbor_idx.shape
    assert D % block_d == 0, (D, block_d)
    has_prev = prev is not None
    if prev_idx is not None and not (has_prev and prev.ndim == 2):
        raise ValueError("prev_idx requires a matrix-form prev")
    kernel = functools.partial(
        _robust_stats_indexed_kernel, K=K, has_prev=has_prev,
        need_gram=need_gram,
    )
    k_spec = pl.BlockSpec((None, 1, K), lambda n, i, k, ir: (n, 0, 0))
    in_specs = [
        k_spec,                                                        # valid
        pl.BlockSpec((None, 1, block_d),
                     lambda n, i, k, ir: (ir[n, k], 0, i)),            # models
    ]
    args = [valid.astype(jnp.float32).reshape(N, 1, K), _row_view(models)]
    table = neighbor_idx
    if has_prev:
        spec, view, table = _prev_spec(prev, models, neighbor_idx, prev_idx,
                                       block_d, phase_axis=False)
        in_specs.append(spec)
        args.append(view)
    out_shapes = [
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # dist2
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # dotmed
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # norm2
        jax.ShapeDtypeStruct((N, 1, 1), jnp.float32),   # mednorm2
    ]
    out_specs = [k_spec, k_spec, k_spec,
                 pl.BlockSpec((None, 1, 1), lambda n, i, k, ir: (n, 0, 0))]
    if need_gram:
        out_shapes.append(jax.ShapeDtypeStruct((N, K, K), jnp.float32))
        out_specs.append(
            pl.BlockSpec((None, K, K), lambda n, i, k, ir: (n, 0, 0)))
    if has_prev:
        out_shapes += [jax.ShapeDtypeStruct((N, 1, K), jnp.float32)] * 3
        out_specs += [k_spec] * 3
    scratch_shapes = [pltpu.VMEM((K, block_d), jnp.float32)]
    if has_prev:
        scratch_shapes.append(pltpu.VMEM((K, block_d), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, D // block_d, K),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(out_shapes),
        interpret=resolve_interpret(interpret),
    )(table.astype(jnp.int32), *args)


def _wfagg_round_indexed_kernel(*refs, K: int, n_d: int, has_prev: bool,
                                has_tbands: bool, need_gram: bool,
                                cfg, alpha: float, mean_fallback: bool):
    """Single-launch WFAgg round body: grid (node, PHASE, D block, slot).

    Phase 0 is the indexed stats pass — each step DMAs one neighbor row
    block via the scalar-prefetch index map into the (K, T) VMEM scratch
    and flushes the D/C/T accumulators (and the Alt-WFAgg Gram) at the
    last slot, exactly like ``_robust_stats_indexed_kernel``.  At the
    phase boundary (last D block, last slot of phase 0) the WFAgg scoring
    stage runs IN-KERNEL on the VMEM-resident (1, K) accumulators
    (``core.trust.derive_trust_weights``), the masks/weights are written
    to their O(K) outputs, and the normalized combine coefficients land
    in a VMEM scratch.  Phase 1 re-DMAs the neighbor blocks through the
    same index map and accumulates the trust-weighted WFAgg-E combine
    into the (1, T) output block — no host round-trip, no second kernel
    launch, and the candidate re-read hits tiles that are still resident
    whenever a node's (K, D) slab fits VMEM.

    The WFAgg-T decision is four compares against the precomputed flat
    (1, 4K) EWMA band input (``core.trust.temporal_bands`` — the history
    lives outside the kernel); the ring-buffer push happens on the host
    off the emitted temporal statistics.
    """
    # deferred import: core.wfagg -> robust_stats.ops -> this module at
    # package-init time; by kernel-trace time repro.core is fully loaded
    from repro.core import trust

    refs = list(refs[1:])  # refs[0]: the prefetched table, read by index maps
    valid_ref = refs.pop(0)
    tbands_ref = refs.pop(0) if has_tbands else None
    local_ref = refs.pop(0)
    u_ref = refs.pop(0)
    prev_ref = refs.pop(0) if has_prev else None
    out_ref, w_ref, md_ref, mc_ref, mt_ref = refs[:5]
    n_acc = 4 + (1 if need_gram else 0) + (3 if has_prev else 0)
    acc_refs = refs[5:5 + n_acc]
    scratch = refs[5 + n_acc:]
    dist2_ref, dotmed_ref, norm2_ref, mednorm2_ref = acc_refs[:4]
    gram_ref = acc_refs[4] if need_gram else None
    prev_acc = acc_refs[5 if need_gram else 4:] if has_prev else ()
    scratch_u = scratch[0]
    scratch_p = scratch[1] if has_prev else None
    wcomb_ref, lcoef_ref = scratch[-2], scratch[-1]

    # program ids are read outside the pl.when bodies
    p = pl.program_id(1)
    i = pl.program_id(2)
    k = pl.program_id(3)
    is_phase0 = p == 0
    is_last_slot = k == K - 1
    is_first_d = i == 0
    is_boundary = is_phase0 & is_last_slot & (i == n_d - 1)

    u_now = u_ref[...].astype(jnp.float32)                  # (1, T)

    @pl.when(is_phase0)
    def _stage():
        scratch_u[pl.ds(k, 1), :] = u_now
        if has_prev:
            scratch_p[pl.ds(k, 1), :] = prev_ref[...].astype(jnp.float32)

    @pl.when(is_phase0 & is_last_slot)
    def _flush():
        _flush_indexed_stats(
            scratch_u[...], valid_ref[...], acc_refs, is_first_d, need_gram,
            scratch_p[...] if has_prev else None)

    @pl.when(is_boundary)
    def _derive():
        valid_f = valid_ref[...]                              # (1, K)
        tail = [r[...] for r in prev_acc] if has_prev else [None] * 3
        stats = RobustStats(
            med=None, trim=None,
            dist2=dist2_ref[...], dotmed=dotmed_ref[...],
            norm2=norm2_ref[...], mednorm2=mednorm2_ref[...],
            prev_dist2=tail[0], prev_dot=tail[1], prev_norm2=tail[2],
        )
        gram = gram_ref[...] if need_gram else None
        tb = tbands_ref[...] if has_tbands else None
        mask_d, mask_c, mask_t, w = trust.derive_trust_weights(
            stats, gram, valid_f, tb, cfg)
        md_ref[...] = mask_d.astype(jnp.float32)
        mc_ref[...] = mask_c.astype(jnp.float32)
        mt_ref[...] = mask_t.astype(jnp.float32)
        w_ref[...] = w
        wcomb, lcoef = trust.combine_coefficients(w, alpha, valid_f,
                                                  mean_fallback)
        wcomb_ref[...] = wcomb
        lcoef_ref[...] = jnp.broadcast_to(lcoef, (1, 1))

    # ---- phase 1: trust-weighted combine (same DMA pattern, weights in
    # VMEM from the boundary step; matches _weighted_agg_indexed_kernel) --
    is_phase1 = jnp.logical_not(is_phase0)
    kio = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    @pl.when(is_phase1 & (k == 0))
    def _seed():
        wk = jnp.sum(jnp.where(kio == k, wcomb_ref[...], 0.0))
        out_ref[...] = (lcoef_ref[...] * local_ref[...].astype(jnp.float32)
                        + wk * u_now)

    @pl.when(is_phase1 & (k != 0))
    def _accum():
        wk = jnp.sum(jnp.where(kio == k, wcomb_ref[...], 0.0))
        out_ref[...] += wk * u_now


def wfagg_round_indexed_pallas(
    local: Array,         # (N, D) local models (combine anchors)
    models: Array,        # (M, D) model matrix (row per node)
    neighbor_idx: Array,  # (N, K) int32 rows into ``models``
    valid: Array,         # (N, K) float32, 1.0 on real edges
    cfg,                  # duck-typed WFAggConfig (static)
    prev: Array | None = None,    # (N, K, D) per-edge, or (M, D) matrix
    tbands: Array | None = None,  # (N, 4K) flat WFAgg-T EWMA bands
    prev_idx: Array | None = None,  # (N, K) rows into matrix ``prev``
    *,
    alpha: float,
    mean_fallback: bool = False,
    need_gram: bool = False,
    block_d: int = 1024,
    interpret: bool | None = None,
):
    """Launch the single-launch WFAgg round kernel over a 4-D
    (node, phase, D block, slot) grid.  Phase 0 accumulates the indexed
    robust statistics, the phase boundary derives the trust weights
    in-kernel, and phase 1 writes the WFAgg-E combine — one launch for
    the entire gossip round.

    With ``prev_idx`` the matrix-form ``prev`` reads through its own
    (N, K) table (concatenated after ``neighbor_idx`` into one (N, 2K)
    SMEM prefetch block) instead of re-using the models table — the
    fault-injected transport's staleness pricing, still one launch.

    Returns (out (N, 1, D), weights, mask_d, mask_c, mask_t (each
    (N, 1, K)), dist2, dotmed, norm2 ((N, 1, K)), mednorm2 ((N, 1, 1))
    [, gram (N, K, K)][, prev_dist2, prev_dot, prev_norm2 ((N, 1, K))]).
    """
    M, D = models.shape
    N, K = neighbor_idx.shape
    assert D % block_d == 0, (D, block_d)
    assert local.shape == (N, D), (local.shape, (N, D))
    n_d = D // block_d
    has_prev = prev is not None
    has_tbands = tbands is not None
    if prev_idx is not None and not (has_prev and prev.ndim == 2):
        raise ValueError("prev_idx requires a matrix-form prev")
    kernel = functools.partial(
        _wfagg_round_indexed_kernel, K=K, n_d=n_d, has_prev=has_prev,
        has_tbands=has_tbands, need_gram=need_gram, cfg=cfg, alpha=alpha,
        mean_fallback=mean_fallback,
    )
    k_spec = pl.BlockSpec((None, 1, K), lambda n, p, i, k, ir: (n, 0, 0))
    in_specs = [k_spec]                                            # valid
    args = [valid.astype(jnp.float32).reshape(N, 1, K)]
    if has_tbands:
        # bands ride flat, (N, 1, 4K): a (N, 4, K) buffer would read as a
        # gossip tensor to the rank-based (N, K, d)-free scan when K == 4
        assert tbands.shape == (N, 4 * K), (tbands.shape, (N, 4 * K))
        in_specs.append(
            pl.BlockSpec((None, 1, 4 * K), lambda n, p, i, k, ir: (n, 0, 0)))
        args.append(tbands.astype(jnp.float32).reshape(N, 1, 4 * K))
    # local and the combine output: pinned to block 0 during phase 0
    # (only phase 1 touches them) — `i * p` keeps the block constant
    # until the combine phase
    row_spec = pl.BlockSpec((None, 1, block_d),
                            lambda n, p, i, k, ir: (n, 0, i * p))
    in_specs.append(row_spec)
    args.append(_row_view(local))
    in_specs.append(pl.BlockSpec(
        (None, 1, block_d), lambda n, p, i, k, ir: (ir[n, k], 0, i)))
    args.append(_row_view(models))
    table = neighbor_idx
    if has_prev:
        spec, view, table = _prev_spec(prev, models, neighbor_idx, prev_idx,
                                       block_d, phase_axis=True)
        in_specs.append(spec)
        args.append(view)

    out_shapes = [
        jax.ShapeDtypeStruct((N, 1, D), jnp.float32),   # combined models
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # trust weights
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # mask_d
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # mask_c
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # mask_t
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # dist2
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # dotmed
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # norm2
        jax.ShapeDtypeStruct((N, 1, 1), jnp.float32),   # mednorm2
    ]
    out_specs = [
        row_spec,
        k_spec, k_spec, k_spec, k_spec,                  # weights + masks
        k_spec, k_spec, k_spec,
        pl.BlockSpec((None, 1, 1), lambda n, p, i, k, ir: (n, 0, 0)),
    ]
    if need_gram:
        out_shapes.append(jax.ShapeDtypeStruct((N, K, K), jnp.float32))
        out_specs.append(
            pl.BlockSpec((None, K, K), lambda n, p, i, k, ir: (n, 0, 0)))
    if has_prev:
        out_shapes += [jax.ShapeDtypeStruct((N, 1, K), jnp.float32)] * 3
        out_specs += [k_spec] * 3
    scratch_shapes = [pltpu.VMEM((K, block_d), jnp.float32)]
    if has_prev:
        scratch_shapes.append(pltpu.VMEM((K, block_d), jnp.float32))
    scratch_shapes += [pltpu.VMEM((1, K), jnp.float32),   # combine weights
                       pltpu.VMEM((1, 1), jnp.float32)]   # local coefficient
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, 2, n_d, K),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(out_shapes),
        interpret=resolve_interpret(interpret),
    )(table.astype(jnp.int32), *args)


def robust_stats_batch_pallas(
    updates: Array,
    prev: Array | None = None,
    *,
    n_trim: int,
    block_d: int = 1024,
    interpret: bool | None = None,
    emit_center: bool = True,
):
    """Batched launch: one kernel over (N, K, D) computes every node's
    statistics.  2-D grid (node, D block); same outputs as the single
    launch with a leading N axis."""
    N, K, D = updates.shape
    assert D % block_d == 0, (D, block_d)
    has_prev = prev is not None
    grid = (N, D // block_d)
    kernel = functools.partial(
        _robust_stats_kernel, n_trim=n_trim, has_prev=has_prev,
        emit_center=emit_center, d_axis=1
    )
    d_spec = pl.BlockSpec((1, 1, block_d), lambda n, i: (n, 0, i))
    k_spec = pl.BlockSpec((1, 1, K), lambda n, i: (n, 0, 0))
    out_shapes, out_specs = [], []
    if emit_center:
        out_shapes += [jax.ShapeDtypeStruct((N, 1, D), jnp.float32)] * 2
        out_specs += [d_spec, d_spec]
    out_shapes += [
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # dist2
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # dotmed
        jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # norm2
        jax.ShapeDtypeStruct((N, 1, 1), jnp.float32),   # mednorm2
    ]
    out_specs += [k_spec, k_spec, k_spec,
                  pl.BlockSpec((1, 1, 1), lambda n, i: (n, 0, 0))]
    in_specs = [pl.BlockSpec((1, K, block_d), lambda n, i: (n, 0, i))]
    args = [updates]
    if has_prev:
        assert prev.shape == updates.shape, (prev.shape, updates.shape)
        in_specs.append(pl.BlockSpec((1, K, block_d), lambda n, i: (n, 0, i)))
        args.append(prev)
        out_shapes += [jax.ShapeDtypeStruct((N, 1, K), jnp.float32)] * 3
        out_specs += [k_spec] * 3
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        interpret=resolve_interpret(interpret),
    )(*args)
