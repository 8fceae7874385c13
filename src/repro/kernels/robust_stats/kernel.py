"""Pallas TPU kernel: the WFAgg round over K neighbor candidates.

One kernel, ``wfagg_round_indexed_pallas``, over a 3-D (node, phase,
D tile) grid.  Each grid step gathers one node's K neighbor rows of a
(K, T) tile straight from the (M, D) model matrix with row DMAs, so the
(N, K, D) gossip tensor never exists in HBM.

  phase 0  the statistics: an odd-even-transposition sorting network
           over the K rows (K is static and small, so it unrolls into
           ~K^2/2 min/max pairs on lane-wide rows) gives the valid-masked
           coordinate-wise median, and the same resident tile yields
           every per-candidate accumulator WFAgg-D/C/T read —
           distance-to-median, dot-with-median, norms, the round-over-
           round metrics against ``prev`` and, for Alt-WFAgg, the (K, K)
           Gram.  One read of the candidates (and one of ``prev``).
  phase 1  the WFAgg-E combine ``lcoef * local + sum_k w_k u_k``, which
           gathers the tiles again.

``phases`` picks what one launch runs: both (``ROUND``: the trust
weights are derived in-kernel at the phase boundary — the whole gossip
round in one launch), phase 0 alone (``STATS``: for a caller that sums
the accumulators over slices of D before it scores them, or that scores
on the host) or phase 1 alone (``COMBINE``: the combine coefficients
come in as an input).
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


def sort_rows(rows: list) -> list:
    """Odd-even transposition sort of K lane-wide rows (static K, fully
    unrolled): ~K^2/2 elementwise min/max pairs; returns the K sorted
    rows."""
    rows = list(rows)
    K = len(rows)
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


def _valid_median(rows: list, ok: list, v) -> Array:
    """Valid-masked median of K lane-wide candidate rows: invalid rows
    (``ok[k]`` false) sort to +inf, the two dynamic middles of the ``v``
    valid rows are selected, and the degree-0 guard zeroes the empty
    median (an all-invalid slate would otherwise pick +inf and 0 * inf
    would poison dotmed with NaNs).  Zero is the safe empty median: every
    accumulated statistic stays finite and the caller's valid mask
    rejects all slots, so the node keeps its local model.  ``ok`` and
    ``v`` are scalars."""
    srt = sort_rows([jnp.where(o, r, jnp.inf) for o, r in zip(ok, rows)])
    # truncating division: lo is wrong only at v == 0, which the guard zeroes
    lo, hi = jax.lax.div(v - 1, 2), jax.lax.div(v, 2)
    pick_lo = pick_hi = srt[0]
    for r, row in enumerate(srt[1:], 1):
        pick_lo = jnp.where(lo == r, row, pick_lo)
        pick_hi = jnp.where(hi == r, row, pick_hi)
    med = 0.5 * (pick_lo + pick_hi)
    return jnp.where(v > 0, med, jnp.zeros_like(med))


def _row_sums(x: Array) -> Array:
    """Per-candidate sums of a (K, T) tile as a (1, K) row."""
    from repro.core import trust  # deferred: see _wfagg_round_indexed_kernel
    return trust.col_to_row(jnp.sum(x, axis=1, keepdims=True))


def _n_partials(K: int, has_prev: bool) -> int:
    """Rows of the flush's partial-sum scratch: ``mednorm2``, then K
    each of dist2, dotmed, norm2 and, with ``prev``, prev_dist2,
    prev_dot, prev_norm2."""
    return 1 + K * (6 if has_prev else 3)


def _flush_indexed_stats(land_u, land_p, slot, cols, ok: list, v,
                         part_ref, gram_ref):
    """Accumulate one chunk of the indexed statistics: phase 0 of the
    round kernel.  The chunk is read as (K, 1, chunk) off the resident
    landing tile, so candidate k is the lane-wide row ``u[k]`` — whole
    vregs — and the sort, the median pick and the products are dense
    elementwise work.  The median honors the node's valid slots: invalid
    (padded) rows sort to +inf and the median picks the dynamic middle
    of the v valid rows.  Per-candidate statistics are computed on the
    RAW rows (padded slots hold the node's own finite model), so they
    stay finite and the caller's mask logic drops them by ``valid``.
    The products are added lane by lane into ``part_ref``
    (``_n_partials`` rows of the chunk's width); ``_reduce_partials``
    sums them across lanes once a node.  ``gram_ref``, when given, takes
    the (K, K) candidate Gram off the same chunk."""
    K = land_u.shape[1]
    u = land_u[slot, :, :, cols]                          # (K, 1, chunk)
    med = _valid_median([u[k] for k in range(K)], ok, v)  # (1, chunk)
    rows = lambda j: pl.ds(1 + j * K, K)                  # noqa: E731
    diff = u - med
    part_ref[0] += med * med
    part_ref[rows(0)] += diff * diff
    part_ref[rows(1)] += u * med
    part_ref[rows(2)] += u * u
    if land_p is not None:
        pv = land_p[slot, :, :, cols]
        dprev = u - pv
        part_ref[rows(3)] += dprev * dprev
        part_ref[rows(4)] += u * pv
        part_ref[rows(5)] += pv * pv
    if gram_ref is not None:
        # the (K, K) candidate Gram comes free off the resident tile
        # (MXU matmul) — no extra pass for the Alt-WFAgg filters, and
        # nothing quadratic in the TOTAL node count M
        u2 = u.reshape(K, u.shape[-1])
        gram_ref[...] += jax.lax.dot_general(
            u2, u2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def _reduce_partials(part_ref, acc_refs, K: int, need_gram: bool):
    """Sum the flush's lane-wide partials across lanes into the (1, K)
    accumulator rows (and the (1, 1) ``mednorm2``) — once a node, at its
    last tile."""
    mednorm2_ref = acc_refs[3]
    rows = list(acc_refs[:3]) + list(acc_refs[5 if need_gram else 4:])
    mednorm2_ref[...] = jnp.sum(part_ref[0], keepdims=True)
    for j, ref in enumerate(rows):
        block = part_ref[pl.ds(1 + j * K, K)]
        ref[...] = _row_sums(block.reshape(K, block.shape[-1]))


def _prev_rows(prev: Array, models: Array, neighbor_idx: Array,
               prev_idx: Array | None):
    """(row-view array, index table, row map) for the ``prev`` input of
    the indexed launches.  ``prev`` is per-edge (N, K, D) — viewed as
    (N*K, 1, D) rows — or a previous-round matrix (M', D) read through
    ``neighbor_idx`` or, with ``prev_idx``, through its own (N, K) table
    (the two tables then ride the scalar prefetch as one (N, 2K) block).
    ``row(table, n, k)`` is the row of the view that slot k of node n
    compares against."""
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
        return _row_view(prev), table, lambda ir, n, k: ir[n, off + k]
    if prev_idx is not None:
        raise ValueError("prev_idx requires a matrix-form prev")
    assert prev.shape == (N, K, D), (prev.shape, (N, K, D))
    return prev.reshape(N * K, 1, D), table, lambda ir, n, k: n * K + k


# VMEM the round kernel's tile-sized buffers and the flush's partial sums
# may take: the double-buffered (K, T) landing tiles of the candidates
# (and of ``prev``) plus the pipelined (1, T) ``local`` and output
# blocks, 4 MiB; and the (_n_partials, 1, ROUND_CHUNK) partial sums,
# 128 KiB, which holds K = 4's with ``prev`` (100 KiB) — a wider slate
# gives up tile width for the rest of its own.  Well under the 16 MiB of
# scoped VMEM, which also holds the flush's live rows
ROUND_TILE_BUDGET = 4 * 1024 * 1024 + 128 * 1024
# lanes one flush of the statistics works on: one vreg (8 x 128 floats)
# of each candidate row, so the sort network's code and live rows do not
# grow with the tile width
ROUND_CHUNK = 1024


def round_tile_width(K: int, d: int, has_prev: bool) -> int:
    """Tile width T of the round kernel: the widest multiple of 1024
    lanes whose tile-sized buffers fit ``ROUND_TILE_BUDGET`` and that
    divides d rounded up to 1024 lanes.  So the operands are padded
    exactly as far as 1024-lane tiles pad them: a d that is a multiple
    of 1024 (the trainer's flat gradients can be) needs no padded copy
    of the (K, d) matrices in HBM.  VMEM depends on (K, T) only, never
    on d (LeNet's d = 44,426: T = 4,096 at K = 30, 11 tiles; 22,528 at
    K = 8, 2 tiles; 45,056 lanes either way)."""
    m_cap = _round_tile_cap(K, has_prev)
    nb = -(-d // 1024)
    return 1024 * max(m for m in range(1, min(m_cap, nb) + 1) if nb % m == 0)


def _round_tile_cap(K: int, has_prev: bool) -> int:
    """The widest tile ``ROUND_TILE_BUDGET`` allows, in 1024-lane blocks,
    once the flush's partial sums are counted."""
    lane_bytes = 4 * (2 * K * (2 if has_prev else 1) + 4)
    partial_bytes = 4 * _n_partials(K, has_prev) * ROUND_CHUNK
    return max(1, (ROUND_TILE_BUDGET - partial_bytes) // (1024 * lane_bytes))


def round_padded_width(K: int, d: int, has_prev: bool) -> int:
    """d rounded up to the fewest tiles of at most the budget's width,
    each as narrow as that count allows: a caller that builds its (K, d)
    matrix anyway pads it this far, so ``round_tile_width`` finds a wide
    tile whatever d's factors (the robust-DP trainer's per-chip slice,
    P / 4 = 63,083 blocks of 1024 lanes = 199 x 317, would otherwise get
    T = 1,024).  The pad is under one 1024-lane block a tile."""
    nb = -(-d // 1024)
    n_t = -(-nb // _round_tile_cap(K, has_prev))
    return 1024 * n_t * -(-nb // n_t)


# what one launch of the round kernel runs: the grid's phase axis walks
# these phases in order (0 = statistics, 1 = combine)
ROUND, STATS, COMBINE = (0, 1), (0,), (1,)


def _wfagg_round_indexed_kernel(*refs, K: int, n_t: int, T: int, chunk: int,
                                phases: tuple, has_prev: bool, prev_row,
                                has_tbands: bool, need_gram: bool, cfg,
                                alpha: float, mean_fallback: bool):
    """WFAgg round body: grid (node, PHASE, D tile), the phase axis
    walking ``phases``.

    Each step gathers the node's K neighbor rows of one (K, T) tile with
    K row DMAs, ``models[table[n, k], tile] -> land_u[slot, k]`` (in
    phase 0 also the K ``prev`` rows), and starts the NEXT step's
    gathers into the other slot before it computes — across phase and
    node boundaries too, so only the launch's first tile is exposed.
    Phase 0 flushes the D/C/T statistics (and the Alt-WFAgg Gram) off
    the resident tile, ``chunk`` lanes at a time
    (``_flush_indexed_stats``), into lane-wide partial sums that the
    node's last tile sums across lanes into the (1, K) accumulators
    (``_reduce_partials``).  Under ``ROUND``, at the phase boundary
    (phase 0, last tile) the WFAgg scoring stage runs IN-KERNEL on the
    VMEM-resident (1, K) accumulators
    (``core.trust.derive_trust_weights``), the masks/weights are written
    to their O(K) outputs, and the normalized combine coefficients land
    in a VMEM scratch.  Phase 1 gathers the tiles again and writes
    ``lcoef * local + sum_k w_k u_k`` to the (1, T) output block — no
    host round-trip and no second kernel launch.  ``STATS`` stops after
    phase 0's accumulators; ``COMBINE`` reads the coefficients from its
    (1, K) and (1, 1) inputs instead of the scratch.

    The WFAgg-T decision is four compares against the precomputed flat
    (1, 4K) EWMA band input (``core.trust.temporal_bands`` — the history
    lives outside the kernel); the ring-buffer push happens on the host
    off the emitted temporal statistics.
    """
    # deferred import: core.wfagg -> robust_stats.ops -> this module at
    # package-init time; by kernel-trace time repro.core is fully loaded
    from repro.core import trust

    run_stats, run_combine = 0 in phases, 1 in phases
    table = refs[0]
    refs = list(refs[1:])
    valid_ref = refs.pop(0) if run_stats else None
    tbands_ref = refs.pop(0) if has_tbands else None
    coef_in = (refs.pop(0), refs.pop(0)) if not run_stats else None
    local_ref = refs.pop(0) if run_combine else None
    models_hbm = refs.pop(0)
    prev_hbm = refs.pop(0) if has_prev else None
    n_out = 5 if phases == ROUND else int(run_combine)
    out_ref, w_ref, md_ref, mc_ref, mt_ref = (refs[:n_out] + [None] * 5)[:5]
    n_acc = (4 + (1 if need_gram else 0) + (3 if has_prev else 0)
             if run_stats else 0)
    acc_refs = refs[n_out:n_out + n_acc]
    scratch = refs[n_out + n_acc:]
    if run_stats:
        dist2_ref, dotmed_ref, norm2_ref, mednorm2_ref = acc_refs[:4]
        gram_ref = acc_refs[4] if need_gram else None
        prev_acc = acc_refs[5 if need_gram else 4:] if has_prev else ()
    land_u, sem_u = scratch.pop(0), scratch.pop(0)
    land_p, sem_p = ((scratch.pop(0), scratch.pop(0)) if has_prev
                     else (None, None))
    part_ref = scratch.pop(0) if run_stats else None
    wcomb_ref, lcoef_ref = coef_in or (
        scratch if run_combine else (None, None))

    n_phase = len(phases)
    n, p, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    step = (n_phase * n + p) * n_t + i
    slot = jax.lax.rem(step, 2)

    def gather(nn, pp, ii, s, op):
        """Start (``op="start"``) or wait for (``op="wait"``) the row DMAs
        of step (nn, pp, ii) into landing slot s: K model rows, and in
        phase 0 the K prev rows."""
        cols = pl.ds(ii * T, T)
        for k in range(K):
            getattr(pltpu.make_async_copy(
                models_hbm.at[table[nn, k], :, cols], land_u.at[s, k],
                sem_u.at[s]), op)()
        if has_prev:
            @pl.when(pp == 0)
            def _prev():
                for k in range(K):
                    getattr(pltpu.make_async_copy(
                        prev_hbm.at[prev_row(table, nn, k), :, cols],
                        land_p.at[s, k], sem_p.at[s]), op)()

    @pl.when(step == 0)
    def _first():
        gather(n, p, i, slot, "start")

    last_t = i == n_t - 1
    nxt_n = jnp.where(last_t & (p == n_phase - 1), n + 1, n)
    nxt_p = jnp.where(last_t, 1 - p, p) if n_phase == 2 else p
    nxt_i = jnp.where(last_t, 0, i + 1)

    @pl.when(nxt_n < pl.num_programs(0))
    def _prefetch():
        gather(nxt_n, nxt_p, nxt_i, 1 - slot, "start")

    gather(n, p, i, slot, "wait")

    if run_stats:
        @pl.when(p == 0)
        def _stats():
            # the node's valid slots as scalars (``valid`` rides in SMEM)
            ok = [valid_ref[0, k] > 0.0 for k in range(K)]
            v = sum(o.astype(jnp.int32) for o in ok)

            @pl.when(i == 0)
            def _init():
                part_ref[...] = jnp.zeros_like(part_ref)
                if need_gram:
                    gram_ref[...] = jnp.zeros_like(gram_ref)

            def flush(c, carry):
                cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                _flush_indexed_stats(land_u, land_p, slot, cols, ok, v,
                                     part_ref, gram_ref)
                return carry

            jax.lax.fori_loop(0, T // chunk, flush, 0)

            @pl.when(last_t)
            def _reduce():
                _reduce_partials(part_ref, acc_refs, K, need_gram)

    if not run_combine:
        return

    if run_stats:
        @pl.when((p == 0) & last_t)
        def _derive():
            kio = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
            valid_f = sum(jnp.where(kio == k, valid_ref[0, k], 0.0)
                          for k in range(K))                     # (1, K)
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

    # ---- phase 1: trust-weighted combine over the resident tile, f32 in
    # slot order
    @pl.when(p == n_phase - 1)
    def _combine():
        kio = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
        wcomb = wcomb_ref[...]
        acc = lcoef_ref[...] * local_ref[...].astype(jnp.float32)
        for k in range(K):
            wk = jnp.sum(jnp.where(kio == k, wcomb, 0.0))
            acc = acc + wk * land_u[slot, k]
        out_ref[...] = acc


def wfagg_round_indexed_pallas(
    local: Array | None,  # (N, D) local models (combine anchors)
    models: Array,        # (M, D) model matrix (row per node)
    neighbor_idx: Array,  # (N, K) int32 rows into ``models``
    valid: Array | None,  # (N, K) float32, 1.0 on real edges
    cfg,                  # duck-typed WFAggConfig (static)
    prev: Array | None = None,    # (N, K, D) per-edge, or (M, D) matrix
    tbands: Array | None = None,  # (N, 4K) flat WFAgg-T EWMA bands
    prev_idx: Array | None = None,  # (N, K) rows into matrix ``prev``
    *,
    alpha: float,
    mean_fallback: bool = False,
    need_gram: bool = False,
    block_d: int,
    interpret: bool | None = None,
    phases: tuple = ROUND,
    coef: tuple | None = None,  # COMBINE: (wcomb (N, K), lcoef (N, 1))
):
    """Launch the WFAgg round kernel over a 3-D (node, phase, D tile)
    grid with (K, block_d) tiles.  ``models`` and ``prev`` stay in HBM
    (``pl.ANY``) and the body gathers each tile's K rows with row DMAs;
    the other operands are BlockSpec-pipelined.

    ``phases=ROUND``: phase 0 accumulates the indexed robust statistics,
    the phase boundary derives the trust weights in-kernel, and phase 1
    writes the WFAgg-E combine — one launch for the entire gossip round.
    Returns (out (N, 1, D), weights, mask_d, mask_c, mask_t (each
    (N, 1, K)), dist2, dotmed, norm2 ((N, 1, K)), mednorm2 ((N, 1, 1))
    [, gram (N, K, K)][, prev_dist2, prev_dot, prev_norm2 ((N, 1, K))]).

    ``phases=STATS`` (``local``, ``tbands`` None) runs phase 0 alone over
    a (node, 1, D tile) grid and returns the accumulators only, from
    ``dist2`` on.  ``phases=COMBINE`` (``valid``, ``prev``, ``tbands``
    None) runs phase 1 alone with the combine coefficients of ``coef``
    and returns ``(out,)``.

    With ``prev_idx`` the matrix-form ``prev`` reads through its own
    (N, K) table (concatenated after ``neighbor_idx`` into one (N, 2K)
    SMEM prefetch block) instead of re-using the models table — the
    fault-injected transport's staleness pricing, still one launch.
    """
    M, D = models.shape
    N, K = neighbor_idx.shape
    assert D % block_d == 0, (D, block_d)
    stats, combine = 0 in phases, 1 in phases
    assert (local is not None) == combine, phases
    assert (valid is not None) == stats and (coef is None) == stats, phases
    if combine:
        assert local.shape == (N, D), (local.shape, (N, D))
    n_t = D // block_d
    has_prev = prev is not None
    has_tbands = tbands is not None
    assert stats or not (has_prev or has_tbands), phases
    if prev_idx is not None and not (has_prev and prev.ndim == 2):
        raise ValueError("prev_idx requires a matrix-form prev")
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    k_spec = pl.BlockSpec((None, 1, K), lambda n, p, i, ir: (n, 0, 0))
    one_spec = pl.BlockSpec((None, 1, 1), lambda n, p, i, ir: (n, 0, 0))
    in_specs, args = [], []
    if stats:
        # valid: read as K scalars a grid step (SMEM)
        in_specs.append(pl.BlockSpec((None, 1, K),
                                     lambda n, p, i, ir: (n, 0, 0),
                                     memory_space=pltpu.SMEM))
        args.append(valid.astype(jnp.float32).reshape(N, 1, K))
    if has_tbands:
        # bands ride flat, (N, 1, 4K): a (N, 4, K) buffer would read as a
        # gossip tensor to the rank-based (N, K, d)-free scan when K == 4
        assert tbands.shape == (N, 4 * K), (tbands.shape, (N, 4 * K))
        in_specs.append(
            pl.BlockSpec((None, 1, 4 * K), lambda n, p, i, ir: (n, 0, 0)))
        args.append(tbands.astype(jnp.float32).reshape(N, 1, 4 * K))
    if not stats:
        wcomb, lcoef = coef
        in_specs += [k_spec, one_spec]
        args += [wcomb.astype(jnp.float32).reshape(N, 1, K),
                 lcoef.astype(jnp.float32).reshape(N, 1, 1)]
    # local and the combine output: under ROUND pinned to tile 0 during
    # phase 0 (only phase 1 touches them) — `i * p` keeps the block
    # constant until the combine phase
    row_spec = pl.BlockSpec(
        (None, 1, block_d),
        (lambda n, p, i, ir: (n, 0, i * p)) if stats
        else (lambda n, p, i, ir: (n, 0, i)))
    if combine:
        in_specs.append(row_spec)
        args.append(_row_view(local))
    in_specs.append(hbm)
    args.append(_row_view(models))
    table, prev_row = neighbor_idx, None
    if has_prev:
        view, table, prev_row = _prev_rows(prev, models, neighbor_idx,
                                           prev_idx)
        in_specs.append(hbm)
        args.append(view)
    chunk = ROUND_CHUNK if block_d % ROUND_CHUNK == 0 else block_d
    kernel = functools.partial(
        _wfagg_round_indexed_kernel, K=K, n_t=n_t, T=block_d, chunk=chunk,
        phases=phases, has_prev=has_prev, prev_row=prev_row,
        has_tbands=has_tbands, need_gram=need_gram, cfg=cfg, alpha=alpha,
        mean_fallback=mean_fallback,
    )

    out_shapes, out_specs = [], []
    if combine:
        out_shapes.append(
            jax.ShapeDtypeStruct((N, 1, D), jnp.float32))  # combined models
        out_specs.append(row_spec)
    if phases == ROUND:
        out_shapes += [
            jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # trust weights
            jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # mask_d
            jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # mask_c
            jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # mask_t
        ]
        out_specs += [k_spec] * 4
    if stats:
        out_shapes += [
            jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # dist2
            jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # dotmed
            jax.ShapeDtypeStruct((N, 1, K), jnp.float32),   # norm2
            jax.ShapeDtypeStruct((N, 1, 1), jnp.float32),   # mednorm2
        ]
        out_specs += [k_spec, k_spec, k_spec, one_spec]
        if need_gram:
            out_shapes.append(jax.ShapeDtypeStruct((N, K, K), jnp.float32))
            out_specs.append(
                pl.BlockSpec((None, K, K), lambda n, p, i, ir: (n, 0, 0)))
        if has_prev:
            out_shapes += [jax.ShapeDtypeStruct((N, 1, K), jnp.float32)] * 3
            out_specs += [k_spec] * 3
    # landing tiles: two slots of K (1, block_d) rows, each row its own
    # (1, 128)-tiled block, so a row DMA lands on whole tiles
    land = [pltpu.VMEM((2, K, 1, block_d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,))]
    scratch_shapes = land * (2 if has_prev else 1)
    if stats:
        # the flush's lane-wide partial sums (``_flush_indexed_stats``)
        scratch_shapes.append(pltpu.VMEM(
            (_n_partials(K, has_prev), 1, chunk), jnp.float32))
    if phases == ROUND:
        scratch_shapes += [pltpu.VMEM((1, K), jnp.float32),  # combine weights
                           pltpu.VMEM((1, 1), jnp.float32)]  # local coefficient
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, len(phases), n_t),
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
