"""Chaos transport: fault-injected gossip delivery for the DFL engine.

The dynamics engine (``repro.dfl.dynamics``) varies WHO talks to whom;
this module varies HOW WELL the talking goes.  Every message that the
topology schedule says is delivered can independently be

  dropped        the packet never arrives (lossy link),
  stale          a straggler delivers the sender's model from ``lag``
                 rounds ago instead of the fresh one,
  duplicated     the network re-delivers last round's packet,
  corrupted      the payload arrives bit-damaged — NaN / +-Inf rows or
                 finite garbage, generated in-scan from a PRNG keyed by
                 (round, edge),
  crashed        the sender is down for the round: it neither trains nor
                 transmits, and everything it would have received is lost
                 (crash-restart: when the node comes back it resumes from
                 its frozen state).

Like the topology scenarios, fault schedules are precomputed host-side
by deterministic numpy generators into scan-friendly ``(R, N, K)`` /
``(R, N)`` stacks (:class:`FaultSchedule`), so a whole faulty experiment
still compiles ONCE and runs through ``jax.lax.scan``.

The delivery mechanics are the *stacked-ring-matrix* trick: the scan
carries a bounded L-deep ring of past post-attack model matrices
(:class:`TransportState`), and :func:`apply_transport` builds one 2-D
``((L+1)*M + C, d)`` stacked matrix

    [ flat (M rows) | ring (L*M rows) | corrupt bank (C rows) ]

then *re-keys the neighbor table* instead of materializing per-edge
payloads: a fresh delivery reads row ``idx``, a lag-l delivery reads row
``l*M + idx``, a corrupted delivery reads a bank row.  The gossip
kernels are untouched — they DMA rows from a 2-D matrix exactly as
before, the (N, K, d) tensor still never exists, and the launch count
stays 1 (the ``chaos_scan`` lint entry pins it).

Graceful degradation, in order:
  * sanitizer — non-finite rows of the stacked matrix are zeroed and the
    edges that read them demoted to invalid BEFORE filter statistics, so
    the indexed kernel's median/mean never sees a NaN;
  * retry-as-redundancy — a dropped/duplicated delivery falls back to
    re-serving the last delivered payload, aged one round
    (``served_lag + 1``), valid while within ``staleness_budget``;
  * staleness pricing — the per-edge ``prev`` index table points at the
    payload the edge ACTUALLY served last round, so WFAgg-T's
    round-over-round metrics price the lag instead of comparing against
    a model the receiver never saw.

See docs/FAULTS.md for the taxonomy and the resume workflow.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import TopologySchedule
from repro.obs import profile as obs_profile

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Static transport parameters (hashable: rides into jit closures).

    ``ring_depth`` L bounds how old a served payload can be (the scan
    carries L past model matrices); ``staleness_budget`` is the oldest
    lag a receiver ACCEPTS — a delivery older than the budget is demoted
    to invalid and the node's slate shrinks.  ``bank_size`` C is the
    number of corrupt-payload rows appended to the stacked matrix;
    ``garbage_scale`` sizes the finite-garbage corruption rows (those
    must survive the sanitizer and be caught by the filters instead).
    """

    ring_depth: int = 3
    staleness_budget: int = 2
    bank_size: int = 4
    max_lag: int = 2          # largest scheduled straggler lag
    garbage_scale: float = 1e3
    seed: int = 0             # keys the in-scan corruption PRNG

    def __post_init__(self):
        if self.ring_depth < 1:
            raise ValueError("ring_depth must be >= 1")
        if self.max_lag > self.ring_depth:
            raise ValueError(
                f"max_lag={self.max_lag} exceeds ring_depth={self.ring_depth}"
                " — the ring cannot serve a payload that old")
        if self.bank_size < 1:
            raise ValueError("bank_size must be >= 1")


class FaultRound(NamedTuple):
    """One round's fault surface (the per-round xs of the scan)."""

    drop: Array      # (N, K) bool  packet lost on this edge
    lag: Array       # (N, K) int32 scheduled straggler lag (0 = fresh)
    dup: Array       # (N, K) bool  re-delivery of last round's packet
    corrupt: Array   # (N, K) bool  payload bit-damaged on the wire
    down: Array      # (N,)   bool  node crashed for this round


class TransportState(NamedTuple):
    """Scan-carried delivery state.

    ``ring[l]`` is the post-attack model matrix from ``l + 1`` rounds ago
    (``ring[0]`` = last round), so the stacked matrix serves lag ``l``
    from row block ``l * M``.  ``served_lag[n, k]`` is the age of the
    payload edge (n, k) actually delivered last round — the anchor for
    both the retry fallback and the WFAgg-T prev re-keying.
    """

    ring: Array        # (L, M, d) f32
    served_lag: Array  # (N, K) int32


class TransportOut(NamedTuple):
    """What :func:`apply_transport` hands the aggregation stage."""

    full: Array        # ((L+1)*M + C, d) sanitized stacked matrix
    eff_idx: Array     # (N, K) int32 re-keyed neighbor table into ``full``
    eff_valid: Array   # (N, K) bool  surviving edges after faults + budget
    prev_idx: Array    # (N, K) int32 last round's delivery, aged, in ``full``
    served_lag: Array  # (N, K) int32 next round's served_lag carry
    dropped: Array     # (N, K) bool  telemetry: delivery was dropped
    stale: Array       # (N, K) bool  telemetry: delivered but lag > 0
    corrupt: Array     # (N, K) bool  telemetry: corruption hit the edge


def init_transport_state(cfg: FaultConfig, n_nodes: int, width: int,
                         d: int) -> TransportState:
    return TransportState(
        ring=jnp.zeros((cfg.ring_depth, n_nodes, d), jnp.float32),
        served_lag=jnp.zeros((n_nodes, width), jnp.int32),
    )


def corrupt_bank(cfg: FaultConfig, d: int, rnd: Array) -> Array:
    """(C, d) corrupted-payload rows for round ``rnd``, generated in-scan.

    Rows cycle NaN / +Inf / -Inf / finite-garbage with the round, so
    every corruption flavor is exercised; the PRNG is keyed by
    (cfg.seed, round) — bit-reproducible, and a resumed scan regenerates
    the identical bank from the carried round counter.
    """
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 9173), rnd)
    noise = cfg.garbage_scale * jax.random.normal(
        key, (cfg.bank_size, d), jnp.float32)
    kind = ((jnp.arange(cfg.bank_size, dtype=jnp.int32) + rnd) % 4)[:, None]
    bank = jnp.where(kind == 0, jnp.nan, noise)
    bank = jnp.where(kind == 1, jnp.inf, bank)
    return jnp.where(kind == 2, -jnp.inf, bank)


def apply_transport(flat: Array, ts: TransportState, neighbor_idx: Array,
                    valid: Array, fr: FaultRound, cfg: FaultConfig,
                    rnd: Array) -> TransportOut:
    """Re-key one round's gossip through the fault surface.

    Pure traced jnp on scan-carried state — no host transfers, no new
    kernel launches, no (N, K, d) tensor (everything d-sized stays 2-D).
    """
    M, d = flat.shape
    N, K = neighbor_idx.shape
    L, C = cfg.ring_depth, cfg.bank_size
    valid_b = valid.astype(bool)

    bank = corrupt_bank(cfg, d, rnd)
    full = jnp.concatenate([flat, ts.ring.reshape(L * M, d), bank], axis=0)

    # --- which payload age does each edge get? ---------------------------
    # re-serving last round's delivery makes it one round older, capped at
    # the ring depth (the oldest representable payload)
    relag = jnp.minimum(ts.served_lag + 1, L)
    sender_down = fr.down[neighbor_idx]
    drop = (fr.drop | sender_down) & valid_b
    lag = jnp.clip(fr.lag, 0, L)
    lag = jnp.where(fr.dup & valid_b, relag, lag)
    lag = jnp.where(drop, relag, lag)         # retry-as-redundancy fallback
    # a payload older than the round count does not exist (the ring is
    # zero-initialized), and one older than the budget is not accepted
    ok = (lag <= cfg.staleness_budget) & (lag <= rnd)
    eff_valid = valid_b & ok & ~fr.down[:, None]

    eff_idx = lag * M + neighbor_idx
    corrupt = fr.corrupt & eff_valid
    slot = ((jnp.arange(N, dtype=jnp.int32)[:, None] * K
             + jnp.arange(K, dtype=jnp.int32)[None, :] + rnd) % C)
    eff_idx = jnp.where(corrupt, (L + 1) * M + slot, eff_idx)

    # --- sanitizer: the kernels must never see a non-finite row ----------
    with obs_profile.phase("sanitize"):
        finite = jnp.isfinite(full).all(axis=1)
        full = jnp.where(finite[:, None], full, 0.0)
        eff_valid = eff_valid & finite[eff_idx]

    # --- staleness pricing: where was last round's delivery? -------------
    # the payload edge (n, k) served last round is one round older now;
    # WFAgg-T compares against what the receiver ACTUALLY saw
    prev_idx = relag * M + neighbor_idx

    # an edge that delivered records its lag; an edge that did not keeps
    # (re-ages) its last delivery — consecutive drops walk down the ring
    # until the budget demotes them
    served_lag = jnp.where(eff_valid, lag, relag)

    return TransportOut(
        full=full, eff_idx=eff_idx, eff_valid=eff_valid, prev_idx=prev_idx,
        served_lag=served_lag,
        dropped=drop | (valid_b & ~ok),
        stale=eff_valid & (lag > 0) & ~corrupt,
        corrupt=fr.corrupt & valid_b,
    )


def advance_ring(ts: TransportState, flat: Array,
                 served_lag: Array) -> TransportState:
    """Post-round carry: push this round's (post-attack, post-freeze)
    model matrix into ring slot 0 and adopt the new served-lag table."""
    return TransportState(
        ring=jnp.concatenate([flat[None], ts.ring[:-1]], axis=0),
        served_lag=served_lag,
    )


def realign_served_lag(served: Array, prev_idx: Array, prev_valid: Array,
                       idx: Array, valid: Array) -> Array:
    """Re-key the slot-positional served-lag table to a new slate.

    Same identity-match contraction as ``wf.realign_temporal_history``:
    column k_new inherits the served lag of the k_old with matching
    neighbor id (both slots valid); a neighbor unseen last round starts
    at lag 0 — its "previous delivery" defaults to the freshest ring
    entry, mirroring the zeroed history column the temporal realign
    gives strangers.
    """
    match = ((idx[:, :, None] == prev_idx[:, None, :])
             & valid.astype(bool)[:, :, None]
             & prev_valid.astype(bool)[:, None, :])   # (N, K_new, K_old)
    m = match.astype(jnp.float32)
    return jnp.einsum("nkj,nj->nk", m, served.astype(jnp.float32)
                      ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# schedules: deterministic host-side generators (mirrors dynamics.SCENARIOS)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """Precomputed per-round fault surface for a whole experiment.

    Array stacks match :class:`FaultRound` with a leading R axis; the
    static :class:`FaultConfig` travels with them so a checkpoint can
    reconstruct the exact transport semantics on resume.
    """

    drop: np.ndarray     # (R, N, K) bool
    lag: np.ndarray      # (R, N, K) int32
    dup: np.ndarray      # (R, N, K) bool
    corrupt: np.ndarray  # (R, N, K) bool
    down: np.ndarray     # (R, N) bool
    config: FaultConfig = FaultConfig()

    @property
    def rounds(self) -> int:
        return self.drop.shape[0]

    def xs(self):
        """The scan xs: device arrays in FaultRound field order."""
        return (jnp.asarray(self.drop), jnp.asarray(self.lag),
                jnp.asarray(self.dup), jnp.asarray(self.corrupt),
                jnp.asarray(self.down))

    def summary(self) -> Dict[str, float]:
        return {
            "drop_rate": float(self.drop.mean()),
            "stale_rate": float((self.lag > 0).mean()),
            "dup_rate": float(self.dup.mean()),
            "corrupt_rate": float(self.corrupt.mean()),
            "down_rate": float(self.down.mean()),
        }


def _zeros(rounds: int, n: int, k: int):
    return (np.zeros((rounds, n, k), bool), np.zeros((rounds, n, k), np.int32),
            np.zeros((rounds, n, k), bool), np.zeros((rounds, n, k), bool),
            np.zeros((rounds, n), bool))


def _gen_none(rng, rounds, n, k, intensity, cfg, **_):
    return _zeros(rounds, n, k)


def _gen_drop(rng, rounds, n, k, intensity, cfg, **_):
    drop, lag, dup, corrupt, down = _zeros(rounds, n, k)
    drop[:] = rng.random((rounds, n, k)) < intensity
    return drop, lag, dup, corrupt, down


def _gen_stale(rng, rounds, n, k, intensity, cfg, max_lag=None, **_):
    drop, lag, dup, corrupt, down = _zeros(rounds, n, k)
    ml = int(max_lag if max_lag is not None else cfg.max_lag)
    hit = rng.random((rounds, n, k)) < intensity
    lag[:] = np.where(hit, rng.integers(1, ml + 1, (rounds, n, k)), 0)
    return drop, lag, dup, corrupt, down


def _gen_duplicate(rng, rounds, n, k, intensity, cfg, **_):
    drop, lag, dup, corrupt, down = _zeros(rounds, n, k)
    dup[:] = rng.random((rounds, n, k)) < intensity
    return drop, lag, dup, corrupt, down


def _gen_corrupt(rng, rounds, n, k, intensity, cfg, **_):
    drop, lag, dup, corrupt, down = _zeros(rounds, n, k)
    corrupt[:] = rng.random((rounds, n, k)) < intensity
    return drop, lag, dup, corrupt, down


def _gen_crash_restart(rng, rounds, n, k, intensity, cfg,
                       p_restart=0.5, **_):
    """Markov crash/restart per node: up -> down with p = intensity per
    round, down -> up with ``p_restart`` — nodes freeze while down and
    resume from their stored state when back."""
    drop, lag, dup, corrupt, down = _zeros(rounds, n, k)
    state = np.zeros((n,), bool)
    for r in range(rounds):
        crash = rng.random(n) < intensity
        restart = rng.random(n) < p_restart
        state = np.where(state, ~restart, crash)
        down[r] = state
    return drop, lag, dup, corrupt, down


def _gen_chaos(rng, rounds, n, k, intensity, cfg, **params):
    """Everything at once, scaled so total disruption tracks intensity:
    drop + stale at intensity/2, duplicate/corrupt/crash at intensity/4."""
    drop, lag, dup, corrupt, down = _gen_drop(
        rng, rounds, n, k, intensity / 2, cfg)
    _, lag, _, _, _ = _gen_stale(rng, rounds, n, k, intensity / 2, cfg,
                                 **params)
    dup[:] = rng.random((rounds, n, k)) < intensity / 4
    corrupt[:] = rng.random((rounds, n, k)) < intensity / 4
    _, _, _, _, down = _gen_crash_restart(rng, rounds, n, k, intensity / 4,
                                          cfg)
    return drop, lag, dup, corrupt, down


FAULTS = {
    "none": _gen_none,
    "drop": _gen_drop,
    "stale": _gen_stale,
    "duplicate": _gen_duplicate,
    "corrupt": _gen_corrupt,
    "crash_restart": _gen_crash_restart,
    "chaos": _gen_chaos,
}

FAULT_NAMES = tuple(FAULTS)


def make_fault_schedule(name: str, schedule: TopologySchedule,
                        intensity: float, seed: int = 0,
                        config: Optional[FaultConfig] = None,
                        **params) -> FaultSchedule:
    """Build a named fault schedule shaped to a topology schedule.

    Deterministic in (name, shape, intensity, seed, params) — the same
    arguments always produce the identical byte-for-byte schedule, which
    is what makes kill-and-resume (and CI reproduction) exact.
    """
    if name not in FAULTS:
        raise ValueError(f"unknown fault scenario {name!r}; "
                         f"choose from {sorted(FAULTS)}")
    cfg = config or FaultConfig()
    rng = np.random.default_rng(seed)
    drop, lag, dup, corrupt, down = FAULTS[name](
        rng, schedule.rounds, schedule.n_nodes, schedule.width,
        float(intensity), cfg, **params)
    return FaultSchedule(drop=drop, lag=np.clip(lag, 0, cfg.ring_depth),
                         dup=dup, corrupt=corrupt, down=down, config=cfg)
