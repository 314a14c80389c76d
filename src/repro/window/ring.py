"""Sliding-window ACE: a device-resident ring of sketch epochs.

The paper's dynamic-update pitch (§3.4.1: O(K·L) insert *and* delete) is
what separates ACE from batch detectors — but the repo's base sketch still
accumulates counts forever, so under concept drift μ/σ and the μ−ασ admit
threshold go stale: the historical mass dominates μ, the regime mix
inflates σ, and the filter either flags everything or nothing.  Streaming
baselines (EXPoSE's decayed feature mean, the in-DRAM active-flows table)
solve this with windows/decay; ACE's count algebra makes it cheap — counts
are an additive monoid, so a window is just a SUM OF EPOCH SKETCHES and
expiry is zeroing one epoch, never replaying a delete stream.

``WindowedAceState`` holds E epoch sketches stacked on a leading axis,
plus a maintained γ-weighted TAIL view so the hot path never recombines
epochs:

    counts        (E, L, 2^K)   per-epoch count arrays
    n             (E,)          per-epoch item counts
    welford_mean  (E,)          per-epoch streaming rate mean
    welford_m2    (E,)          per-epoch streaming rate M2
    tail          (L, 2^K) f32  Σ_{e≠cursor} γ^age · C_e  (maintained)
    ssq           ()       f32  ‖C_w‖², C_w = tail + C_cursor
    cursor        ()  int32     index of the LIVE epoch (ring pointer)
    tick          ()  int32     insert steps since init (drives rotation)

The split matters for throughput: the live epoch takes every insert, the
tail only changes at rotation.  So an insert is ONE scatter (identical
to the flat sketch's) and a windowed score is the live gather the flat
sketch does anyway plus one extra gather against the frozen tail —
O(B·L) either way, independent of E.  A maintained full-combine view
would instead pay a SECOND scatter per insert (measured: scatters cost
~2× gathers on the scan hot path), and query-time epoch recombination
would pay E gathers plus O(E·L·2^K) moment sweeps (measured: halved
ingest at E=6).

Everything here is pure and fixed-shape — jit/scan/donation safe, no
host syncs anywhere:

* ``rotate``       — advance the ring: cursor moves one slot (O(1)
                     pointer math), the slot it moves INTO (the expired
                     epoch) is zeroed, and the tail absorbs the old live
                     epoch, sheds the expired one, and decays one γ
                     step.  O(L·2^K) device work ONCE PER EPOCH —
                     amortised over the ``rotate_every`` steps the epoch
                     lasted; the per-step hot path never touches full
                     tables.
* ``insert_current`` — masked insert into the live epoch (one scatter),
                     with ``ssq`` advanced by the windowed Eq. 11
                     increment  Δ‖C_w‖² = 2⟨h, C_w⟩ + ‖h‖²  recovered
                     from the pre/post score gathers the step does
                     anyway.  Every term is an integer-valued float32
                     for γ=1 (exact while < 2^24 — the same envelope as
                     every count reduction in the repo).
* ``window_table_sums`` / ``score_live`` — the hot-path windowed score:
                     tail gather + live gather, combined per item.
* ``score_windowed`` — the query-time E-way combine (reference + the
                     contract of the ``ace_window_combine`` Pallas
                     kernel): works for ANY γ, reads all E epochs.
* ``admit_threshold_windowed`` — the μ−ασ score-space rule from
                     WINDOW-combined moments: μ_w from the maintained
                     ``ssq`` (γ-generalised Eq. 11 closed form), σ_w by
                     a γ-weighted Chan merge of the per-epoch Welford
                     streams.

γ is a CONFIG property (``WindowConfig.decay``), not stored in the
state: the ``tail``/``ssq`` caches are maintained AT that γ, so every
call that takes a ``gamma`` argument must pass the ring's own decay
(the filter/guardrail/train wrappers thread it; mixing γs is checked
only by the ``*_direct`` test oracles).

Degenerate-case contracts (tests/test_window.py + the property suite):
with E=1 every windowed op is BITWISE the plain ``AceState`` op (the
tail is identically zero, γ⁰ = 1 exactly, and the moment fold starts
from epoch 0's scalars, not a zero accumulator); with γ=1 and no
rotation the E-epoch window is ``sketch.merge`` of the epochs;
``rotate`` applied E times returns the ring to an all-zero sketch with
the cursor back where it started.

HBM accounting: E epochs + the f32 tail cost (E + 2) × the paper's
int16 base sketch (K=15, L=50: E=8 → 31 MB — still far under one
device), and the window length in items is E × rotate_every × B,
tunable at constant memory by trading E against rotate_every.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core import sketch as sk
from repro.core.sketch import AceConfig, AceState


class WindowedAceState(NamedTuple):
    """Ring of E epoch sketches + the maintained γ-weighted tail view
    (a pytree — jit/scan/psum/donation safe)."""

    counts: jax.Array        # (E, L, 2^K) counter dtype
    n: jax.Array             # (E,) float32
    welford_mean: jax.Array  # (E,) float32
    welford_m2: jax.Array    # (E,) float32
    tail: jax.Array          # (L, 2^K) float32 — Σ_{e≠cursor} γ^age·C_e
    ssq: jax.Array           # () float32 — ‖tail + C_cursor‖²
    cursor: jax.Array        # ()  int32 — live epoch index
    tick: jax.Array          # ()  int32 — insert steps since init
    qhist: Optional[jax.Array] = None  # (E, quantile.NUM_BINS) float32
    #                          per-epoch collision-rate histograms for
    #                          threshold_mode="quantile"; None (default)
    #                          keeps every existing pytree contract
    attr: Optional[jax.Array] = None  # (E, 2, NL, R, C) float32 per-epoch
    #                          signed count-sketch attribution planes
    #                          (repro.attribution); None (default) keeps
    #                          every existing pytree contract

    @property
    def num_epochs(self) -> int:
        return self.counts.shape[0]


@dataclasses.dataclass(frozen=True)
class WindowConfig:
    """Static window configuration (hashable; safe as a jit static arg).

    decay γ: epoch e is weighted γ^age in the window combine.  γ=1 is
    the hard window (all live epochs weigh equally; expiry is the only
    forgetting); γ<1 additionally down-weights older epochs —
    EXPoSE-style exponential decay at epoch granularity, with none of
    the per-item decay cost.

    rotate_every: insert steps per epoch (0 = never rotate — the window
    degenerates to the frozen sketch).  The window spans
    ``num_epochs × rotate_every`` steps of history.
    """

    ace: AceConfig
    num_epochs: int = 4
    decay: float = 1.0
    rotate_every: int = 0

    def __post_init__(self):
        if self.num_epochs < 1:
            raise ValueError(f"num_epochs must be >= 1, got {self.num_epochs}")
        if not (0.0 < self.decay <= 1.0):
            raise ValueError(f"decay must be in (0, 1], got {self.decay}")
        if self.ace.esc_capacity > 0:
            raise NotImplementedError(
                "overflow promotion (esc_capacity > 0) is wired for the "
                "flat sketch only; window rings take narrow count dtypes "
                "without an escalation table (exact below saturation). "
                "See docs/ARCHITECTURE.md §7.")

    def memory_bytes(self) -> int:
        """The window's HBM bill: E epochs + the f32 tail view."""
        ace = self.ace
        tail = ace.num_tables * ace.num_buckets * 4
        return self.num_epochs * ace.memory_bytes() + tail


def init(cfg: AceConfig, num_epochs: int,
         quantile: bool = False) -> WindowedAceState:
    if num_epochs < 1:
        raise ValueError(f"num_epochs must be >= 1, got {num_epochs}")
    if cfg.esc_capacity > 0:
        raise NotImplementedError(
            "overflow promotion (esc_capacity > 0) is flat-sketch only; "
            "window rings take narrow count dtypes without promotion")
    if quantile:
        from repro.quantile import sketch as qsk
        qhist = qsk.init_hist(num_epochs)
    else:
        qhist = None
    acfg = cfg.attr
    attr = (jnp.zeros((num_epochs,) + acfg.plane_shape(), jnp.float32)
            if acfg is not None else None)
    return WindowedAceState(
        counts=jnp.zeros((num_epochs, cfg.num_tables, cfg.num_buckets),
                         dtype=jnp.dtype(cfg.counter_dtype)),
        n=jnp.zeros((num_epochs,), jnp.float32),
        welford_mean=jnp.zeros((num_epochs,), jnp.float32),
        welford_m2=jnp.zeros((num_epochs,), jnp.float32),
        tail=jnp.zeros((cfg.num_tables, cfg.num_buckets), jnp.float32),
        ssq=jnp.zeros((), jnp.float32),
        cursor=jnp.zeros((), jnp.int32),
        tick=jnp.zeros((), jnp.int32),
        qhist=qhist,
        attr=attr,
    )


def init_window(cfg: WindowConfig, quantile: bool = False) -> WindowedAceState:
    return init(cfg.ace, cfg.num_epochs, quantile=quantile)


# ---------------------------------------------------------------------------
# Ring mechanics.
# ---------------------------------------------------------------------------

def rotate(state: WindowedAceState, gamma: float = 1.0) -> WindowedAceState:
    """Advance the ring: the oldest epoch expires and becomes the new
    live epoch (zeroed counts AND zeroed moments), and the tail is
    RECOMPUTED from the updated ring as one weighted tensordot:

        tail' = Σ_e γ^age'_e · C'_e      (the zeroed new-live slab
                                          contributes nothing)

    The incremental fold this replaced — γ·(tail + C_live −
    γ^{E−1}·C_expired) — was algebraically identical but NOT bitwise
    stable for γ<1: when traced into a larger program (the maybe_rotate
    cond, a vmapped fleet, a scan body) XLA CPU fuses the
    subtract-of-product into an FMA, rounding the decayed tail up to
    1 ulp (up to ~700 ulp after the γ multiply) differently than the
    eager op-by-op sequence (an optimization_barrier did not stop it —
    measured), which forced the strict bitwise windowed contracts to
    pin γ=1.  A single dot_general lowers identically across
    eager/jit/cond/scan/vmap (and the fleet-native einsum matches the
    vmapped form bitwise — both verified empirically on this backend),
    so γ<1 is now bitwise across execution contexts, and the recompute
    additionally flushes any incremental float error in the tail once
    per epoch instead of letting it γ-decay.  Same O(L·2^K) cost class
    as the old fold — once per ``rotate_every`` steps, never on the
    per-item path, and nothing here syncs to the host.  ``ssq`` is
    recomputed from the new tail (the new live epoch is empty, so
    ‖C_w‖² = ‖tail'‖²).  Applied E times this returns the ring to the
    all-zero init with the cursor back where it started
    (property-tested).
    """
    with obs.scope("rotate"):
        E = state.num_epochs
        new_cursor = jnp.mod(state.cursor + 1, E)
        zero_slab = jnp.zeros(state.counts.shape[1:], state.counts.dtype)
        counts = jax.lax.dynamic_update_index_in_dim(
            state.counts, zero_slab, new_cursor, axis=0)
        w = epoch_weights(new_cursor, E, gamma)
        tail = jnp.tensordot(w, counts.astype(jnp.float32), axes=1)
        zero1 = jnp.zeros((1,), jnp.float32)
        qhist = state.qhist
        if qhist is not None:
            qhist = jax.lax.dynamic_update_index_in_dim(
                qhist, jnp.zeros((qhist.shape[1],), jnp.float32),
                new_cursor, axis=0)
        attr = state.attr
        if attr is not None:
            attr = jax.lax.dynamic_update_index_in_dim(
                attr, jnp.zeros(attr.shape[1:], jnp.float32),
                new_cursor, axis=0)
        return WindowedAceState(
            counts=counts,
            n=jax.lax.dynamic_update_slice(state.n, zero1, (new_cursor,)),
            welford_mean=jax.lax.dynamic_update_slice(
                state.welford_mean, zero1, (new_cursor,)),
            welford_m2=jax.lax.dynamic_update_slice(
                state.welford_m2, zero1, (new_cursor,)),
            tail=tail,
            ssq=jnp.sum(tail * tail),
            cursor=new_cursor,
            tick=state.tick,
            qhist=qhist,
            attr=attr,
        )


def maybe_rotate(state: WindowedAceState, rotate_every: int,
                 gamma: float = 1.0) -> WindowedAceState:
    """Rotate when the tick says the live epoch is full.

    Call AFTER an insert step (``insert_current`` bumps the tick): the
    R-th insert completes an epoch and the ring rotates eagerly, so each
    epoch holds exactly ``rotate_every`` steps and every driver (the
    per-batch filter ``__call__``, the guardrail admit, the train tail
    path) sees the same rotation positions as the stream runner's
    cond-free segment scan.  Pure device control flow (lax.cond on
    device scalars — scan-safe, no host sync), but note the cond makes
    XLA copy the carry on every call — fine once per host-driven batch,
    NOT fine inside a scan body, which is why ``StreamRunner`` lowers
    rotation to straight-line segment boundaries instead whenever the
    chunk shape allows (see ``_consume_impl``).  With
    ``rotate_every <= 0`` this is the identity.
    """
    if rotate_every <= 0:
        return state
    should = jnp.logical_and(state.tick > 0,
                             jnp.mod(state.tick, rotate_every) == 0)
    return jax.lax.cond(should, lambda s: rotate(s, gamma), lambda s: s,
                        state)


def live_epoch(state: WindowedAceState) -> AceState:
    """The live epoch as a plain ``AceState`` view (traced-index gather)."""
    return AceState(
        counts=jax.lax.dynamic_index_in_dim(
            state.counts, state.cursor, axis=0, keepdims=False),
        n=jnp.take(state.n, state.cursor),
        welford_mean=jnp.take(state.welford_mean, state.cursor),
        welford_m2=jnp.take(state.welford_m2, state.cursor),
    )


def window_table_sums(state: WindowedAceState, buckets: jax.Array,
                      table_mask: jax.Array | None = None):
    """Hot-path windowed table sums, split by provenance:

        tail_sums[i] = Σ_j tail[j, b_ij]         (frozen between rotations)
        live_sums[i] = Σ_j C_cursor[j, b_ij]     (pre-insert)

    Two (B, L) gathers — the live one is what the flat sketch gathers
    anyway, the tail one is the whole extra per-step cost of windowing,
    independent of E.  The live gather addresses the ring as an
    (E·L, 2^K) matrix with row offset cursor·L (a 3-index gather lowers
    poorly, and slab-slicing the epoch copies (L, 2^K) per step).
    Returns (tail_sums, live_sums), both (B,) integer-valued float32
    (tail exactly so only when γ=1).

    ``table_mask`` (L,) zeroes corrupted tables out of BOTH row-sums —
    the Python-level ``None`` branch keeps the healthy program untouched
    (the repo-wide degraded-mode convention, see
    ``sketch.batch_scores``).  The caller pairs this with the masked
    ``score_live`` combine, which divides by the healthy count.
    """
    E, L, nbuckets = state.counts.shape
    with obs.scope("score"):
        rows = jnp.broadcast_to(
            jnp.arange(L, dtype=jnp.int32)[None, :], buckets.shape)
        ring_rows = rows + state.cursor * L
        flat = state.counts.reshape(E * L, nbuckets)
        tail_g = state.tail[rows, buckets]                       # (B, L)
        live_g = flat[ring_rows, buckets].astype(jnp.float32)    # (B, L)
        if table_mask is not None:
            maskf = table_mask.astype(jnp.float32)
            tail_g = tail_g * maskf
            live_g = live_g * maskf
        return jnp.sum(tail_g, axis=-1), jnp.sum(live_g, axis=-1)


def score_live(tail_sums: jax.Array, live_sums: jax.Array,
               num_tables: int,
               table_mask: jax.Array | None = None) -> jax.Array:
    """(tail_sums, live_sums) -> (B,) windowed scores.

    The canonical combine: one add, ONE reciprocal multiply by 1/L
    (same literal constant as ``sketch.batch_scores``).  With E=1 the
    tail is identically zero and ``0.0 + x`` is exact, so this is
    ``batch_scores`` bitwise.

    With ``table_mask`` the sums are assumed already masked (from the
    masked ``window_table_sums``) and the reciprocal is 1/num_healthy —
    the degraded-mode mean over surviving tables."""
    with obs.scope("score"):
        if table_mask is None:
            return (tail_sums + live_sums) * jnp.float32(1.0 / num_tables)
        nh = jnp.maximum(jnp.sum(table_mask.astype(jnp.float32)), 1.0)
        return (tail_sums + live_sums) * (1.0 / nh)


def score_combined(state: WindowedAceState,
                   buckets: jax.Array) -> jax.Array:
    """Hot-path windowed Ŝ(q) at the ring's own γ: tail + live gathers,
    canonical combine.  For arbitrary-γ queries use ``score_windowed``."""
    tail_sums, live_sums = window_table_sums(state, buckets)
    return score_live(tail_sums, live_sums, state.counts.shape[1])


def insert_current(state: WindowedAceState, buckets: jax.Array,
                   mask: jax.Array, cfg: AceConfig, gamma: float = 1.0,
                   pre_sums=None) -> WindowedAceState:
    """Masked insert into the LIVE epoch; bumps the tick by one step.

    ONE 2-D scatter, exactly like ``sketch.insert_buckets_masked`` (the
    ring is addressed as an (E·L, 2^K) matrix with row offset cursor·L;
    the tail is untouched — it only changes at rotation).

    ``ssq`` advances by the windowed Eq. 11 increment without touching
    a full table: with h the masked batch histogram and m_· the masked
    sums of the pre/post gathers this step does anyway
    (``pre_sums = (tail_sums, live_sums)`` lets the caller pass the
    scoring gathers it already did),

        Δ‖C_w‖² = 2⟨h, C_w⟩ + ‖h‖²
                 = 2·m_tail + m_live_pre + m_live_post

    since ⟨h, C_w⟩ = ⟨h, tail⟩ + ⟨h, C_cur⟩ and ⟨h, C_cur + h⟩ =
    ⟨h, C_cur⟩ + ‖h‖² — the batch analogue of the paper's (2A+1)
    streaming term.

    The per-epoch Welford stream folds the POST-insert WINDOWED rates
    (score_w / n_w — the same quantity the threshold tests), mirroring
    ``sketch.masked_batch_welford`` term for term with the stream-length
    weighting on the epoch's own n; with E=1 the fold is bitwise the
    flat masked insert's, and the ``welford_min_n`` cold-start gate
    re-arms after every rotation exactly as it does at sketch init.
    """
    E = state.num_epochs
    L = buckets.shape[1]
    nbuckets = state.counts.shape[2]
    rows = jnp.broadcast_to(
        jnp.arange(L, dtype=jnp.int32)[None, :], buckets.shape)
    ring_rows = rows + state.cursor * L
    maskf = mask.astype(jnp.float32)

    if pre_sums is None:
        pre_sums = window_table_sums(state, buckets)
    tail_sums, live_pre = pre_sums

    # -- THE scatter (live epoch rows of the ring)
    with obs.scope("insert"):
        w_ctr = jnp.broadcast_to(
            mask.astype(state.counts.dtype)[:, None], buckets.shape)
        new_ring = state.counts.reshape(E * L, nbuckets) \
            .at[ring_rows, buckets].add(w_ctr).reshape(state.counts.shape)

    with obs.scope("rescore"):
        # -- post-insert windowed sums/scores (tail unchanged; same float
        #    sequence as sketch.batch_scores: row-sum, add, ONE 1/L
        #    reciprocal multiply)
        live_post = jnp.sum(
            new_ring.reshape(E * L, nbuckets)[ring_rows, buckets]
            .astype(jnp.float32), axis=-1)
        scores = score_live(tail_sums, live_post, L)

        # -- ssq increment from masked pre/post sums only
        m_tail = jnp.sum(tail_sums * maskf)
        m_pre = jnp.sum(live_pre * maskf)
        m_post = jnp.sum(live_post * maskf)
        new_ssq = state.ssq + 2.0 * m_tail + m_pre + m_post

        # -- per-epoch Welford fold of windowed post-insert rates; mirrors
        #    sketch.masked_batch_welford with the epoch's n as the stream
        #    length and the WINDOW's n as the rate normaliser (equal when
        #    E=1 — bitwise the flat fold)
        b = jnp.sum(maskf)
        n_e = jnp.take(state.n, state.cursor)
        tot_e = n_e + b
        n_w = combined_n(state, gamma) + b
        rates = scores / jnp.maximum(n_w, 1.0)
        mean_b = jnp.sum(rates * maskf) / jnp.maximum(b, 1.0)
        m2_b = jnp.sum(((rates - mean_b) ** 2) * maskf)
        new_mean, new_m2 = sk.welford_fold(
            jnp.take(state.welford_mean, state.cursor),
            jnp.take(state.welford_m2, state.cursor),
            n_e, b, tot_e, mean_b, m2_b, cfg.welford_min_n)
        has = b > 0
        new_mean = jnp.where(has, new_mean,
                             jnp.take(state.welford_mean, state.cursor))
        new_m2 = jnp.where(has, new_m2,
                           jnp.take(state.welford_m2, state.cursor))

    c = state.cursor
    return state._replace(
        counts=new_ring,
        n=jax.lax.dynamic_update_slice(state.n, tot_e[None], (c,)),
        welford_mean=jax.lax.dynamic_update_slice(
            state.welford_mean, new_mean[None], (c,)),
        welford_m2=jax.lax.dynamic_update_slice(
            state.welford_m2, new_m2[None], (c,)),
        ssq=new_ssq,
        tick=state.tick + 1)


# ---------------------------------------------------------------------------
# Window-combined views: weights, counts, scores, moments, threshold.
# ---------------------------------------------------------------------------

def epoch_weights(cursor: jax.Array, num_epochs: int,
                  gamma: float) -> jax.Array:
    """(E,) float32 query-time weights: γ^age, age = (cursor − e) mod E.

    The live epoch always weighs exactly 1.0 (γ^0 — exact in float), so
    every windowed op with E=1 reduces to a multiply-by-1.0, keeping the
    single-epoch window bitwise equal to the plain sketch path.
    """
    ages = jnp.mod(cursor - jnp.arange(num_epochs, dtype=jnp.int32),
                   num_epochs)
    return jnp.power(jnp.float32(gamma), ages.astype(jnp.float32))


def decayed_counts(state: WindowedAceState, gamma: float) -> jax.Array:
    """γ-weighted combined counts recomputed FROM THE EPOCHS:
    C_w = Σ_e γ^age · C_e   (L, 2^K) f32.

    The test oracle for the maintained ``state.tail`` (C_w minus the
    live epoch; bitwise for γ=1 where everything is exact integers,
    float-tolerance for γ<1, where the maintained view's error also
    γ-decays every rotation).  With γ=1 this is the exact hard-window
    count sum (the monoid merge of the live epochs)."""
    w = epoch_weights(state.cursor, state.num_epochs, gamma)
    return jnp.tensordot(w, state.counts.astype(jnp.float32), axes=1)


def score_windowed(state: WindowedAceState, buckets: jax.Array,
                   gamma: float) -> jax.Array:
    """Query-time E-way windowed Ŝ(q) (any γ, reads every epoch):

        score(q) = (1/L) · Σ_e γ^age_e · Σ_j C_e[j, H_j(q)]

    CANONICAL summation order — per-epoch row-sum in float32, weighted,
    accumulated over e in ring-index order, then ONE reciprocal multiply
    by 1/L (same literal constant as ``sketch.batch_scores``).  The
    Pallas kernel (``repro.kernels.ace_window_combine``) and its
    ``kernels.ref`` oracle implement the same formula sequence
    (kernel-side reductions agree to float tolerance, the usual
    score-kernel contract); with E=1 the whole thing is ``batch_scores``
    bitwise (1.0-weight multiply is exact), and at the ring's own γ it
    matches the tail+live hot path (``score_combined``) — bitwise for
    γ=1.
    """
    L = state.counts.shape[1]
    return score_from_sums(epoch_table_sums(state, buckets),
                           state.cursor, gamma, L)


def epoch_table_sums(state: WindowedAceState,
                     buckets: jax.Array) -> jax.Array:
    """Per-epoch table sums  t[e, i] = Σ_j C_e[j, b_ij]   (E, B) f32.

    One fused gather for all E epochs (the ring addressed as an
    (E·L, 2^K) matrix) — the reference/diagnostic path behind
    ``score_windowed``; the hot path gathers tail + live instead."""
    E, L, nbuckets = state.counts.shape
    B = buckets.shape[0]
    ring_rows = (jnp.arange(E, dtype=jnp.int32)[:, None] * L
                 + jnp.arange(L, dtype=jnp.int32)[None, :]).reshape(-1)
    rows = jnp.broadcast_to(ring_rows[None, :], (B, E * L))
    cols = jnp.tile(buckets, (1, E))
    flat = state.counts.reshape(E * L, nbuckets)
    gathered = flat[rows, cols].astype(jnp.float32)      # (B, E·L)
    return jnp.sum(gathered.reshape(B, E, L), axis=-1).T  # (E, B)


def score_from_sums(sums: jax.Array, cursor: jax.Array, gamma: float,
                    num_tables: int) -> jax.Array:
    """(E, B) per-epoch table sums -> (B,) windowed scores (the canonical
    combine order; see ``score_windowed``)."""
    E = sums.shape[0]
    w = epoch_weights(cursor, E, gamma)
    acc = jnp.zeros(sums.shape[1:], jnp.float32)
    for e in range(E):  # static unroll, ring-index order (kernel parity)
        acc = acc + w[e] * sums[e]
    return acc * jnp.float32(1.0 / num_tables)


def combined_n(state: WindowedAceState, gamma: float) -> jax.Array:
    """Effective window item count  n_w = Σ_e γ^age · n_e."""
    w = epoch_weights(state.cursor, state.num_epochs, gamma)
    return jnp.sum(w * state.n)


def combined_qhist(state: WindowedAceState, gamma: float) -> jax.Array:
    """γ-weighted combined-window rate histogram:
    H_w = Σ_e γ^age · H_e   (NUM_BINS,) f32 — the same ``epoch_weights``
    tensordot as ``decayed_counts``, exact at γ=1 (integer-valued unit
    weights), and a valid weighted CDF for any γ ∈ (0, 1].  Rotation
    composes for free: the expired epoch's histogram row is zeroed, so
    its rates leave the window quantile exactly when its counts leave
    the score."""
    if state.qhist is None:
        raise ValueError("window has no qhist leaf (threshold_mode="
                         "'quantile' needs init_window(..., quantile=True))")
    w = epoch_weights(state.cursor, state.num_epochs, gamma)
    return jnp.tensordot(w, state.qhist, axes=1)


def observe_current(state: WindowedAceState, rates: jax.Array,
                    maskf: jax.Array) -> WindowedAceState:
    """Fold a batch of windowed rates into the LIVE epoch's histogram
    row — one flat scatter at cursor·NUM_BINS + bin (the ring analogue
    of ``quantile.observe_rates``; fixed-shape, scan/donation safe).
    ``maskf`` is the OBSERVE mask (finite rows), not the admit mask."""
    from repro.quantile import sketch as qsk
    E, nb = state.qhist.shape
    offs = state.cursor * nb + qsk.bin_index(rates)
    flat = state.qhist.reshape(E * nb)
    qhist = flat.at[offs].add(maskf.astype(jnp.float32)).reshape(E, nb)
    return state._replace(qhist=qhist)


def mean_mu_windowed(state: WindowedAceState, gamma: float,
                     table_mask: jax.Array | None = None) -> jax.Array:
    """γ-generalised Eq. 11 closed form:  μ_w = ‖C_w‖² / (n_w · L).

    For γ=1 this is EXACT — C_w is the merged counts and the derivation
    of ``sketch.mean_mu`` applies verbatim to the merged sketch.  For
    γ<1 it is the natural weighted self-collision estimate (each pair's
    contribution decays with both members' ages).  ‖C_w‖² is the
    maintained ``state.ssq`` stream (O(1) at query time; re-anchored
    from the tail at every rotation), never an O(L·2^K) sweep on the
    per-step path.

    ``table_mask`` (degraded mode only) cannot use the scalar ssq — it
    recomputes per-table squared norms from the epochs via
    ``decayed_counts`` (a full-table sweep, acceptable off the healthy
    hot path) and means over the healthy tables."""
    L = state.counts.shape[1]
    if table_mask is None:
        denom = jnp.maximum(combined_n(state, gamma), 1.0) * L
        return state.ssq / denom
    maskf = table_mask.astype(jnp.float32)
    nh = jnp.maximum(jnp.sum(maskf), 1.0)
    cw = decayed_counts(state, gamma)                            # (L, 2^K)
    per_table = jnp.sum(cw * cw, axis=1)                         # (L,)
    denom = jnp.maximum(combined_n(state, gamma), 1.0) * nh
    return jnp.sum(per_table * maskf) / denom


def sigma_windowed(state: WindowedAceState, gamma: float) -> jax.Array:
    """Window σ of windowed-score rates from the combined Welford stream."""
    n_w, _, m2_w = combined_moments(state, gamma)
    return jnp.sqrt(m2_w / jnp.maximum(n_w - 1.0, 1.0))


def combined_moments(state: WindowedAceState, gamma: float):
    """Window-combined Welford stream: (n_w, mean_w, m2_w).

    Chan's parallel merge rule (the same one ``sketch.merge`` uses)
    folded across epochs in ring-index order, with epoch e's stream
    entering at effective weight γ^age — i.e. n_e → γ^age·n_e and
    M2_e → γ^age·M2_e, the standard exponential-decay moment combine.
    The fold STARTS from epoch 0's own (weighted) moments, not a zero
    accumulator, so E=1 returns the epoch's scalars bitwise.
    """
    E = state.num_epochs
    w = epoch_weights(state.cursor, E, gamma)
    n_acc = w[0] * state.n[0]
    mean_acc = state.welford_mean[0]
    m2_acc = w[0] * state.welford_m2[0]
    for e in range(1, E):  # static unroll
        n_b = w[e] * state.n[e]
        delta = state.welford_mean[e] - mean_acc
        tot = n_acc + n_b
        safe = jnp.maximum(tot, 1.0)
        mean_acc = mean_acc + delta * n_b / safe
        m2_acc = (m2_acc + w[e] * state.welford_m2[e]
                  + delta**2 * n_acc * n_b / safe)
        n_acc = tot
    return n_acc, mean_acc, m2_acc


def admit_threshold_windowed(state: WindowedAceState, gamma: float,
                             alpha: float, warmup_items: float,
                             table_mask: jax.Array | None = None,
                             threshold_mode: str = "mu_sigma",
                             q: float = 0.01) -> jax.Array:
    """Score-space admission threshold from WINDOW-combined statistics.

    ``"mu_sigma"`` mirrors ``sketch.admit_threshold``
    operation-for-operation (rate = μ_w/n_w, t = (rate − α·σ_w)·
    max(n_w, 1), −inf during warmup) with every statistic swapped for
    its window-combined counterpart, so the E=1 window thresholds
    bitwise like the plain sketch.  ``"quantile"`` reads the q-quantile
    of the γ-weighted combined-window rate histogram
    (``combined_qhist``) and scales by the same max(n_w, 1) — the E=1
    quantile window is bitwise the flat quantile path (γ⁰ = 1 weight is
    exact).  Both modes are trace-time Python dispatch (one cached
    executable per mode) and return ONE device scalar.  Because expired
    epochs leave the combined statistics, the threshold TRACKS the
    stream: after a distribution shift the stale regime ages out of the
    window instead of pinning the threshold forever.  Pure device
    scalar ops — no host sync.
    """
    with obs.scope("threshold"):
        n_w = combined_n(state, gamma)
        if threshold_mode == "quantile":
            from repro.quantile import sketch as qsk
            t = qsk.hist_quantile(combined_qhist(state, gamma), q) \
                * jnp.maximum(n_w, 1.0)
            return jnp.where(n_w >= warmup_items, t, -jnp.inf)
        if threshold_mode != "mu_sigma":
            raise ValueError(f"unknown threshold_mode {threshold_mode!r}")
        rate = mean_mu_windowed(state, gamma, table_mask=table_mask) \
            / jnp.maximum(n_w, 1.0)
        t = (rate - alpha * sigma_windowed(state, gamma)) \
            * jnp.maximum(n_w, 1.0)
        return jnp.where(n_w >= warmup_items, t, -jnp.inf)


def combined_ace(state: WindowedAceState) -> AceState:
    """Hard-window (γ=1) combine into ONE plain ``AceState``.

    Counts sum in the counter dtype (exact); n sums; the Welford streams
    merge by Chan's rule — i.e. this is ``sketch.merge`` folded over the
    epochs.  Diagnostics/export convenience; the hot paths never
    materialise it (they read tail + live).
    """
    out = AceState(counts=state.counts[0], n=state.n[0],
                   welford_mean=state.welford_mean[0],
                   welford_m2=state.welford_m2[0])
    for e in range(1, state.num_epochs):
        out = sk.merge(out, AceState(
            counts=state.counts[e], n=state.n[e],
            welford_mean=state.welford_mean[e],
            welford_m2=state.welford_m2[e]))
    return out
