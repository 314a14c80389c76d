"""The ACE sketch: L count arrays of size 2^K + streaming statistics.

Paper Algorithm 1, made batch-parallel and SPMD-friendly:

* state  = counts (L, 2^K) integer array + n (items inserted) — nothing else;
  no data points are ever stored (the paper's core memory claim).
* insert = scatter-add of the batch bucket histogram (order-invariant).
* score  = mean over L of counts[j, H_j(q)]  (Theorem 1: unbiased for S(q,D)).
* mean   = closed form  μ = Σ_j Σ_b A_j[b]² / (n·L)

The closed form is derived from the paper's Eq. 11: inserting into a bucket
with count c changes Σ_b A²  by (c+1)² − c² = 2c+1, matching the paper's
incremental term (2A+1)/L exactly — so maintaining Σ‖A‖² tracks n·L·μ with
*no sequential dependency*.  ``tests/test_ace_core.py`` property-tests the
two formulations against each other, including deletes (Eq. 12).

Because counts are additive, sketches over disjoint data shards merge by
elementwise addition — this is the whole multi-pod story (see
``repro.dist.sketch_parallel``): each data shard sketches locally, a psum
merges; and because the L arrays are independent, counts also shard over
the L axis (the table-sharded layout there) when the sketch outgrows one
device.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core import quantize as qz
from repro.core.axes import on_auto_axes
from repro.core.srp import SrpConfig, hash_buckets, make_projections


class AceState(NamedTuple):
    """Dynamic sketch state (a pytree — jit/scan/psum friendly).

    counts: (L, 2^K) integer counters.
    n:      () float32 — number of items currently represented.  float so the
            pytree is uniform under optimizers/donation; exact up to 2^24.
    welford_mean / welford_m2: () float32 — streaming mean/M2 of *insert-time*
            scores (for the σ estimate in the streaming threshold policy; the
            exact μ never uses these).
    esc:    overflow escalation table for quantized (int8/int16) count
            planes, or None (the default — unquantized sketches carry no
            extra leaves, so every existing pytree contract is unchanged).
            When present, ``counts`` stores ``min(count, dtype max)`` and
            the exact logical count of a promoted bucket is
            ``counts + esc`` (see repro.core.quantize).
    qhist:  (repro.quantile.NUM_BINS,) float32 collision-rate histogram
            for ``threshold_mode="quantile"`` admission, or None (the
            default — μ−ασ sketches carry no extra leaves, same contract
            as ``esc``).  Observed by the admit entry points, not the
            insert primitives (see repro.quantile.sketch for why the
            observe mask differs from the admit mask).
    attr:   (2, NL, R, C) float32 signed count-sketch attribution
            hierarchy (repro.attribution; enabled by
            ``AceConfig.attr_rows > 0``), or None (the default — same
            no-extra-leaves contract as ``esc``/``qhist``).  Channel 0
            accumulates all finite traffic's per-coordinate energy,
            channel 1 the flagged anomalies'.  Observed chunk-wise by
            the stream runner, not by the insert primitives (like
            ``qhist``); the inserts only carry the leaf through.
    """

    counts: jax.Array
    n: jax.Array
    welford_mean: jax.Array   # streaming mean of RATES score/n (stationary)
    welford_m2: jax.Array
    esc: Optional[qz.EscTable] = None
    qhist: Optional[jax.Array] = None
    attr: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class AceConfig:
    """Static ACE configuration (hashable; safe as a jit static arg)."""

    dim: int
    num_bits: int = 15          # K
    num_tables: int = 50        # L
    seed: int = 0
    counter_dtype: str = "int32"  # "int16" reproduces the paper's 2x saving
    welford_min_n: float = 0.0  # skip σ-stream updates below this n (the
                                # cold-start rates score/n are off-scale and
                                # would inflate σ forever)
    hash_mode: str = "dense"    # "dense" | "srht" | "auto" — threaded into
                                # .srp; part of the persisted-sketch
                                # contract (see SrpConfig.hash_mode)
    esc_capacity: int = 0       # > 0 enables exact overflow promotion for
                                # narrow (int8/int16) count planes: that
                                # many buckets may exceed the dtype max
                                # before excess is dropped (and counted).
                                # 0 = plain counters (narrow dtypes then
                                # wrap past saturation, like any int add).
    attr_rows: int = 0          # > 0 attaches the signed count-sketch
                                # attribution hierarchy (repro.attribution)
                                # with that many median rows; 0 (default)
                                # carries no attr leaf — every existing
                                # pytree contract is unchanged.
    attr_bits: int = 8          # attribution bucket-space log2 (width
                                # 2^attr_bits per row); only read when
                                # attr_rows > 0.

    def __post_init__(self):
        if self.esc_capacity < 0:
            raise ValueError("esc_capacity must be >= 0, got "
                             f"{self.esc_capacity}")
        if self.attr_rows < 0:
            raise ValueError("attr_rows must be >= 0, got "
                             f"{self.attr_rows}")
        if self.attr_rows > 0:
            # delegate range validation (dim/rows/bits) to AttrConfig
            from repro.attribution import AttrConfig
            AttrConfig(dim=self.dim, rows=self.attr_rows,
                       bits=self.attr_bits, seed=self.seed)
        if self.esc_capacity > 0:
            if not qz.is_narrow(self.counter_dtype):
                raise ValueError(
                    "esc_capacity > 0 (overflow promotion) requires a "
                    "narrow count_dtype (int8/int16); got "
                    f"{self.counter_dtype!r}")
            if self.num_tables * (1 << self.num_bits) > qz.SENTINEL:
                raise ValueError(
                    "quantized planes must stay int32 flat-addressable: "
                    f"L·2^K = {self.num_tables * (1 << self.num_bits)}")

    @property
    def srp(self) -> SrpConfig:
        return SrpConfig(dim=self.dim, num_bits=self.num_bits,
                         num_tables=self.num_tables, seed=self.seed,
                         hash_mode=self.hash_mode)

    @property
    def num_buckets(self) -> int:
        return 1 << self.num_bits

    @property
    def count_dtype(self) -> str:
        """ISSUE/paper-facing alias of the stored ``counter_dtype``."""
        return self.counter_dtype

    @property
    def quantized(self) -> bool:
        """True when the sketch carries an overflow escalation table."""
        return self.esc_capacity > 0

    @property
    def attr(self):
        """The attribution hierarchy config, or None when disabled."""
        if self.attr_rows <= 0:
            return None
        from repro.attribution import AttrConfig
        return AttrConfig(dim=self.dim, rows=self.attr_rows,
                          bits=self.attr_bits, seed=self.seed)

    def memory_bytes(self) -> int:
        """The paper's headline number: L × 2^K × sizeof(counter)
        (plus the escalation side table when promotion is enabled, plus
        the attribution hierarchy when attr_rows > 0)."""
        itemsize = jnp.dtype(self.counter_dtype).itemsize
        base = self.num_tables * self.num_buckets * itemsize
        base += self.esc_capacity * 8 + (4 if self.quantized else 0)
        acfg = self.attr
        return base + (acfg.memory_bytes() if acfg is not None else 0)


def init(cfg: AceConfig) -> AceState:
    attr = None
    if cfg.attr_rows > 0:
        from repro.attribution import init_plane
        attr = init_plane(cfg.attr)
    return AceState(
        counts=jnp.zeros((cfg.num_tables, cfg.num_buckets),
                         dtype=jnp.dtype(cfg.counter_dtype)),
        n=jnp.zeros((), jnp.float32),
        welford_mean=jnp.zeros((), jnp.float32),
        welford_m2=jnp.zeros((), jnp.float32),
        esc=qz.init_esc(cfg.esc_capacity) if cfg.quantized else None,
        attr=attr,
    )


def _flat_offsets(buckets: jax.Array, L: int, nbuckets: int) -> jax.Array:
    """(B, L) bucket ids -> (B, L) flat element offsets j·2^K + bucket."""
    rows = jnp.broadcast_to(
        jnp.arange(L, dtype=jnp.int32)[None, :], buckets.shape)
    return buckets + rows * nbuckets


def make_params(cfg: AceConfig, dtype=jnp.float32) -> jax.Array:
    """The SRP projection matrix W (d, KL_padded)."""
    return make_projections(cfg.srp, dtype=dtype)


# ---------------------------------------------------------------------------
# Bucket-level primitives (input: precomputed bucket ids (B, L)).
# These are what the Pallas kernels accelerate; everything here is the
# reference path and stays pure-jnp.
# ---------------------------------------------------------------------------

def gather_counts(counts: jax.Array, buckets: jax.Array) -> jax.Array:
    """counts[j, buckets[., j]]: (B, L) ids -> (B, L) in the counts dtype.

    The rows-broadcast gather every unquantized score starts from.
    """
    L = counts.shape[0]
    rows = jnp.broadcast_to(
        jnp.arange(L, dtype=jnp.int32)[None, :], buckets.shape)
    return counts[rows, buckets]


def table_mean(gathered: jax.Array,
               table_mask: jax.Array | None = None) -> jax.Array:
    """Mean of a (..., L) integer gather over its L tables, float32.

    THE score formula: float32 row-sum × reciprocal 1/L.  The mean is an
    explicit reciprocal multiply, never a bare `/ L`: XLA fast-math
    rewrites `/ L` to `* (1/L)` in SOME programs but not others, which
    would break the bitwise parity contracts across the single-device,
    fused-kernel and repro.dist paths — every score, and every
    post-insert score (gathered, or derived by
    ``insert_buckets_masked``), goes through THIS helper (or mirrors its
    constant, where table-sharding makes the gather structurally
    different) so the formula exists once.

    ``table_mask`` (L,) 0/1 float32 restricts the mean to HEALTHY tables
    (repro.resilience): score = Σ_j m_j·c_j / max(Σ_j m_j, 1) — the L−k
    surviving tables are an unbiased estimator of the same Ŝ(q, D)
    (Theorem 1 holds for any subset of the independent tables).  The
    ``None`` default is a Python-level branch so the unmasked program is
    untouched — the bitwise parity contracts above never see the mask.
    """
    g = gathered.astype(jnp.float32)
    if table_mask is None:
        return jnp.sum(g, axis=-1) * jnp.float32(1.0 / g.shape[-1])
    return masked_table_mean(g, table_mask)


def batch_scores(counts: jax.Array, buckets: jax.Array,
                 table_mask: jax.Array | None = None) -> jax.Array:
    """Scores of a batch of bucket ids vs a counts array: (B, L) -> (B,).

    ``table_mean`` of ``gather_counts``: the gather, then the one score
    formula (see ``table_mean`` for the reciprocal mean and the
    ``table_mask`` degraded mode).
    """
    return table_mean(gather_counts(counts, buckets), table_mask)


def masked_table_mean(gathered: jax.Array,
                      table_mask: jax.Array) -> jax.Array:
    """Mean of a (..., L) gather over the healthy tables only.

    THE degraded-mode combine (single home, like the 1/L reciprocal of
    the healthy paths): masked sum × reciprocal of the healthy-table
    count.  A corrupted table contributes an exact float 0.0 (mask 0 ×
    finite gather — inject.py never writes NaN into count planes, bit
    flips yield huge-but-finite integers), so the healthy tables'
    summation values are identical to an oracle sketch that never held
    the corrupted tables.
    """
    maskf = table_mask.astype(jnp.float32)
    nh = jnp.maximum(jnp.sum(maskf), 1.0)
    return jnp.sum(gathered * maskf, axis=-1) * (1.0 / nh)


def lookup(state: AceState, buckets: jax.Array,
           table_mask: jax.Array | None = None) -> jax.Array:
    """counts[j, buckets[., j]] averaged over j.  (B, L) -> (B,) float32.

    This is Ŝ(q, D) of Algorithm 1 (query phase).  ``table_mask``
    averages over healthy tables only (see ``table_mean``).
    """
    return lookup_gathered(state, buckets, table_mask=table_mask)[0]


def lookup_gathered(state: AceState, buckets: jax.Array,
                    table_mask: jax.Array | None = None):
    """``lookup`` that also hands back its gather: (scores (B,), pre).

    ``pre`` is the (B, L) pre-insert gather of the unquantized counts
    (never masked), for ``insert_buckets_masked(..., pre=pre)`` to
    derive the post-insert scores from; None for a quantized sketch,
    whose scatter yields its own post-insert counts.
    """
    with obs.scope("score"):
        if state.esc is not None:
            return qz.batch_scores_logical(state.counts, state.esc, buckets,
                                           table_mask=table_mask), None
        pre = gather_counts(state.counts, buckets)
        return table_mean(pre, table_mask), pre


# Largest batch whose post-insert counts ``insert_buckets_masked`` derives
# from in-batch collisions rather than gathering them again.  The pairwise
# count costs O(B²·L) against the gather's O(B·L) random reads; on a TPU
# v5e at L=50 it takes 0.64 ms at B=3,901 and 2.65 ms at B=8,192 against
# 2.6 and 4.3 ms for the gather, which it passes near B=12,000.
PAIRWISE_MAX_BATCH = 8192


def batch_collisions(buckets: jax.Array, weights: jax.Array) -> jax.Array:
    """In-batch collision counts: (B, L) ids, (B,) 0/1 weights -> (B, L).

    ``c[i, l] = Σ_j weights[j] · [buckets[j, l] == buckets[i, l]]`` (int32)
    — what a 0/1-weighted scatter-add of this batch adds to item i's
    bucket in table l, its own weight included.  So the counts after the
    scatter, at the batch's buckets, are ``pre + c`` where ``pre`` is the
    gather before it, with no access to the table.

    Compared pairwise, one fused compare-and-count over (B, B, L) with the
    unweighted items' ids set to −1 (never a bucket): no per-element
    random access, which on a TPU costs about as much as the table gather
    it replaces, and little code.  (Sorting the ids per table is faster
    at B=3,901 but its sorts and scans add ~2 MB of program to device
    memory.)
    """
    live = jnp.where(weights.astype(bool)[:, None], buckets, -1)
    return jnp.sum(live[:, None, :] == buckets[None, :, :], axis=0,
                   dtype=jnp.int32)


def histogram(buckets: jax.Array, cfg: AceConfig) -> jax.Array:
    """Batch bucket histogram: (B, L) ids -> (L, 2^K) counts of this batch."""
    L = cfg.num_tables
    B = buckets.shape[0]
    rows = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None, :], (B, L))
    zero = jnp.zeros((L, cfg.num_buckets), dtype=jnp.dtype(cfg.counter_dtype))
    return zero.at[rows, buckets].add(1)


def welford_fold(welford_mean: jax.Array, welford_m2: jax.Array,
                 n: jax.Array, b: jax.Array, tot: jax.Array,
                 mean_b: jax.Array, m2_b: jax.Array, min_n: float):
    """Fold one batch's rate statistics into the Welford stream.

    (mean_b, m2_b) are the batch mean / sum-of-squared-deviations of the
    rates; the cold-start gate (min_n) RESTARTS the stream on the first
    gated batch — early rates are off-scale and Welford never forgets.
    Shared by every insert path (single-device, replicated shard_map,
    table-sharded — repro.dist.sketch_parallel) so their Welford numerics
    stay identical by construction, not by copy-synced formulas.
    """
    delta = mean_b - welford_mean
    gate = (n >= min_n).astype(jnp.float32)
    eff_n = jnp.where(gate > 0, n, 0.0)
    new_mean = jnp.where(
        gate > 0,
        welford_mean + delta * b / jnp.maximum(tot, 1.0),
        mean_b)
    new_m2 = jnp.where(
        gate > 0,
        welford_m2 + m2_b + delta**2 * eff_n * b / jnp.maximum(tot, 1.0),
        m2_b)
    return new_mean, new_m2


def insert_buckets(state: AceState, buckets: jax.Array,
                   cfg: AceConfig) -> AceState:
    """Insert a batch.  Order-invariant; exact for any batch size.

    Welford stats are updated with the *post-insert* score of each item
    (its own count included), matching Algorithm 1 line 12's convention of
    scoring x against D ∪ {x}.
    """
    L = cfg.num_tables
    if state.esc is not None:
        offs = _flat_offsets(buckets, L, cfg.num_buckets)
        new_counts, new_esc, post = qz.quantized_scatter(
            state.counts, state.esc, offs,
            jnp.ones((buckets.shape[0],), jnp.int32))
        # post IS the post-insert gather (exact logical counts) — same
        # score formula as batch_scores, so below saturation this is
        # bitwise the unquantized path.
        scores = table_mean(post)
    else:
        rows = jnp.broadcast_to(
            jnp.arange(L, dtype=jnp.int32)[None, :], buckets.shape)
        new_counts = state.counts.at[rows, buckets].add(1)
        new_esc = None

        # Post-insert scores of the batch items (vs the fully updated
        # arrays).
        scores = batch_scores(new_counts, buckets)             # (B,)

    # Welford over collision RATES score/n, not raw scores: raw insert-time
    # scores grow ~linearly with n (item i scores ≈ O(i)), which inflates σ
    # with ramp variance and makes μ−ασ thresholds useless.  Rates are
    # stationary for a stationary stream.
    b = jnp.asarray(scores.shape[0], jnp.float32)
    n = state.n
    tot = n + b
    rates = scores / jnp.maximum(tot, 1.0)
    mean_b = jnp.mean(rates)
    m2_b = jnp.sum((rates - mean_b) ** 2)
    new_mean, new_m2 = welford_fold(
        state.welford_mean, state.welford_m2, n, b, tot, mean_b, m2_b,
        cfg.welford_min_n)

    return AceState(counts=new_counts, n=tot,
                    welford_mean=new_mean, welford_m2=new_m2, esc=new_esc,
                    qhist=state.qhist, attr=state.attr)


def masked_batch_welford(state: AceState, scores: jax.Array,
                         maskf: jax.Array, min_n: float, reduce=None):
    """Welford fold over only the masked items of a fixed-shape batch.

    ``scores`` are post-insert scores of ALL items (B,); ``maskf`` is the
    0/1 float admit mask.  Returns (n, welford_mean, welford_m2) after
    folding the masked subset's rate statistics — identical (up to float
    summation order) to folding ``scores[mask]`` through the dense path.
    An all-zero mask leaves the stream untouched (the dense path would
    NaN on an empty batch).

    ``reduce`` (optional) is applied to each scalar partial sum (count,
    rate sum, M2 sum) — a psum over the data axes when the batch is
    sharded, identity otherwise.  Every masked insert path (single-device,
    fused-kernel admit via repro.kernels.ops.ace_admit, and both
    repro.dist.sketch_parallel layouts) folds through THIS function, so
    their numerics stay identical by construction, not by copy-synced
    formulas (same contract as ``welford_fold`` for the dense paths).
    """
    if reduce is None:
        def reduce(v):  # noqa: A001 — identity for the unsharded batch
            return v
    b = reduce(jnp.sum(maskf))
    n = state.n
    tot = n + b
    rates = scores / jnp.maximum(tot, 1.0)
    mean_b = reduce(jnp.sum(rates * maskf)) / jnp.maximum(b, 1.0)
    m2_b = reduce(jnp.sum(((rates - mean_b) ** 2) * maskf))
    new_mean, new_m2 = welford_fold(
        state.welford_mean, state.welford_m2, n, b, tot, mean_b, m2_b,
        min_n)
    has = b > 0
    return (tot,
            jnp.where(has, new_mean, state.welford_mean),
            jnp.where(has, new_m2, state.welford_m2))


def insert_buckets_masked(state: AceState, buckets: jax.Array,
                          mask: jax.Array, cfg: AceConfig,
                          pre: jax.Array | None = None) -> AceState:
    """Masked (weighted) insert: insert only the items where ``mask``.

    Equivalent to ``insert_buckets(state, buckets[mask], cfg)`` — exactly
    for counts/n/μ (the scatter-add of 0/1 weights builds the identical
    histogram), and up to float summation order for the Welford stream —
    but FIXED-SHAPE: no data-dependent gather, so one compiled program
    serves every batch regardless of how many items are admitted.  This
    is the serving guardrail's insert (order-invariant and shape-stable;
    see Guardrail.admit).

    The Welford fold takes every item's post-insert score, the one score
    formula (``table_mean``) over its post-insert counts, which come
    from one of three places: a quantized sketch's saturating scatter
    (exact logical counts); ``pre + batch_collisions(buckets, mask)``
    when the caller hands over ``pre``, the (B, L) gather of
    ``state.counts`` it scored from (``lookup_gathered``), and B is at
    most ``PAIRWISE_MAX_BATCH`` — integer adds that wrap as the
    scatter's do, so the counts are the gathered ones exactly and the
    table is read once per batch; else a second gather from the updated
    counts.  ``pre`` is ignored for a quantized sketch.
    """
    L = cfg.num_tables
    with obs.scope("insert"):
        if state.esc is not None:
            offs = _flat_offsets(buckets, L, cfg.num_buckets)
            new_counts, new_esc, post = qz.quantized_scatter(
                state.counts, state.esc, offs, mask.astype(jnp.int32))
        else:
            rows = jnp.broadcast_to(
                jnp.arange(L, dtype=jnp.int32)[None, :], buckets.shape)
            w_ctr = jnp.broadcast_to(
                mask.astype(state.counts.dtype)[:, None], buckets.shape)
            new_counts = state.counts.at[rows, buckets].add(w_ctr)
            new_esc = post = None

    with obs.scope("rescore"):
        # post-insert counts of ALL items — masked-out items included
        # (colliding admits may bump their buckets); the masked-out
        # items just don't contribute to the Welford fold below
        if (post is None and pre is not None
                and buckets.shape[0] <= PAIRWISE_MAX_BATCH):
            post = pre + batch_collisions(buckets, mask).astype(pre.dtype)
        elif post is None:
            post = gather_counts(new_counts, buckets)
        scores = table_mean(post)                                # (B,)
        tot, new_mean, new_m2 = masked_batch_welford(
            state, scores, mask.astype(jnp.float32), cfg.welford_min_n)
    return AceState(counts=new_counts, n=tot,
                    welford_mean=new_mean, welford_m2=new_m2, esc=new_esc,
                    qhist=state.qhist, attr=state.attr)


def delete_buckets(state: AceState, buckets: jax.Array,
                   cfg: AceConfig) -> AceState:
    """Remove previously inserted items (paper §3.4.1, Eq. 12).

    Welford stats are *not* un-merged (not possible in one pass); the exact μ
    (``mean_mu``) is unaffected since it is a pure function of counts.

    Quantized planes delete through the saturating scatter with weight
    −1: a promoted bucket whose logical count drops back to the cap is
    un-promoted (its escalation slot is freed).  Counts below the narrow
    dtype's min clamp (they cannot arise from matched insert/delete
    streams, which never go below 0).
    """
    if state.esc is not None:
        offs = _flat_offsets(buckets, cfg.num_tables, cfg.num_buckets)
        new_counts, new_esc, _ = qz.quantized_scatter(
            state.counts, state.esc, offs,
            jnp.full((buckets.shape[0],), -1, jnp.int32))
        return state._replace(
            counts=new_counts, esc=new_esc,
            n=state.n - jnp.asarray(buckets.shape[0], jnp.float32))
    rows = jnp.broadcast_to(
        jnp.arange(cfg.num_tables, dtype=jnp.int32)[None, :], buckets.shape)
    new_counts = state.counts.at[rows, buckets].add(-1)
    return state._replace(counts=new_counts,
                          n=state.n - jnp.asarray(buckets.shape[0], jnp.float32))


def merge(a: AceState, b: AceState) -> AceState:
    """Merge two sketches over disjoint data (counts add — CRDT style).

    Exact for counts/n; Welford streams merge by Chan's parallel rule.

    Quantized sketches merge exactly: both sides densify to int32
    logical planes, add, and requantize (narrow + fresh escalation
    table).  Excess that no longer fits the escalation capacity is
    accumulated into ``lost`` (plus both inputs' prior losses).
    Quantile histograms merge by exact addition (CRDT, like counts),
    and so do attribution planes (the signed count-sketch is linear).
    """
    delta = b.welford_mean - a.welford_mean
    tot = a.n + b.n
    safe = jnp.maximum(tot, 1.0)
    if (a.esc is None) != (b.esc is None):
        raise ValueError("cannot merge a quantized sketch with an "
                         "unquantized one")
    if a.esc is not None:
        if (a.esc.capacity != b.esc.capacity
                or a.counts.dtype != b.counts.dtype):
            raise ValueError("quantized merge requires matching "
                             "count_dtype and esc_capacity")
        dense = qz.densify(a.counts, a.esc) + qz.densify(b.counts, b.esc)
        counts, esc = qz.requantize(dense, a.esc.capacity,
                                    a.counts.dtype)
        esc = esc._replace(lost=esc.lost + a.esc.lost + b.esc.lost)
    else:
        counts, esc = a.counts + b.counts, None
    if (a.qhist is None) != (b.qhist is None):
        raise ValueError("cannot merge a quantile-tracking sketch with a "
                         "non-tracking one")
    qhist = None if a.qhist is None else a.qhist + b.qhist
    if (a.attr is None) != (b.attr is None):
        raise ValueError("cannot merge an attribution-tracking sketch "
                         "with a non-tracking one")
    attr = None if a.attr is None else a.attr + b.attr
    return AceState(
        counts=counts,
        n=tot,
        welford_mean=a.welford_mean + delta * b.n / safe,
        welford_m2=a.welford_m2 + b.welford_m2 + delta**2 * a.n * b.n / safe,
        esc=esc,
        qhist=qhist,
        attr=attr,
    )


# ---------------------------------------------------------------------------
# Statistics of the sketch.
# ---------------------------------------------------------------------------

def mean_mu(state: AceState,
            table_mask: jax.Array | None = None) -> jax.Array:
    """Exact dataset mean score  μ = Σ‖A_j‖² / (n·L)  (≡ paper Eq. 11 stream).

    Proof sketch: Algorithm 1 maintains n·μ = Σ_i Ŝ(x_i, D); item i in bucket
    b of array j contributes A_j[b]/L once per array, and bucket b holds
    A_j[b] items, so Σ_i A_j[H_j(x_i)] = Σ_b A_j[b]².

    ``table_mask`` (L,) restricts the closed form to healthy tables:
    μ = Σ_{j healthy} ‖A_j‖² / (n · num_healthy).  Each healthy table's
    counts still sum to n (conservation is per table), so per-table the
    formula is unchanged — only the mean over tables shrinks.  The
    masked path sweeps a densified plane for quantized sketches
    (degraded mode only — never the healthy hot path).
    """
    L = state.counts.shape[0]
    if table_mask is None:
        denom = jnp.maximum(state.n, 1.0) * L
        if state.esc is not None:
            return qz.sq_sum(state.counts, state.esc) / denom
        c = state.counts.astype(jnp.float32)
        return jnp.sum(c * c) / denom
    maskf = table_mask.astype(jnp.float32)
    nh = jnp.maximum(jnp.sum(maskf), 1.0)
    dense = (qz.densify(state.counts, state.esc)
             if state.esc is not None else state.counts)
    c = dense.astype(jnp.float32)
    per_table = jnp.sum(c * c, axis=1)                           # (L,)
    return jnp.sum(per_table * maskf) / (jnp.maximum(state.n, 1.0) * nh)


def mu_sequential_increment(state: AceState, buckets_one: jax.Array,
                            cfg: AceConfig):
    """One step of the paper's literal Eq. 11 (sequential, for testing).

    Returns (new_state, new_mu) for a SINGLE item with bucket ids (L,).
    """
    L = cfg.num_tables
    rows = jnp.arange(L, dtype=jnp.int32)
    old_mu = mean_mu(state)
    n = state.n
    new_counts = state.counts.at[rows, buckets_one].add(1)
    incr = jnp.sum(
        (2.0 * new_counts[rows, buckets_one].astype(jnp.float32) - 1.0) / L)
    new_mu = (n * old_mu + incr) / (n + 1.0)
    new_state = state._replace(counts=new_counts, n=n + 1.0)
    return new_state, new_mu


def mean_rate(state: AceState,
              table_mask: jax.Array | None = None) -> jax.Array:
    """Exact mean collision RATE  μ/n  (scale-free across stream growth)."""
    return mean_mu(state, table_mask=table_mask) / jnp.maximum(state.n, 1.0)


def sigma_welford(state: AceState) -> jax.Array:
    """Streaming σ of collision RATES (score/n) from insert-time stream."""
    return jnp.sqrt(state.welford_m2 / jnp.maximum(state.n - 1.0, 1.0))


def admit_threshold(state: AceState, alpha: float,
                    warmup_items: float,
                    table_mask: jax.Array | None = None,
                    threshold_mode: str = "mu_sigma",
                    q: float = 0.01) -> jax.Array:
    """Score-space admission threshold: admit iff  score >= threshold.

    Two modes, dispatched at trace time (``threshold_mode`` is a Python
    string, so each mode is its own cached executable and the default
    μ−ασ program is byte-identical to before the mode existed):

    * ``"mu_sigma"`` — the μ−ασ rule in rate space (rate = score/n);
      multiplying both sides by max(n, 1) > 0 moves it to score space so
      the decision is a single compare against ONE device scalar — which
      is what the fused admit kernel consumes.
    * ``"quantile"`` — flag the worst q%: the q-quantile of the
      collision-rate histogram ``state.qhist`` (repro.quantile), moved
      to score space by the same max(n, 1) multiply — still ONE device
      scalar, so the kernels never change.  Calibrated for heavy-tailed
      traffic where a single α miscalibrates FPR.

    During warmup (n < warmup_items) the threshold is −inf: everything
    is admitted.  Pure device scalar ops — no host sync.

    ``table_mask`` keeps the μ−ασ threshold consistent with masked
    scores: masked μ over the same healthy subset the scores average
    over (the Welford σ stream is a scalar over batch means —
    table-independent, so it needs no masking; the quantile histogram
    aggregates over the table MEAN, also table-independent).
    """
    if threshold_mode == "quantile":
        from repro.quantile import sketch as qsk
        if state.qhist is None:
            raise ValueError("threshold_mode='quantile' needs a sketch "
                             "with an attached qhist leaf "
                             "(see repro.quantile.sketch.init_hist)")
        with obs.scope("threshold"):
            return qsk.quantile_threshold(state.qhist, state.n, q,
                                          warmup_items)
    if threshold_mode != "mu_sigma":
        raise ValueError(f"unknown threshold_mode {threshold_mode!r}")
    with obs.scope("threshold"):
        t = (mean_rate(state, table_mask=table_mask)
             - alpha * sigma_welford(state)) * jnp.maximum(state.n, 1.0)
        return jnp.where(state.n >= warmup_items, t, -jnp.inf)


def sigma_cubic_proxy(state: AceState) -> jax.Array:
    """Per-array second-moment proxy:  E_i[A²] per array = Σ_b A³ / n.

    Var_proxy = mean_j Σ_b A_j[b]³/n − μ²  upper-bounds the true score
    variance when arrays are independent (Jensen); exposed as a diagnostics
    alternative to the Welford stream.
    """
    c = state.counts.astype(jnp.float32)
    n = jnp.maximum(state.n, 1.0)
    second = jnp.mean(jnp.sum(c**3, axis=1)) / n
    var = jnp.maximum(second - mean_mu(state) ** 2, 0.0)
    return jnp.sqrt(var)


# ---------------------------------------------------------------------------
# Vector-level convenience API (hashing included).
# ---------------------------------------------------------------------------

@on_auto_axes
@partial(jax.jit, static_argnames=("cfg",))
def insert(state: AceState, w: jax.Array, x: jax.Array,
           cfg: AceConfig) -> AceState:
    """Insert raw vectors x (B, d)."""
    return insert_buckets(state, hash_buckets(x, w, cfg.srp), cfg)


@on_auto_axes
@partial(jax.jit, static_argnames=("cfg",))
def delete(state: AceState, w: jax.Array, x: jax.Array,
           cfg: AceConfig) -> AceState:
    return delete_buckets(state, hash_buckets(x, w, cfg.srp), cfg)


@on_auto_axes
@partial(jax.jit, static_argnames=("cfg",))
def score(state: AceState, w: jax.Array, q: jax.Array,
          cfg: AceConfig) -> jax.Array:
    """Ŝ(q, D) for raw queries q (B, d) -> (B,)."""
    return lookup(state, hash_buckets(q, w, cfg.srp))


@on_auto_axes
@partial(jax.jit, static_argnames=("cfg", "alpha"))
def is_anomaly(state: AceState, w: jax.Array, q: jax.Array,
               cfg: AceConfig, alpha: float = 1.0) -> jax.Array:
    """Decision rule of Algorithm 1 line 22 with the paper's experimental
    μ − α·σ threshold, applied in RATE space (score/n vs μ/n − α·σ_rate) so
    the streaming σ is stationary."""
    r = score(state, w, q, cfg) / jnp.maximum(state.n, 1.0)
    thresh = mean_rate(state) - alpha * sigma_welford(state)
    return r < thresh
