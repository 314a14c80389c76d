"""Multi-tenant ACE fleets: T tenants' sketches stacked on a leading axis.

The paper's headline is that a full detector is ~4 MB of count arrays —
which means ONE accelerator can host thousands of independent detectors.
But every stateful subsystem in this repo (filter, Guardrail,
StreamRunner, window ring, dist layouts) assumes exactly one ``AceState``,
so serving per-user / per-stream detectors meant a Python loop of separate
device programs: T dispatches, T host syncs, T executables per arrival
wave.  EXPOSE (Schneider et al., 2016) makes the same one-model-per-stream
argument at scale; ACE's count algebra makes the batched fix trivial —
counts and moments have NO cross-tenant coupling, so T sketches stack
along a leading tenant axis and a mixed-tenant batch is served by one
fused program:

    counts        (T, L, 2^K)   per-tenant count arrays
    n             (T,)          per-tenant item counts
    welford_mean  (T,)          per-tenant streaming rate means
    welford_m2    (T,)          per-tenant streaming rate M2s

Routing is ONE gather index computation: the fleet addressed as a
(T·L, 2^K) matrix makes item i's table j live at row
``tenant_ids[i]·L + j`` — the tenant·L row-offset extension of the
sketch's own flat row-offset gather (one vectorised gather, no
per-tenant loop, no padding).  Inserts are ONE
scatter-add at the same rows; thresholds are per-tenant μ−ασ computed as
(T,) vectors of the exact same elementwise ops as ``sketch``'s scalars,
then routed by ``thresholds[tenant_ids]``.

Differential contracts (tests/test_fleet.py):

* **fleet-of-1**: with T=1 and all-zero tenant_ids every op here is
  BITWISE the corresponding ``repro.core.sketch`` op (the row offset is
  identically ``j``; the (1,)-vector stats are the same float ops as the
  scalars).
* **mixed batch ≡ per-tenant sequential**: routing a mixed batch through
  ``insert_masked`` equals, bitwise on counts/n/μ AND the Welford
  moments, giving each tenant the full fixed-shape batch with its own
  sub-mask via ``sketch.insert_buckets_masked`` — because the per-tenant
  moment sums here are rows of a (T, B) masked reduction whose masked-out
  entries are exact float zeros, each row reduces the identical value
  sequence the single-tenant path reduces.
* **tenant isolation**: items routed to tenant a touch only rows
  ``a·L..a·L+L`` of the flat fleet and slot a of every stat vector —
  every other tenant's state is bitwise untouched (property-tested).

Like the base sketch, everything is pure, fixed-shape, and
jit/scan/donation-safe; the tenant axis never forces a host sync.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core import sketch as sk
from repro.core.sketch import AceConfig, AceState


_INT32_MAX = 2**31 - 1


def check_flat_addressable(n_rows: int, nbuckets: int, what: str) -> None:
    """Fail loudly where the flat-offset gather/scatter would overflow.

    Every fleet hot path addresses the stacked tables as one flattened
    space of ``n_rows × 2^K`` int32 element offsets; past 2^31 the
    offsets wrap silently and jnp.take/scatter clamp the wrapped
    indices — every high-tenant item would score against and insert
    into the WRONG rows with no error.  At the paper's K=15, L=50 that
    caps one fleet at T ≈ 1310 tenants; beyond it, split into multiple
    ``FleetState``s (the offsets are computed on the GLOBAL logical
    array, so device sharding does not lift the cap).
    """
    if n_rows * nbuckets > _INT32_MAX:
        raise ValueError(
            f"{what}: flat table space {n_rows} rows × {nbuckets} "
            f"buckets = {n_rows * nbuckets} exceeds the int32 offset "
            f"range ({_INT32_MAX}); the routed gather/scatter offsets "
            "would silently wrap.  Split the fleet into multiple "
            "FleetStates (device sharding does not lift this cap — the "
            "offsets address the global logical array).")


class FleetState(NamedTuple):
    """T stacked tenant sketches (a pytree — jit/scan/psum/donation safe)."""

    counts: jax.Array        # (T, L, 2^K) counter dtype
    n: jax.Array             # (T,) float32
    welford_mean: jax.Array  # (T,) float32
    welford_m2: jax.Array    # (T,) float32
    qhist: Optional[jax.Array] = None  # (T, quantile.NUM_BINS) float32
    #                          per-tenant rate histograms for
    #                          threshold_mode="quantile"; None (default)
    #                          keeps every existing pytree contract
    attr: Optional[jax.Array] = None  # (T, 2, NL, R, C) float32 per-tenant
    #                          signed count-sketch attribution planes
    #                          (repro.attribution); None (default) keeps
    #                          every existing pytree contract

    @property
    def num_tenants(self) -> int:
        return self.counts.shape[0]


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Static fleet configuration (hashable; safe as a jit static arg).

    Every tenant shares one ``AceConfig`` — same K/L/seed, hence the SAME
    hash functions.  Sharing the hash bank is what makes the fleet one
    program (hash once for the whole mixed batch); tenants are isolated
    by their counts, not their projections, exactly like L tables within
    one sketch are isolated by rows of one matrix.
    """

    ace: AceConfig
    num_tenants: int

    def __post_init__(self):
        if self.num_tenants < 1:
            raise ValueError(
                f"num_tenants must be >= 1, got {self.num_tenants}")
        check_flat_addressable(self.num_tenants * self.ace.num_tables,
                               self.ace.num_buckets, "FleetConfig")
        if self.ace.esc_capacity > 0:
            raise NotImplementedError(
                "overflow promotion (esc_capacity > 0) is wired for the "
                "flat sketch only; fleet tables take narrow count dtypes "
                "without an escalation table (exact below saturation). "
                "See docs/ARCHITECTURE.md §7.")

    def memory_bytes(self) -> int:
        """The fleet HBM bill: T × the paper's per-detector table."""
        return self.num_tenants * self.ace.memory_bytes()


def init(cfg: FleetConfig, quantile: bool = False) -> FleetState:
    ace = cfg.ace
    if quantile:
        from repro.quantile import sketch as qsk
        qhist = qsk.init_hist(cfg.num_tenants)
    else:
        qhist = None
    acfg = ace.attr
    attr = (jnp.zeros((cfg.num_tenants,) + acfg.plane_shape(), jnp.float32)
            if acfg is not None else None)
    return FleetState(
        counts=jnp.zeros(
            (cfg.num_tenants, ace.num_tables, ace.num_buckets),
            dtype=jnp.dtype(ace.counter_dtype)),
        n=jnp.zeros((cfg.num_tenants,), jnp.float32),
        welford_mean=jnp.zeros((cfg.num_tenants,), jnp.float32),
        welford_m2=jnp.zeros((cfg.num_tenants,), jnp.float32),
        qhist=qhist,
        attr=attr,
    )


def tenant_view(state: FleetState, t) -> AceState:
    """Tenant t's sketch as a plain ``AceState`` (static or traced t)."""
    return AceState(counts=state.counts[t], n=state.n[t],
                    welford_mean=state.welford_mean[t],
                    welford_m2=state.welford_m2[t],
                    qhist=None if state.qhist is None else state.qhist[t],
                    attr=None if state.attr is None else state.attr[t])


def set_tenant(state: FleetState, t: int, ace: AceState) -> FleetState:
    """Write one tenant's sketch back into the fleet (static index)."""
    qhist = state.qhist
    if qhist is not None and ace.qhist is not None:
        qhist = qhist.at[t].set(ace.qhist)
    attr = state.attr
    if attr is not None and ace.attr is not None:
        attr = attr.at[t].set(ace.attr)
    return FleetState(
        counts=state.counts.at[t].set(ace.counts),
        n=state.n.at[t].set(ace.n),
        welford_mean=state.welford_mean.at[t].set(ace.welford_mean),
        welford_m2=state.welford_m2.at[t].set(ace.welford_m2),
        qhist=qhist,
        attr=attr,
    )


def promote_fleet(state: FleetState, dtype=jnp.int32) -> FleetState:
    """Widen a fleet's count planes to ``dtype`` (default int32).

    The cross-host promotion point (repro.cluster): narrow int8/int16
    planes are exact below saturation per host, but ADDING two hosts'
    planes in the narrow dtype would wrap silently — gossip merges
    therefore promote first and add in the wide dtype.  Stats are
    untouched (they are float and dtype-independent).
    """
    return state._replace(counts=state.counts.astype(jnp.dtype(dtype)))


def merge_fleet(a: FleetState, b: FleetState) -> FleetState:
    """Merge two fleets over disjoint data — ``sketch.merge`` vectorised
    over the tenant axis (counts add CRDT-style, Welford streams by
    Chan's parallel rule applied elementwise to the (T,) stat vectors —
    per tenant these are literally the same float ops as the scalar
    merge, so merging tenant-by-tenant via ``sketch.merge`` is bitwise
    identical; tests/test_cluster.py asserts it).

    Counts always add in int32: narrow (int8/int16) planes would wrap
    at their dtype cap, so ``merge_fleet(a8, b8)`` ≡
    ``merge_fleet(promote_fleet(a8), promote_fleet(b8))`` by
    construction — the merge-then-promote ≡ promote-then-merge
    differential oracle.  Requantize the result back down only if every
    bucket provably fits (the caller knows its stream); the merged fleet
    defaults to staying wide.
    """
    if a.counts.shape != b.counts.shape:
        raise ValueError(f"fleet shape mismatch: {a.counts.shape} vs "
                         f"{b.counts.shape}")
    counts = (a.counts.astype(jnp.int32) + b.counts.astype(jnp.int32))
    delta = b.welford_mean - a.welford_mean                    # (T,)
    tot = a.n + b.n
    safe = jnp.maximum(tot, 1.0)
    if (a.qhist is None) != (b.qhist is None):
        raise ValueError("cannot merge a quantile-tracking fleet with a "
                         "non-tracking one")
    if (a.attr is None) != (b.attr is None):
        raise ValueError("cannot merge an attribution-tracking fleet with "
                         "a non-tracking one")
    return FleetState(
        counts=counts,
        n=tot,
        welford_mean=a.welford_mean + delta * b.n / safe,
        welford_m2=(a.welford_m2 + b.welford_m2
                    + delta**2 * a.n * b.n / safe),
        qhist=None if a.qhist is None else a.qhist + b.qhist,
        # count-sketch planes are linear — disjoint-data merge is a sum
        attr=None if a.attr is None else a.attr + b.attr,
    )


def from_states(states: Sequence[AceState]) -> FleetState:
    """Stack existing single-tenant sketches into a fleet."""
    qhists = [s.qhist for s in states]
    attrs = [s.attr for s in states]
    return FleetState(
        counts=jnp.stack([s.counts for s in states]),
        n=jnp.stack([s.n for s in states]),
        welford_mean=jnp.stack([s.welford_mean for s in states]),
        welford_m2=jnp.stack([s.welford_m2 for s in states]),
        qhist=(jnp.stack(qhists)
               if all(h is not None for h in qhists) else None),
        attr=(jnp.stack(attrs)
              if all(p is not None for p in attrs) else None),
    )


# ---------------------------------------------------------------------------
# Batched tenant-routed primitives (input: precomputed bucket ids (B, L)
# + tenant ids (B,)).  These are the fleet analogues of the bucket-level
# sketch primitives, and what the ace_fleet_score kernel accelerates.
# ---------------------------------------------------------------------------

def fleet_table_gather(counts: jax.Array, tenant_ids: jax.Array,
                       buckets: jax.Array) -> jax.Array:
    """Gather counts[tid_i, j, buckets[i, j]] as ONE flattened take.

    The tenant·L row-offset extension of the flat sketch's row-offset
    gather: the (T, L, 2^K) fleet ravels row-major so
    item i's table j is row ``tenant_ids[i]·L + j`` of a (T·L, 2^K)
    matrix — a single vectorised gather routes the whole mixed batch,
    no per-tenant loop, no sorting, no padding.  (B, L) float32 out;
    the gathered integers are exact, so downstream sums match the
    single-tenant ``batch_scores`` bitwise.
    """
    T, L, nbuckets = counts.shape
    check_flat_addressable(T * L, nbuckets, "fleet_table_gather")
    flat = counts.reshape(T * L * nbuckets)
    rows = tenant_ids[:, None] * L + jnp.arange(L, dtype=jnp.int32)[None, :]
    offs = buckets + rows * nbuckets
    return jnp.take(flat, offs, axis=0).astype(jnp.float32)


def fleet_scores(state: FleetState, tenant_ids: jax.Array,
                 buckets: jax.Array,
                 table_mask: jax.Array | None = None) -> jax.Array:
    """Each item's Ŝ(q, D_tenant) vs its OWN tenant's sketch: (B,) f32.

    Same row-sum + ONE reciprocal 1/L multiply sequence as
    ``sketch.batch_scores`` (the bitwise-parity convention every score
    path in the repo shares).

    ``table_mask`` (T, L) 0/1 restricts each item's mean to ITS OWN
    tenant's healthy tables: item i averages over
    Σ_j mask[tid_i, j] tables — per-tenant degradation, routed by the
    same tenant_ids gather as everything else.  Python-level ``None``
    branch keeps the healthy program untouched.
    """
    L = state.counts.shape[1]
    with obs.scope("score"):
        gathered = fleet_table_gather(state.counts, tenant_ids, buckets)
        if table_mask is None:
            return jnp.sum(gathered, axis=-1) * jnp.float32(1.0 / L)
        maskf = table_mask.astype(jnp.float32)[tenant_ids]       # (B, L)
        nh = jnp.maximum(jnp.sum(maskf, axis=-1), 1.0)           # (B,)
        return jnp.sum(gathered * maskf, axis=-1) * (1.0 / nh)


def _tenant_onehot(tenant_ids: jax.Array, num_tenants: int) -> jax.Array:
    """(T, B) float32 routing matrix; row t selects tenant t's items."""
    return (jnp.arange(num_tenants, dtype=jnp.int32)[:, None]
            == tenant_ids[None, :]).astype(jnp.float32)


def fleet_masked_welford(state: FleetState, tenant_ids: jax.Array,
                         scores: jax.Array, maskf: jax.Array,
                         min_n: float):
    """Per-tenant masked Welford fold of a mixed batch — segment-reduced.

    The fleet analogue of ``sketch.masked_batch_welford``: every
    per-tenant partial sum is a row of a (T, B) masked reduction.  A
    masked-out entry contributes an exact float 0.0 (finite × 0), so row
    t reduces the identical value sequence that
    ``masked_batch_welford(state_t, scores, maskf·[tid==t])`` reduces —
    per-tenant moments are BITWISE the sequential single-tenant fold's
    (the contract tests/test_fleet.py asserts), and the fold itself is
    ``sketch.welford_fold`` applied elementwise to (T,) vectors, i.e.
    literally the same jnp ops as the scalars.  Tenants with no masked
    items keep their stream untouched; the ``min_n`` cold-start gate
    applies per tenant.  Returns (n, welford_mean, welford_m2), all (T,).
    """
    onehot = _tenant_onehot(tenant_ids, state.num_tenants)      # (T, B)
    b = jnp.sum(onehot * maskf[None, :], axis=1)                # (T,)
    n = state.n
    tot = n + b                                                 # (T,)
    # each item's rate is normalised by its OWN tenant's post-batch n —
    # the same scalar the sequential fold divides by
    rates = scores / jnp.maximum(tot, 1.0)[tenant_ids]          # (B,)
    rm = rates * maskf                                          # (B,)
    mean_b = jnp.sum(onehot * rm[None, :], axis=1) \
        / jnp.maximum(b, 1.0)                                   # (T,)
    dev = (rates - mean_b[tenant_ids]) ** 2 * maskf             # (B,)
    m2_b = jnp.sum(onehot * dev[None, :], axis=1)               # (T,)
    new_mean, new_m2 = sk.welford_fold(
        state.welford_mean, state.welford_m2, n, b, tot, mean_b, m2_b,
        min_n)
    has = b > 0
    return (tot,
            jnp.where(has, new_mean, state.welford_mean),
            jnp.where(has, new_m2, state.welford_m2))


def insert_masked(state: FleetState, tenant_ids: jax.Array,
                  buckets: jax.Array, mask: jax.Array,
                  cfg: AceConfig) -> FleetState:
    """Masked insert of a mixed-tenant batch: ONE scatter-add.

    The fleet analogue of ``sketch.insert_buckets_masked``, fixed-shape
    and order-invariant: the 0/1-weighted scatter at rows
    ``tenant_ids·L + j`` of the (T·L, 2^K) flat fleet lands every item
    in its own tenant's tables (identical integer adds as T sequential
    single-tenant inserts), post-insert scores come from the same rows,
    and the Welford streams fold per tenant via
    ``fleet_masked_welford``.  Items of absent tenants simply contribute
    no rows — no per-tenant branching anywhere.
    """
    T, L, nbuckets = state.counts.shape
    with obs.scope("insert"):
        rows = tenant_ids[:, None] * L \
            + jnp.arange(L, dtype=jnp.int32)[None, :]
        w_ctr = jnp.broadcast_to(
            mask.astype(state.counts.dtype)[:, None], buckets.shape)
        new_counts = state.counts.reshape(T * L, nbuckets) \
            .at[rows, buckets].add(w_ctr).reshape(state.counts.shape)

    # Post-insert scores of ALL items vs their own tenant's updated
    # tables (Algorithm 1 line 12's x-vs-D∪{x} convention, same as every
    # other insert path).  The outer scope names the phase: the score
    # helper's own scope nests inside it.
    with obs.scope("rescore"):
        new_state_counts = state._replace(counts=new_counts)
        scores = fleet_scores(new_state_counts, tenant_ids, buckets)
        tot, new_mean, new_m2 = fleet_masked_welford(
            state, tenant_ids, scores, mask.astype(jnp.float32),
            cfg.welford_min_n)
    return FleetState(counts=new_counts, n=tot,
                      welford_mean=new_mean, welford_m2=new_m2,
                      qhist=state.qhist, attr=state.attr)


# ---------------------------------------------------------------------------
# Per-tenant statistics and thresholds — (T,) vectors of the exact same
# elementwise ops as the repro.core.sketch scalars (bitwise per tenant).
# ---------------------------------------------------------------------------

def mean_mu_fleet(state: FleetState,
                  table_mask: jax.Array | None = None) -> jax.Array:
    """(T,) exact per-tenant μ = Σ‖A_j‖² / (n·L) (Eq. 11 closed form).

    ``table_mask`` (T, L) restricts each tenant's table mean to its
    healthy tables (μ_t = Σ_{j healthy} ‖A_tj‖² / (n_t · nh_t))."""
    L = state.counts.shape[1]
    c = state.counts.astype(jnp.float32)
    if table_mask is None:
        return jnp.sum(c * c, axis=(1, 2)) \
            / (jnp.maximum(state.n, 1.0) * L)
    maskf = table_mask.astype(jnp.float32)                       # (T, L)
    nh = jnp.maximum(jnp.sum(maskf, axis=1), 1.0)                # (T,)
    per_table = jnp.sum(c * c, axis=2)                           # (T, L)
    return jnp.sum(per_table * maskf, axis=1) \
        / (jnp.maximum(state.n, 1.0) * nh)


def mean_rate_fleet(state: FleetState,
                    table_mask: jax.Array | None = None) -> jax.Array:
    """(T,) exact per-tenant mean collision rate μ/n."""
    return mean_mu_fleet(state, table_mask=table_mask) \
        / jnp.maximum(state.n, 1.0)


def sigma_welford_fleet(state: FleetState) -> jax.Array:
    """(T,) per-tenant streaming σ of collision rates."""
    return jnp.sqrt(state.welford_m2 / jnp.maximum(state.n - 1.0, 1.0))


def admit_thresholds(state: FleetState, alpha: float,
                     warmup_items: float,
                     table_mask: jax.Array | None = None,
                     threshold_mode: str = "mu_sigma",
                     q: float = 0.01) -> jax.Array:
    """(T,) per-tenant score-space admission thresholds.

    ``sketch.admit_threshold`` vectorised over the tenant axis — same
    formula sequence per mode (μ−ασ: rate − ασ moved to score space by
    max(n, 1); quantile: each tenant's OWN q-quantile from its row of
    ``state.qhist`` — THE heavy-tailed fleet fix, since one α
    miscalibrates FPR across tenants with different score-distribution
    shapes while the per-tenant quantile holds FPR ≈ q for every shape
    — with −inf during each tenant's OWN warmup), so each component is
    bitwise the single-tenant threshold.  Route to items with
    ``admit_thresholds(...)[tenant_ids]``.  ``table_mask`` (T, L) keeps
    each tenant's μ−ασ threshold consistent with its masked scores (the
    σ stream is per tenant but table-independent — no masking needed).
    """
    if threshold_mode == "quantile":
        from repro.quantile import sketch as qsk
        if state.qhist is None:
            raise ValueError("threshold_mode='quantile' needs a fleet "
                             "initialised with quantile=True")
        with obs.scope("threshold"):
            rates = jax.vmap(lambda h: qsk.hist_quantile(h, q))(state.qhist)
            t = rates * jnp.maximum(state.n, 1.0)
            return jnp.where(state.n >= warmup_items, t, -jnp.inf)
    if threshold_mode != "mu_sigma":
        raise ValueError(f"unknown threshold_mode {threshold_mode!r}")
    with obs.scope("threshold"):
        t = (mean_rate_fleet(state, table_mask=table_mask)
             - alpha * sigma_welford_fleet(state)) \
            * jnp.maximum(state.n, 1.0)
        return jnp.where(state.n >= warmup_items, t, -jnp.inf)


def per_tenant_counts(tenant_ids: jax.Array, values: jax.Array,
                      num_tenants: int) -> jax.Array:
    """(T,) masked per-tenant sums of a (B,) value vector (0/1 masks,
    margins, ...) — the summary-building helper the stream runner and
    benchmarks use; one (T, B) reduction, no host loop."""
    onehot = _tenant_onehot(tenant_ids, num_tenants)
    return jnp.sum(onehot * values.astype(jnp.float32)[None, :], axis=1)
