"""Scan-fused streaming ingest: T batches per device program, one host
transfer per chunk.

The paper's headline claim is *stream-rate* detection — each item costs
one hash plus O(L) cache lookups.  The per-batch ingest loop this module
replaces broke that on the host side: every Python-level batch paid a
device-program dispatch, and every batch synced at least twice (the
kept-fraction metric plus the next dispatch's argument feed), so at high
stream rates the filter was bounded by the host, not the sketch.

``StreamRunner`` stacks T batches into one (T, B, d) chunk and consumes
it with ONE donated-state ``lax.scan`` program whose body is exactly
``AceDataFilter.step`` — hash once → score from the same bucket ids →
on-device μ−ασ threshold → ``sk.insert_buckets_masked`` — and returns
only a small per-chunk summary (kept fraction, per-step anomaly counts,
on-device top-k most-anomalous item coordinates).  Host traffic per T
batches: one stacked H2D feed + one summary D2H pull, versus ≥ 2·T
transfers for the legacy loop; the sketch state never leaves the device
(the carry is donated, so the counts buffer is updated in place across
chunks).  ``benchmarks/stream_throughput.py`` counts both.

Sharded ingest: pass a mesh + ``sketch_layout`` ("replicated" or
"table_sharded") and the sketch state is placed via
``repro.dist.sketch_parallel`` and sharding-constrained inside the scan
body — the SAME jitted program in every layout; GSPMD inserts the
collectives (jit/SPMD mode, exactly like the guardrail and train_step).

The hash family follows the filter's ``hash_mode`` knob (dense matmul,
SRHT fast transform, or auto break-even) because the scan body hashes
through ``repro.core.srp.hash_buckets``.

Multi-tenant fleets: with a ``repro.fleet.FleetDataFilter`` the chunk
additionally carries a (T_chunk, B) tenant-id plane and every scan step
routes its mixed-tenant batch through the fleet's flat-offset
gather/scatter — same ONE donated program, same 1 H2D + 1 D2H per
chunk, with the summary upgraded to ``FleetChunkSummary`` (per-tenant
kept/item counts and per-tenant n ride in the same single pull).
Sharded fleets place via ``repro.dist.sketch_parallel
.fleet_shardings_for_layout`` (tenant, table, or composed 2-D
sharding).

Sliding windows: with a ``repro.window.WindowedAceFilter`` (or any
filter whose state is a ``WindowedAceState`` ring), ``rotate_every=R``
advances the epoch ring every R scan steps INSIDE the donated device
program — as straight-line code between R-step scan segments when R
divides the chunk (no per-step branching; a per-step cond would copy
the multi-MB carry), or as one tick-gated clock per chunk boundary when
R spans chunks.  Windowing therefore adds ZERO extra host syncs: still
exactly 1 H2D + 1 D2H per chunk, still one executable
(``trace_count``), and rotations land at the same stream positions as
the per-batch drivers' eager ``maybe_rotate`` clock.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.core.axes import on_auto_axes
from repro.core.sketch import AceState
from repro.data.pipeline import AceDataFilter


class ChunkSummary(NamedTuple):
    """Everything the host learns about a chunk — ONE small transfer.

    kept_frac:   () float32 — fraction of the chunk's T·B items kept.
    anom_counts: (T,) int32 — anomalies flagged per step.
    topk_step:   (k,) int32 — step index of the k most-anomalous items.
    topk_item:   (k,) int32 — row index within that step's batch.
    topk_margin: (k,) float32 — score − threshold (most negative = most
                 anomalous; +inf while the sketch is in warmup).
    n:           () float32 — sketch item count after the chunk.
    quarantined: () int32 — non-finite feature rows sanitized at the
                 filter entry point (margin = −inf; counted among the
                 anomalies, never inserted).
    degraded:    () bool — True when the chunk was scored with a health
                 mask (some tables excluded — repro.resilience).
    falpha:      () float32 — normalized α-th frequency-moment index of
                 the post-chunk count planes (repro.quantile.moments,
                 α = 1.25): 1.0 for a uniform plane, grows with bucket
                 concentration, stationary in n — a Compressed-Counting
                 style drift statistic the host can watch chunk-over-
                 chunk without pulling the (L, 2^K) table.  Windowed
                 states report it over the γ-combined ring.  Quantized
                 planes with an escalation table report it over the
                 EXACT densified logical counts (raw saturated narrow
                 planes would understate concentration right when the
                 heavy buckets matter most).
    topk_valid:  (k,) bool — True where the topk row is a GENUINE
                 flagged anomaly (finite negative margin).  False rows
                 are report padding: +inf warmup sentinels, non-
                 anomalous fill when the chunk had fewer than k
                 anomalies, or a fully-quarantined chunk.  Hosts must
                 mask on this instead of consuming topk_* raw.
    hh_coord/hh_est/hh_valid: (topk,) heavy-hitter attribution — the
                 coordinates driving this chunk's anomalies, named by
                 the dyadic findHH drill-down over the signed sketch of
                 the chunk's drift vector (anomaly-mean − background-
                 mean energy per coordinate, repro.attribution), with
                 their signed estimated drift energies.  ``hh_valid``
                 masks beam padding.  None unless the filter enables
                 attribution (``attr_rows > 0``).
    """

    kept_frac: jax.Array
    anom_counts: jax.Array
    topk_step: jax.Array
    topk_item: jax.Array
    topk_margin: jax.Array
    n: jax.Array
    quarantined: jax.Array
    degraded: jax.Array
    falpha: jax.Array
    topk_valid: jax.Array = None
    hh_coord: jax.Array = None
    hh_est: jax.Array = None
    hh_valid: jax.Array = None


class FleetChunkSummary(NamedTuple):
    """The fleet upgrade of ``ChunkSummary`` — still ONE small transfer.

    Same global fields, plus per-tenant rows so the host can follow T
    detectors without T pulls:

    per_tenant_items: (T,) float32 — items routed to each tenant.
    per_tenant_kept:  (T,) float32 — of those, how many were kept.
    n:                (T,) float32 — each tenant's sketch n after the
                      chunk (replaces the scalar n of the flat summary).
    quarantined:      () int32 — sanitized non-finite rows (see
                      ``ChunkSummary``).
    degraded:         () bool — chunk scored under a health mask.
    misrouted:        () int32 — items routed to tenants outside the
                      replica's ownership mask (repro.cluster): scored
                      but never kept/inserted.  0 when no tenant_mask.
    falpha:           (T,) float32 — each tenant's frequency-moment
                      drift index (see ``ChunkSummary.falpha``).
    topk_valid:       (k,) bool — genuine-anomaly mask over the topk_*
                      rows (see ``ChunkSummary.topk_valid``).
    hh_coord/hh_est/hh_valid: (topk,) chunk-global heavy-hitter
                      coordinates (see ``ChunkSummary``); None unless
                      the filter enables attribution.
    hh_tenant/hh_tenant_est: (min(topk, T),) the tenants whose anomaly
                      traffic drifted hardest this chunk (exact dense
                      per-tenant drift L2, descending) and those
                      magnitudes; None unless attribution is enabled.
    """

    kept_frac: jax.Array
    anom_counts: jax.Array
    topk_step: jax.Array
    topk_item: jax.Array
    topk_margin: jax.Array
    per_tenant_items: jax.Array
    per_tenant_kept: jax.Array
    n: jax.Array
    quarantined: jax.Array
    degraded: jax.Array
    misrouted: jax.Array
    falpha: jax.Array
    topk_valid: jax.Array = None
    hh_coord: jax.Array = None
    hh_est: jax.Array = None
    hh_valid: jax.Array = None
    hh_tenant: jax.Array = None
    hh_tenant_est: jax.Array = None


class StreamRunner:
    """Chunked scan ingest around an ``AceDataFilter``.

    ``consume`` is ONE fixed-shape jitted program (state donated) per
    (T, B, d) chunk shape; ``trace_count`` asserts it stays one
    executable across chunks.  ``return_masks=True`` additionally returns
    the (T, B) keep mask — still a single transfer when the caller pulls
    it together with the summary — which is how the training loop's
    chunked prefilter applies the verdicts to its loss masks.
    """

    def __init__(self, filt: AceDataFilter, chunk_T: int, topk: int = 8,
                 return_masks: bool = False, *, mesh=None,
                 sketch_layout: str = "replicated",
                 table_axis: str = "model",
                 rotate_every: int | None = None):
        self.filt = filt
        self.chunk_T = int(chunk_T)
        self.topk = int(topk)
        self.return_masks = return_masks
        self.mesh = mesh
        self.sketch_layout = sketch_layout
        # Multi-tenant fleet filter: the scan body routes a per-step
        # (B,) tenant-id vector and the summary grows per-tenant rows.
        self.is_fleet = hasattr(filt, "num_tenants")
        # Epoch-ring rotation clock: None inherits the filter's own
        # ``rotate_every`` (0 for the flat AceDataFilter — no rotation).
        if rotate_every is None:
            rotate_every = int(getattr(filt, "rotate_every", 0))
        self.rotate_every = int(rotate_every)
        if self.is_fleet and self.rotate_every:
            raise NotImplementedError(
                "windowed fleets are host-driven for now (per-tenant "
                "clocks via repro.fleet.window.maybe_rotate_fleet); the "
                "scan runner consumes FLAT fleets only")
        if self.rotate_every and not hasattr(filt, "num_epochs"):
            raise ValueError("rotate_every needs a windowed filter "
                             "(repro.window.WindowedAceFilter); the flat "
                             "AceDataFilter has no epoch ring to rotate")
        if self.rotate_every and self.chunk_T % self.rotate_every != 0 \
                and self.rotate_every % self.chunk_T != 0:
            raise ValueError(
                f"rotate_every={self.rotate_every} must divide or be a "
                f"multiple of chunk_T={self.chunk_T} so epoch boundaries "
                "land deterministically inside or between chunks")
        self.trace_count = 0          # incremented at TRACE time only
        # sequence number of the next chunk, the ``chunk`` argument of its
        # ``ace:stream/consume`` span
        self.chunks_consumed = 0
        # Heavy-hitter attribution: non-None when the filter carries
        # attr planes (attr_rows > 0) — the consume program then also
        # observes per-chunk energy sketches and drills down for the
        # summary's hh_* fields (same single program, same 1 D2H).
        self.attr_cfg = (filt.ace_cfg.attr
                         if hasattr(filt, "ace_cfg") else None)
        self._shardings = None
        if mesh is not None:
            quantile = (getattr(filt, "threshold_mode", "mu_sigma")
                        == "quantile")
            attr = self.attr_cfg is not None
            if self.is_fleet:
                from repro.dist.sketch_parallel import \
                    fleet_shardings_for_layout
                self._shardings = fleet_shardings_for_layout(
                    filt.ace_cfg, mesh, filt.num_tenants, sketch_layout,
                    table_axis, quantile=quantile, attr=attr)
            elif hasattr(filt, "num_epochs"):
                from repro.dist.sketch_parallel import \
                    window_shardings_for_layout
                self._shardings = window_shardings_for_layout(
                    filt.ace_cfg, mesh, filt.num_epochs, sketch_layout,
                    table_axis, quantile=quantile, attr=attr)
            else:
                from repro.dist.sketch_parallel import shardings_for_layout
                self._shardings = shardings_for_layout(
                    filt.ace_cfg, mesh, sketch_layout, table_axis,
                    quantile=quantile, attr=attr)
        # The incoming state is dead the moment consume() rebinds it —
        # donate it so the (L, 2^K) counts update in place every chunk.
        self._consume = jax.jit(on_auto_axes(self._consume_impl),
                                donate_argnums=0)

    def init(self):
        """(state, w), with the state placed per the mesh layout."""
        state, w = self.filt.init()
        return self._place(state), w

    def _place(self, state: AceState) -> AceState:
        if self._shardings is None:
            return state
        return jax.device_put(state, self._shardings)

    def _constrain(self, state: AceState) -> AceState:
        """Pin the scan carry to the requested repro.dist layout so GSPMD
        keeps the collectives inside the scan body (no-op off-mesh).
        Works for both the flat ``AceState`` and the epoch-ring
        ``WindowedAceState`` (the shardings pytree mirrors the carry —
        absent optional leaves pair None-with-None and pass through)."""
        if self._shardings is None:
            return state
        return type(state)(*(
            leaf if (leaf is None or sh is None)
            else jax.lax.with_sharding_constraint(leaf, sh)
            for leaf, sh in zip(state, self._shardings)))

    def _consume_impl(self, state: AceState, w: jax.Array,
                      feats: jax.Array, tenant_ids=None,
                      table_mask=None, tenant_mask=None):
        self.trace_count += 1
        T, B = feats.shape[0], feats.shape[1]
        R = self.rotate_every
        gamma = getattr(self.filt, "decay", 1.0)

        if self.is_fleet:
            # fleet scan: the step consumes (feat, tids) pairs — same
            # donated carry, same single program (R is 0 by __init__)
            def fstep(carry, xs):
                feat, tids = xs
                new_state, keep, margin = self.filt.step(
                    carry, w, feat, tids, table_mask=table_mask,
                    tenant_mask=tenant_mask)
                return self._constrain(new_state), (keep, margin)

            state, (keeps, margins) = jax.lax.scan(
                fstep, state, (feats, tenant_ids))
            with obs.scope("summary"):
                return self._fleet_summary(state, keeps, margins,
                                           tenant_ids, feats, T, B,
                                           table_mask, tenant_mask)

        def step(carry, feat):
            new_state, keep, margin = self.filt.step(
                carry, w, feat, table_mask=table_mask)
            return self._constrain(new_state), (keep, margin)

        if R and T % R == 0:
            # Epoch-ring rotation INSIDE the donated program, with no
            # per-step branching: the chunk scans in R-step segments and
            # the tick-gated clock runs once per segment boundary.  (A
            # per-step lax.cond would make XLA copy the multi-MB ring
            # carry on EVERY step — measured, that cost more than the
            # whole flat filter step; once per R steps it is noise.)
            # The tick-gated clock, not an unconditional rotate: a state
            # handed over mid-epoch (tick off the R-grid — out of this
            # runner's contract, which owns the stream from tick 0) then
            # keeps its epoch open instead of rotating at phase-shifted
            # positions, preserving the global invariant that rotations
            # only ever land on tick ≡ 0 (mod R).  On-contract entry
            # (every chunk starts at a multiple of T, R | T) makes the
            # gate fire at every boundary — identical to the per-batch
            # eager clock, asserted bitwise in tests/test_window.py.
            from repro.window import maybe_rotate

            def segment(carry, seg_feats):
                carry, outs = jax.lax.scan(step, carry, seg_feats)
                return self._constrain(
                    maybe_rotate(carry, R, gamma)), outs

            seg_feats = feats.reshape((T // R, R) + feats.shape[1:])
            state, (keeps, margins) = jax.lax.scan(
                segment, state, seg_feats)
            keeps = keeps.reshape((T,) + keeps.shape[2:])
            margins = margins.reshape((T,) + margins.shape[2:])
        elif R:
            # R is a multiple of T (validated in __init__): rotations
            # only ever land on chunk boundaries — scan the chunk, then
            # ONE tick-gated clock check (a single cond per chunk, not
            # T per-step conds).
            from repro.window import maybe_rotate
            state, (keeps, margins) = jax.lax.scan(step, state, feats)
            state = maybe_rotate(state, R, gamma)
        else:
            state, (keeps, margins) = jax.lax.scan(step, state, feats)
        with obs.scope("summary"):
            return self._flat_summary(state, keeps, margins, feats, T, B,
                                      gamma, table_mask)

    def _flat_summary(self, state, keeps, margins, feats, T, B, gamma,
                      table_mask=None):
        """The chunk's ``ChunkSummary`` from the scan outputs — all device
        reductions, one transfer with the keep masks."""
        keepf = keeps.astype(jnp.float32)                     # (T, B)
        k = min(self.topk, T * B)
        # top-k most anomalous = smallest margins — but quarantined rows
        # carry margin = −inf by the sanitize contract (a SENTINEL, not
        # a measurement: their features never touched the sketch), so
        # raw top-k would let garbage rows displace every GENUINE
        # anomaly from the report during a corruption burst.  Substitute
        # +inf so they sort dead last, like warmup rows; the raw margins
        # still feed the ``quarantined`` count below.
        ranked = jnp.where(jnp.isneginf(margins), jnp.inf, margins)
        neg, idx = jax.lax.top_k(-ranked.reshape(-1), k)
        topk_margin = -neg
        # drift statistic: one O(L·2^K) pass over the post-chunk planes
        from repro.quantile import falpha_index
        if hasattr(self.filt, "num_epochs"):
            from repro.window import ring
            falpha = falpha_index(ring.decayed_counts(state, gamma),
                                  ring.combined_n(state, gamma),
                                  table_mask=table_mask)
        elif state.esc is not None:
            # quantized planes with overflow promotion: the moment index
            # must see the EXACT logical counts — a saturated narrow
            # plane clips precisely the heavy buckets the α-moment
            # weights hardest, so falpha over raw int8/int16 counts
            # diverges from the true statistic right at the saturation
            # boundary (differential-tested vs the wide dtypes)
            from repro.core import quantize as qz
            falpha = falpha_index(qz.densify(state.counts, state.esc),
                                  state.n, table_mask=table_mask)
        else:
            falpha = falpha_index(state.counts, state.n,
                                  table_mask=table_mask)
        # heavy-hitter attribution: sketch the chunk's energy split into
        # the state planes + drill down on the chunk drift vector — all
        # fixed-shape device work inside the same jitted program
        hh = None
        if self.attr_cfg is not None:
            state, hh, _ = self._attr_observe(state, feats,
                                              margins.reshape(-1))
        summary = ChunkSummary(
            kept_frac=jnp.mean(keepf),
            anom_counts=jnp.sum(1 - keeps.astype(jnp.int32), axis=1),
            topk_step=(idx // B).astype(jnp.int32),
            topk_item=(idx % B).astype(jnp.int32),
            topk_margin=topk_margin,
            # windowed carries hold per-epoch (E,) counts — report the
            # ring total so the summary shape is layout-independent
            n=state.n if state.n.ndim == 0 else jnp.sum(state.n),
            # −inf margins uniquely mark sanitized rows (warmup margins
            # are +inf, real margins finite) — count them without
            # changing the filter step protocol
            quarantined=jnp.sum(jnp.isneginf(margins)).astype(jnp.int32),
            degraded=jnp.asarray(table_mask is not None),
            falpha=falpha,
            # a topk row is real only if a GENUINE anomaly filled it:
            # finite (not +inf warmup / not a quarantine sentinel routed
            # to +inf by the ranking substitution) AND negative (flagged)
            topk_valid=jnp.isfinite(topk_margin) & (topk_margin < 0.0),
            hh_coord=None if hh is None else hh[0],
            hh_est=None if hh is None else hh[1],
            hh_valid=None if hh is None else hh[2])
        if self.return_masks:
            return state, summary, keeps
        return state, summary

    def _attr_observe(self, state, feats, margins_flat,
                      tenant_ids=None):
        """Fold one chunk's energy split into the state's attribution
        planes and drill down on the chunk drift vector.

        The flat path runs the IDENTICAL T=1 segment-sum program the
        fleet path runs per tenant (``tenant_ids=None`` ⇒ all-zero ids
        inside ``chunk_energy``), which makes fleet-of-1 attribution
        bitwise the single-tenant path.  Returns (state, (hh_coord,
        hh_est, hh_valid)) plus the raw energy split for the fleet
        summary's per-tenant rows."""
        from repro import attribution as at
        acfg = self.attr_cfg
        d = feats.shape[-1]
        feat = feats.reshape(-1, d)
        # quarantined rows carry non-finite features — margin −inf
        # already excludes them from both channels, but inf·0 = nan
        # would poison the scatter, so zero them first (same sanitize
        # the filter step applies)
        finite = jnp.all(jnp.isfinite(feat), axis=-1)
        feat = jnp.where(finite[:, None], feat, 0.0)
        nt = self.filt.num_tenants if self.is_fleet else 1
        e_all, e_anom, n_all, n_anom = at.chunk_energy(
            feat, margins_flat, nt, tenant_ids)
        planes = at.chunk_planes(acfg, e_all, e_anom)
        if self.is_fleet:
            attr = at.observe_fleet(state.attr, planes)
        elif hasattr(self.filt, "num_epochs"):
            attr = at.observe_window(state.attr, planes[0], state.cursor)
        else:
            attr = at.observe_flat(state.attr, planes)
        state = state._replace(attr=attr)
        drift = at.drift_vector(e_all, e_anom, n_all, n_anom)
        hh = at.find_hh(acfg, at.sketch_vector(acfg, drift), self.topk)
        return state, hh, (e_all, e_anom, n_all, n_anom)

    def _fleet_summary(self, state, keeps, margins, tenant_ids, feats,
                       T, B, table_mask=None, tenant_mask=None):
        """Per-tenant summary rows from the scan outputs — all device
        reductions, one transfer with the rest of the summary."""
        from repro.fleet.state import per_tenant_counts
        from repro.quantile import falpha_index
        nt = self.filt.num_tenants
        keepf = keeps.astype(jnp.float32)
        k = min(self.topk, T * B)
        # quarantined (−inf margin) rows sort LAST, not first — the
        # flat-path rationale above applies per mixed batch too
        ranked = jnp.where(jnp.isneginf(margins), jnp.inf, margins)
        neg, idx = jax.lax.top_k(-ranked.reshape(-1), k)
        topk_margin = -neg
        tids_flat = tenant_ids.reshape(-1)
        if tenant_mask is None:
            misrouted = jnp.zeros((), jnp.int32)
        else:
            misrouted = jnp.sum(
                (tenant_mask[tids_flat] <= 0).astype(jnp.int32))
        hh = split = None
        if self.attr_cfg is not None:
            from repro import attribution as at
            state, hh, split = self._attr_observe(
                state, feats, margins.reshape(-1), tids_flat)
            tl2 = at.tenant_drift_l2(*split)                     # (T,)
            kt = min(self.topk, nt)
            hh_tenant_est, hh_tenant = jax.lax.top_k(tl2, kt)
        summary = FleetChunkSummary(
            kept_frac=jnp.mean(keepf),
            anom_counts=jnp.sum(1 - keeps.astype(jnp.int32), axis=1),
            topk_step=(idx // B).astype(jnp.int32),
            topk_item=(idx % B).astype(jnp.int32),
            topk_margin=topk_margin,
            per_tenant_items=per_tenant_counts(
                tids_flat, jnp.ones_like(tids_flat), nt),
            per_tenant_kept=per_tenant_counts(
                tids_flat, keepf.reshape(-1), nt),
            n=state.n,
            quarantined=jnp.sum(jnp.isneginf(margins)).astype(jnp.int32),
            degraded=jnp.asarray(table_mask is not None),
            misrouted=misrouted,
            falpha=falpha_index(state.counts, state.n,
                                table_mask=table_mask),
            topk_valid=jnp.isfinite(topk_margin) & (topk_margin < 0.0),
            hh_coord=None if hh is None else hh[0],
            hh_est=None if hh is None else hh[1],
            hh_valid=None if hh is None else hh[2],
            hh_tenant=(None if hh is None
                       else hh_tenant.astype(jnp.int32)),
            hh_tenant_est=None if hh is None else hh_tenant_est)
        if self.return_masks:
            return state, summary, keeps
        return state, summary

    def consume(self, state: AceState, w: jax.Array, feats: jax.Array,
                tenant_ids: jax.Array | None = None,
                table_mask: jax.Array | None = None,
                tenant_mask: jax.Array | None = None):
        """One chunk: feats (T, B, d) features (d = filter's dim+1 when
        produced by ``AceDataFilter.features``), plus the (T, B) int32
        tenant-id plane when the filter is a fleet.  Returns
        (new_state, summary[, keeps]) — all still on device; pull the
        summary with ONE ``jax.device_get`` when the host needs it.  The
        dispatch is one ``ace:stream/consume`` host span (``repro.core
        .obs``) whose arguments are the chunk's sequence number
        (``chunks_consumed`` before the call) and its T·B items.

        ``table_mask`` ((L,) or (T, L) f32, repro.resilience serving
        mask) scores the chunk over healthy tables only and stamps the
        summary ``degraded``.  None (the healthy default) traces no mask
        code — the degraded program is a SECOND cached executable
        (distinct treedef), so flipping back and forth costs no retrace
        and no extra host syncs.

        ``tenant_mask`` ((T,) f32, repro.cluster ownership mask, fleet
        filters only): items of unowned tenants are scored but never
        kept/inserted and counted in the summary's ``misrouted`` — a
        re-shard updates the mask VALUE host-side with no retrace (same
        treedef), and None keeps the single-host program untouched."""
        assert feats.ndim == 3 and feats.shape[0] == self.chunk_T, \
            (feats.shape, self.chunk_T)
        if self.is_fleet:
            assert tenant_ids is not None and \
                tenant_ids.shape == feats.shape[:2], \
                "fleet filters need a (T, B) tenant_ids plane"
            args = (tenant_ids, table_mask, tenant_mask)
        else:
            assert tenant_ids is None, \
                "tenant_ids given but the filter is not a fleet"
            assert tenant_mask is None, \
                "tenant_mask needs a fleet filter"
            args = (None, table_mask)
        with obs.span("stream/consume", chunk=self.chunks_consumed,
                      items=feats.shape[0] * feats.shape[1]):
            out = self._consume(state, w, feats, *args)
        self.chunks_consumed += 1
        return out

    def run(self, state: AceState, w: jax.Array,
            batches: Iterable[np.ndarray], tenant_ids=None):
        """Host driver: chunk an iterator of (B, d) feature batches and
        consume each chunk with one device program + one summary pull.

        ``tenant_ids``: for fleet filters, an iterable of (B,) int32
        vectors aligned with ``batches``.  Returns (final state,
        [host ChunkSummary per chunk]).  A trailing partial chunk (fewer
        than T batches) is dropped — the stream is infinite in
        production; pad explicitly if the tail matters.
        """
        if self.is_fleet and tenant_ids is None:
            raise ValueError("fleet filters need tenant_ids batches")
        if not self.is_fleet and tenant_ids is not None:
            # fail loudly: silently dropping the ids would make the
            # caller believe per-tenant routing happened (and the tenant
            # buffer would grow unbounded on an infinite stream)
            raise ValueError("tenant_ids given but the filter is not a "
                             "fleet (num_tenants attribute missing)")
        summaries = []
        buf: list[np.ndarray] = []
        tbuf: list[np.ndarray] = []
        tit = iter(tenant_ids) if tenant_ids is not None else None
        for b in batches:
            buf.append(np.asarray(b))
            if tit is not None:
                tbuf.append(np.asarray(next(tit)))
            if len(buf) < self.chunk_T:
                continue
            feats = jnp.asarray(np.stack(buf))     # ONE H2D per chunk
            buf.clear()
            if self.is_fleet:
                tids = jnp.asarray(np.stack(tbuf), jnp.int32)
                tbuf.clear()
                out = self.consume(state, w, feats, tids)
            else:
                out = self.consume(state, w, feats)
            state, summary = out[0], out[1]
            summaries.append(jax.device_get(summary))  # ONE D2H per chunk
        return state, summaries
