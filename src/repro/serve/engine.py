"""Batched serving engine: prefill + decode with KV caches, greedy/sampled
generation, and the ACE request guardrail (OOD requests rejected in O(K·L)
before touching the model — the paper's query phase as an admission filter).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sketch as sk
from repro.core.axes import on_auto_axes
from repro.core.sketch import AceConfig
from repro.core.srp import hash_buckets
from repro.models.registry import Arch, is_whisper


@dataclasses.dataclass(frozen=True)
class GuardrailConfig:
    d_model: int
    num_bits: int = 13
    num_tables: int = 32
    alpha: float = 4.0
    warmup_items: float = 256.0
    bias_const: float = 0.25
    hash_mode: str = "dense"    # "dense" | "srht" | "auto" (SrpConfig)
    # Sliding-window mode (repro.window): >1 epochs turns the sketch into
    # a device-resident epoch ring whose admit threshold is computed from
    # WINDOW-combined moments, so the guardrail tracks traffic drift by
    # FORGETTING stale regimes instead of letting them pin μ/σ forever.
    window_epochs: int = 1      # 1 = the flat (cumulative) sketch
    window_decay: float = 1.0   # γ; epoch weight γ^age at query time
    rotate_every: int = 0       # admit calls per epoch (0 = never rotate)
    # Multi-tenant fleet mode (repro.fleet): >1 tenants stacks T
    # independent sketches behind ONE admit program; every admit call
    # carries a (B,) tenant_ids routing vector and each request is
    # scored / thresholded / inserted against its own tenant's state
    # (per-tenant warmup, per-tenant drift — isolation is bitwise).
    num_tenants: int = 1        # 1 = the classic single-tenant guardrail
    # Quantized count planes (repro.core.quantize): narrow count dtypes
    # cut the resident table (and every gather's bandwidth) 2–4×.
    # esc_capacity > 0 additionally enables exact overflow promotion —
    # flat (window_epochs == 1, num_tenants == 1) guardrails only.
    count_dtype: str = "int32"  # "float32" | "int32" | "int16" | "int8"
    esc_capacity: int = 0
    # Admission threshold rule (repro.quantile): "mu_sigma" is the
    # classic μ−ασ score threshold; "quantile" targets a FLAG RATE —
    # admit iff score ≥ Q_q of the running per-tenant rate histogram —
    # which stays calibrated on heavy-tailed traffic where μ−ασ
    # over-flags (power-law tails inflate σ late) or under-flags
    # (early σ underestimates the tail).  Threshold-mode dispatch is
    # trace-time Python: mu_sigma guardrails trace zero quantile code
    # and their executables stay byte-identical to the pre-PR ones.
    threshold_mode: str = "mu_sigma"
    quantile_q: float = 0.01    # target flag rate for quantile mode
    # Quarantine fail policy (repro.resilience): requests whose features
    # are non-finite are sanitized OUT of the sketch (never scored
    # against real counts, never inserted, counted in
    # ``Guardrail.quarantined``) and their admit verdict comes from this
    # policy instead: "fail_open" admits them downstream (availability
    # first), "fail_closed" rejects them (integrity first).  Multi-tenant
    # guardrails accept a per-tenant tuple of length num_tenants.
    fail_policy: str | tuple = "fail_open"


class Guardrail:
    """ACE admission filter over request embeddings (stateful host wrapper).

    ``admit`` is ONE fixed-shape jitted device program: hash once, score
    from the same bucket ids, compare against the on-device μ−ασ score
    threshold, and fold the admitted items back in with a masked
    (0/1-weighted) insert — order-invariant and shape-stable, so a single
    compiled executable serves every batch no matter how many items are
    admitted, and the only host transfer per batch is the returned (B,)
    mask.  (The pre-PR path synced n/σ to the host, hashed twice, and
    retraced on every distinct admitted-count via a data-dependent
    gather.)

    With ``use_kernels=True`` the score→threshold→masked-insert after the
    one hash runs as the single fused Pallas kernel
    ``repro.kernels.ace_admit_fused`` (one launch, counts resident in
    VMEM; interpret mode off-TPU).

    With a ``mesh``, the sketch state is placed via ``repro.dist``:
    ``sketch_layout="replicated"`` mirrors the counts on every device (the
    default single-device behaviour, scaled out), while ``"table_sharded"``
    splits the (L, 2^K) counts over the L axis across ``table_axis`` —
    jit/SPMD mode of repro.dist.sketch_parallel — so guardrail sketches
    beyond one device's memory (K=18+, L=200+) stay servable; the same
    jitted admit program works in every layout (GSPMD inserts the
    collectives around the masked insert).

    With ``gcfg.window_epochs > 1`` the sketch is a sliding-window epoch
    ring (``repro.window``): the admit threshold comes from the
    WINDOW-combined μ/σ, admits insert into the live epoch, and every
    ``gcfg.rotate_every`` admit calls the ring rotates INSIDE the same
    jitted program (device-side cond on the ring's tick) — so a traffic
    regime that stops arriving ages out of the filter in
    ``window_epochs × rotate_every`` calls instead of biasing μ/σ
    forever.  Still one hash, one executable, one host transfer; the
    epoch ring shards over the SAME layouts (the L axis splits, the E
    axis never does).

    With ``gcfg.num_tenants > 1`` the guardrail is a FLEET
    (``repro.fleet``): T independent tenant sketches stacked behind the
    same single admit program, with ``admit(embeds, tenant_ids)``
    routing each request to its own tenant — per-tenant thresholds,
    per-tenant warmup, one mixed-batch scatter, and (combined with
    ``window_epochs > 1``) per-tenant epoch rings with per-tenant
    rotation clocks.  Still one hash, one executable, one host
    transfer; flat fleets shard over the tenant and/or table layouts of
    ``repro.dist.sketch_parallel.fleet_shardings_for_layout``.
    """

    def __init__(self, gcfg: GuardrailConfig, *, mesh=None,
                 sketch_layout: str = "replicated",
                 table_axis: str = "model", use_kernels: bool = False):
        self.gcfg = gcfg
        self.ace_cfg = AceConfig(dim=gcfg.d_model + 1,
                                 num_bits=gcfg.num_bits,
                                 num_tables=gcfg.num_tables, seed=41,
                                 welford_min_n=gcfg.warmup_items / 2,
                                 hash_mode=gcfg.hash_mode,
                                 counter_dtype=gcfg.count_dtype,
                                 esc_capacity=gcfg.esc_capacity)
        self.windowed = gcfg.window_epochs > 1
        self.multi_tenant = gcfg.num_tenants > 1
        if gcfg.threshold_mode not in ("mu_sigma", "quantile"):
            raise ValueError(f"unknown threshold_mode "
                             f"{gcfg.threshold_mode!r} — expected "
                             "'mu_sigma' or 'quantile'")
        quantile = gcfg.threshold_mode == "quantile"
        if self.multi_tenant:
            from repro.fleet import state as fl
            from repro.fleet import window as fw
            if self.windowed:
                from repro.window import ring
                if gcfg.rotate_every <= 0:
                    raise ValueError(
                        "windowed guardrail (window_epochs > 1) needs "
                        "rotate_every > 0 — without a rotation clock the "
                        "ring never expires and behaves like the frozen "
                        "sketch")
                # per-tenant epoch rings with per-tenant rotation clocks
                self.state = fw.init_fleet_window(ring.WindowConfig(
                    ace=self.ace_cfg, num_epochs=gcfg.window_epochs,
                    decay=gcfg.window_decay,
                    rotate_every=gcfg.rotate_every), gcfg.num_tenants,
                    quantile=quantile)
            else:
                self.state = fl.init(fl.FleetConfig(
                    ace=self.ace_cfg, num_tenants=gcfg.num_tenants),
                    quantile=quantile)
        elif self.windowed:
            from repro.window import ring
            if gcfg.rotate_every <= 0:
                # nothing else rotates a guardrail's ring: E>1 epochs
                # with no clock silently degenerates to the frozen
                # sketch at E× the memory — exactly the misconfig the
                # windowed mode exists to replace
                raise ValueError(
                    "windowed guardrail (window_epochs > 1) needs "
                    "rotate_every > 0 — without a rotation clock the "
                    "ring never expires and behaves like the frozen "
                    "sketch")
            # WindowConfig VALIDATES (epochs, decay, rotate_every) up
            # front — a bad γ must fail loudly here, not silently weight
            # stale epochs above live traffic
            self.state = ring.init_window(ring.WindowConfig(
                ace=self.ace_cfg, num_epochs=gcfg.window_epochs,
                decay=gcfg.window_decay,
                rotate_every=gcfg.rotate_every), quantile=quantile)
        else:
            self.state = sk.init(self.ace_cfg)
            if quantile:
                from repro.quantile import sketch as qsk
                self.state = self.state._replace(qhist=qsk.init_hist())
        self.w = sk.make_params(self.ace_cfg)
        if use_kernels and mesh is not None:
            raise ValueError("use_kernels admission is single-device; "
                             "drop the mesh or use the jnp path")
        self.use_kernels = use_kernels
        # Per-tenant quarantine fail policy (resilience): a scalar policy
        # broadcasts to every tenant; a tuple must cover each tenant.
        pol = gcfg.fail_policy
        if isinstance(pol, str):
            pol = (pol,) * max(gcfg.num_tenants, 1)
        if len(pol) != max(gcfg.num_tenants, 1):
            raise ValueError(
                f"fail_policy tuple has {len(pol)} entries for "
                f"{gcfg.num_tenants} tenants")
        bad = [p for p in pol if p not in ("fail_open", "fail_closed")]
        if bad:
            raise ValueError(f"unknown fail_policy {bad[0]!r} — expected "
                             "'fail_open' or 'fail_closed'")
        self._fail_open = np.array([p == "fail_open" for p in pol])
        # Host-side health/degradation state (repro.resilience).  The
        # serving table mask is None on the healthy path — the mask code
        # is then never traced, keeping the healthy executable untouched
        # — and a device (L,)/(T, L) float mask while degraded (a SECOND
        # cached executable, switched host-side with zero hot-path
        # syncs).
        self.quarantined = 0          # total non-finite rows seen
        self._table_mask = None       # device f32 serving mask | None
        self._repair_offsets = None   # flat/fleet per-table n-at-repair
        self._rewarm_admits = 0       # windowed repair re-warm countdown
        self._rewarming = None        # host bool mask of re-warming tables
        self.trace_count = 0          # incremented at TRACE time only
        # The incoming state is dead the moment admit() rebinds it, so
        # donate it: the masked insert updates the counts buffer in place
        # instead of copying (L, 2^K) every batch.
        self._admit = jax.jit(on_auto_axes(self._admit_impl),
                              donate_argnums=0)
        if mesh is not None:
            quantile = gcfg.threshold_mode == "quantile"
            if self.multi_tenant:
                if self.windowed:
                    raise NotImplementedError(
                        "sharded windowed fleets are not wired yet — "
                        "drop the mesh or use window_epochs=1")
                from repro.dist.sketch_parallel import \
                    fleet_shardings_for_layout
                shardings = fleet_shardings_for_layout(
                    self.ace_cfg, mesh, gcfg.num_tenants, sketch_layout,
                    table_axis, quantile=quantile)
            elif self.windowed:
                from repro.dist.sketch_parallel import \
                    window_shardings_for_layout
                shardings = window_shardings_for_layout(
                    self.ace_cfg, mesh, gcfg.window_epochs, sketch_layout,
                    table_axis, quantile=quantile)
            else:
                from repro.dist.sketch_parallel import shardings_for_layout
                shardings = shardings_for_layout(
                    self.ace_cfg, mesh, sketch_layout, table_axis,
                    quantile=quantile)
            self.state = jax.device_put(self.state, shardings)

    def _features(self, embeds: jax.Array) -> jax.Array:
        """Unit-normalised mean embedding + bias coordinate — the SAME
        shared helper as the data filters (``mean_embed_features``), so
        the serving guardrail and the training-side filters can never
        drift apart on featurisation."""
        from repro.data.pipeline import mean_embed_features
        return mean_embed_features(embeds, self.gcfg.bias_const)

    def _admit_impl(self, state: sk.AceState, w: jax.Array,
                    embeds: jax.Array, tenant_ids=None, table_mask=None):
        """The whole admission step as one traced device program.

        Entry-point sanitization (repro.resilience): rows whose features
        are non-finite are zeroed BEFORE hashing (NaN·0 would re-poison
        anything downstream), barred from admission AND insertion
        (``item_mask`` — the silent fail-open bug this replaces admitted
        them into one bucket per table, skewing ssq/μ forever), and
        reported back so the host can count them as quarantined; their
        returned verdict is the per-tenant fail policy's.  For all-finite
        batches the sanitization is bitwise identity.

        ``table_mask`` ((L,) or (T, L) f32 health mask) scores over
        healthy tables only; None (the healthy path) never traces any
        mask code — the degraded program is a separate cached executable.
        """
        self.trace_count += 1
        cfg = self.ace_cfg
        feat = self._features(embeds)
        finite = jnp.all(jnp.isfinite(feat), axis=-1)         # (B,)
        feat = jnp.where(finite[:, None], feat, 0.0)
        new_state, admit = self._admit_branches(
            state, w, feat, finite, tenant_ids, table_mask)
        if self.multi_tenant:
            fail_open = jnp.asarray(self._fail_open)[tenant_ids]
        else:
            fail_open = jnp.asarray(bool(self._fail_open[0]))
        final = jnp.where(finite, admit, fail_open)
        # ONE packed (2, B) transfer: verdicts + the quarantine mask.
        return new_state, jnp.stack([final, finite])

    def _admit_branches(self, state, w, feat, finite, tenant_ids,
                        table_mask):
        """Score → threshold → masked insert for every sketch flavour.

        ``finite`` rides into each branch as the item mask: quarantined
        rows never admit and never insert (the fused kernels gate them
        in-launch; the jnp paths AND them out of the insert mask).
        """
        cfg = self.ace_cfg
        if self.multi_tenant:
            from repro.fleet import state as fl
            from repro.fleet import window as fw
            if self.windowed:
                # per-tenant windowed admission: one hash, routed tail +
                # live gathers, per-tenant windowed μ−ασ thresholds, one
                # live-epoch scatter, then the per-tenant rotation
                # clocks — mirrors the single-ring windowed branch below
                if self.use_kernels:
                    # the ONE all-in-one launch (hash + routed gathers +
                    # γ-combine + threshold + masked insert welded) —
                    # rotation clocks included in the dispatch
                    from repro.kernels import ops as kops
                    return kops.ace_fleet_window_admit(
                        state, feat, tenant_ids, w, cfg,
                        gamma=self.gcfg.window_decay,
                        alpha=self.gcfg.alpha,
                        warmup_items=self.gcfg.warmup_items,
                        rotate_every=self.gcfg.rotate_every,
                        table_mask=table_mask, item_mask=finite,
                        threshold_mode=self.gcfg.threshold_mode,
                        quantile_q=self.gcfg.quantile_q)
                buckets = hash_buckets(feat, w, cfg.srp)
                pre = fw.window_table_sums_fleet(state, tenant_ids,
                                                 buckets)
                from repro.window import ring
                if table_mask is None:
                    scores = ring.score_live(pre[0], pre[1],
                                             cfg.num_tables)
                else:
                    # degraded: masked combine for the DECISION; the
                    # insert's ssq increment keeps the true sums (pre)
                    scores = fw.window_fleet_scores(
                        state, tenant_ids, buckets,
                        table_mask=table_mask)
                admit = scores >= fw.window_admit_thresholds(
                    state, self.gcfg.window_decay, self.gcfg.alpha,
                    self.gcfg.warmup_items, table_mask=table_mask,
                    threshold_mode=self.gcfg.threshold_mode,
                    q=self.gcfg.quantile_q)[tenant_ids]
                admit = jnp.logical_and(admit, finite)
                new_state = fw.insert_current_fleet(
                    state, tenant_ids, buckets, admit, cfg,
                    gamma=self.gcfg.window_decay, pre_sums=pre)
                if self.gcfg.threshold_mode == "quantile":
                    # every finite-scored item feeds its tenant's LIVE
                    # epoch histogram, BEFORE the clocks tick (rotation
                    # retires the epoch row); admitted-only observation
                    # would freeze the rejected tail out of Q_q
                    from repro.quantile import sketch as qsk
                    n_w = jax.vmap(
                        lambda s: ring.combined_n(
                            s, self.gcfg.window_decay))(
                        ring.WindowedAceState(*state))
                    rates = scores / jnp.maximum(n_w, 1.0)[tenant_ids]
                    new_state = fw.observe_current_fleet(
                        new_state, rates, tenant_ids,
                        qsk.calib_mask(finite.astype(jnp.float32),
                                       n_w[tenant_ids],
                                       self.gcfg.warmup_items))
                new_state = fw.maybe_rotate_fleet(
                    new_state, self.gcfg.rotate_every,
                    self.gcfg.window_decay, tenant_ids=tenant_ids)
                return new_state, admit
            if self.use_kernels:
                from repro.kernels import ops as kops
                return kops.ace_fleet_admit(
                    state, feat, tenant_ids, w, cfg,
                    alpha=self.gcfg.alpha,
                    warmup_items=self.gcfg.warmup_items,
                    table_mask=table_mask, item_mask=finite,
                    threshold_mode=self.gcfg.threshold_mode,
                    quantile_q=self.gcfg.quantile_q)
            buckets = hash_buckets(feat, w, cfg.srp)   # the ONE hash
            scores = fl.fleet_scores(state, tenant_ids, buckets,
                                     table_mask=table_mask)
            admit = scores >= fl.admit_thresholds(
                state, self.gcfg.alpha, self.gcfg.warmup_items,
                table_mask=table_mask,
                threshold_mode=self.gcfg.threshold_mode,
                q=self.gcfg.quantile_q)[tenant_ids]
            admit = jnp.logical_and(admit, finite)
            new_state = fl.insert_masked(state, tenant_ids, buckets,
                                         admit, cfg)
            if self.gcfg.threshold_mode == "quantile":
                from repro.quantile import sketch as qsk
                rates = scores / jnp.maximum(state.n, 1.0)[tenant_ids]
                new_state = new_state._replace(
                    qhist=qsk.observe_rates_fleet(
                        new_state.qhist, rates, tenant_ids,
                        qsk.calib_mask(finite.astype(jnp.float32),
                                       state.n[tenant_ids],
                                       self.gcfg.warmup_items)))
            return new_state, admit
        if self.windowed:
            from repro.window import ring
            if self.use_kernels:
                from repro.kernels import ops as kops
                return kops.ace_admit_windowed(
                    state, feat, w, cfg, gamma=self.gcfg.window_decay,
                    alpha=self.gcfg.alpha,
                    warmup_items=self.gcfg.warmup_items,
                    rotate_every=self.gcfg.rotate_every,
                    table_mask=table_mask, item_mask=finite,
                    threshold_mode=self.gcfg.threshold_mode,
                    quantile_q=self.gcfg.quantile_q)
            buckets = hash_buckets(feat, w, cfg.srp)   # the ONE hash
            # tail + live gathers (the live one is the flat path's own)
            tail_sums, live_sums = ring.window_table_sums(state, buckets)
            if table_mask is None:
                scores = ring.score_live(tail_sums, live_sums,
                                         cfg.num_tables)
            else:
                # degraded: masked gathers for the DECISION; the
                # insert's ssq increment keeps the true (unmasked) sums
                mt, ml = ring.window_table_sums(state, buckets,
                                                table_mask=table_mask)
                scores = ring.score_live(mt, ml, cfg.num_tables,
                                         table_mask=table_mask)
            admit = scores >= ring.admit_threshold_windowed(
                state, self.gcfg.window_decay, self.gcfg.alpha,
                self.gcfg.warmup_items, table_mask=table_mask,
                threshold_mode=self.gcfg.threshold_mode,
                q=self.gcfg.quantile_q)
            admit = jnp.logical_and(admit, finite)
            new_state = ring.insert_current(
                state, buckets, admit, cfg,
                gamma=self.gcfg.window_decay,
                pre_sums=(tail_sums, live_sums))
            if self.gcfg.threshold_mode == "quantile":
                # observe BEFORE the clock below retires the live epoch
                from repro.quantile import sketch as qsk
                n_w = ring.combined_n(state, self.gcfg.window_decay)
                rates = scores / jnp.maximum(n_w, 1.0)
                new_state = ring.observe_current(
                    new_state, rates,
                    qsk.calib_mask(finite.astype(jnp.float32), n_w,
                                   self.gcfg.warmup_items))
            # eager epoch clock: the admit call that fills an epoch
            # rotates the ring on its way out (device-side cond)
            new_state = ring.maybe_rotate(new_state,
                                          self.gcfg.rotate_every,
                                          self.gcfg.window_decay)
            return new_state, admit
        if self.use_kernels:
            from repro.kernels import ops as kops
            return kops.ace_admit(state, feat, w, cfg,
                                  alpha=self.gcfg.alpha,
                                  warmup_items=self.gcfg.warmup_items,
                                  table_mask=table_mask,
                                  item_mask=finite,
                                  threshold_mode=self.gcfg.threshold_mode,
                                  quantile_q=self.gcfg.quantile_q)
        buckets = hash_buckets(feat, w, cfg.srp)       # the ONE hash
        scores, pre = sk.lookup_gathered(              # same bucket ids
            state, buckets, table_mask=table_mask)
        admit = scores >= sk.admit_threshold(
            state, self.gcfg.alpha, self.gcfg.warmup_items,
            table_mask=table_mask,
            threshold_mode=self.gcfg.threshold_mode,
            q=self.gcfg.quantile_q)
        admit = jnp.logical_and(admit, finite)
        new_state = sk.insert_buckets_masked(state, buckets, admit, cfg,
                                             pre=pre)
        if self.gcfg.threshold_mode == "quantile":
            from repro.quantile import sketch as qsk
            rates = scores / jnp.maximum(state.n, 1.0)
            new_state = new_state._replace(qhist=qsk.observe_rates(
                new_state.qhist, rates,
                qsk.calib_mask(finite.astype(jnp.float32), state.n,
                               self.gcfg.warmup_items)))
        return new_state, admit

    def admit(self, embeds: jax.Array,
              tenant_ids: jax.Array | None = None) -> np.ndarray:
        """(B, S, D) request embeddings -> (B,) bool admitted; admits update
        the sketch (the serving distribution drifts with traffic — the
        paper's dynamic-update property).  One host transfer: the packed
        verdict+quarantine block.

        Multi-tenant guardrails additionally take ``tenant_ids`` (B,)
        int32 routing each request to its own tenant's sketch.

        Non-finite rows are quarantined (sanitized out of the sketch,
        counted in ``self.quarantined``) and answered per
        ``gcfg.fail_policy``; while ``self.degraded`` the decision runs
        over healthy tables only — both with zero additional host syncs
        (the health mask is a device arg of a second cached executable,
        the quarantine count rides the one existing transfer)."""
        if self.multi_tenant:
            if tenant_ids is None:
                raise ValueError("multi-tenant guardrail needs tenant_ids")
            self.state, packed = self._admit(
                self.state, self.w, embeds,
                jnp.asarray(tenant_ids, jnp.int32), self._table_mask)
        else:
            if tenant_ids is not None:
                raise ValueError("tenant_ids given but num_tenants == 1")
            self.state, packed = self._admit(self.state, self.w, embeds,
                                             None, self._table_mask)
        out = np.asarray(packed)          # the ONE device→host transfer
        self.quarantined += int((~out[1]).sum())
        if self._rewarm_admits > 0:
            self._rewarm_admits -= 1      # host arithmetic, no syncs
        return out[0].astype(bool)

    @property
    def degraded(self) -> bool:
        """True while the serving mask excludes any table (health_check
        found corruption, or a repair is still re-warming)."""
        return self._table_mask is not None

    @property
    def fail_open_mask(self) -> np.ndarray:
        """(T,) host bool — True where the tenant's quarantine/shedding
        policy is fail_open (shed ⇒ admit), False for fail_closed
        (shed ⇒ reject).  The open-loop front end
        (``repro.serve.frontend``) reads this to answer load-shed
        requests with each tenant's OWN policy — the same verdict a
        quarantined row of that tenant gets."""
        return self._fail_open.copy()

    def health_check(self):
        """Audit the sketch invariants (repro.resilience.health_check)
        and refresh the serving table mask.  A control-plane call: it
        syncs the report to the host (the hot path never does).

        Returns the host-side ``HealthReport``.  Tables failing their
        invariants — or repaired tables still re-warming — are excluded
        from scoring on subsequent ``admit`` calls via the degraded
        executable; once every table passes again (and the re-warm
        window has elapsed) the mask drops back to None and the original
        healthy executable resumes.
        """
        from repro import resilience as rz
        report = rz.health_check(self.state, self._repair_offsets)
        host = jax.device_get(report)
        table_ok = np.asarray(host.table_ok, bool)
        serving = table_ok.copy()
        if self._repair_offsets is not None:
            # flat/fleet re-warm gate: a repaired table rejoins once it
            # has re-absorbed a warmup's worth of the live stream
            offs = np.asarray(jax.device_get(self._repair_offsets))
            n = np.asarray(jax.device_get(self.state.n), np.float32)
            seen = (n[..., None] if offs.ndim == n.ndim + 1 else n) - offs
            # only repaired tables (offset > 0) carry the re-warm gate
            serving &= (offs == 0) | (seen >= self.gcfg.warmup_items)
        if self._rewarm_admits > 0:
            # windowed re-warm gate: repaired ring tables stay masked
            # until the zeroed epochs have fully expired
            serving &= ~self._rewarming
        if serving.all():
            self._table_mask = None
        else:
            self._table_mask = jnp.asarray(serving, jnp.float32)
        return host

    def repair(self):
        """Re-zero every table failing its invariants (and any poisoned
        Welford stream) while the healthy tables keep serving — the
        repro.resilience repair ops, wired to this guardrail's sketch
        flavour.  Control-plane: syncs, retains the degraded mask over
        the repaired tables until they re-warm (flat/fleet: a warmup's
        worth of stream, tracked via repair offsets; windowed: one full
        ring of rotations, tracked host-side).  Returns the host-side
        pre-repair ``HealthReport``.
        """
        from repro import resilience as rz
        report = rz.health_check(self.state, self._repair_offsets)
        host = jax.device_get(report)
        table_ok = report.table_ok
        if self.multi_tenant and self.windowed:
            self.state = rz.repair_fleet_window(self.state, table_ok)
        elif self.multi_tenant:
            self.state, self._repair_offsets = rz.repair_fleet(
                self.state, table_ok, self._repair_offsets)
        elif self.windowed:
            self.state = rz.repair_window(self.state, table_ok)
        else:
            self.state, self._repair_offsets = rz.repair_ace(
                self.state, table_ok, self._repair_offsets)
        if self.windowed and not np.asarray(host.table_ok, bool).all():
            # E·rotate_every admits flush every zeroed epoch out
            self._rewarm_admits = (self.gcfg.window_epochs
                                   * self.gcfg.rotate_every)
            self._rewarming = ~np.asarray(host.table_ok, bool)
        if not np.asarray(host.moments_ok, bool).all():
            self.state = rz.repair_moments(self.state)
        self.health_check()
        return host


def _to_host(x: jax.Array) -> np.ndarray:
    """The ONE device→host transfer of a generate() call.

    A separate named function (not an inline np.asarray) so the decode
    loop's zero-sync contract is a single call site — tests wrap it to
    count transfers, and a stray np.asarray inside the loop would have to
    bypass it visibly.
    """
    return np.asarray(x)


class ServeEngine:
    """Greedy generation over a fixed batch (the paper-kind e2e driver)."""

    def __init__(self, arch: Arch, s_max: int = 256,
                 guardrail: Guardrail | None = None):
        self.arch = arch
        self.s_max = s_max
        self.guardrail = guardrail
        self._prefill = jax.jit(
            lambda p, b: arch.prefill(p, b, s_max=s_max))
        self._decode = jax.jit(arch.decode_step)

    def generate(self, params, batch, num_new_tokens: int,
                 prompt_len: int) -> np.ndarray:
        """Greedy decode.  Returns (B, num_new_tokens) int32.

        Tokens accumulate ON DEVICE across the decode loop and transfer
        once at the end — the pre-PR loop pulled every token to the host
        (``np.asarray(tok)`` per step), serialising decode on B·4-byte
        syncs; now the loop body enqueues async dispatches back-to-back
        and the only device→host transfer is the final (B, T) stack
        (``_to_host``; counted in tests/test_stream.py).
        """
        cfg = self.arch.cfg
        if self.guardrail is not None and "embeds" not in batch:
            embeds = jnp.take(params["embed"], batch["tokens"], axis=0)
            admit = self.guardrail.admit(embeds)
        logits, cache = self._prefill(params, batch)
        B = logits.shape[0]
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        toks = [tok]
        for i in range(1, num_new_tokens):
            pos = jnp.full((B,), prompt_len + i - 1, jnp.int32)
            if cfg.mrope_sections is not None:
                pos = jnp.broadcast_to(pos[None], (3, B))
            step_batch = {"tokens": tok[:, None]}
            logits, cache = self._decode(params, step_batch, cache, pos)
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            toks.append(tok)
        return _to_host(jnp.stack(toks, axis=1))    # the ONE transfer

