"""Streaming-ingest overhaul tests: scan-fused StreamRunner equivalence,
SRHT Pallas kernel parity, hash_mode dispatch, the filter's hash-once and
Welford-delegation contracts, and the serve decode loop's single-transfer
contract."""
import dataclasses
import functools
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_allclose_dtype
from repro.core import sketch as sk
from repro.core import srp
from repro.core.srp import SrpConfig, hash_buckets, resolve_hash_mode
from repro.core.srht import (choose_hash_mode, effective_cost_dense,
                             effective_cost_srht, srht_hash_buckets,
                             srht_params)
from repro.data.pipeline import AceDataFilter
from repro.kernels import runtime
from repro.kernels.srht_hash import srht_hash
from repro.stream import StreamRunner

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _embeds(rng, B=8, S=4, D=16, scale=0.3, mu=2.0):
    return jnp.asarray(rng.normal(size=(B, S, D)) * scale + mu, jnp.float32)


# ---------------------------------------------------------------------------
# StreamRunner: chunk-of-T ≡ T sequential per-batch filter calls.
# ---------------------------------------------------------------------------

class TestStreamRunner:
    def _filter(self):
        return AceDataFilter(d_model=16, warmup_items=64.0, alpha=3.0)

    def test_chunk_equals_sequential_filter_calls(self):
        """One scan chunk must reproduce T per-batch AceDataFilter calls
        bitwise on counts/n (and to fp tolerance on the Welford stream),
        masks included — mixing warmup and armed steps."""
        filt = self._filter()
        rng = np.random.default_rng(0)
        T = 12
        embeds = [_embeds(rng) for _ in range(T)]
        embeds[-1] = _embeds(rng, mu=-6.0)         # a batch the armed
        embeds[-2] = _embeds(rng, mu=-6.0)         # filter should reject

        s_seq, w = filt.init()
        masks_seq, fracs = [], []
        for e in embeds:
            m = jnp.ones((e.shape[0], e.shape[1]), jnp.float32)
            s_seq, new_mask, frac = filt(s_seq, w, e, m)
            masks_seq.append(new_mask)
            fracs.append(float(frac))

        runner = StreamRunner(filt, chunk_T=T, return_masks=True)
        s_run, w2 = runner.init()
        feats = jnp.stack([filt.features(e) for e in embeds])
        s_run, summary, keeps = runner.consume(s_run, w2, feats)

        assert bool(jnp.all(s_run.counts == s_seq.counts))
        assert float(s_run.n) == float(s_seq.n)
        assert_allclose_dtype(s_run.welford_mean, s_seq.welford_mean)
        assert_allclose_dtype(s_run.welford_m2, s_seq.welford_m2)
        for t in range(T):
            want = masks_seq[t][:, 0] > 0
            assert bool(jnp.all(keeps[t] == want)), f"mask mismatch at {t}"
        assert_allclose_dtype(summary.kept_frac,
                              np.float32(np.mean(fracs)))
        # the rejected batches show up in the per-step anomaly counts
        assert int(summary.anom_counts[-1]) == 8
        assert int(summary.anom_counts[0]) == 0

    def test_one_executable_across_chunks_with_donation(self):
        filt = self._filter()
        runner = StreamRunner(filt, chunk_T=4)
        state, w = runner.init()
        rng = np.random.default_rng(1)
        for _ in range(3):
            feats = jnp.stack([filt.features(_embeds(rng))
                               for _ in range(4)])
            state, _summary = runner.consume(state, w, feats)
        assert runner.trace_count == 1
        assert float(state.n) > 0

    def test_topk_points_at_most_anomalous_items(self):
        """The on-device top-k must name the poisoned coordinates."""
        filt = self._filter()
        runner = StreamRunner(filt, chunk_T=4, topk=2)
        state, w = runner.init()
        rng = np.random.default_rng(2)
        # warmup chunk (filter arms at 64 items; 4*8=32 per chunk)
        for _ in range(2):
            feats = jnp.stack([filt.features(_embeds(rng))
                               for _ in range(4)])
            state, summary = runner.consume(state, w, feats)
        # poisoned chunk: step 2 rows are far out of cone
        embeds = [_embeds(rng) for _ in range(4)]
        embeds[2] = _embeds(rng, mu=-6.0)
        feats = jnp.stack([filt.features(e) for e in embeds])
        state, summary = runner.consume(state, w, feats)
        s = jax.device_get(summary)
        assert (s.topk_step == 2).all(), s
        assert (np.diff(s.topk_margin) >= 0).all()   # most anomalous first
        assert runner.trace_count == 1

    def test_quarantine_never_displaces_genuine_anomalies(self):
        """S3 regression: quarantined non-finite rows ride the transfer
        with margin = −inf, which is also the most-anomalous extreme of
        the top-k ordering — a dirty batch must NOT mask a genuine
        burst.  The ranking maps −inf to +inf so junk sorts last."""
        filt = self._filter()
        runner = StreamRunner(filt, chunk_T=4, topk=4)
        state, w = runner.init()
        rng = np.random.default_rng(3)
        for _ in range(2):                    # arm the filter (64 items)
            feats = jnp.stack([filt.features(_embeds(rng))
                               for _ in range(4)])
            state, summary = runner.consume(state, w, feats)
        # mixed chunk: step 1 is a genuine out-of-cone burst; NaN rows
        # land in OTHER steps and would out-sort it under raw margins
        embeds = [_embeds(rng) for _ in range(4)]
        embeds[1] = _embeds(rng, mu=-6.0)
        feats = np.array(jnp.stack([filt.features(e) for e in embeds]))
        feats[0, 2] = np.nan
        feats[3, 6] = np.nan
        state, summary = runner.consume(state, w, jnp.asarray(feats))
        s = jax.device_get(summary)
        assert int(s.quarantined) == 2
        got = {(int(s.topk_step[i]), int(s.topk_item[i]))
               for i in range(4)}
        assert not (got & {(0, 2), (3, 6)})   # junk never in top-k
        assert (s.topk_step == 1).all()       # the burst owns the top-k
        assert np.isfinite(s.topk_margin).all()
        assert runner.trace_count == 1

    def test_fleet_quarantine_never_displaces_genuine_anomalies(self):
        """S3, fleet path: same contract through ``_fleet_summary`` —
        mixed-tenant chunk, NaN rows in one tenant's traffic, burst in
        another's."""
        from repro.fleet.filter import FleetDataFilter
        filt = FleetDataFilter(d_model=16, num_tenants=2,
                               warmup_items=32.0, alpha=3.0)
        runner = StreamRunner(filt, chunk_T=4, topk=4)
        state, w = runner.init()
        rng = np.random.default_rng(4)
        tids = jnp.asarray(np.tile([0, 1], 4 * 4).reshape(4, 8), jnp.int32)
        for _ in range(3):                    # arm both tenants
            feats = jnp.stack([filt.features(_embeds(rng))
                               for _ in range(4)])
            state, summary = runner.consume(state, w, feats, tids)
        embeds = [_embeds(rng) for _ in range(4)]
        embeds[2] = _embeds(rng, mu=-6.0)     # burst step
        feats = np.array(jnp.stack([filt.features(e) for e in embeds]))
        feats[0, 1] = np.nan
        feats[1, 4] = np.nan
        state, summary = runner.consume(state, w, jnp.asarray(feats), tids)
        s = jax.device_get(summary)
        assert int(s.quarantined) == 2
        got = {(int(s.topk_step[i]), int(s.topk_item[i]))
               for i in range(4)}
        assert not (got & {(0, 1), (1, 4)})
        assert (s.topk_step == 2).all()
        assert np.isfinite(s.topk_margin).all()
        assert runner.trace_count == 1

    @pytest.mark.slow
    def test_sharded_layouts_match_single_device(self):
        """Same scan program under repro.dist placements (jit/SPMD mode):
        replicated and table-sharded chunk ingest must match the
        single-device runner bitwise on counts/n (fake 2-device CPU mesh
        in a subprocess, like tests/test_dist_sharded.py)."""
        code = """
            import jax, jax.numpy as jnp, numpy as np
            from repro.data.pipeline import AceDataFilter
            from repro.stream import StreamRunner

            filt = AceDataFilter(d_model=8, num_bits=6, num_tables=10,
                                 warmup_items=16.0, alpha=3.0)
            rng = np.random.default_rng(0)
            feats = jnp.asarray(rng.normal(size=(6, 16, 9)) + 1.0,
                                jnp.float32)

            base = StreamRunner(filt, chunk_T=6)
            s0, w = base.init()
            s_ref, _ = base.consume(s0, w, feats)

            mesh = jax.make_mesh((1, 2), ("data", "model"))
            for layout in ("replicated", "table_sharded"):
                r = StreamRunner(filt, chunk_T=6, mesh=mesh,
                                 sketch_layout=layout)
                s, w2 = r.init()
                s, _ = r.consume(s, w2, feats)
                assert np.array_equal(np.asarray(jax.device_get(s.counts)),
                                      np.asarray(jax.device_get(
                                          s_ref.counts))), layout
                assert float(s.n) == float(s_ref.n), layout
                np.testing.assert_allclose(
                    float(s.welford_mean), float(s_ref.welford_mean),
                    rtol=1e-6)
            print("LAYOUTS-MATCH")
        """
        env = dict(os.environ)
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                            + env.get("XLA_FLAGS", ""))
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, timeout=420,
                             env=env)
        assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
        assert "LAYOUTS-MATCH" in out.stdout


# ---------------------------------------------------------------------------
# AceDataFilter contracts: hash once; Welford delegation bitwise.
# ---------------------------------------------------------------------------

class TestFilterContracts:
    def test_filter_hashes_exactly_once_per_batch(self, monkeypatch):
        """__call__ (and step) must hit the hash dispatch exactly once —
        the pre-PR path hashed every batch twice (score + insert)."""
        calls = []
        orig = srp.hash_buckets

        def counting(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(srp, "hash_buckets", counting)
        filt = AceDataFilter(d_model=16, warmup_items=8.0)
        state, w = filt.init()
        rng = np.random.default_rng(3)
        e = _embeds(rng)
        filt(state, w, e, jnp.ones((8, 4), jnp.float32))
        assert len(calls) == 1
        calls.clear()
        filt.step(state, w, filt.features(e))
        assert len(calls) == 1

    @pytest.mark.parametrize("insert_all", [False, True])
    @pytest.mark.parametrize("masked", [False, True])
    def test_step_equals_lookup_then_gathering_insert(self, insert_all,
                                                      masked):
        """``step`` scores from its one gather and derives the post-insert
        scores from it; composed from ``sk.lookup`` and the insert that
        gathers again, the same step gives the same state, keep and
        margin, bitwise — with a degraded ``table_mask`` too, which masks
        the score and not the post-insert counts."""
        filt = AceDataFilter(d_model=16, num_bits=3, num_tables=6,
                             warmup_items=16.0, alpha=0.5,
                             insert_all=insert_all)
        cfg = filt.ace_cfg
        state, w = filt.init()
        rng = np.random.default_rng(5)
        for _ in range(3):
            state, _, _ = filt.step(state, w, filt.features(_embeds(rng)))
        tm = (jnp.asarray([1, 1, 0, 1, 0, 1], jnp.float32) if masked
              else None)
        feat = filt.features(jnp.concatenate(
            [_embeds(rng, B=12), _embeds(rng, B=12, mu=0.0, scale=1.0)]))
        got, keep, margin = filt.step(state, w, feat, table_mask=tm)

        buckets = srp.hash_buckets(feat, w, cfg.srp)
        scores = sk.lookup(state, buckets, table_mask=tm)
        thresh = sk.admit_threshold(state, filt.alpha, filt.warmup_items,
                                    table_mask=tm)
        want_keep = scores >= thresh
        want = sk.insert_buckets_masked(
            state, buckets, jnp.ones_like(want_keep) if insert_all
            else want_keep, cfg)
        assert 0 < int(keep.sum()) < 24 or insert_all
        np.testing.assert_array_equal(np.asarray(keep),
                                      np.asarray(want_keep))
        np.testing.assert_array_equal(np.asarray(margin),
                                      np.asarray(scores - thresh))
        for name in ("counts", "n", "welford_mean", "welford_m2"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    def test_masked_welford_matches_old_inline_formulas_bitwise(self):
        """The pre-rewrite hand-rolled Welford block of
        AceDataFilter.__call__, fed the same (state, scores, keep), must
        equal sk.masked_batch_welford BITWISE (min_n=0 — the old block
        had no cold-start gate)."""

        def old_fold(state, scores, keep):
            b = jnp.sum(keep.astype(jnp.float32))
            n = state.n
            tot = n + b
            kept_rates = jnp.where(keep,
                                   scores / jnp.maximum(tot, 1.0), 0.0)
            mean_b = jnp.sum(kept_rates) / jnp.maximum(b, 1.0)
            m2_b = jnp.sum(jnp.where(keep,
                                     (kept_rates - mean_b) ** 2, 0.0))
            delta = mean_b - state.welford_mean
            safe = jnp.maximum(tot, 1.0)
            return (tot,
                    state.welford_mean + delta * b / safe,
                    state.welford_m2 + m2_b + delta ** 2 * n * b / safe)

        rng = np.random.default_rng(4)
        cfg = sk.AceConfig(dim=8, num_bits=6, num_tables=10, seed=0)
        state = sk.insert(sk.init(cfg), sk.make_params(cfg),
                          jnp.asarray(rng.normal(size=(40, 8)),
                                      jnp.float32), cfg)
        for keep_p in (1.0, 0.5, 0.0):
            scores = jnp.asarray(rng.uniform(1, 9, size=(32,)), jnp.float32)
            keep = jnp.asarray(rng.uniform(size=(32,)) < keep_p)
            want = old_fold(state, scores, keep)
            got = sk.masked_batch_welford(
                state, scores, keep.astype(jnp.float32), min_n=0.0)
            for g, wnt in zip(got, want):
                assert float(g) == float(wnt), (keep_p, got, want)


# ---------------------------------------------------------------------------
# SRHT Pallas kernel ≡ core.srht reference; hash_mode dispatch.
# ---------------------------------------------------------------------------

SHAPES = [
    (16, 32, 8, 10),
    (100, 300, 15, 50),   # paper's K, L
    (7, 9, 4, 3),
    (33, 128, 12, 50),
    (256, 64, 6, 7),
]


class TestSrhtHashKernel:
    @pytest.mark.parametrize("B,d,K,L", SHAPES)
    def test_matches_reference_bitwise(self, B, d, K, L):
        cfg = SrpConfig(dim=d, num_bits=K, num_tables=L, seed=B + d,
                        hash_mode="srht")
        x = jnp.asarray(np.random.default_rng(d).normal(size=(B, d)),
                        jnp.float32)
        got = srht_hash(x, cfg)
        want = srht_hash_buckets(x, srht_params(cfg))
        assert got.shape == (B, L) and got.dtype == jnp.int32
        assert bool(jnp.all(got == want))

    @pytest.mark.parametrize("bm", [8, 32, 256])
    def test_batch_tiling_invariance(self, bm):
        cfg = SrpConfig(dim=48, num_bits=9, num_tables=12, seed=5,
                        hash_mode="srht")
        x = jnp.asarray(np.random.default_rng(6).normal(size=(70, 48)),
                        jnp.float32)
        assert bool(jnp.all(srht_hash(x, cfg, bm=bm) ==
                            srht_hash_buckets(x, srht_params(cfg))))

    def test_hash_buckets_dispatches_by_mode(self):
        d = 64
        x = jnp.asarray(np.random.default_rng(7).normal(size=(20, d)),
                        jnp.float32)
        dense_cfg = SrpConfig(dim=d, num_bits=8, num_tables=10, seed=1)
        srht_cfg = dataclasses.replace(dense_cfg, hash_mode="srht")
        w = srp.make_projections(dense_cfg)
        assert bool(jnp.all(
            hash_buckets(x, w, srht_cfg) ==
            srht_hash_buckets(x, srht_params(srht_cfg))))
        assert bool(jnp.all(
            hash_buckets(x, w, dense_cfg) ==
            srp.pack_buckets(srp.srp_bits(x, w, dense_cfg), dense_cfg)))
        # the two families are genuinely different hash draws
        assert not bool(jnp.all(hash_buckets(x, w, srht_cfg) ==
                                hash_buckets(x, w, dense_cfg)))


class TestHashModeDispatch:
    def test_auto_break_even_picks_the_measured_winner(self):
        """dense below the crossover (tiny matmul, the m-row gather
        dominates SRHT), srht above it (O(d·KL) vs O(d log d)) — the two
        benchmark corners of benchmarks/stream_throughput.py."""
        lo = SrpConfig(dim=64, hash_mode="auto")      # K=15, L=50
        hi = SrpConfig(dim=4096, hash_mode="auto")
        assert choose_hash_mode(lo) == "dense"
        assert choose_hash_mode(hi) == "srht"
        assert resolve_hash_mode(lo) == "dense"
        assert resolve_hash_mode(hi) == "srht"
        assert effective_cost_srht(hi) < effective_cost_dense(hi)
        assert effective_cost_srht(lo) > effective_cost_dense(lo)

    def test_auto_is_batch_free_and_monotone_at_scale(self):
        # crossover is a pure function of the static config
        for d in (1024, 2048, 8192, 12288):
            cfg = SrpConfig(dim=d, hash_mode="auto")
            assert choose_hash_mode(cfg) == "srht", d

    def test_estimator_kernel_path_respects_hash_mode(self):
        """AceEstimator(use_kernels=True) must hash through the dispatch:
        under 'srht' the dense w is a (d, 0) placeholder and a direct
        srp_hash call would crash; insert/score must match the jnp path."""
        from repro.core.estimators import AceEstimator
        from repro.core.sketch import AceConfig
        cfg = AceConfig(dim=12, num_bits=6, num_tables=8, seed=3,
                        hash_mode="srht")
        x = jnp.asarray(np.random.default_rng(9).normal(size=(40, 12)),
                        jnp.float32)
        q = jnp.asarray(np.random.default_rng(10).normal(size=(8, 12)),
                        jnp.float32)
        est_k = AceEstimator(cfg, use_kernels=True).update(x)
        est_j = AceEstimator(cfg).update(x)
        assert bool(jnp.all(est_k.state.counts == est_j.state.counts))
        assert_allclose_dtype(est_k.score(q), est_j.score(q))

    def test_invalid_mode_raises(self):
        with pytest.raises(ValueError, match="hash_mode"):
            resolve_hash_mode(SrpConfig(dim=8, hash_mode="fwht"))

    def test_interpret_resolver_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        assert runtime.default_interpret() is False
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert runtime.default_interpret() is True
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
        # backend probe: this container is CPU -> interpret
        assert runtime.default_interpret() is True
        assert runtime.resolve_interpret(False) is False
        assert runtime.resolve_interpret(None) is True


# ---------------------------------------------------------------------------
# Serve decode loop: tokens accumulate on device, ONE transfer per call.
# ---------------------------------------------------------------------------

class TestServeDecodeTransfers:
    def _engine(self):
        from repro.models.registry import Arch
        from repro.serve import engine as engine_mod
        a = Arch("qwen2_1_5b", reduced=True)
        a.cfg = dataclasses.replace(
            a.cfg, num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
            head_dim=32, d_ff=128, vocab_size=256, dtype="float32")
        params, _ = a.init_params(jax.random.PRNGKey(0))
        return engine_mod, engine_mod.ServeEngine(a, s_max=32), params

    def test_generate_transfers_once(self, monkeypatch):
        engine_mod, eng, params = self._engine()
        transfers = []
        orig = engine_mod._to_host

        def counting(x):
            transfers.append(1)
            return orig(x)

        monkeypatch.setattr(engine_mod, "_to_host", counting)
        toks = jnp.asarray(
            np.random.default_rng(8).integers(0, 256, (2, 8)), jnp.int32)
        out = eng.generate(params, {"tokens": toks}, num_new_tokens=6,
                           prompt_len=8)
        assert out.shape == (2, 6) and out.dtype == np.int32
        assert len(transfers) == 1, \
            f"decode loop made {len(transfers)} host transfers, want 1"
        # deterministic greedy decode: a second call agrees
        out2 = eng.generate(params, {"tokens": toks}, num_new_tokens=6,
                            prompt_len=8)
        np.testing.assert_array_equal(out, out2)


# ---------------------------------------------------------------------------
# Phase scopes and the consume span (repro.core.obs): what a profile of the
# stream path can name.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _compiled_consume_text(fleet: bool) -> str:
    """Optimized HLO text of a small consume program, flat or fleet."""
    if fleet:
        from repro.fleet import FleetDataFilter
        filt = FleetDataFilter(d_model=8, num_tenants=3, num_bits=6,
                               num_tables=4, warmup_items=16.0)
    else:
        filt = AceDataFilter(d_model=8, num_bits=6, num_tables=4,
                             warmup_items=16.0)
    runner = StreamRunner(filt, chunk_T=3, topk=4)
    state, w = runner.init()
    feats = jnp.ones((3, 16, 9), jnp.float32)
    args = (state, w, feats) + (
        (jnp.zeros((3, 16), jnp.int32),) if fleet else ())
    return runner._consume.lower(*args).compile().as_text()



class TestPhaseScopes:
    @pytest.mark.parametrize("fleet,phase", [
        (False, "hash"), (False, "score"), (False, "threshold"),
        (False, "insert"), (False, "rescore"), (False, "summary"),
        (True, "score"), (True, "threshold"), (True, "insert"),
        (True, "rescore"), (True, "summary")])
    def test_consume_program_carries_phase_scopes(self, fleet, phase):
        text = _compiled_consume_text(fleet)
        assert 'op_name="jit(_consume_impl)/' in text
        assert re.search(rf'op_name="[^"]*/ace/{phase}/', text), phase

    def test_features_carry_their_scope(self):
        filt = AceDataFilter(d_model=8)
        text = jax.jit(filt.features).lower(
            jnp.ones((4, 2, 8), jnp.float32)).compile().as_text()
        assert re.search(r'op_name="[^"]*ace/features/', text)

    def test_rescore_names_the_phase_around_a_nested_score(self):
        """The outermost scope names an op's phase: the fleet's post-insert
        re-score runs the score helper inside the ``rescore`` scope."""
        text = _compiled_consume_text(True)
        assert re.search(r'op_name="[^"]*/ace/rescore/ace/score/', text)

    def test_flat_step_reads_the_table_once(self):
        """The flat scan step gathers from the counts once, for the score:
        its post-insert scores come from that gather and the batch's own
        collisions.  The fleet step still re-gathers for its re-score."""
        def gathers(text):
            return re.findall(r'= \S+ gather\([^\n]*op_name="([^"]*)"', text)

        flat = gathers(_compiled_consume_text(False))
        assert len(flat) == 1 and "/ace/score/" in flat[0], flat
        fleet = gathers(_compiled_consume_text(True))
        assert any("/ace/rescore/ace/score/" in g for g in fleet), fleet

    def test_consume_counts_its_chunks(self):
        filt = AceDataFilter(d_model=8, num_bits=6, num_tables=4)
        runner = StreamRunner(filt, chunk_T=2)
        state, w = runner.init()
        assert runner.chunks_consumed == 0
        for _ in range(3):
            state, _ = runner.consume(state, w,
                                      jnp.ones((2, 8, 9), jnp.float32))
        assert runner.chunks_consumed == 3 and runner.trace_count == 1
