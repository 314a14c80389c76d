"""Sliding-window ACE tests: epoch-ring algebra (rotation, tail/ssq
streams, windowed moments), degenerate-case bitwise contracts, the
stream runner's in-scan rotation (chunk ≡ sequential, no retraces, no
extra transfers), the windowed guardrail, dist-layout parity on a fake
2-device mesh, and checkpoint round-tripping of the ring state."""
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
from repro.data.pipeline import AceDataFilter
from repro.stream import StreamRunner
from repro.window import ring
from repro.window import WindowConfig, WindowedAceFilter

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**kw):
    base = dict(dim=10, num_bits=6, num_tables=8, seed=3,
                welford_min_n=8.0)
    base.update(kw)
    return sk.AceConfig(**base)


def _buckets(rng, B, cfg):
    return jnp.asarray(
        rng.integers(0, cfg.num_buckets, size=(B, cfg.num_tables)),
        jnp.int32)


def _embeds(rng, B=8, S=4, D=16, scale=0.3, mu=2.0):
    return jnp.asarray(rng.normal(size=(B, S, D)) * scale + mu, jnp.float32)


# ---------------------------------------------------------------------------
# Ring algebra: maintained tail/ssq vs recompute oracles.
# ---------------------------------------------------------------------------

class TestRingAlgebra:
    @pytest.mark.parametrize("gamma", [1.0, 0.7])
    def test_tail_and_ssq_match_direct_recompute(self, gamma):
        """The maintained tail (Σ_{e≠cur} γ^age C_e) and ssq (‖C_w‖²)
        streams equal a from-scratch recompute after any interleaving of
        masked inserts and rotations — bitwise for γ=1 (exact integer
        f32), float-tolerance for γ<1 (error also γ-decays)."""
        cfg = _cfg()
        rng = np.random.default_rng(0)
        st = ring.init(cfg, 4)
        for i in range(25):
            b = _buckets(rng, 9, cfg)
            m = jnp.asarray(rng.uniform(size=9) < 0.6)
            st = ring.insert_current(st, b, m, cfg, gamma=gamma)
            st = ring.maybe_rotate(st, 3, gamma)
            dc = np.asarray(ring.decayed_counts(st, gamma))
            want_tail = dc - np.asarray(ring.live_epoch(st).counts,
                                        dtype=np.float32)
            want_ssq = float(np.sum(dc * dc))
            if gamma == 1.0:
                assert np.array_equal(np.asarray(st.tail), want_tail), i
                assert float(st.ssq) == want_ssq, i
            else:
                assert_allclose_dtype(st.tail, want_tail, atol=1e-4)
                assert_allclose_dtype(st.ssq, want_ssq, rtol=1e-4)

    def test_rotate_pow_E_is_zeroed_ring(self):
        cfg = _cfg()
        rng = np.random.default_rng(1)
        st = ring.init(cfg, 3)
        for _ in range(4):
            st = ring.insert_current(st, _buckets(rng, 7, cfg),
                                     jnp.ones((7,), bool), cfg)
        cursor0 = int(st.cursor)
        for _ in range(3):
            st = ring.rotate(st)
        assert int(st.cursor) == cursor0
        assert int(jnp.sum(jnp.abs(st.counts))) == 0
        assert float(jnp.sum(jnp.abs(st.tail))) == 0.0
        assert float(st.ssq) == 0.0
        assert float(jnp.sum(st.n)) == 0.0
        assert float(jnp.sum(jnp.abs(st.welford_m2))) == 0.0

    def test_hard_window_equals_merge_of_epochs(self):
        """γ=1, one batch per epoch: the window is sketch.merge of the
        epochs — counts/n exact, μ via the γ-generalised closed form."""
        cfg = _cfg()
        rng = np.random.default_rng(2)
        st = ring.init(cfg, 3)
        for e in range(3):
            st = ring.insert_current(st, _buckets(rng, 7, cfg),
                                     jnp.ones((7,), bool), cfg)
            if e < 2:
                st = ring.rotate(st)
        acc = ring.combined_ace(st)
        q = _buckets(rng, 5, cfg)
        got = ring.score_windowed(st, q, 1.0)
        want = sk.batch_scores(acc.counts.astype(jnp.float32), q)
        assert_allclose_dtype(got, want)
        assert_allclose_dtype(ring.mean_mu_windowed(st, 1.0),
                              sk.mean_mu(acc))
        assert float(ring.combined_n(st, 1.0)) == float(acc.n)

    @pytest.mark.parametrize("gamma", [1.0, 0.5])
    def test_score_hot_path_matches_eway_reference(self, gamma):
        """tail+live scoring (the hot path) ≡ the E-way query-time
        combine at the ring's own γ — bitwise for the hard window."""
        cfg = _cfg()
        rng = np.random.default_rng(3)
        st = ring.init(cfg, 4)
        for _ in range(9):
            st = ring.insert_current(st, _buckets(rng, 6, cfg),
                                     jnp.ones((6,), bool), cfg,
                                     gamma=gamma)
            st = ring.maybe_rotate(st, 2, gamma)
        q = _buckets(rng, 11, cfg)
        hot = ring.score_combined(st, q)
        ref = ring.score_windowed(st, q, gamma)
        if gamma == 1.0:
            assert bool(jnp.all(hot == ref))
        else:
            assert_allclose_dtype(hot, ref, rtol=1e-5)

    def test_window_config_validation(self):
        with pytest.raises(ValueError, match="num_epochs"):
            WindowConfig(ace=_cfg(), num_epochs=0)
        with pytest.raises(ValueError, match="decay"):
            WindowConfig(ace=_cfg(), decay=0.0)
        with pytest.raises(ValueError, match="decay"):
            WindowConfig(ace=_cfg(), decay=1.5)
        assert WindowConfig(ace=_cfg(), num_epochs=4).memory_bytes() > \
            4 * _cfg().memory_bytes()


# ---------------------------------------------------------------------------
# Degenerate case: E=1 window ≡ the flat sketch, bitwise.
# ---------------------------------------------------------------------------

class TestSingleEpochIsFlatSketch:
    def test_filter_step_bitwise(self):
        """WindowedAceFilter(num_epochs=1) ≡ AceDataFilter step for step:
        same keep/margin decisions, same counts, same Welford scalars,
        same admit threshold — bitwise."""
        fw = WindowedAceFilter(d_model=12, num_bits=6, num_tables=8,
                               warmup_items=16.0, alpha=3.0, num_epochs=1)
        ff = AceDataFilter(d_model=12, num_bits=6, num_tables=8,
                           warmup_items=16.0, alpha=3.0)
        ws, w1 = fw.init()
        fs, w2 = ff.init()
        assert np.array_equal(np.asarray(w1), np.asarray(w2))
        rng = np.random.default_rng(4)
        for i in range(8):
            feat = jnp.asarray(rng.normal(size=(8, 13)) + 1.0, jnp.float32)
            ws, kw, mw = fw.step(ws, w1, feat)
            fs, kf, mf = ff.step(fs, w2, feat)
            assert bool(jnp.all(kw == kf)), i
            assert bool(jnp.all(mw == mf)), i
            live = ring.live_epoch(ws)
            assert bool(jnp.all(live.counts == fs.counts)), i
            assert float(live.n) == float(fs.n)
            assert float(live.welford_mean) == float(fs.welford_mean), i
            assert float(live.welford_m2) == float(fs.welford_m2), i
            assert float(ring.admit_threshold_windowed(
                ws, 1.0, 3.0, 16.0)) == \
                float(sk.admit_threshold(fs, 3.0, 16.0)), i

    def test_ssq_equals_flat_mu_numerator(self):
        """E=1 ssq stream ≡ the flat sketch's fresh Σ‖A‖² reduction
        (both exact integers inside the f32 envelope)."""
        cfg = _cfg()
        rng = np.random.default_rng(5)
        st = ring.init(cfg, 1)
        flat = sk.init(cfg)
        for _ in range(6):
            b = _buckets(rng, 9, cfg)
            m = jnp.asarray(rng.uniform(size=9) < 0.7)
            st = ring.insert_current(st, b, m, cfg)
            flat = sk.insert_buckets_masked(flat, b, m, cfg)
        c = flat.counts.astype(jnp.float32)
        assert float(st.ssq) == float(jnp.sum(c * c))


# ---------------------------------------------------------------------------
# StreamRunner: rotation inside the donated program.
# ---------------------------------------------------------------------------

class TestWindowedStreamRunner:
    def _filter(self, **kw):
        base = dict(d_model=16, num_bits=7, num_tables=12,
                    warmup_items=64.0, alpha=3.0, num_epochs=3,
                    rotate_every=4)
        base.update(kw)
        return WindowedAceFilter(**base)

    def test_chunk_equals_sequential_with_rotation(self):
        """One scan chunk (rotations at in-chunk segment boundaries) ≡
        T per-batch calls (rotations via the eager maybe_rotate clock):
        counts/tail/ssq/cursor/tick bitwise, masks included."""
        filt = self._filter()
        rng = np.random.default_rng(6)
        T = 12
        embeds = [_embeds(rng) for _ in range(T)]
        embeds[-1] = _embeds(rng, mu=-6.0)
        s_seq, w = filt.init()
        keeps_seq = []
        for e in embeds:
            m = jnp.ones((e.shape[0], e.shape[1]), jnp.float32)
            s_seq, new_mask, _frac = filt(s_seq, w, e, m)
            keeps_seq.append(new_mask[:, 0] > 0)

        runner = StreamRunner(filt, chunk_T=T, return_masks=True)
        s_run, w2 = runner.init()
        feats = jnp.stack([filt.features(e) for e in embeds])
        s_run, _summary, keeps = runner.consume(s_run, w2, feats)

        assert bool(jnp.all(s_run.counts == s_seq.counts))
        assert bool(jnp.all(s_run.tail == s_seq.tail))
        assert float(s_run.ssq) == float(s_seq.ssq)
        assert int(s_run.cursor) == int(s_seq.cursor)
        assert int(s_run.tick) == int(s_seq.tick)
        assert_allclose_dtype(s_run.welford_m2, s_seq.welford_m2,
                              rtol=1e-5)
        for t in range(T):
            assert bool(jnp.all(keeps[t] == keeps_seq[t])), t

    def test_rotate_every_multiple_of_chunk(self):
        """R a multiple of T: rotations land on chunk boundaries via one
        tick-gated clock per chunk — still equivalent to sequential."""
        filt = self._filter(rotate_every=8)
        runner = StreamRunner(filt, chunk_T=4, return_masks=True)
        s_run, w = runner.init()
        s_seq, _ = filt.init()
        rng = np.random.default_rng(7)
        feats = jnp.stack([filt.features(_embeds(rng)) for _ in range(12)])
        for c in range(3):
            chunk = feats[c * 4:(c + 1) * 4]
            s_run, _s, _k = runner.consume(s_run, w, chunk)
            for t in range(4):
                s_seq, _keep, _m = filt.step(s_seq, w, chunk[t])
                s_seq = ring.maybe_rotate(s_seq, 8, 1.0)
        assert bool(jnp.all(s_run.counts == s_seq.counts))
        assert int(s_run.cursor) == int(s_seq.cursor)
        assert runner.trace_count == 1

    def test_unaligned_rotate_every_rejected(self):
        with pytest.raises(ValueError, match="rotate_every"):
            StreamRunner(self._filter(rotate_every=7), chunk_T=10)

    def test_flat_filter_with_rotate_every_rejected(self):
        with pytest.raises(ValueError, match="windowed"):
            StreamRunner(AceDataFilter(d_model=8), chunk_T=4,
                         rotate_every=2)

    def test_rotation_adds_no_retraces_or_transfers(self, monkeypatch):
        """The windowed runner with in-scan rotation stays ONE compiled
        executable across chunks, and the host driver still pulls
        exactly one D2H per chunk — rotation costs zero extra syncs."""
        filt = self._filter()
        runner = StreamRunner(filt, chunk_T=4)
        state, w = runner.init()
        pulls = []
        orig = jax.device_get

        def counting(x):
            pulls.append(1)
            return orig(x)

        monkeypatch.setattr(jax, "device_get", counting)
        rng = np.random.default_rng(8)
        batches = [np.asarray(filt.features(_embeds(rng)))
                   for _ in range(12)]
        state, summaries = runner.run(state, w, batches)
        assert len(summaries) == 3
        assert len(pulls) == 3, \
            f"{len(pulls)} D2H pulls for 3 chunks (want exactly 1 each)"
        assert runner.trace_count == 1
        # rotations actually happened on schedule: 12 steps / R=4
        assert int(state.tick) == 12
        assert int(state.cursor) == 0      # 3 rotations mod E=3

    def test_summary_n_is_ring_total(self):
        filt = self._filter()
        runner = StreamRunner(filt, chunk_T=4)
        state, w = runner.init()
        rng = np.random.default_rng(9)
        feats = jnp.stack([filt.features(_embeds(rng)) for _ in range(4)])
        state, summary = runner.consume(state, w, feats)
        assert float(summary.n) == float(jnp.sum(state.n))

    @pytest.mark.slow
    def test_sharded_layouts_match_single_device(self):
        """Windowed scan ingest under repro.dist placements (jit/SPMD):
        replicated and table-sharded epoch rings must match the
        single-device runner bitwise on counts/tail/cursor (fake
        2-device CPU mesh in a subprocess)."""
        code = """
            import jax, jax.numpy as jnp, numpy as np
            from repro.window import WindowedAceFilter
            from repro.stream import StreamRunner

            filt = WindowedAceFilter(d_model=8, num_bits=6, num_tables=10,
                                     warmup_items=16.0, alpha=3.0,
                                     num_epochs=3, rotate_every=2)
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
                assert np.array_equal(
                    np.asarray(jax.device_get(s.counts)),
                    np.asarray(jax.device_get(s_ref.counts))), layout
                assert np.array_equal(
                    np.asarray(jax.device_get(s.tail)),
                    np.asarray(jax.device_get(s_ref.tail))), layout
                assert int(s.cursor) == int(s_ref.cursor), layout
                assert float(jnp.sum(s.n)) == float(jnp.sum(s_ref.n))
                np.testing.assert_allclose(
                    float(s.ssq), float(s_ref.ssq), rtol=1e-6)

            # shard_map-mode E-way windowed score builder: per-epoch
            # partials psum BEFORE the gamma weighting, so it matches
            # the replicated combine bitwise for every gamma
            from repro.dist.sketch_parallel import \\
                make_table_sharded_window_score
            from repro.window import ring, epoch_weights, score_windowed
            cfg = filt.ace_cfg
            q = jnp.asarray(rng.normal(size=(8, cfg.dim)), jnp.float32)
            for gamma in (1.0, 0.6):
                wts = epoch_weights(s_ref.cursor, 3, gamma)
                scr = make_table_sharded_window_score(mesh, cfg)
                got = scr(s_ref.counts, wts, q, w)
                import repro.core.srp as srp
                want = score_windowed(
                    s_ref, srp.hash_buckets(q, w, cfg.srp), gamma)
                assert np.array_equal(np.asarray(got),
                                      np.asarray(want)), gamma
            print("WINDOW-LAYOUTS-MATCH")
        """
        env = dict(os.environ)
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                            + env.get("XLA_FLAGS", ""))
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, timeout=420,
                             env=env)
        assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
        assert "WINDOW-LAYOUTS-MATCH" in out.stdout


# ---------------------------------------------------------------------------
# Windowed guardrail + kernel-path admission.
# ---------------------------------------------------------------------------

class TestWindowedGuardrail:
    def _gcfg(self):
        from repro.serve.engine import GuardrailConfig
        return GuardrailConfig(d_model=12, num_bits=6, num_tables=8,
                               warmup_items=32.0, alpha=3.0,
                               window_epochs=3, rotate_every=4)

    def test_one_executable_and_ring_advances(self):
        from repro.serve.engine import Guardrail
        g = Guardrail(self._gcfg())
        rng = np.random.default_rng(10)
        for _ in range(9):
            admit = g.admit(_embeds(rng, D=12))
        assert g.trace_count == 1
        assert int(g.state.tick) == 9
        assert int(g.state.cursor) == 2          # 2 rotations, E=3
        assert admit.shape == (8,)

    def test_kernel_path_matches_jnp_windowed_sequence(self):
        """ops.ace_admit_windowed (SRHT/dense hash dispatch + fused
        E-way combine kernel + shared ring helpers) reproduces the jnp
        windowed admission sequence: same masks, same counts/tail."""
        from repro.kernels import ops
        cfg = _cfg()
        w = sk.make_params(cfg)
        rng = np.random.default_rng(11)
        st_k = st_j = ring.init(cfg, 3)
        for i in range(6):
            q = jnp.asarray(rng.normal(size=(16, cfg.dim)) + 1.0,
                            jnp.float32)
            st_k, mk = ops.ace_admit_windowed(
                st_k, q, w, cfg, gamma=0.8, alpha=2.0,
                warmup_items=16.0, rotate_every=2)
            b = srp.hash_buckets(q, w, cfg.srp)
            ts, ls = ring.window_table_sums(st_j, b)
            s = ring.score_live(ts, ls, cfg.num_tables)
            mj = s >= ring.admit_threshold_windowed(st_j, 0.8, 2.0, 16.0)
            st_j = ring.insert_current(st_j, b, mj, cfg, gamma=0.8,
                                       pre_sums=(ts, ls))
            st_j = ring.maybe_rotate(st_j, 2, 0.8)
            assert bool(jnp.all(mk == mj)), i
        assert bool(jnp.all(st_k.counts == st_j.counts))
        assert_allclose_dtype(st_k.tail, st_j.tail, rtol=1e-6)

    def test_windowed_guardrail_recovers_from_traffic_shift(self):
        """After a regime shift, the frozen guardrail keeps rejecting the
        new inlier traffic forever (it can never re-learn: rejects are
        not inserted); the windowed guardrail's stale epochs expire, its
        window drains below warmup, and it re-admits + re-learns."""
        from repro.serve.engine import Guardrail, GuardrailConfig
        common = dict(d_model=12, num_bits=8, num_tables=16,
                      warmup_items=64.0, alpha=2.0)
        frozen = Guardrail(GuardrailConfig(**common))
        windowed = Guardrail(GuardrailConfig(
            **common, window_epochs=3, rotate_every=6))
        rng = np.random.default_rng(12)
        mu_a = np.zeros(12); mu_a[:6] = 3.0
        mu_b = np.zeros(12); mu_b[6:] = 3.0

        def batch(mu):
            return jnp.asarray(
                rng.normal(size=(16, 4, 12)) * 0.3 + mu, jnp.float32)

        fa, wa = [], []
        for _ in range(20):                      # regime A
            fa.append(frozen.admit(batch(mu_a)).mean())
            wa.append(windowed.admit(batch(mu_a)).mean())
        # both armed and admitting the in-distribution traffic (the
        # windowed σ is tighter, so allow the odd borderline flag)
        assert np.mean(fa[-5:]) > 0.8 and np.mean(wa[-5:]) > 0.7
        f_admit, w_admit = [], []
        for i in range(30):                      # regime B
            f_admit.append(frozen.admit(batch(mu_b)).mean())
            w_admit.append(windowed.admit(batch(mu_b)).mean())
        # frozen never recovers; windowed re-admits after the window
        # (3 epochs × 6 calls) has drained the stale regime
        assert np.mean(f_admit[-5:]) < 0.2, f_admit
        assert np.mean(w_admit[-5:]) > 0.8, w_admit


# ---------------------------------------------------------------------------
# Checkpoint round-tripping of the ring state.
# ---------------------------------------------------------------------------

class TestWindowCheckpoint:
    def test_ring_state_roundtrips_exactly(self, tmp_path):
        """save → restore reproduces every leaf of the ring bitwise —
        cursor and tick (int32 scalars) included."""
        from repro.train import checkpoint as ck
        cfg = _cfg()
        rng = np.random.default_rng(13)
        st = ring.init(cfg, 3)
        for _ in range(5):
            st = ring.insert_current(st, _buckets(rng, 9, cfg),
                                     jnp.ones((9,), bool), cfg)
            st = ring.maybe_rotate(st, 2, 1.0)
        ck.save(str(tmp_path), 1, st)
        like = jax.tree.map(jnp.zeros_like, st)
        restored, _manifest = ck.restore(str(tmp_path), 1, like)
        for got, want in zip(restored, st):
            assert np.asarray(got).dtype == np.asarray(want).dtype
            assert np.array_equal(np.asarray(got), np.asarray(want))
        # restore hands back host arrays (device placement is the
        # caller's shardings choice) — put them back on device to resume
        restored = jax.tree.map(jnp.asarray, restored)
        assert int(restored.cursor) == int(st.cursor)
        assert int(restored.tick) == int(st.tick)
        # the restored ring keeps operating identically
        b = _buckets(rng, 9, cfg)
        m = jnp.ones((9,), bool)
        a = ring.insert_current(restored, b, m, cfg)
        bb = ring.insert_current(st, b, m, cfg)
        assert bool(jnp.all(a.counts == bb.counts))
        assert float(a.ssq) == float(bb.ssq)


# ---------------------------------------------------------------------------
# Phase scopes (repro.core.obs) on the windowed stream path.
# ---------------------------------------------------------------------------

_SCOPE_FILTER = dict(d_model=8, num_bits=7, num_tables=5, warmup_items=16.0,
                     num_epochs=3, rotate_every=2, insert_all=True)
_TABLE = 5 << 7            # L * 2^K: every ring or tail operand is a multiple
_COMP = re.compile(r"^(?:ENTRY )?%([\w.-]+) ")
_INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.-]+ = \S+ ([\w-]+)\((.*)$")
_CALLS = re.compile(r"(?:to_apply|body|condition|calls|true_computation|"
                    r"false_computation)=%([\w.-]+)|"
                    r"branch_computations=\{([^}]*)\}")


def _scoped_ops(text: str):
    """(opcode, operand element counts, scoped) of every instruction of a
    pre-optimization HLO module printed with metadata and operand shapes;
    an instruction is scoped when its own ``op_name`` or that of an
    instruction calling its computation holds an ``ace/`` scope."""
    comp, rows, callers = None, [], {}
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        own = bool(name and "ace/" in name.group(1))
        sizes = [int(np.prod([int(x) for x in dims.split(",") if x]))
                 for dims in re.findall(r"\w+\[([\d,]*)\]\S* %", m.group(2))]
        rows.append((comp, m.group(1), sizes, own))
        for one, many in _CALLS.findall(line):
            for callee in ([one] if one else
                           re.findall(r"%([\w.-]+)", many)):
                callers.setdefault(callee, []).append((comp, own))
    memo = {}

    def scoped(c):
        if c not in memo:
            memo[c] = False
            up = callers.get(c, [])
            memo[c] = bool(up) and all(own or scoped(cc) for cc, own in up)
        return memo[c]

    return [(op, sizes, own or scoped(c)) for c, op, sizes, own in rows]


@functools.lru_cache(maxsize=None)
def _window_programs():
    """Pre-optimization HLO text of the windowed consume program (R | T,
    rotation at a segment boundary) and of the filter's featurization."""
    from jax._src.lib import xla_client as xc

    opts = xc._xla.HloPrintOptions()
    opts.print_metadata = True
    opts.print_operand_shape = True
    filt = WindowedAceFilter(**_SCOPE_FILTER)
    runner = StreamRunner(filt, chunk_T=4, topk=4, return_masks=True)
    state, w = runner.init()
    feats = jnp.ones((4, 16, 9), jnp.float32)
    texts = []
    for lowered in (runner._consume.lower(state, w, feats),
                    jax.jit(filt.features).lower(
                        jnp.ones((16, 2, 8), jnp.float32))):
        texts.append(lowered.compiler_ir("hlo").as_hlo_module().to_string(
            opts))
    return tuple(texts)


class TestWindowScopes:
    def test_phases_are_the_stream_phases_and_rotate(self):
        phases = set()
        for text in _window_programs():
            phases |= set(re.findall(r'op_name="(?:[^"]*/)?ace/([\w.-]+)/',
                                     text))
        assert phases == {"features", "hash", "score", "threshold",
                          "insert", "rescore", "rotate", "summary"}, phases

    def test_ring_and_tail_ops_carry_a_scope(self):
        """Every gather, scatter, dot and reduce that reads the ring or the
        tail (an operand of a multiple of L * 2^K elements) is in an
        ``ace/`` scope; and there are such ops of each kind."""
        ops = [(op, own) for op, sizes, own in _scoped_ops(
            _window_programs()[0])
            if op in ("gather", "scatter", "dot", "reduce")
            and any(s and s % _TABLE == 0 for s in sizes)]
        assert {op for op, _ in ops} == {"gather", "scatter", "dot",
                                         "reduce"}, ops
        assert all(own for _, own in ops), ops
