"""Device-resident guardrail admission: masked-insert equivalence, the
one-executable compile contract, layout parity on a 1×2 CPU mesh, and the
fused-kernel path agreeing with the jnp reference path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import assert_allclose_dtype
from repro.core import sketch as sk
from repro.core.sketch import AceConfig
from repro.serve.engine import Guardrail, GuardrailConfig

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 2, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def _seeded_state(cfg: AceConfig, seed: int, n_prior: int = 30):
    """A sketch with a prior batch inserted so n > 0 and σ is live."""
    rng = np.random.default_rng(seed)
    w = sk.make_params(cfg)
    x = jnp.asarray(rng.normal(size=(n_prior, cfg.dim)), jnp.float32)
    return sk.insert(sk.init(cfg), w, x, cfg), w, rng


class TestMaskedInsertEquivalence:
    @settings(max_examples=20, deadline=None)
    @given(B=st.integers(1, 48), K=st.integers(3, 8), L=st.integers(1, 12),
           seed=st.integers(0, 1000), density=st.integers(0, 10))
    def test_masked_equals_gather_insert(self, B, K, L, seed, density):
        """insert_buckets_masked(mask) ≡ insert_buckets(buckets[mask]):
        counts/n/μ exact, Welford within float tolerance."""
        cfg = AceConfig(dim=6, num_bits=K, num_tables=L, seed=seed % 7,
                        welford_min_n=float(seed % 3) * 8.0)
        state, _, rng = _seeded_state(cfg, seed)
        buckets = jnp.asarray(
            rng.integers(0, 1 << K, size=(B, L)), jnp.int32)
        mask_np = rng.random(B) < density / 10.0
        mask = jnp.asarray(mask_np)

        got = sk.insert_buckets_masked(state, buckets, mask, cfg)
        if mask_np.any():
            want = sk.insert_buckets(state, buckets[mask_np], cfg)
            assert bool(jnp.all(got.counts == want.counts))
            assert float(got.n) == float(want.n)
            assert float(sk.mean_mu(got)) == float(sk.mean_mu(want))
            assert_allclose_dtype(got.welford_mean, want.welford_mean)
            assert_allclose_dtype(got.welford_m2, want.welford_m2,
                                  rtol=1e-4, atol=1e-7)
        else:
            # empty admit: state must be untouched (the dense path would
            # NaN on a (0, L) batch — the masked path must not)
            assert bool(jnp.all(got.counts == state.counts))
            assert float(got.n) == float(state.n)
            assert float(got.welford_mean) == float(state.welford_mean)
            assert float(got.welford_m2) == float(state.welford_m2)

    def test_all_true_mask_is_plain_insert(self):
        cfg = AceConfig(dim=6, num_bits=6, num_tables=10, seed=0)
        state, _, rng = _seeded_state(cfg, 5)
        buckets = jnp.asarray(rng.integers(0, 64, size=(20, 10)), jnp.int32)
        got = sk.insert_buckets_masked(state, buckets,
                                       jnp.ones(20, bool), cfg)
        want = sk.insert_buckets(state, buckets, cfg)
        assert bool(jnp.all(got.counts == want.counts))
        assert float(got.n) == float(want.n)
        assert_allclose_dtype(got.welford_mean, want.welford_mean)
        assert_allclose_dtype(got.welford_m2, want.welford_m2)

    @pytest.mark.parametrize("B", [1, 7, 249])
    @pytest.mark.parametrize("K", [2, 3, 8])
    @pytest.mark.parametrize("mask_kind", ["random", "ones", "zeros"])
    @pytest.mark.parametrize("dup", [False, True])
    def test_derived_post_insert_equals_the_gather(self, B, K, mask_kind,
                                                   dup):
        """The post-insert counts derived from the pre-insert gather plus
        the in-batch collisions equal the gather from the updated table
        exactly, and the insert that uses them (``pre=``) equals the one
        that gathers again, bitwise: counts, n, Welford.  2^K <= 8 forces
        heavy collisions; ``dup`` repeats whole rows of bucket ids."""
        L = 5
        cfg = AceConfig(dim=6, num_bits=K, num_tables=L, seed=1,
                        welford_min_n=4.0)
        state, _, rng = _seeded_state(cfg, 100 * B + K)
        ids = rng.integers(0, 1 << K, size=(B, L))
        if dup and B > 1:
            ids[1::3] = ids[0]
        buckets = jnp.asarray(ids, jnp.int32)
        mask = jnp.asarray({"random": rng.random(B) < 0.7,
                            "ones": np.ones(B, bool),
                            "zeros": np.zeros(B, bool)}[mask_kind])

        pre = sk.gather_counts(state.counts, buckets)
        got = sk.insert_buckets_masked(state, buckets, mask, cfg, pre=pre)
        want = sk.insert_buckets_masked(state, buckets, mask, cfg)
        post = pre + sk.batch_collisions(buckets, mask)
        np.testing.assert_array_equal(
            np.asarray(post), np.asarray(sk.gather_counts(want.counts,
                                                          buckets)))
        for name in ("counts", "n", "welford_mean", "welford_m2"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    def test_collisions_match_a_loop(self):
        """``batch_collisions`` against the definition, item by item."""
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 4, size=(33, 6))
        w = rng.random(33) < 0.5
        got = np.asarray(sk.batch_collisions(jnp.asarray(ids, jnp.int32),
                                             jnp.asarray(w)))
        want = np.array([[int(np.sum(w & (ids[:, l] == ids[i, l])))
                          for l in range(6)] for i in range(33)])
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("B,gathers", [
        (sk.PAIRWISE_MAX_BATCH, 1), (sk.PAIRWISE_MAX_BATCH + 1, 2)])
    def test_large_batch_gathers_again(self, B, gathers):
        """Past ``PAIRWISE_MAX_BATCH`` items the quadratic collision count
        would cost more than the gather, so the insert given ``pre``
        gathers again (traced: one gather, the caller's, or two), with
        the same result either way."""
        cfg = AceConfig(dim=4, num_bits=4, num_tables=1)
        state = sk.init(cfg)
        ids = jnp.asarray(np.random.default_rng(4).integers(
            0, 16, size=(B, 1)), jnp.int32)
        mask = jnp.ones(B, bool)

        def insert(counts, ids):
            st = state._replace(counts=counts)
            return sk.insert_buckets_masked(
                st, ids, mask, cfg, pre=sk.gather_counts(counts, ids))

        jaxpr = str(jax.make_jaxpr(insert)(state.counts, ids))
        assert jaxpr.count(" gather[") == gathers
        got = insert(state.counts, ids)
        want = sk.insert_buckets_masked(state, ids, mask, cfg)
        for name in ("counts", "n", "welford_mean", "welford_m2"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    @pytest.mark.parametrize("mask_kind", ["random", "ones", "zeros"])
    def test_derived_insert_matches_replicated_update(self, mask_kind):
        """The replicated ``repro.dist`` layout, which gathers its own
        post-insert scores, stays bitwise equal to the derived insert."""
        from repro.dist.sketch_parallel import update_global_masked
        cfg = AceConfig(dim=6, num_bits=3, num_tables=7, seed=2,
                        welford_min_n=4.0)
        state, w, rng = _seeded_state(cfg, 9)
        x = jnp.asarray(rng.normal(size=(40, 6)), jnp.float32)
        mask = jnp.asarray({"random": rng.random(40) < 0.6,
                            "ones": np.ones(40, bool),
                            "zeros": np.zeros(40, bool)}[mask_kind])
        buckets = sk.hash_buckets(x, w, cfg.srp)
        got = sk.insert_buckets_masked(
            state, buckets, mask, cfg,
            pre=sk.gather_counts(state.counts, buckets))
        want = update_global_masked(state, x, w, mask, cfg)
        for name in ("counts", "n", "welford_mean", "welford_m2"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)


class TestAdmitThreshold:
    def test_warmup_is_minus_inf(self):
        cfg = AceConfig(dim=4, num_bits=4, num_tables=4, seed=0)
        state = sk.init(cfg)
        t = sk.admit_threshold(state, alpha=2.0, warmup_items=10.0)
        assert float(t) == float("-inf")

    def test_armed_matches_rate_rule(self):
        cfg = AceConfig(dim=6, num_bits=5, num_tables=6, seed=1)
        state, _, _ = _seeded_state(cfg, 3, n_prior=40)
        t = sk.admit_threshold(state, alpha=1.5, warmup_items=10.0)
        want = (float(sk.mean_rate(state))
                - 1.5 * float(sk.sigma_welford(state))) * float(state.n)
        assert_allclose_dtype(t, np.float32(want))


class TestGuardrailCompileOnce:
    @pytest.mark.parametrize("use_kernels", [False, True])
    def test_traces_once_across_varying_admitted_counts(self, use_kernels):
        """The regression this PR exists for: the pre-PR admit retraced on
        every distinct admitted-count (data-dependent gather shape); the
        masked insert is fixed-shape, so exactly ONE trace serves them
        all."""
        g = Guardrail(GuardrailConfig(d_model=12, num_bits=6, num_tables=8,
                                      warmup_items=48.0, alpha=3.0),
                      use_kernels=use_kernels)
        rng = np.random.default_rng(7)
        base_dir = rng.normal(size=16)
        admitted = []
        for i in range(10):
            e = rng.normal(size=(24, 3, 12)).astype(np.float32) * 0.05
            e += base_dir[:12] * 2.0          # tight in-distribution cluster
            if i >= 3:                        # growing OOD fraction
                k = min(3 * (i - 2), 24)
                e[:k] = rng.normal(size=(k, 3, 12)) * 4.0
            mask = g.admit(jnp.asarray(e))
            assert mask.shape == (24,) and mask.dtype == np.bool_
            admitted.append(int(mask.sum()))
        assert g.trace_count == 1, admitted
        assert len(set(admitted)) > 1, (
            f"test vacuous: admitted counts never varied ({admitted})")

    def test_state_stays_on_device(self):
        """At most one host transfer per batch: the returned mask.  The
        sketch state threading through admit must remain jax Arrays (no
        np round-trip of counts/n)."""
        g = Guardrail(GuardrailConfig(d_model=8, num_bits=5, num_tables=4,
                                      warmup_items=8.0))
        e = jnp.asarray(np.random.default_rng(0).normal(size=(8, 2, 8)),
                        jnp.float32)
        mask = g.admit(e)
        assert isinstance(mask, np.ndarray)
        assert isinstance(g.state.counts, jax.Array)
        assert isinstance(g.state.n, jax.Array)

    def test_kernel_path_matches_reference_path(self):
        """Kernel vs jnp admit paths.  The kernel's tiled hash may flip a
        sign where |proj| ~ 0 (the srp kernels' documented 0.1% bucket
        tolerance), so masks get a tiny slack; with zero flips — the case
        on this toolchain — the downstream state must match exactly."""
        cfgkw = dict(d_model=12, num_bits=6, num_tables=8,
                     warmup_items=32.0, alpha=2.0)
        gj = Guardrail(GuardrailConfig(**cfgkw))
        gk = Guardrail(GuardrailConfig(**cfgkw), use_kernels=True)
        rng = np.random.default_rng(11)
        mismatch, total = 0, 0
        for i in range(6):
            e = jnp.asarray(rng.normal(size=(16, 3, 12)), jnp.float32)
            mj, mk = gj.admit(e), gk.admit(e)
            mismatch += int((mj != mk).sum())
            total += mj.size
        assert mismatch / total < 0.01, f"{mismatch}/{total} masks differ"
        assert abs(float(gj.state.n) - float(gk.state.n)) <= mismatch
        if mismatch == 0:
            assert bool(jnp.all(gj.state.counts == gk.state.counts))
            np.testing.assert_allclose(float(gj.state.welford_m2),
                                       float(gk.state.welford_m2),
                                       rtol=1e-5)

    def test_kernels_plus_mesh_rejected(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        with pytest.raises(ValueError):
            Guardrail(GuardrailConfig(d_model=8), mesh=mesh,
                      use_kernels=True)


class TestMaskedLayoutParity:
    @pytest.mark.slow
    def test_masked_insert_replicated_vs_table_sharded(self):
        """The masked insert keeps the replicated↔table-sharded parity
        contract: counts/n bitwise, Welford to float32 round-off, on the
        1×2 CPU mesh."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import sketch as sk
            from repro.core.sketch import AceConfig
            from repro.dist.sketch_parallel import (
                make_masked_update, make_table_sharded_masked_update,
                table_sharded_shardings)

            cfg = AceConfig(dim=8, num_bits=6, num_tables=10, seed=0,
                            welford_min_n=16.0)
            mesh = jax.make_mesh((1, 2), ("data", "model"))
            w = sk.make_params(cfg)
            rng = np.random.default_rng(0)
            xs = [jnp.asarray(rng.normal(size=(48, 8)), jnp.float32)
                  for _ in range(3)]
            masks = [jnp.asarray(rng.random(48) < p) for p in (1.0, .6, .3)]

            ref = sk.init(cfg)
            for x, m in zip(xs, masks):
                bk = sk.hash_buckets(x, w, cfg.srp)
                ref = sk.insert_buckets_masked(ref, bk, m, cfg)

            rep_upd = make_masked_update(mesh, cfg)
            ts_upd = make_table_sharded_masked_update(mesh, cfg)
            with jax.set_mesh(mesh):
                rep = sk.init(cfg)
                ts = jax.device_put(sk.init(cfg),
                                    table_sharded_shardings(mesh))
                for x, m in zip(xs, masks):
                    rep = rep_upd(rep, x, w, m)
                    ts = ts_upd(ts, x, w, m)

            for name, got in (("replicated", rep), ("table_sharded", ts)):
                assert bool(jnp.all(jnp.asarray(got.counts)
                                    == ref.counts)), name + " counts"
                assert float(got.n) == float(ref.n), name + " n"
                np.testing.assert_allclose(float(got.welford_mean),
                                           float(ref.welford_mean),
                                           rtol=1e-6)
                np.testing.assert_allclose(float(got.welford_m2),
                                           float(ref.welford_m2), rtol=1e-6)
            assert bool(jnp.all(jnp.asarray(ts.counts)
                                == jnp.asarray(rep.counts)))
            print("MASKED_PARITY_OK")
        """)
        assert "MASKED_PARITY_OK" in out

    @pytest.mark.slow
    def test_guardrail_admit_table_sharded_jit_mode(self):
        """Guardrail.admit (jit/SPMD mode) keeps the table-sharded
        placement through the masked insert and still traces once."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            import repro.core.sketch  # noqa: F401
            from repro.serve.engine import Guardrail, GuardrailConfig

            mesh = jax.make_mesh((1, 2), ("data", "model"))
            g = Guardrail(GuardrailConfig(d_model=16, num_bits=6,
                                          num_tables=8, warmup_items=32.0),
                          mesh=mesh, sketch_layout="table_sharded")
            rng = np.random.default_rng(0)
            for _ in range(4):
                m = g.admit(jnp.asarray(rng.normal(size=(16, 4, 16)),
                                        jnp.float32))
            assert g.trace_count == 1, g.trace_count
            spec = g.state.counts.sharding.spec
            assert tuple(spec)[0] == "model", spec
            assert float(g.state.n) == 64.0
            print("SHARDED_ADMIT_OK", spec)
        """)
        assert "SHARDED_ADMIT_OK" in out
