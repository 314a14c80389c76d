"""Deterministic, checkpointable data pipeline with the ACE anomaly filter.

The paper's original deployment surface: a high-rate stream where each
record must be scored in O(K·L) against a 4 MB sketch BEFORE it reaches the
expensive consumer (here: the training loss).

* Determinism & restart: batches are a pure function of (seed, step) — the
  iterator state IS the step counter, so checkpoint/restart and elastic
  re-sharding reproduce the exact stream (fault-tolerance substrate).
* Filtering: per-sequence feature = mean token embedding (or the stub
  frame/patch embedding mean), bias-augmented; scored against the running
  sketch; sequences below μ − α·σ get loss-mask 0 (skip) but still advance
  the stream.  The sketch updates ONLINE with non-anomalous items only.
* Poisoning injection (for tests/examples): ``corrupt_every`` swaps a batch
  with far-out-of-cone garbage, which the filter must catch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.core import sketch as sk
from repro.core import srp
from repro.core.axes import on_auto_axes
from repro.core.sketch import AceConfig


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    corrupt_every: int = 0        # 0 = clean stream
    n_docs: int = 4096            # synthetic corpus size


def synth_batch(cfg: StreamConfig, step: int) -> dict[str, np.ndarray]:
    """Markov-ish synthetic LM batch, pure function of (seed, step)."""
    rng = np.random.default_rng(cfg.seed * 1_000_003 + step)
    B, S, V = cfg.global_batch, cfg.seq_len, cfg.vocab_size
    # low-entropy structured stream: random walk over the vocab
    start = rng.integers(0, V, (B, 1))
    steps = rng.integers(-3, 4, (B, S - 1))
    toks = np.concatenate([start, start + np.cumsum(steps, axis=1)], axis=1)
    toks = np.mod(toks, V).astype(np.int32)
    batch = {"tokens": toks, "labels": toks,
             "mask": np.ones((B, S), np.float32)}
    if cfg.corrupt_every and step % cfg.corrupt_every == cfg.corrupt_every - 1:
        # poisoned batch: uniform garbage tokens (very different embedding
        # statistics from the random-walk stream)
        batch["tokens"] = rng.integers(0, V, (B, S)).astype(np.int32)
        batch["labels"] = batch["tokens"]
        batch["_poisoned"] = np.ones((), np.bool_)
    return batch


class DataStream:
    """Stateless-iterator facade: state == step (checkpoint-friendly)."""

    def __init__(self, cfg: StreamConfig, start_step: int = 0):
        self.cfg = cfg
        self.step = start_step

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        return self

    def __next__(self):
        b = synth_batch(self.cfg, self.step)
        self.step += 1
        return b

    def state_dict(self):
        return {"step": self.step}

    def load_state_dict(self, s):
        self.step = int(s["step"])


# ---------------------------------------------------------------------------
# ACE data filter (jit-compatible; compiled into train_step)
# ---------------------------------------------------------------------------

def mean_embed_features(embeds: jax.Array, bias_const: float) -> jax.Array:
    """(B, S, D) embeddings -> (B, D+1) unit-mean + bias features.

    Unit-normalised mean embedding + a bias coordinate: direction drift
    is what the angular SRP sees; the bias re-encodes magnitude at a
    controlled weight.  THE featurisation — shared by the flat
    ``AceDataFilter`` and the windowed ``repro.window.WindowedAceFilter``
    so frozen-vs-windowed comparisons (and the E=1 bitwise contract)
    rest on identical features by construction, not by copy-sync.
    """
    with obs.scope("features"):
        f = jnp.mean(embeds.astype(jnp.float32), axis=1)
        f = f / (jnp.linalg.norm(f, axis=-1, keepdims=True) + 1e-9)
        bias = jnp.full((f.shape[0], 1), bias_const, jnp.float32)
        return jnp.concatenate([f, bias], axis=-1)


@dataclasses.dataclass(frozen=True)
class AceDataFilter:
    d_model: int
    num_bits: int = 13
    num_tables: int = 32
    alpha: float = 4.0
    warmup_items: float = 512.0
    bias_const: float = 0.25
    hash_mode: str = "dense"     # "dense" | "srht" | "auto" (SrpConfig)
    insert_all: bool = False     # detector mode: still flag (keep=False)
                                 # but insert EVERY item — for monitoring
                                 # a stream you don't gate (benchmarks,
                                 # dashboards); default is filter mode
                                 # (anomalies never enter the sketch)
    count_dtype: str = "int32"   # narrow planes ("int16"/"int8") cut the
                                 # table and its gather bandwidth 2–4×
    esc_capacity: int = 0        # > 0: exact overflow promotion
                                 # (repro.core.quantize)
    threshold_mode: str = "mu_sigma"   # "mu_sigma" | "quantile" — admit
                                 # rule (repro.quantile); quantile mode
                                 # targets a per-stream false-positive
                                 # RATE q instead of a σ-multiple, which
                                 # μ−ασ cannot hold on heavy-tailed
                                 # score distributions
    quantile_q: float = 0.01     # target flag rate for quantile mode
    attr_rows: int = 0           # > 0: heavy-hitter attribution planes
                                 # (repro.attribution) ride the state
    attr_bits: int = 8           # log2 columns per attribution row

    @property
    def ace_cfg(self) -> AceConfig:
        return AceConfig(dim=self.d_model + 1, num_bits=self.num_bits,
                         num_tables=self.num_tables, seed=29,
                         welford_min_n=self.warmup_items / 2,
                         hash_mode=self.hash_mode,
                         counter_dtype=self.count_dtype,
                         esc_capacity=self.esc_capacity,
                         attr_rows=self.attr_rows,
                         attr_bits=self.attr_bits)

    def init(self):
        state = sk.init(self.ace_cfg)
        if self.threshold_mode == "quantile":
            from repro.quantile import sketch as qsk
            state = state._replace(qhist=qsk.init_hist())
        return state, sk.make_params(self.ace_cfg)

    def features(self, embeds: jax.Array) -> jax.Array:
        """(B, S, D) token/patch/frame embeddings -> (B, D+1) features
        (see ``mean_embed_features``)."""
        return mean_embed_features(embeds, self.bias_const)

    @on_auto_axes
    def step(self, state, w, feat, table_mask=None):
        """One filter step over precomputed features: hash ONCE, score from
        the same bucket ids, threshold on-device, masked insert.

        Returns (new_state, keep (B,) bool, margin (B,) float32) where
        ``margin = score − threshold`` (most-negative = most anomalous;
        +inf during warmup, when the threshold is −inf and everything is
        kept).  This is the scan body of ``repro.stream.StreamRunner`` and
        the filter path compiled into ``train_step`` — ONE implementation
        for both, so chunked and per-batch ingest stay equivalent by
        construction.

        Entry-point sanitization (repro.resilience): rows with non-finite
        features are zeroed before hashing, never kept, never inserted
        (even under ``insert_all``), and marked with ``margin = −inf`` so
        drivers can count them as quarantined.  The pre-fix behaviour
        silently inserted them at one bucket per table, skewing counts
        and ssq/μ forever — training data fails CLOSED (garbage must not
        train or enter the sketch).  For all-finite batches the
        sanitization is bitwise identity.

        ``table_mask`` (L,) f32, when given, scores and thresholds over
        healthy tables only (the repro.resilience degraded mode); None
        traces no mask code.

        The decision matches the pre-rewrite μ−ασ rate-space rule moved to
        score space via ``sk.admit_threshold`` (multiply both sides by
        max(n, 1) > 0); the insert + Welford fold delegate to
        ``sk.insert_buckets_masked`` → ``sk.masked_batch_welford``, the
        same single-homed helpers as the serving guardrail and both
        ``repro.dist`` layouts.  The table is read once: the score's
        gather (``sk.lookup_gathered``, unmasked) goes to the insert as
        ``pre``, which derives the post-insert scores from it.  Two
        behaviour notes vs the old inline
        block (both unifications, property-tested in tests/test_stream.py):
        the Welford stream now folds POST-insert scores (Algorithm 1 line
        12's x-vs-D∪{x} convention, like every other insert path) where
        the old code folded pre-insert scores, and the ``welford_min_n``
        cold-start gate declared in ``ace_cfg`` is now actually honoured
        (the hand-rolled block ignored it).
        """
        cfg = self.ace_cfg
        finite = jnp.all(jnp.isfinite(feat), axis=-1)
        feat = jnp.where(finite[:, None], feat, 0.0)
        buckets = srp.hash_buckets(feat, w, cfg.srp)   # the ONE hash
        scores, pre = sk.lookup_gathered(              # same bucket ids
            state, buckets, table_mask=table_mask)
        thresh = sk.admit_threshold(state, self.alpha, self.warmup_items,
                                    table_mask=table_mask,
                                    threshold_mode=self.threshold_mode,
                                    q=self.quantile_q)
        keep = jnp.logical_and(scores >= thresh, finite)
        margin = jnp.where(finite, scores - thresh, -jnp.inf)
        ins = finite if self.insert_all else keep
        new_state = sk.insert_buckets_masked(state, buckets, ins, cfg,
                                             pre=pre)
        if self.threshold_mode == "quantile":
            # Calibration stream: EVERY finite-scored item feeds the rate
            # histogram — observing only admitted items would freeze the
            # rejected tail out of the empirical CDF and the Q_q threshold
            # would self-reinforce upward (classic quantile-feedback bug).
            from repro.quantile import sketch as qsk
            rates = scores / jnp.maximum(state.n, 1.0)
            new_state = new_state._replace(qhist=qsk.observe_rates(
                new_state.qhist, rates,
                qsk.calib_mask(finite.astype(jnp.float32), state.n,
                               self.warmup_items)))
        return new_state, keep, margin

    @on_auto_axes
    def __call__(self, state, w, embeds, mask):
        """Score + filter + update.  Returns (new_state, new_mask, frac_kept).

        mask: (B, S) loss mask; anomalous sequences are zeroed out.
        """
        feat = self.features(embeds)                       # (B, d+1)
        new_state, keep, _margin = self.step(state, w, feat)
        new_mask = mask * keep[:, None].astype(mask.dtype)
        return new_state, new_mask, jnp.mean(keep.astype(jnp.float32))
