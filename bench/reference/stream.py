"""Reference replay and comparison for the stream configurations.

The stream is the stand-in data set replayed pass after pass, in batches of
B rows, every row inserted (detector mode).  Because every pass inserts the
same rows in the same order, the counts before step s of pass p are exactly
``p * H + C_s``, where H is one whole pass's histogram and C_s the histogram
of the pass's first s steps.  The replay walks one pass once and derives
every step of the window from these sums, in float64 with exact integer
counts, so its cost does not grow with the number of passes.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import ace


@dataclasses.dataclass
class StreamOutputs:
    keep: np.ndarray          # (G, B) bool: kept (not flagged) per item
    counts: np.ndarray        # (L, 2^K) integer counts after the window
    n: float
    welford_mean: float
    welford_m2: float
    topk: list                # [(global step, item, margin)] per chunk
    margin: np.ndarray | None = None   # (G, B) score - threshold
    thr: np.ndarray | None = None      # (G,) threshold before each step
    fragile: np.ndarray | None = None  # (G, B) items a rounding may rehash


def replay(buckets: np.ndarray, B: int, steps: int, *, num_bits: int,
           alpha: float, warmup: float, chunk_T: int,
           topk: int | None = None, arith: str = "float64",
           fragile: np.ndarray | None = None) -> StreamOutputs:
    """ACE over ``steps`` batches of B rows of the pass ``buckets`` (N, L);
    with ``topk``, also each chunk's report of its most anomalous items.
    ``arith`` is the precision of the derived floats (``ace.rounding``);
    ``fragile`` (N,) marks the pass's items whose hash a rounding may flip."""
    rnd = ace.rounding(arith)
    N, L = buckets.shape
    NB = 1 << num_bits
    S = N // B
    if S * B != N:
        raise ValueError(f"{N} rows are not whole batches of {B}")
    offs = buckets + (np.arange(L, dtype=np.int64) * NB)[None, :]
    c = np.zeros(L * NB, np.int64)
    pre = np.empty(N)
    post = np.empty(N)
    qc = np.zeros(S + 1)              # sum of C_s squared
    for s in range(S):
        o = offs[s * B:(s + 1) * B]
        pre[s * B:(s + 1) * B] = c[o].sum(axis=1)
        u, k = np.unique(o, return_counts=True)
        qc[s + 1] = qc[s] + float(np.sum(2 * c[u] * k + k * k))
        c[u] += k
        post[s * B:(s + 1) * B] = c[o].sum(axis=1)
    hist = c
    sh = hist[offs].sum(axis=1).astype(np.float64)   # (N,) sum of H at x's cells
    qh = float(np.sum(hist.astype(np.float64) ** 2))
    xh = np.concatenate([[0.0], np.cumsum(sh.reshape(S, B).sum(axis=1))])

    min_n = warmup / 2
    G = steps
    keep = np.empty((G, B), bool)
    margin = np.empty((G, B))
    thr = np.empty(G)
    mean = m2 = 0.0
    for p in range(-(-G // S)):
        s_hi = min(S, G - p * S)
        sl = slice(0, s_hi * B)
        score_pre = rnd(((p * sh[sl] + pre[sl]) / L).reshape(s_hi, B))
        score_post = rnd(((p * sh[sl] + post[sl]) / L).reshape(s_hi, B))
        g0 = p * S
        n_pre = (g0 + np.arange(s_hi)) * float(B)
        rates = rnd(score_post / np.maximum(n_pre + B, 1.0)[:, None])
        mean_b = rnd(rates.mean(axis=1))
        m2_b = rnd(((rates - mean_b[:, None]) ** 2).sum(axis=1))
        q_pre = p * p * qh + 2.0 * p * xh[:s_hi] + qc[:s_hi]
        for s in range(s_hi):
            thr[g0 + s] = float(ace.threshold(q_pre[s], n_pre[s], m2, alpha,
                                              warmup, L, rnd))
            mean, m2 = (float(v) for v in ace.welford_fold(
                mean, m2, n_pre[s], float(B), mean_b[s], m2_b[s], min_n, rnd))
        th = thr[g0:g0 + s_hi][:, None]
        keep[g0:g0 + s_hi] = score_pre >= th
        margin[g0:g0 + s_hi] = rnd(score_pre - th)

    passes, s_last = divmod(G, S)
    counts = passes * hist + np.bincount(
        offs[:s_last * B].ravel(), minlength=L * NB)
    if fragile is not None:
        fragile = fragile.reshape(S, B)[np.arange(G) % S]
    return StreamOutputs(
        keep=keep, counts=counts.reshape(L, NB), n=float(G * B),
        welford_mean=mean, welford_m2=m2,
        topk=chunk_topk(margin, chunk_T, topk) if topk else [],
        margin=margin, thr=thr, fragile=fragile)


def chunk_topk(margin: np.ndarray, chunk_T: int, k: int) -> list:
    """Each chunk's k most anomalous items that were flagged, as the
    stream's chunk summary reports them: (global step, item, margin)."""
    G, B = margin.shape
    out = []
    for g0 in range(0, G, chunk_T):
        m = margin[g0:g0 + chunk_T].ravel()
        idx = np.argpartition(m, min(k, m.size - 1))[:k]
        idx = idx[np.argsort(m[idx], kind="stable")]
        out += [(g0 + int(i) // B, int(i) % B, float(m[i])) for i in idx
                if np.isfinite(m[i]) and m[i] < 0]
    return out


def compare(got: StreamOutputs, ref: StreamOutputs) -> dict:
    """The numbers compared with the reference (0 where equal).  Verdicts
    and margins leave out the items that a float32 rounding may put in
    other buckets (``ref.fragile``) or on the other side of the threshold."""
    L = ref.counts.shape[0]
    skip = ace.near_threshold(ref.margin, ref.thr[:, None])
    if ref.fragile is not None:
        skip |= ref.fragile
    diff = (got.keep != ref.keep) & ~skip
    topk = [abs(m - ref.margin[g, i]) / max(abs(ref.thr[g]), 1.0)
            for g, i, m in got.topk
            if ref.fragile is None or not ref.fragile[g, i]]
    d = np.abs(got.counts.astype(np.int64) - ref.counts).sum()
    return {
        "counts_off": float(d) / (2.0 * L * max(ref.n, 1.0)),
        "keep_off": float(diff.sum()) / diff.size,
        "topk_off": max(topk, default=0.0),
        "welford_off": max(
            abs(got.welford_mean - ref.welford_mean)
            / max(abs(ref.welford_mean), 1e-30),
            abs(got.welford_m2 - ref.welford_m2)
            / max(abs(ref.welford_m2), 1e-30)),
        "n_off": abs(got.n - ref.n) / max(ref.n, 1.0),
    }
