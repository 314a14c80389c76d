"""Reference replay and comparison for the windowed stream configurations.

The stream is the stand-in data set replayed pass after pass, in batches of
B rows, every row inserted (detector mode), into a ring of E epoch sketches
that rotates every R steps (a hard window, decay 1).  Written from the
configuration's guarantees, in float64 with exact integer counts:

* an item's score is the mean over the L tables of the window's counts at
  its buckets, where the window is the sum of the E epochs as they stood
  before the item's batch; its threshold is the mu - alpha*sigma rule of
  ``ace.threshold`` over the window's n, the window's sum of squared counts
  and the epochs' Welford streams merged by Chan's rule;
* every item is inserted once, in order, into the live epoch, whose Welford
  stream folds the batch's post-insert rates (score over the window's n
  after the batch) with the epoch's own n as the stream length;
* after every R steps the oldest epoch leaves the window: its counts, n and
  moments are zeroed and it becomes the live epoch.  The tail is the sum of
  the epochs other than the live one.

When a pass of S steps is a whole number P of epochs, epoch k holds the rows
of the pass's epoch k mod P, and its state depends only on k mod P once its
E - 1 predecessors are whole epochs (k >= E - 1) and theirs are too (k >=
2E - 2).  ``replay`` walks the passes up to and including the first that
begins at or after epoch 2E - 2, and derives every later step, and the
final ring, from that pass; ``derive=False`` walks every step.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import ace
from bench.reference.stream import chunk_topk


@dataclasses.dataclass
class WindowOutputs:
    keep: np.ndarray          # (G, B) bool: kept (not flagged) per item
    counts: np.ndarray        # (E, L, 2^K) integer counts of each ring slot
    tail: np.ndarray          # (L, 2^K) the sum of the non-live epochs
    n: np.ndarray             # (E,) items per epoch
    welford_mean: np.ndarray  # (E,) per-epoch rate mean
    welford_m2: np.ndarray    # (E,) per-epoch rate M2
    ssq: float                # the window's sum of squared counts
    cursor: int               # the live epoch's ring slot
    topk: list                # [(global step, item, margin)] per chunk
    margin: np.ndarray | None = None   # (G, B) score - threshold
    thr: np.ndarray | None = None      # (G,) threshold before each step
    fragile: np.ndarray | None = None  # (G, B) items a rounding may rehash


def merge_moments(n, mean, m2, order, rnd):
    """The window's (n, mean, M2): Chan's rule over the epochs in
    ``order``, starting from the first epoch's own moments; ``rnd`` rounds
    a scalar."""
    e0 = order[0]
    n_acc, mean_acc, m2_acc = n[e0], mean[e0], m2[e0]
    for e in order[1:]:
        delta = mean[e] - mean_acc
        tot = n_acc + n[e]
        safe = max(tot, 1.0)
        mean_acc = rnd(mean_acc + delta * n[e] / safe)
        m2_acc = rnd(m2_acc + m2[e] + delta * delta * n_acc * n[e] / safe)
        n_acc = tot
    return n_acc, mean_acc, m2_acc


def _walk(offs, B, steps, *, size, E, R, L, alpha, warmup, rnd,
          snapshot_at):
    """Walks ``steps`` batches of the pass ``offs`` (N, L), flat offsets
    into a table of ``size`` counts: per-step keep, margin and threshold,
    and the state after ``snapshot_at`` steps."""
    S = offs.shape[0] // B

    def rnd1(v):                        # a rounded scalar, as a float
        return float(np.ravel(rnd(v))[0])

    ring = np.zeros((E, size), np.int64)
    tail = np.zeros(size, np.int64)
    n, mean, m2 = np.zeros(E), np.zeros(E), np.zeros(E)
    ssq, c = 0.0, 0
    min_n = warmup / 2
    keep = np.empty((steps, B), bool)
    margin = np.empty((steps, B))
    thr = np.empty(steps)
    snap = None
    for g in range(steps + 1):
        if g == snapshot_at:
            snap = (ring.copy(), tail.copy(), n.copy(), mean.copy(),
                    m2.copy(), ssq, c)
        if g == steps:
            break
        o = offs[(g % S) * B:(g % S + 1) * B]
        live = ring[c]
        tail_s = tail[o].sum(axis=1)
        score = rnd((tail_s + live[o].sum(axis=1)) / L)
        order = [(c + 1 + i) % E for i in range(E)]      # oldest first
        n_w = rnd1(n.sum())
        _, _, m2_w = merge_moments(n, mean, m2, order, rnd1)
        thr[g] = rnd1(ace.threshold(ssq, n_w, m2_w, alpha, warmup, L, rnd))
        keep[g] = score >= thr[g]
        margin[g] = rnd(score - thr[g])

        u, k = np.unique(o, return_counts=True)
        ssq = rnd1(ssq + float(np.sum(2 * (tail[u] + live[u]) * k + k * k)))
        live[u] += k
        score_post = rnd((tail_s + live[o].sum(axis=1)) / L)
        rates = rnd(score_post / max(rnd1(n_w + B), 1.0))
        mean_b = rnd1(rates.mean())
        m2_b = rnd1(((rates - mean_b) ** 2).sum())
        mean[c], m2[c] = (rnd1(v) for v in ace.welford_fold(
            mean[c], m2[c], n[c], float(B), mean_b, m2_b, min_n, rnd))
        n[c] = rnd1(n[c] + B)
        if R and (g + 1) % R == 0:
            new = (c + 1) % E
            tail += ring[c]
            tail -= ring[new]
            ring[new] = 0
            n[new] = mean[new] = m2[new] = 0.0
            ssq = rnd1(float(np.dot(tail, tail)))
            c = new
    return keep, margin, thr, snap


def replay(buckets: np.ndarray, B: int, steps: int, *, num_bits: int,
           num_epochs: int, rotate_every: int, alpha: float, warmup: float,
           chunk_T: int, topk: int | None = None, arith: str = "float64",
           fragile: np.ndarray | None = None,
           derive: bool = True) -> WindowOutputs:
    """The windowed detector over ``steps`` batches of B rows of the pass
    ``buckets`` (N, L), rotating every ``rotate_every`` steps (0: never);
    with ``topk``, also each chunk's report of its most anomalous items.
    ``arith`` is the precision of the derived floats (``ace.rounding``);
    ``fragile`` (N,) marks the pass's items whose hash a rounding may
    flip."""
    rnd = ace.rounding(arith)
    N, L = buckets.shape
    NB = 1 << num_bits
    E, R = num_epochs, rotate_every
    S = N // B
    if S * B != N:
        raise ValueError(f"{N} rows are not whole batches of {B}")
    offs = buckets + (np.arange(L, dtype=np.int64) * NB)[None, :]
    walk = dict(size=L * NB, E=E, R=R, L=L, alpha=alpha, warmup=warmup,
                rnd=rnd)
    G = steps
    periodic = derive and R > 0 and S % R == 0
    if periodic:
        first = -(-(2 * E - 2) // (S // R)) * S    # first periodic pass
    if periodic and G > first + S:
        final = first + (G - first) % S
        shift = ((G - final) // R) % E
        keep_w, margin_w, thr_w, snap = _walk(offs, B, first + S,
                                              snapshot_at=final, **walk)
        idx = np.arange(G)
        idx[first + S:] = first + (idx[first + S:] - first) % S
        keep, margin, thr = keep_w[idx], margin_w[idx], thr_w[idx]
    else:
        shift = 0
        keep, margin, thr, snap = _walk(offs, B, G, snapshot_at=G, **walk)
    ring, tail, n, mean, m2, ssq, c = snap
    if fragile is not None:
        fragile = fragile.reshape(S, B)[np.arange(G) % S]
    return WindowOutputs(
        keep=keep, counts=np.roll(ring, shift, axis=0).reshape(E, L, NB),
        tail=tail.reshape(L, NB), n=np.roll(n, shift),
        welford_mean=np.roll(mean, shift), welford_m2=np.roll(m2, shift),
        ssq=ssq, cursor=(c + shift) % E,
        topk=chunk_topk(margin, chunk_T, topk) if topk else [],
        margin=margin, thr=thr, fragile=fragile)


def compare(got: WindowOutputs, ref: WindowOutputs) -> dict:
    """The numbers compared with the reference (0 where equal).  Verdicts
    and margins leave out the items that a float32 rounding may put in
    other buckets (``ref.fragile``) or on the other side of the
    threshold."""
    L = ref.counts.shape[1]
    n_w = max(float(np.sum(ref.n)), 1.0)
    skip = ace.near_threshold(ref.margin, ref.thr[:, None])
    if ref.fragile is not None:
        skip |= ref.fragile
    diff = (got.keep != ref.keep) & ~skip
    topk = [abs(m - ref.margin[g, i]) / max(abs(ref.thr[g]), 1.0)
            for g, i, m in got.topk
            if ref.fragile is None or not ref.fragile[g, i]]

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))

    def off(a, b):
        d = np.abs(np.asarray(a, np.int64) - np.asarray(b, np.int64)).sum()
        return float(d) / (2.0 * L * n_w)

    return {
        "counts_off": off(got.counts, ref.counts),
        "tail_off": off(got.tail, ref.tail),
        "keep_off": float(diff.sum()) / diff.size,
        "topk_off": max(topk, default=0.0),
        "welford_off": max(rel(got.welford_mean, ref.welford_mean),
                           rel(got.welford_m2, ref.welford_m2)),
        "n_off": float(np.max(np.abs(np.asarray(got.n, np.float64)
                                     - ref.n))) / n_w,
        "ssq_off": abs(got.ssq - ref.ssq) / max(ref.ssq, 1.0),
    }
