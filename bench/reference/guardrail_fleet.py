"""Reference replay and comparison for the multi-tenant guardrail.

Every tenant keeps its own sketch.  A batch, as the front end formed it, is
scored against each request's own tenant's counts before any of the batch
is inserted; the requests at or above their tenant's threshold are admitted
and inserted together, and each tenant's Welford stream folds the post-insert
rates of its admitted requests.  Float64, exact integer counts.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import ace


@dataclasses.dataclass
class FleetOutputs:
    verdicts: list            # per batch: (n,) bool admitted
    counts: np.ndarray        # (T, L, 2^K) counts after the window
    n: np.ndarray             # (T,) items per tenant
    margin: list | None = None    # per batch: (n,) score - threshold
    thr: list | None = None       # per batch: (n,) the request's threshold


def replay(batches, buckets: np.ndarray, *, num_tenants: int, num_bits: int,
           alpha: float, warmup: float, arith: str = "float64") -> FleetOutputs:
    """``batches``: [(pool rows (n,), tenants (n,))] in service order;
    ``buckets``: (pool, L) bucket ids of the request pool; ``arith`` the
    precision of the derived floats (``ace.rounding``)."""
    rnd = ace.rounding(arith)
    T, L = num_tenants, buckets.shape[1]
    NB = 1 << num_bits
    counts = np.zeros(T * L * NB, np.int32)
    sq = np.zeros(T)
    n = np.zeros(T)
    mean = np.zeros(T)
    m2 = np.zeros(T)
    min_n = warmup / 2
    cols = np.arange(L, dtype=np.int64) * NB
    verdicts, margins, thrs = [], [], []
    for rows, tids in batches:
        tids = np.asarray(tids, np.int64)
        offs = buckets[rows] + cols[None, :] + (tids * (L * NB))[:, None]
        score = rnd(counts[offs].sum(axis=1, dtype=np.int64) / L)
        thr = ace.threshold(sq, n, m2, alpha, warmup, L, rnd)[tids]
        admit = score >= thr
        u, k = np.unique(offs[admit], return_counts=True)
        cu = counts[u].astype(np.int64)
        sq += np.bincount(u // (L * NB), weights=2 * cu * k + k * k,
                          minlength=T)
        counts[u] += k.astype(np.int32)
        post = rnd(counts[offs].sum(axis=1, dtype=np.int64) / L)
        b = np.bincount(tids, weights=admit, minlength=T)
        tot = n + b
        rates = rnd(post / np.maximum(tot, 1.0)[tids])
        mean_b = rnd(np.bincount(tids, weights=rates * admit, minlength=T)
                     / np.maximum(b, 1.0))
        m2_b = rnd(np.bincount(tids, weights=(rates - mean_b[tids]) ** 2
                               * admit, minlength=T))
        mean, m2 = ace.welford_fold(mean, m2, n, b, mean_b, m2_b, min_n, rnd)
        n = tot
        verdicts.append(admit)
        margins.append(rnd(score - thr))
        thrs.append(thr)
    return FleetOutputs(verdicts=verdicts, counts=counts.reshape(T, L, NB),
                        n=n, margin=margins, thr=thrs)


def compare(got: FleetOutputs, ref: FleetOutputs) -> dict:
    """The numbers compared with the reference (0 where equal)."""
    served = sum(len(v) for v in ref.verdicts)
    off = sum(int(np.sum((g != r) & ~ace.near_threshold(m, t)))
              for g, r, m, t in zip(got.verdicts, ref.verdicts, ref.margin,
                                    ref.thr))
    L = ref.counts.shape[1]
    d = np.abs(got.counts.astype(np.int64) - ref.counts).sum(dtype=np.int64)
    total = max(float(ref.n.sum()), 1.0)
    return {
        "verdicts_off": off / max(served, 1),
        "counts_off": float(d) / (2.0 * L * total),
        "n_off": float(np.abs(got.n - ref.n).sum()) / total,
    }
