"""Plain NumPy reference of ACE's semantics (arXiv:1706.06664, Algorithm 1
with the paper's mu - alpha*sigma rule), independent of the code under test.

Everything here is written from the paper and from the semantics the
configurations state, in float64 on the host, with exact integer counts:

* features: the unit-normalised mean of a request's rows, with a constant
  bias coordinate appended;
* hash: K*L signed random projections with sign(0) = +1; bits j*K .. j*K+K-1
  form table j's bucket id, the first bit the most significant;
* score: the mean over the L tables of the counts at the item's buckets;
* threshold, in score space: (mu/n - alpha*sigma) * max(n, 1) once n has
  reached the warm-up count, -inf before; mu = sum of squared counts over
  (n*L) (the paper's Eq. 11 in closed form), sigma the Welford standard
  deviation of post-insert collision rates (score/n), folded per batch and
  restarted while n is below half the warm-up count.

The control of the correctness check is this reference one step below the
precision each quantity is stated in: the float32 projections at ``HIGHEST``
rounded as a TPU matmul at ``Precision.HIGH`` rounds them (three bfloat16
passes, ``hash_buckets(..., "bf16x3")``), and every other float32 quantity
(scores, rates, moments, thresholds) rounded to bfloat16 (``rounding``).
Both are emulated on the host, so the control reads the same on any backend.
"""
from __future__ import annotations

import numpy as np

ROW_BLOCK = 32768


def features(x: np.ndarray, bias: float, dtype=np.float64) -> np.ndarray:
    """(n, S, D) rows -> (n, D+1): unit mean direction plus a bias column."""
    f = np.asarray(x, dtype).mean(axis=1)
    f = f / (np.linalg.norm(f, axis=-1, keepdims=True) + 1e-9)
    return np.concatenate([f, np.full((f.shape[0], 1), bias, dtype)], axis=1)


def _bf16(a: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), held in float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    u = (u + (((u >> 16) & 1) + 0x7FFF)) & 0xFFFF0000
    return u.view(np.float32)


def _project(f: np.ndarray, w: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return f.astype(np.float64) @ w.astype(np.float64)
    if precision == "bf16x3":
        f32, w32 = f.astype(np.float32), w.astype(np.float32)
        fh, wh = _bf16(f32), _bf16(w32)
        fl, wl = _bf16(f32 - fh), _bf16(w32 - wh)
        return fh @ wh + fh @ wl + fl @ wh
    raise ValueError(f"unknown precision {precision!r}")


def hash_buckets(f: np.ndarray, w: np.ndarray, num_bits: int,
                 num_tables: int, precision: str = "float64",
                 fragile_below: float = 0.0):
    """(n, d) features, (d, >= K*L) projections -> (n, L) int64 bucket ids.

    With ``fragile_below`` > 0, also (n,) bool: the items with a projection
    whose cosine with its column lies within ``fragile_below`` of 0, whose
    bucket a float32 rounding of the projection may flip."""
    K, L = num_bits, num_tables
    # columns reordered bit-major, (k, j), so each bit's L signs are adjacent
    w = np.asarray(w)[:, :K * L].reshape(-1, L, K).transpose(0, 2, 1)
    w = np.ascontiguousarray(w.reshape(-1, K * L))
    out = np.empty((f.shape[0], L), np.int64)
    fragile = np.zeros(f.shape[0], bool)
    inv_scale = 1.0 / np.linalg.norm(w.astype(np.float64), axis=0)
    for r0 in range(0, f.shape[0], ROW_BLOCK):
        fb = f[r0:r0 + ROW_BLOCK]
        proj = _project(fb, w, precision)
        bits = (proj >= 0).reshape(-1, K, L)
        ids = np.zeros((bits.shape[0], L), np.int32)
        for k in range(K):                      # the first bit is the highest
            ids <<= 1
            ids |= bits[:, k]
        out[r0:r0 + ROW_BLOCK] = ids
        if fragile_below > 0:
            np.abs(proj, out=proj)
            proj *= inv_scale
            cos = proj.min(axis=1) / np.linalg.norm(fb, axis=1)
            fragile[r0:r0 + ROW_BLOCK] = cos < fragile_below
    return (out, fragile) if fragile_below > 0 else out


def rounding(arith: str):
    """The rounding applied to each derived float quantity: none for the
    float64 reference, to bfloat16 for the control."""
    if arith == "float64":
        return lambda a: a
    if arith == "bfloat16":
        return lambda a: _bf16(np.asarray(a, np.float32)).astype(np.float64)
    raise ValueError(f"unknown arithmetic {arith!r}")


def _exact(a):
    return a


def threshold(sq_sum, n, m2, alpha: float, warmup: float, num_tables: int,
              rnd=_exact):
    """Score-space admission threshold (scalar or per-tenant arrays)."""
    n = np.asarray(n, np.float64)
    nn = np.maximum(n, 1.0)
    mu_rate = rnd(np.asarray(sq_sum, np.float64) / (nn * num_tables) / nn)
    sigma = rnd(np.sqrt(rnd(np.asarray(m2, np.float64)
                            / np.maximum(n - 1.0, 1.0))))
    return np.where(n >= warmup, rnd((mu_rate - alpha * sigma) * nn),
                    -np.inf)


def welford_fold(mean, m2, n, b, mean_b, m2_b, min_n: float, rnd=_exact):
    """Fold one batch's rate statistics (b items) into the stream; returns
    the new (mean, m2).  Below ``min_n`` the stream restarts at the batch."""
    tot = np.maximum(n + b, 1.0)
    delta = mean_b - mean
    gated = n >= min_n
    new_mean = rnd(np.where(gated, mean + delta * b / tot, mean_b))
    new_m2 = rnd(np.where(gated, m2 + m2_b + delta * delta * n * b / tot,
                          m2_b))
    has = b > 0
    return np.where(has, new_mean, mean), np.where(has, new_m2, m2)


def near_threshold(margin, thr, rel: float = 1e-4) -> np.ndarray:
    """Items whose score lies within ``rel`` of a finite threshold (margin =
    score - threshold): float32 and float64 thresholds may fall on either
    side of them."""
    thr = np.asarray(thr, np.float64)
    with np.errstate(invalid="ignore"):
        return np.isfinite(thr) & (np.abs(margin)
                                   <= rel * np.maximum(np.abs(thr), 1.0))
