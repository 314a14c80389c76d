"""Signed Random Projections (SRP) — the LSH family used by ACE.

The paper (§2.1) uses the Goemans–Williamson / Charikar family

    h_w(x) = sign(w^T x),   w ~ N(0, I_d)

with collision probability  Pr[h_w(x) = h_w(y)] = 1 − θ(x, y)/π.

ACE needs K·L independent SRP bits per input, grouped into L meta-hashes of
K bits each; the K bits are packed into an integer bucket id in [0, 2^K).

TPU adaptation: all K·L projections are one (B, d) @ (d, K·L) matmul (MXU),
followed by a sign + bit-pack epilogue (VPU).  ``K*L`` is padded up to a
multiple of 128 internally so the matmul is lane-aligned; pad lanes are
discarded before packing.  The Pallas kernel in ``repro.kernels.srp_hash``
implements the same contract with explicit VMEM tiling; this module is the
reference / small-scale path and the parameter factory.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs

LANE = 128  # TPU vector lane width; MXU is 128x128.


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


HASH_MODES = ("dense", "srht", "auto")


@dataclasses.dataclass(frozen=True)
class SrpConfig:
    """Static configuration of an SRP meta-hash bank.

    Attributes:
      dim:  input dimensionality d.
      num_bits: K — bits per meta-hash (bucket space is 2^K).
      num_tables: L — number of independent meta-hashes / count arrays.
      seed: PRNG seed for the projection matrix.
      pad_lanes: if True, the projection matrix is materialised with K*L
        rounded up to a multiple of 128 (extra columns are ignored at pack
        time).  The paper uses K=15, L=50 -> 750 projections; we compute 768.
      hash_mode: which hash construction every hot path uses —
        ``"dense"`` (the O(d·KL) Gaussian matmul, MXU), ``"srht"`` (the
        O(d log d + KL) Fast-JL transform of paper §2.2, VPU —
        ``repro.core.srht``), or ``"auto"`` (the throughput-weighted
        break-even ``repro.core.srht.choose_hash_mode`` picks per config).
        The two families draw DIFFERENT hash functions: a sketch built
        under one mode must be queried under the same mode (the mode is
        part of the persisted-sketch contract, like ``seed``).
    """

    dim: int
    num_bits: int = 15
    num_tables: int = 50
    seed: int = 0
    pad_lanes: bool = True
    hash_mode: str = "dense"

    @property
    def num_projections(self) -> int:
        return self.num_bits * self.num_tables

    @property
    def padded_projections(self) -> int:
        if not self.pad_lanes:
            return self.num_projections
        return _round_up(self.num_projections, LANE)

    @property
    def num_buckets(self) -> int:
        return 1 << self.num_bits


def make_projections(cfg: SrpConfig, dtype=jnp.float32) -> jax.Array:
    """Sample the (d, K*L_padded) Gaussian projection matrix.

    The first K*L columns are the live projections (column j*K + k is bit k of
    meta-hash j); trailing pad columns are only there for lane alignment.

    When the config resolves to the SRHT hash family, the matrix is never
    consumed — return a (d, 0) placeholder instead of materialising (and
    threading through every jitted program) what would be ~37 MB of dead
    fp32 at d_model=12288.  The placeholder keeps every ``(state, w, x)``
    call signature intact.
    """
    if resolve_hash_mode(cfg) == "srht":
        return jnp.zeros((cfg.dim, 0), dtype=dtype)
    key = jax.random.PRNGKey(cfg.seed)
    w = jax.random.normal(key, (cfg.dim, cfg.padded_projections), dtype=dtype)
    return w


def srp_bits(x: jax.Array, w: jax.Array, cfg: SrpConfig) -> jax.Array:
    """Raw sign bits.  x: (..., d) -> (..., K*L) int32 in {0, 1}.

    sign(0) is defined as +1 (bit 1) so the map is deterministic; with
    Gaussian projections the event has measure zero for real data anyway.
    """
    # HIGHEST: on a TPU the default f32 matmul rounds its operands to
    # bf16, which flips the sign of near-zero projections and would make
    # the bucket ids depend on the backend (and disagree with the Pallas
    # kernels, whose f32 matmuls are exact).
    proj = jnp.einsum("...d,dp->...p", x, w.astype(x.dtype),
                      precision=jax.lax.Precision.HIGHEST)
    bits = (proj >= 0).astype(jnp.int32)
    return bits[..., : cfg.num_projections]


def pack_buckets(bits: jax.Array, cfg: SrpConfig) -> jax.Array:
    """Pack K-bit groups into bucket ids.  (..., K*L) -> (..., L) int32.

    Bit k of meta-hash j is column j*K + k; packing is big-endian on k
    (first bit = MSB) — any fixed convention works, it only has to match the
    kernel and stay stable across versions (sketch state is persisted).
    """
    K, L = cfg.num_bits, cfg.num_tables
    grouped = bits.reshape(*bits.shape[:-1], L, K)
    weights = (1 << jnp.arange(K - 1, -1, -1, dtype=jnp.int32))
    return jnp.sum(grouped * weights, axis=-1, dtype=jnp.int32)


def resolve_hash_mode(cfg: SrpConfig) -> str:
    """Resolve ``cfg.hash_mode`` to a concrete family (auto → break-even)."""
    if cfg.hash_mode not in HASH_MODES:
        raise ValueError(f"unknown hash_mode {cfg.hash_mode!r} "
                         f"(want one of {HASH_MODES})")
    if cfg.hash_mode == "auto":
        from repro.core import srht  # local: srht imports this module
        return srht.choose_hash_mode(cfg)
    return cfg.hash_mode


def hash_buckets(x: jax.Array, w: jax.Array, cfg: SrpConfig) -> jax.Array:
    """Full SRP meta-hash: (..., d) -> (..., L) bucket ids in [0, 2^K).

    THE hash entry point of every jnp hot path (sketch insert/score, both
    ``repro.dist`` layouts, the data filter, the stream runner): dispatches
    on ``cfg.hash_mode`` between the dense matmul and the SRHT fast path,
    so flipping the knob re-routes them all at once.  ``w`` is ignored
    under ``"srht"`` (the transform is parameterised by sign diagonals and
    a row sample derived from ``cfg.seed``) but keeps its place in the
    signature so the two families are drop-in interchangeable.
    """
    with obs.scope("hash"):
        if resolve_hash_mode(cfg) == "srht":
            from repro.core import srht  # local: srht imports this module
            return srht.srht_hash_buckets(x, srht.srht_params(cfg))
        return pack_buckets(srp_bits(x, w, cfg), cfg)


def collision_probability(q: jax.Array, x: jax.Array) -> jax.Array:
    """p(q, x) = 1 − θ/π for SRP (paper Eq. 1).  Broadcasts over leading dims."""
    qn = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-12)
    xn = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
    cos = jnp.clip(jnp.sum(qn * xn, axis=-1), -1.0, 1.0)
    return 1.0 - jnp.arccos(cos) / jnp.pi


@partial(jax.jit, static_argnames=("cfg",))
def hash_buckets_jit(x: jax.Array, w: jax.Array, cfg: SrpConfig) -> jax.Array:
    return hash_buckets(x, w, cfg)


def projection_memory_bytes(cfg: SrpConfig, dtype_bytes: int = 4) -> int:
    """Memory to store the projections (paper §3.4: ~6d KB for K=15,L=50)."""
    return cfg.dim * cfg.padded_projections * dtype_bytes


def seeds_memory_bytes(cfg: SrpConfig) -> int:
    """Paper's alternative: store K*L integer seeds, regenerate rows on the fly."""
    return cfg.num_projections * 4
