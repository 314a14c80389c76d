"""Test hooks of the ``stream`` kind, found by the configuration's ``kind``
(``tests/bench``): the sizes a CPU test can hold, the control at a test
size, and the faults planted in the timed path."""
from __future__ import annotations

import numpy as np

FAULTS = ("state_unchanged", "half_batch", "answer")


def tiny(cfg: dict, mix: dict):
    """(configuration override, mix override) at sizes a CPU test can hold;
    the semantics are the cell's own."""
    return (dict(rows=2000, anomalies=20, num_bits=8, num_tables=8,
                 warmup_items=64), dict(chunk_T=2, chunk_B=50))


def control_numbers() -> dict:
    """The numbers compared for the reference with bf16x3 projections
    against the float64 reference, at widths where a rounding of the
    projections flips some signs."""
    from bench.reference import ace
    from bench.reference import stream as stream_ref
    from bench.traffic import stream

    x = stream.standin(5, 19600, 40, 36, 5)
    w = np.random.default_rng(1).standard_normal((37, 768))
    out = {}
    for prec, dt in (("float64", np.float64), ("bf16x3", np.float32)):
        b = ace.hash_buckets(ace.features(x[:, None, :], 0.25, dt),
                             w.astype(np.float32), 15, 50, prec)
        out[prec] = stream_ref.replay(b, 392, 150, num_bits=15,
                                      alpha=1.0, warmup=512, chunk_T=2,
                                      topk=8)
    return stream_ref.compare(out["bf16x3"], out["float64"])


def plant(fault: str, monkeypatch) -> None:
    """Breaks ``StreamRunner.consume``, the timed path, by ``fault``."""
    import jax
    import jax.numpy as jnp

    from repro.stream import StreamRunner

    orig = StreamRunner.consume

    def consume(self, state, w, feats, *a, **k):
        if fault == "half_batch":
            feats = feats.at[:, feats.shape[1] // 2:].set(jnp.nan)
        kept = jax.tree.map(jnp.copy, state)
        new, summ, keep = orig(self, state, w, feats, *a, **k)
        if fault == "answer":
            keep = keep.at[0, 0].set(~keep[0, 0])
        return (kept if fault == "state_unchanged" else new), summ, keep

    monkeypatch.setattr(StreamRunner, "consume", consume)
