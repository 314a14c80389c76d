"""Test hooks of the ``stream_window`` kind, found by the configuration's
``kind`` (``tests/bench``): the sizes a CPU test can hold, the control at a
test size, and the faults planted in the timed path."""
from __future__ import annotations

import os

import numpy as np

from bench import common

# The stream kind's three, and an expiring epoch whose counts are kept.
FAULTS = ("state_unchanged", "half_batch", "answer", "no_expiry")


def tiny(cfg: dict, mix: dict):
    """(configuration override, mix override) at sizes a CPU test can hold;
    as in the cell, a pass is a whole number of epochs (5 of 2 steps) and
    the window (4 epochs) is shorter than a pass."""
    return (dict(rows=500, anomalies=10, num_bits=8, num_tables=8,
                 warmup_items=64, num_epochs=4, rotate_every=2,
                 epoch_rows=100), dict(chunk_T=2, chunk_B=50))


def control_numbers() -> dict:
    """The numbers compared for the reference with bf16x3 projections and
    bfloat16 floats against the float64 reference, at widths where a
    rounding of the projections flips some signs: K=15, L=50, an 8-epoch
    window of 0.8 of a pass."""
    from bench.reference import ace
    from bench.reference import stream_window as window_ref
    from bench.traffic import stream

    x = stream.standin(5, 19600, 40, 36, 5)
    w = np.random.default_rng(1).standard_normal((37, 768))
    out = {}
    for prec, dt, arith in (("float64", np.float64, "float64"),
                            ("bf16x3", np.float32, "bfloat16")):
        b = ace.hash_buckets(ace.features(x[:, None, :], 0.25, dt),
                             w.astype(np.float32), 15, 50, prec)
        out[prec] = window_ref.replay(
            b, 392, 150, num_bits=15, num_epochs=8, rotate_every=5,
            alpha=1.0, warmup=512, chunk_T=5, topk=8, arith=arith)
    return window_ref.compare(out["bf16x3"], out["float64"])


def plant(fault: str, monkeypatch) -> None:
    """Breaks the timed path by ``fault``: ``StreamRunner.consume`` as the
    stream kind does, or the ring's rotation (``no_expiry``)."""
    if fault != "no_expiry":
        common.load_module(os.path.join(
            common.BENCH, "kinds", "stream.py")).plant(fault, monkeypatch)
        return
    from repro.window import ring

    orig = ring.rotate

    def rotate(state, gamma=1.0):
        return orig(state, gamma)._replace(counts=state.counts)

    monkeypatch.setattr(ring, "rotate", rotate)
