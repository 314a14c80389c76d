"""CPU tests of the windowed stream's plain reference
(``bench/reference/stream_window.py``): the program at a test size gives its
counts, n, sum of squares and verdicts; a one-epoch window that never
rotates is the flat stream's reference; and the replay derived from one
periodic pass is the plain walk of every step."""
from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common, run  # noqa: E402
from bench.reference import stream as stream_ref  # noqa: E402
from bench.reference import stream_window as window_ref  # noqa: E402

CELL = "stream-kdd99-window"


def _buckets(seed=0, rows=600, tables=6, bits=5):
    return np.random.default_rng(seed).integers(0, 1 << bits,
                                                size=(rows, tables))


def _same(a, b) -> None:
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            assert np.array_equal(x, y), f.name
        else:
            assert x == y, f.name


def test_program_gives_the_reference_counts_and_verdicts(tmp_path,
                                                         monkeypatch):
    """``StreamRunner.consume`` around ``WindowedAceFilter`` at the kind's
    test size, over many passes: every count of every epoch, the tail, each
    epoch's n, the window's sum of squares and every verdict are the
    reference's."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    _, cfg, _ = run.cell_spec(CELL, run.benchmark())
    hooks = common.load_module(os.path.join(common.BENCH, "kinds",
                                            cfg["kind"] + ".py"))
    over_cfg, over_mix = hooks.tiny(cfg, {})
    d = run.drive(CELL, 2**31 + 21, 0.5, False, require_tpu=False,
                  cfg_override=over_cfg, mix_override=over_mix)
    passes = d.out.attempted / over_cfg["rows"]
    assert passes > 4, passes
    numbers = d.out.check()
    for k in ("counts_off", "tail_off", "n_off", "ssq_off", "keep_off"):
        assert numbers[k] == 0, numbers


@pytest.mark.parametrize("arith", ["float64", "bfloat16"])
@pytest.mark.parametrize("epochs,every", [(8, 2), (4, 2), (3, 5), (1, 4)])
def test_derived_replay_is_the_plain_walk(epochs, every, arith):
    """Over more than four passes, ending mid-pass and mid-epoch, the replay
    that derives the later passes from the first periodic one equals the
    walk of every step, in the reference's and the control's arithmetic."""
    b = _buckets()
    S = 600 // 30
    kw = dict(num_bits=5, num_epochs=epochs, rotate_every=every, alpha=1.0,
              warmup=64, chunk_T=every, topk=4, arith=arith)
    derived = window_ref.replay(b, 30, 5 * S + 7, **kw)
    walked = window_ref.replay(b, 30, 5 * S + 7, derive=False, **kw)
    _same(derived, walked)
    assert all(v == 0 for v in window_ref.compare(derived, walked).values())


def test_one_epoch_without_rotation_is_the_flat_stream():
    b = _buckets(1)
    kw = dict(num_bits=5, alpha=1.0, warmup=64, chunk_T=7, topk=4)
    win = window_ref.replay(b, 30, 77, num_epochs=1, rotate_every=0, **kw)
    flat = stream_ref.replay(b, 30, 77, **kw)
    assert np.array_equal(win.keep, flat.keep)
    assert np.array_equal(win.margin, flat.margin)
    assert np.array_equal(win.thr, flat.thr)
    assert np.array_equal(win.counts[0], flat.counts)
    assert np.array_equal(win.tail, np.zeros_like(flat.counts))
    assert (win.n[0], win.welford_mean[0], win.welford_m2[0]) == (
        flat.n, flat.welford_mean, flat.welford_m2)
    assert win.topk == flat.topk


def test_an_epoch_leaves_the_window_whole():
    """After E rotations past an epoch, none of its items is counted: with
    every batch the same rows, the window holds exactly E - 1 whole epochs
    after each rotation, and the tail is their sum."""
    b = _buckets(2, rows=60)
    out = window_ref.replay(b, 60, 4 * 7, num_bits=5, num_epochs=3,
                            rotate_every=4, alpha=1.0, warmup=64, chunk_T=4)
    one = np.bincount((b + np.arange(6) * 32).ravel(), minlength=6 * 32)
    assert out.cursor == 7 % 3
    assert np.array_equal(out.counts.sum(axis=0).ravel(), 2 * 4 * one)
    assert np.array_equal(out.tail.ravel(), 2 * 4 * one)
    assert list(out.n) == [240.0 if e != out.cursor else 0.0
                           for e in range(3)]
    assert out.ssq == float(np.sum((8 * one) ** 2))


def test_comparison_passes_equal_and_fails_a_kept_epoch():
    import copy
    limits = common.load_json(ROOT, "bench/configs/kdd99-http-window.json")[
        "limits"]
    ref = window_ref.replay(_buckets(3), 30, 60, num_bits=5, num_epochs=4,
                            rotate_every=2, alpha=1.0, warmup=64, chunk_T=2,
                            topk=4)
    assert all(v == 0 for v in window_ref.compare(copy.deepcopy(ref),
                                                  ref).values())
    bad = copy.deepcopy(ref)
    bad.counts[ref.cursor] += bad.counts[(ref.cursor + 1) % 4]
    numbers = window_ref.compare(bad, ref)
    assert numbers["counts_off"] > limits["counts_off"], numbers


def test_a_count_dtype_the_ring_does_not_hold_is_refused(tmp_path,
                                                        monkeypatch):
    """The configuration's ``count_dtype`` documents the ring's counts; a
    configuration that states another dtype than the filter builds is
    refused before any data is made."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    _, cfg, _ = run.cell_spec(CELL, run.benchmark())
    hooks = common.load_module(os.path.join(common.BENCH, "kinds",
                                            cfg["kind"] + ".py"))
    over_cfg, over_mix = hooks.tiny(cfg, {})
    with pytest.raises(ValueError, match="int16"):
        run.drive(CELL, 7, 0.1, False, require_tpu=False,
                  cfg_override=dict(over_cfg, count_dtype="int16"),
                  mix_override=over_mix)
