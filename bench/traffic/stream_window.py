"""Closed-loop stream ingest over a sliding window, through
``StreamRunner.consume`` around a ``WindowedAceFilter``.

As ``bench/traffic/stream.py``: the stand-in data set is cut into chunks of
(T, B) rows and replayed, chunk after chunk and pass after pass, for the
whole window; each chunk's raw rows go host -> device, are featurized by the
filter's own ``features``, consumed, and the chunk's keep masks and summary
come back to the host, with one chunk in flight.  The filter's ring of
``num_epochs`` epochs rotates every ``rotate_every`` steps inside the
consume program.

Mix parameters: ``chunk_T``, ``chunk_B`` (rows per batch), ``topk`` (rows
of the chunk summary's anomaly report).  A mix whose ``chunk_B`` times the
configuration's ``rotate_every`` is not its ``epoch_rows`` is refused.
"""
from __future__ import annotations

import numpy as np

from bench import common
from bench.reference import ace
from bench.reference import stream_window as window_ref
from bench.traffic.stream import standin


def run(r):
    import jax

    from repro.stream import StreamRunner
    from repro.window import WindowedAceFilter

    cfg, mix = r.cfg, r.mix
    T, B = int(mix["chunk_T"]), int(mix["chunk_B"])
    K, L = cfg["num_bits"], cfg["num_tables"]
    E, R = int(cfg["num_epochs"]), int(cfg["rotate_every"])
    if B * R != cfg["epoch_rows"]:
        raise ValueError(f"chunk_B {B} x rotate_every {R} is not the "
                         f"configuration's epoch_rows {cfg['epoch_rows']}")
    d = cfg["dim"]
    filt = WindowedAceFilter(d_model=d, num_bits=K, num_tables=L,
                             alpha=cfg["alpha"],
                             warmup_items=cfg["warmup_items"],
                             bias_const=cfg["bias_const"], insert_all=True,
                             num_epochs=E, decay=cfg["decay"],
                             rotate_every=R)
    if filt.ace_cfg.counter_dtype != cfg["count_dtype"]:
        raise ValueError(f"the ring counts in {filt.ace_cfg.counter_dtype}, "
                         f"the configuration states {cfg['count_dtype']}")
    runner = StreamRunner(filt, chunk_T=T, topk=int(mix["topk"]),
                          return_masks=True, rotate_every=R)
    t_data = common.clock()
    x = standin(r.seed, cfg["rows"], cfg["anomalies"], d, cfg["clusters"])
    t_data = common.clock() - t_data
    N = x.shape[0]
    if N % (T * B):
        raise ValueError(f"{N} rows are not whole ({T}, {B}) chunks")
    chunks = [np.ascontiguousarray(c) for c in x.reshape(-1, T, B, d)]
    w = common.projections(r.seed, (d + 1, -(-K * L // 128) * 128))
    featurize = jax.jit(lambda raw: filt.features(
        raw.reshape(T * B, 1, d)).reshape(T, B, d + 1))

    # Warm-up: the cell's two programs, on a state that is then dropped.
    t_warm = common.clock()
    scratch, _ = runner.init()
    jax.device_get(runner.consume(scratch, w, featurize(
        jax.device_put(chunks[0])))[1:])
    del scratch
    state, _ = runner.init()
    jax.block_until_ready(state)
    t_warm = common.clock() - t_warm

    span = r.spans
    keeps, summaries, call_s = [], [], []

    def pull(p):
        t_begin, summ, keep = p
        with span("pull"):
            s, k = jax.device_get((summ, keep))
        call_s.append(common.clock() - t_begin)
        summaries.append(s)
        keeps.append(k)

    r.window_begins()
    t0 = common.clock()
    end = t0 + r.seconds
    pending, k = None, 0
    while True:
        now = common.clock()
        if now >= end:
            break
        r.tracer.poll(now - t0)
        with span("h2d"):
            raw = jax.device_put(chunks[k % len(chunks)])
        with span("consume"):
            state, summ, keep = runner.consume(state, w, featurize(raw))
        if pending is not None:
            pull(pending)
        pending, k = (now, summ, keep), k + 1
    pull(pending)
    t_end = common.clock()
    r.window_ends(runner.trace_count)

    items = k * T * B
    peak = r.read_peak()
    got = window_ref.WindowOutputs(
        keep=np.concatenate(keeps), counts=np.asarray(state.counts),
        tail=np.asarray(state.tail), n=np.asarray(state.n, np.float64),
        welford_mean=np.asarray(state.welford_mean, np.float64),
        welford_m2=np.asarray(state.welford_m2, np.float64),
        ssq=float(state.ssq), cursor=int(state.cursor),
        topk=[(c * T + int(st), int(it), float(m))
              for c, s in enumerate(summaries)
              for st, it, m, ok in zip(s.topk_step, s.topk_item,
                                       s.topk_margin, s.topk_valid) if ok])
    w_host = np.asarray(w)
    del state, runner, w, summaries, keeps

    def outputs(precision: str):
        control = precision != "float64"
        dtype = np.float32 if control else np.float64
        feats = ace.features(x[:, None, :], cfg["bias_const"], dtype)
        fragile = None
        if control:
            buckets = ace.hash_buckets(feats, w_host, K, L, precision)
        else:
            buckets, fragile = ace.hash_buckets(
                feats, w_host, K, L, precision,
                fragile_below=cfg["fragile_cosine"])
        return window_ref.replay(
            buckets, B, k * T, num_bits=K, num_epochs=E, rotate_every=R,
            alpha=cfg["alpha"], warmup=cfg["warmup_items"], chunk_T=T,
            topk=int(mix["topk"]) if control else None,
            arith="bfloat16" if control else "float64", fragile=fragile)

    refs = {}

    def check(control: bool = False) -> dict:
        """The numbers compared: the window's outputs, or the control's
        (the reference one precision step down), against the reference."""
        if "float64" not in refs:
            refs["float64"] = outputs("float64")
        return window_ref.compare(outputs("bf16x3") if control else got,
                                  refs["float64"])

    return r.outcome(
        metrics={"stream_items_per_s": items / (t_end - t0),
                 "device_peak_mib": peak / 2**20},
        attempted=items, failed=0, peak=peak, check=check,
        samples={"consume_call_s": call_s,
                 "items_per_chunk": T * B},
        notes=[f"set-up: data {t_data:.3f} s, warm-up {t_warm:.3f} s",
               f"chunks={k} of (T={T}, B={B}) in {t_end - t0:.6f} s, "
               f"passes={k * T * B / N:.3f}, epochs={k * T // R} of "
               f"{R} steps in a ring of {E}"])
