"""Closed-loop stream ingest through ``StreamRunner.consume``.

The stand-in data set is cut into chunks of (T, B) rows and replayed, chunk
after chunk and pass after pass, for the whole window.  Each chunk's raw rows
go host -> device, are featurized by the filter's own ``features``, consumed,
and the chunk's keep masks and summary come back to the host.  One chunk is
kept in flight: the next is dispatched before the previous one's verdicts
are pulled, as a stream consumer that wants its throughput would do.

Mix parameters: ``chunk_T``, ``chunk_B`` (rows per batch), ``topk`` (rows
of the chunk summary's anomaly report).
"""
from __future__ import annotations

import numpy as np

from bench import common
from bench.reference import ace
from bench.reference import stream as stream_ref


def standin(seed: int, rows: int, anomalies: int, dim: int,
            clusters: int) -> np.ndarray:
    """The KDD-Cup99-HTTP stand-in: nonnegative rows in a few balanced
    clusters, with scattered and two loose rare-cluster anomalies, shuffled.
    (Copied from ``repro.data.synthetic.make_paper_dataset``.)"""
    g = common.rng(seed, 1)
    n_in = rows - anomalies
    centers = g.gamma(shape=2.0, scale=2.0, size=(clusters, dim))
    centers *= (g.uniform(4.0, 9.0, size=(clusters, 1))
                / np.linalg.norm(centers, axis=1, keepdims=True))
    weights = g.dirichlet(np.full(clusters, 20.0))
    assign = g.choice(clusters, size=n_in, p=weights)
    spread = g.uniform(0.4, 1.1, size=(clusters,))
    x_in = np.abs(centers[assign]
                  + g.normal(size=(n_in, dim)) * spread[assign][:, None])
    n_scatter = (3 * anomalies) // 4
    dirs = g.normal(size=(n_scatter, dim))
    dirs /= np.linalg.norm(dirs, axis=-1, keepdims=True) + 1e-12
    x_scatter = np.abs(dirs) * g.uniform(6.0, 14.0, size=(n_scatter, 1))
    x_scatter += g.exponential(1.0, size=x_scatter.shape)
    n_rare = anomalies - n_scatter
    rare = g.normal(size=(2, dim))
    rare = np.abs(rare / (np.linalg.norm(rare, axis=-1, keepdims=True)
                          + 1e-12)) * 12.0
    x_rare = np.abs(rare[g.choice(2, size=n_rare)]
                    + 0.35 * g.normal(size=(n_rare, dim)))
    x = np.concatenate([x_in, x_scatter, x_rare]).astype(np.float32)
    return x[g.permutation(rows)]


def run(r):
    import jax

    from repro.data.pipeline import AceDataFilter
    from repro.stream import StreamRunner

    cfg, mix = r.cfg, r.mix
    T, B = int(mix["chunk_T"]), int(mix["chunk_B"])
    K, L = cfg["num_bits"], cfg["num_tables"]
    t_data = common.clock()
    x = standin(r.seed, cfg["rows"], cfg["anomalies"], cfg["dim"],
                cfg["clusters"])
    t_data = common.clock() - t_data
    N, d = x.shape
    if N % (T * B):
        raise ValueError(f"{N} rows are not whole ({T}, {B}) chunks")
    chunks = [np.ascontiguousarray(c) for c in x.reshape(-1, T, B, d)]
    filt = AceDataFilter(d_model=d, num_bits=K, num_tables=L,
                         alpha=cfg["alpha"], warmup_items=cfg["warmup_items"],
                         bias_const=cfg["bias_const"], insert_all=True,
                         count_dtype=cfg["count_dtype"])
    runner = StreamRunner(filt, chunk_T=T, topk=int(mix["topk"]),
                          return_masks=True)
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
    counts = np.asarray(state.counts)
    got = stream_ref.StreamOutputs(
        keep=np.concatenate(keeps), counts=counts, n=float(state.n),
        welford_mean=float(state.welford_mean),
        welford_m2=float(state.welford_m2),
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
        return stream_ref.replay(
            buckets, B, k * T, num_bits=K, alpha=cfg["alpha"],
            warmup=cfg["warmup_items"], chunk_T=T,
            topk=int(mix["topk"]) if control else None,
            arith="bfloat16" if control else "float64", fragile=fragile)

    refs = {}

    def check(control: bool = False) -> dict:
        """The numbers compared: the window's outputs, or the control's
        (the reference one precision step down), against the reference."""
        if "float64" not in refs:
            refs["float64"] = outputs("float64")
        return stream_ref.compare(outputs("bf16x3") if control else got,
                                  refs["float64"])

    return r.outcome(
        metrics={"stream_items_per_s": items / (t_end - t0),
                 "device_peak_mib": peak / 2**20},
        attempted=items, failed=0, peak=peak, check=check,
        samples={"consume_call_s": call_s,
                 "items_per_chunk": T * B},
        notes=[f"set-up: data {t_data:.3f} s, warm-up {t_warm:.3f} s",
               f"chunks={k} of (T={T}, B={B}) in {t_end - t0:.6f} s, "
               f"passes={k * T * B / N:.3f}"])
