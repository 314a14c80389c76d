"""Open-loop serving traffic through ``FrontEnd`` -> ``Guardrail.admit``.

Requests arrive on a seeded Poisson schedule at a fixed offered rate, one
thread submitting each when it is due whether or not earlier ones have been
answered (wrk2's rule): every deadline is anchored at the scheduled arrival,
and every latency is measured from it.  Each request is drawn uniformly from
a seeded pool of distinct (seq, d_model) float32 requests whose tenants
follow a Zipf law, each tenant's requests clustered round its own direction,
a few pointing anywhere.

Every request due in the window is accounted for: requests still queued when
the window closes are drained, and a shed request enters the latency sample
at the window's length, above every served one.

Mix parameters: ``rate_per_s`` (offered), ``pool`` (distinct requests),
``zipf`` (tenant skew), ``anomaly_frac``, ``shed_is_failure`` (whether a
shed or late request counts as failed: below the knee it does; above it,
shedding is the designed answer to overload).
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import common
from bench.reference import ace
from bench.reference import guardrail_fleet as fleet_ref


def request_pool(seed: int, n: int, seq: int, d: int, tenants: int,
                 zipf: float, anomaly_frac: float):
    """(n, seq, d) float32 requests and their (n,) tenants.  (Construction
    copied from ``chip_smoke._requests``.)"""
    g = common.rng(seed, 2)
    p = 1.0 / np.arange(1, tenants + 1) ** zipf
    tids = g.choice(tenants, size=n, p=p / p.sum()).astype(np.int32)
    centers = g.standard_normal((tenants, d), dtype=np.float32)
    emb = g.standard_normal((n, seq, d), dtype=np.float32)
    emb *= np.float32(0.6)
    emb += centers[tids][:, None, :]
    odd = np.flatnonzero(g.random(n) < anomaly_frac)
    emb[odd] = g.standard_normal((len(odd), seq, d), dtype=np.float32) * 2
    return emb, tids


def arrivals(seed: int, rate: float, seconds: float, pool: int):
    """Scheduled arrival offsets (s) over the window, and pool picks."""
    g = common.rng(seed, 3)
    n = int(rate * seconds * 1.2 + 10 * np.sqrt(rate * seconds) + 100)
    t = np.cumsum(g.exponential(1.0 / rate, size=n))
    t = t[t < seconds]
    return t, g.integers(0, pool, size=len(t))


def account(tickets, due_at, end: float, seconds: float):
    """Latencies of every request due in the window, from its scheduled
    arrival ``due_at``: a served request's to its verdict, a shed one's
    the window's length, above every served one.  Also the requests served
    within the window, those served after their deadline, and those shed."""
    lat, in_window, late, shed = [], 0, 0, 0
    for tk, t in zip(tickets, due_at):
        if tk.status == "served":
            lat.append(tk.t_done - t)
            in_window += tk.t_done <= end
            late += tk.t_done > tk.deadline
        else:
            lat.append(seconds)
            shed += 1
    return lat, in_window, late, shed


class TimedGuardrail:
    """The guardrail as the front end sees it, with each admit call timed
    from the harness's side."""

    def __init__(self, g, span):
        self.g = g
        self.span = span
        self.calls = []           # (start, end) of each admit call
        self.multi_tenant = g.multi_tenant

    @property
    def fail_open_mask(self):
        return self.g.fail_open_mask

    def admit(self, embeds, tenant_ids=None):
        t = common.clock()
        with self.span("admit"):
            out = self.g.admit(embeds, tenant_ids)
        self.calls.append((t, common.clock()))
        return out


def run(r):
    import jax
    import jax.numpy as jnp

    from repro.serve.engine import Guardrail, GuardrailConfig
    from repro.serve.frontend import FrontEnd, FrontEndConfig

    cfg, mix = r.cfg, r.mix
    K, L, D = cfg["num_bits"], cfg["num_tables"], cfg["d_model"]
    S, Bsz, T = cfg["seq"], cfg["batch"], cfg["num_tenants"]
    gcfg = GuardrailConfig(d_model=D, num_bits=K, num_tables=L,
                           alpha=cfg["alpha"],
                           warmup_items=cfg["warmup_items"],
                           bias_const=cfg["bias_const"],
                           num_tenants=T, count_dtype=cfg["count_dtype"])
    g = Guardrail(gcfg)
    g.w = common.projections(r.seed, (D + 1, -(-K * L // 128) * 128))
    fcfg = FrontEndConfig(batch_size=Bsz, seq=S, d_model=D,
                          max_queue=cfg["max_queue"],
                          default_deadline=cfg["deadline_s"],
                          max_wait=cfg["max_wait_s"])
    t_data = common.clock()
    pool, pool_tid = request_pool(r.seed, int(mix["pool"]), S, D, T,
                                  float(mix["zipf"]),
                                  float(mix["anomaly_frac"]))
    t_data = common.clock() - t_data
    sched, pick = arrivals(r.seed, float(mix["rate_per_s"]), r.seconds,
                           len(pool))
    # Warm-up: one all-NaN batch compiles the admit program; its rows are
    # quarantined, so the sketch state is left bitwise as it was.
    t_warm = common.clock()
    g.admit(jnp.full((Bsz, S, D), jnp.nan, jnp.float32),
            jnp.zeros((Bsz,), jnp.int32))
    jax.block_until_ready(g.state)
    t_warm = common.clock() - t_warm

    span = r.spans
    tg = TimedGuardrail(g, span)
    fe = FrontEnd(tg, fcfg)
    tickets = [None] * len(sched)
    queued = collections.deque()
    batches = []              # (request numbers, admit call index)
    t_submit = t_pump = 0.0

    def pump(force=False):
        nonlocal t_pump
        t = common.clock()
        with span("pump"):
            got = fe.pump(force=force)
        t_pump += common.clock() - t
        while queued and tickets[queued[0]].status == "shed":
            queued.popleft()
        if got:
            nums = [queued.popleft() for _ in range(got)]
            if any(tickets[k].status != "served" for k in nums):
                raise RuntimeError("the front end served out of order")
            batches.append((nums, len(tg.calls) - 1))
        return got

    def submit(i, t0):
        nonlocal t_submit
        t = common.clock()
        with span("submit"):
            j = pick[i]
            tk = fe.submit(pool[j], int(pool_tid[j]),
                           deadline=t0 + sched[i] + fcfg.default_deadline)
        t_submit += common.clock() - t
        tickets[i] = tk
        if tk.status == "queued":
            queued.append(i)

    r.window_begins()
    t0 = common.clock()
    end = t0 + r.seconds
    i = 0
    while True:
        now = common.clock()
        if now >= end:
            break
        r.tracer.poll(now - t0)
        while i < len(sched) and t0 + sched[i] <= now:
            submit(i, t0)
            i += 1
        if not pump() and not fe.ready():
            nxt = t0 + sched[i] if i < len(sched) else end
            if queued:
                nxt = min(nxt, tickets[queued[0]].t_submit + fcfg.max_wait)
            wait = min(nxt, end) - common.clock()
            if wait > 2e-4:
                with span("sleep"):
                    time.sleep(wait - 1e-4)
    while i < len(sched):           # due in the window, not yet submitted
        submit(i, t0)
        i += 1
    r.window_ends(g.trace_count)
    while fe.queue_len:
        pump(force=True)
    t_drained = common.clock()

    due = len(sched)
    lat, served_in_window, late, shed = account(tickets, t0 + sched,
                                                end, r.seconds)
    m = fe.metrics()
    failed = shed + late if mix["shed_is_failure"] else 0
    calls = tg.calls
    longest, longest_at = max((b - a, a - t0) for a, b in calls)
    slow = sum(b - a > fcfg.default_deadline for a, b in calls)
    call_of = {}
    for nums, c in batches:
        for k in nums:
            call_of[k] = c
    peak = r.read_peak()

    # -- what the window produced, for the reference ------------------------
    got = fleet_ref.FleetOutputs(
        verdicts=[np.array([tickets[k].admitted for k in nums], bool)
                  for nums, _ in batches],
        counts=np.asarray(g.state.counts), n=np.asarray(g.state.n,
                                                        np.float64))
    order = [(pick[np.array(nums)], pool_tid[pick[np.array(nums)]])
             for nums, _ in batches]
    w_host = np.asarray(g.w)
    del g, fe, tg

    def outputs(precision: str):
        control = precision != "float64"
        dtype = np.float32 if control else np.float64
        buckets = ace.hash_buckets(ace.features(pool, cfg["bias_const"],
                                                dtype),
                                   w_host, K, L, precision)
        return fleet_ref.replay(order, buckets, num_tenants=T, num_bits=K,
                                alpha=cfg["alpha"],
                                warmup=cfg["warmup_items"],
                                arith="bfloat16" if control else "float64")

    refs = {}

    def check(control: bool = False) -> dict:
        """The numbers compared: the window's outputs, or the control's
        (the reference one precision step down), against the reference."""
        if "float64" not in refs:
            refs["float64"] = outputs("float64")
        return fleet_ref.compare(outputs("bf16x3") if control else got,
                                 refs["float64"])

    served = m["served"]
    return r.outcome(
        metrics={
            "admit_p99_ms": 1e3 * common.nearest_rank(lat, 0.99),
            "guard_served_per_s": served_in_window / r.seconds,
            "device_peak_mib": peak / 2**20},
        attempted=due, failed=failed, peak=peak, check=check,
        samples={
            "loadgen_lag_s": [tk.t_submit - (t0 + sched[k])
                              for k, tk in enumerate(tickets)],
            "frontend_wait_s": [calls[c][0] - (t0 + sched[k])
                                for k, c in call_of.items()],
            "admit_call_s": [b - a for a, b in calls],
            "frontend_host_s": t_submit + t_pump
            - sum(b - a for a, b in calls),
            "served": served,
            "shed": shed,
            "late": late,
            "batch_rows": Bsz},
        notes=[f"set-up: request pool {t_data:.3f} s, warm-up {t_warm:.3f} s",
               f"offered {mix['rate_per_s']} req/s: due={due} "
               f"served={served} (in window {served_in_window}) "
               f"shed_queue_full={m['shed_queue_full']} "
               f"shed_deadline={m['shed_deadline']} late={late} "
               f"batches={len(batches)} pad_rows={m['pad_rows']} "
               f"drain={t_drained - end:.6f} s",
               f"admit calls: longest {1e3 * longest:.3f} ms at "
               f"{longest_at:.3f} s into the window, {slow} over "
               f"{1e3 * fcfg.default_deadline:.0f} ms; the front end's "
               f"service estimate at the close {1e3 * m['est_service_s']:.3f}"
               " ms"])
