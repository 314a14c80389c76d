"""CPU tests of the on-chip benchmark's harness (``bench/``): its layout and
contract, its refusal of a machine without a TPU, its tail and rate
arithmetic, its work counts, its trace reduction, and its correctness check
(passing on sound runs, failing on the control and on planted faults)."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common, run  # noqa: E402
from bench import trace as btrace  # noqa: E402
from bench.reference import ace  # noqa: E402
from bench.reference import guardrail_fleet as fleet_ref  # noqa: E402
from bench.reference import stream as stream_ref  # noqa: E402

BENCH = run.benchmark()
ALL = run.benchmark(pending=True)     # with the cells not yet admitted
CELLS = [w["name"] for w in BENCH["workloads"]]
ALL_CELLS = [w["name"] for w in ALL["workloads"]]
CONFIGS = {c["name"]: c for c in ALL["configs"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# Sizes a CPU test can hold; the semantics are the cells' own.
TINY = {
    "stream": (dict(rows=2000, anomalies=20, num_bits=8, num_tables=8,
                    warmup_items=64), dict(chunk_T=2, chunk_B=50)),
    "guardrail_fleet": (dict(d_model=32, num_tenants=4, num_bits=6,
                             num_tables=8, warmup_items=16, batch=16),
                        dict(pool=256)),
}


def tiny(cell: str):
    cfg = common.load_json(ROOT, CONFIGS[
        {w["name"]: w for w in ALL["workloads"]}[cell]["config"]]["file"])
    over_cfg, over_mix = TINY[cfg["kind"]]
    mix = dict(over_mix)
    if cfg["kind"] == "guardrail_fleet":
        mix["rate_per_s"] = 1500.0
    return dict(cfg_override=dict(over_cfg), mix_override=mix, pending=True)


@pytest.fixture
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


# -- layout and contract ----------------------------------------------------

@pytest.mark.parametrize("cell", ALL_CELLS)
def test_cell_files_found_by_name(cell):
    w, cfg, mix = run.cell_spec(cell, ALL)
    assert os.path.isfile(os.path.join(common.BENCH, "traffic",
                                       mix["driver"] + ".py"))
    assert os.path.isfile(os.path.join(common.BENCH, "work",
                                       w["config"] + ".py"))
    assert set(cfg["limits"]) and all(v >= 0 for v in cfg["limits"].values())
    for m in run.cell_metrics(cell, ALL, trace=True):
        assert os.path.isfile(os.path.join(common.BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    e2e = [m["name"] for m in run.cell_metrics(cell, ALL, trace=False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.cell_metrics(cell, ALL, trace=True)


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in ALL["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and c["reduced"] == []
        assert common.load_json(ROOT, c["file"])["name"] == c["name"]
    for w in ALL["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in CONFIGS and w["chips"] == 1
        assert len(w["why"]) <= 200
    assert {c["name"] for c in BENCH["configs"]} == {
        w["config"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["name"] not in names
        names.add(m["name"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", CELLS)


# -- refusals ----------------------------------------------------------------

def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_backend(tmp_path):
    p = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""


# -- tail and rate arithmetic --------------------------------------------------

class _Ticket:
    def __init__(self, status, t_done=None, deadline=1e9):
        self.status, self.t_done, self.deadline = status, t_done, deadline


def test_nearest_rank():
    assert common.nearest_rank(range(1, 101), 0.99) == 99
    assert common.nearest_rank([5.0], 0.99) == 5.0
    assert common.median([3, 1, 2, 10]) == 2.5


@pytest.mark.parametrize("case", ["served", "shed", "stall"])
def test_open_loop_tail_counts_every_request(case):
    from bench.traffic import openloop
    n, seconds = 1000, 10.0
    due = np.arange(n) * 0.01
    tickets = [_Ticket("served", t + 0.001, deadline=t + 0.05) for t in due]
    if case == "shed":
        for k in range(0, n, 50):            # 2% shed
            tickets[k] = _Ticket("shed")
    if case == "stall":
        for k in range(500, 520):            # 20 requests held 0.2 s
            tickets[k].t_done = due[k] + 0.2
    end = 9.5                 # answers after it do not count as served in it
    lat, in_window, late, shed = openloop.account(tickets, due, end, seconds)
    p99 = common.nearest_rank(lat, 0.99)
    if case == "served":
        assert p99 == pytest.approx(0.001) and late == shed == 0
        assert in_window == 950
    elif case == "shed":
        assert p99 == seconds and shed == 20
        assert max(v for v in lat if v < seconds) < seconds
    else:
        assert p99 == pytest.approx(0.2) and late == 20


# -- work counts ---------------------------------------------------------------

def test_work_counts_match_hand_counts():
    stream = common.load_module(os.path.join(common.BENCH, "work",
                                             "kdd99-http-stream.py"))
    fleet = common.load_module(os.path.join(common.BENCH, "work",
                                            "guardrail-fleet64-d4096.py"))
    w = stream.per_item({"num_bits": 2, "num_tables": 3, "dim": 4})
    assert w == {"flops": 2 * 5 * 2 * 3, "bytes": 3 * 4 + 3 * 8 + 4 * 4}
    w = fleet.per_item({"num_bits": 2, "num_tables": 3, "d_model": 4,
                        "seq": 2})
    assert w == {"flops": 2 * 5 * 2 * 3, "bytes": 3 * 4 + 3 * 8 + 2 * 4 * 4}


# -- trace reduction -----------------------------------------------------------

def test_trace_helpers_by_hand():
    assert btrace._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    self_t = btrace._self_times([(0, 10, "loop"), (2, 5, "a"), (6, 8, "b")])
    assert dict(self_t) == {"loop": 5, "a": 3, "b": 2}
    spans = [(0, 100, "bench:pump"), (10, 40, "bench:admit"),
             (50, 60, "bench:sleep")]
    got = btrace._label_gaps([(15, 25), (52, 58), (70, 80), (200, 210)],
                             spans)
    assert got == pytest.approx({"admit": 10e-9, "sleep": 6e-9,
                                 "pump": 10e-9, "no harness span": 10e-9})


def test_trace_reduction_of_a_cpu_trace():
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    spans = common.Spans()
    tr = btrace.Tracer(True, 0.0, spans)
    try:
        tr.poll(0.0)
        for _ in range(3):
            with spans("consume"):
                f(x).block_until_ready()
            with spans("sleep"):
                time.sleep(0.02)
        tr.window_ends()
        s = btrace.reduce(tr.stop())
    finally:
        tr.cleanup()
    assert s.devices == 1 and s.window_s >= 0.06
    assert 0 < s.busy_s < s.window_s and 0 < s.idle_share < 1
    assert s.span_counts == {"consume": 3, "sleep": 3}
    assert max(s.idle_by_span, key=s.idle_by_span.get) == "sleep"
    assert s.idle_by_span["sleep"] >= 0.05
    assert any("dot" in k for k in s.op_self_s)
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s, rel=0.05)
    assert len(s.breakdown()["device_ops"]) <= 10


# -- the correctness check -------------------------------------------------------

def _stream_case():
    g = np.random.default_rng(0)
    buckets = g.integers(0, 1 << 6, size=(200, 4))
    ref = stream_ref.replay(buckets, 50, 8, num_bits=6, alpha=1.0,
                            warmup=64, chunk_T=2, topk=4)
    return ref, stream_ref.compare, "kdd99-http-stream"


def _fleet_case():
    g = np.random.default_rng(0)
    buckets = g.integers(0, 1 << 6, size=(64, 4))
    batches = []
    for _ in range(30):
        rows = g.integers(0, 64, 16)
        batches.append((rows, rows % 3))
    ref = fleet_ref.replay(batches, buckets, num_tenants=3, num_bits=6,
                           alpha=1.0, warmup=16)
    return ref, fleet_ref.compare, "guardrail-fleet64-d4096"


@pytest.mark.parametrize("case", [_stream_case, _fleet_case])
def test_comparison_passes_equal_and_fails_one_flipped_count(case):
    import copy
    ref, compare, config = case()
    limits = common.load_json(ROOT, CONFIGS[config]["file"])["limits"]
    same = compare(copy.deepcopy(ref), ref)
    assert all(same[k] == 0 for k in limits)
    bad = copy.deepcopy(ref)
    bad.counts.reshape(-1)[7] += 1
    numbers = compare(bad, ref)
    assert any(numbers[k] > limits[k] for k in limits)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_control_fails_the_limits(config):
    """The reference with bf16x3 projections must fail, at widths where a
    rounding of the projections flips some signs."""
    from bench.traffic import openloop, stream
    limits = common.load_json(ROOT, CONFIGS[config]["file"])["limits"]
    if config == "kdd99-http-stream":
        x = stream.standin(5, 19600, 40, 36, 5)
        w = np.random.default_rng(1).standard_normal((37, 768))
        out = {}
        for prec, dt in (("float64", np.float64), ("bf16x3", np.float32)):
            b = ace.hash_buckets(ace.features(x[:, None, :], 0.25, dt),
                                 w.astype(np.float32), 15, 50, prec)
            out[prec] = stream_ref.replay(b, 392, 150, num_bits=15,
                                          alpha=1.0, warmup=512, chunk_T=2,
                                          topk=8)
        numbers = stream_ref.compare(out["bf16x3"], out["float64"])
    else:
        pool, tid = openloop.request_pool(3, 2048, 4, 1024, 8, 1.1, 0.05)
        w = np.random.default_rng(2).standard_normal((1025, 768))
        g = np.random.default_rng(4)
        batches = [(rows, tid[rows]) for rows in
                   (g.integers(0, 2048, 100) for _ in range(400))]
        out = {}
        for prec, dt in (("float64", np.float64), ("bf16x3", np.float32)):
            b = ace.hash_buckets(ace.features(pool, 0.25, dt),
                                 w.astype(np.float32), 15, 50, prec)
            out[prec] = fleet_ref.replay(batches, b, num_tenants=8,
                                         num_bits=15, alpha=1.0, warmup=64)
        numbers = fleet_ref.compare(out["bf16x3"], out["float64"])
    assert any(numbers[k] > limits[k] for k in limits), numbers


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_tiny_run_is_correct(cell, cache):
    res = run.run_cell(cell, 2**31 + 11, 1.0, False, require_tpu=False,
                       **tiny(cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    json.dumps(res)


def _stream_faults(fault, monkeypatch):
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


def _guard_faults(fault, monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.serve.engine import Guardrail

    orig = Guardrail.admit

    def admit(self, embeds, tenant_ids=None):
        if fault == "half_batch":
            embeds = embeds.at[embeds.shape[0] // 2:].set(jnp.nan)
        kept = jax.tree.map(jnp.copy, self.state)
        out = orig(self, embeds, tenant_ids)
        if fault == "state_unchanged":
            self.state = kept
        if fault == "answer":
            out = out.copy()
            out[0] = ~out[0]
        return out

    monkeypatch.setattr(Guardrail, "admit", admit)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer"])
@pytest.mark.parametrize("cell", ["stream-kdd99-chunk",
                                  "guard-fleet64-steady"])
def test_planted_fault_is_not_correct(cell, fault, cache, monkeypatch):
    plant = _stream_faults if cell.startswith("stream") else _guard_faults
    plant(fault, monkeypatch)
    res = run.run_cell(cell, 2**31 + 12, 1.0, False, require_tpu=False,
                       **tiny(cell))
    assert not res["correct"], res["checks"]
