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
from bench.reference import guardrail_fleet as fleet_ref  # noqa: E402
from bench.reference import stream as stream_ref  # noqa: E402

BENCH = run.benchmark()
ALL = run.benchmark(pending=True)     # with the cells not yet admitted
CELLS = [w["name"] for w in BENCH["workloads"]]
ALL_CELLS = [w["name"] for w in ALL["workloads"]]
CONFIGS = {c["name"]: c for c in ALL["configs"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def hooks(kind: str):
    """The test hooks of a configuration kind, ``bench/kinds/<kind>.py``:
    ``tiny``, ``control_numbers``, ``FAULTS`` and ``plant``."""
    return common.load_module(os.path.join(common.BENCH, "kinds",
                                           kind + ".py"))


def kind_of(config: str) -> str:
    return common.load_json(ROOT, CONFIGS[config]["file"])["kind"]


def tiny(cell: str):
    w = {w["name"]: w for w in ALL["workloads"]}[cell]
    cfg = common.load_json(ROOT, CONFIGS[w["config"]]["file"])
    mix = common.load_json(common.BENCH, "traffic", w["traffic"] + ".json")
    over_cfg, over_mix = hooks(cfg["kind"]).tiny(cfg, mix)
    return dict(cfg_override=dict(over_cfg), mix_override=dict(over_mix),
                pending=True)


def _fault_cases():
    """(cell, fault): each fault its kind's hooks plant, in the first cell of
    each kind that has hooks (a kind without them fails
    ``test_kind_hooks_found_by_name``)."""
    cases, seen = [], set()
    for w in ALL["workloads"]:
        kind = kind_of(w["config"])
        if kind in seen or not os.path.isfile(
                os.path.join(common.BENCH, "kinds", kind + ".py")):
            continue
        seen.add(kind)
        cases += [(w["name"], f) for f in hooks(kind).FAULTS]
    return cases


FAULT_CASES = _fault_cases()


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
        assert c["file"].startswith("bench/")
        cfg = common.load_json(ROOT, c["file"])
        assert cfg["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            # a cut names a key of the file, which states the source's value
            assert isinstance(key, str) and NAME.match(key), key
            assert key in cfg and key in cfg.get("published", {}), key
    for w in ALL["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in CONFIGS and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(len(BENCH["workloads"]) // 2, 1)
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


@pytest.mark.parametrize("kind", sorted({kind_of(c) for c in CONFIGS}))
def test_kind_hooks_found_by_name(kind):
    """Every configuration's kind brings its test hooks; a kind without
    them fails here by name, and falls into no other kind's."""
    path = os.path.join(common.BENCH, "kinds", kind + ".py")
    assert os.path.isfile(path), f"kind {kind!r} has no hooks: no {path}"
    h = hooks(kind)
    for name in ("tiny", "control_numbers", "plant"):
        assert callable(getattr(h, name, None)), f"{path} has no {name}()"
    assert h.FAULTS, f"{path} plants no fault"


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_control_fails_the_limits(config):
    """The control, one precision step down at a test size (the kind's
    ``control_numbers``), must fail the configuration's limits."""
    limits = common.load_json(ROOT, CONFIGS[config]["file"])["limits"]
    numbers = hooks(kind_of(config)).control_numbers()
    assert any(numbers[k] > limits[k] for k in limits), numbers


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_tiny_run_is_correct(cell, cache):
    res = run.run_cell(cell, 2**31 + 11, 1.0, False, require_tpu=False,
                       **tiny(cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    json.dumps(res)


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_tiny_traced_run_reads_its_metrics(cell, cache):
    """A traced run hands its readers both reductions of the trace; what
    they read is the cell's own per-layer metrics, each a finite number."""
    res = run.run_cell(cell, 2**31 + 13, 1.0, True, require_tpu=False,
                       **tiny(cell))
    assert res["correct"], res["checks"]
    wanted = {m["name"] for m in run.cell_metrics(cell, ALL, trace=True)}
    assert set(res["metrics"]) <= wanted
    assert all(np.isfinite(m["value"]) for m in res["metrics"].values())
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0


def test_traced_run_reads_the_program_spans(cache):
    """On the CPU the program's consume spans are in the trace (its device
    scopes are not: no ``/device:*`` plane), and reach the readers through
    ``Context.scopes``."""
    res = run.run_cell("stream-kdd99-chunk", 2**31 + 14, 1.0, True,
                       require_tpu=False, **tiny("stream-kdd99-chunk"))
    assert res["metrics"]["runner_dispatch_us.stream"]["value"] > 0


@pytest.mark.parametrize("cell,fault", FAULT_CASES,
                         ids=[f"{c}-{f}" for c, f in FAULT_CASES])
def test_planted_fault_is_not_correct(cell, fault, cache, monkeypatch):
    w = {w["name"]: w for w in ALL["workloads"]}[cell]
    hooks(kind_of(w["config"])).plant(fault, monkeypatch)
    res = run.run_cell(cell, 2**31 + 12, 1.0, False, require_tpu=False,
                       **tiny(cell))
    assert not res["correct"], res["checks"]
