"""CPU test that the benchmark takes a configuration of a new kind, its cell
and a per-layer metric that reads the program's scopes as new files and new
entries in ``BENCHMARK.json`` alone: in a copy of the benchmark, a throwaway
kind is added, and ``tests/bench/test_bench.py``'s checks, run there, pick it
up; without the kind's hooks, a check fails by name."""
from __future__ import annotations

import filecmp
import json
import os
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# The throwaway kind: a jitted bincount of seeded ids in an ``ace/toy``
# scope, its reference a NumPy bincount, its control the counts rounded to
# float16, its fault a count altered where it is produced.
TOY_FILES = {
    "bench/configs/toy-config.json": json.dumps({
        "name": "toy-config", "kind": "toy", "ids": 4096, "table": 64,
        "reduced": ["ids"], "published": {"ids": 1048576},
        "limits": {"counts_off": 0}}),
    "bench/traffic/toy-mix.json": json.dumps({"driver": "toy",
                                              "chunk": 512}),
    "bench/traffic/toy.py": '''
import numpy as np

from bench import common


def run(r):
    import jax
    import jax.numpy as jnp

    size, chunk = int(r.cfg["table"]), int(r.mix["chunk"])
    ids = common.rng(r.seed, 1).integers(0, size, int(r.cfg["ids"]))
    ids = ids.astype(np.int32).reshape(-1, chunk)
    traces = []

    @jax.jit
    def step(table, x):
        traces.append(1)
        with jax.named_scope("ace/toy"):
            return table + jnp.bincount(x, length=size).astype(table.dtype)

    jax.block_until_ready(step(jnp.zeros(size, jnp.int32), ids[0]))
    table = jax.block_until_ready(jnp.zeros(size, jnp.int32))
    r.window_begins()
    t0 = common.clock()
    k = 0
    while common.clock() < t0 + r.seconds:
        r.tracer.poll(common.clock() - t0)
        with r.spans("consume"):
            table = step(table, ids[k % len(ids)])
        k += 1
    got = np.asarray(jax.device_get(table))
    t_end = common.clock()
    r.window_ends(len(traces))
    peak = r.read_peak()
    want = (k // len(ids)) * np.bincount(ids.ravel(), minlength=size) \\
        + np.bincount(ids[:k % len(ids)].ravel(), minlength=size)

    def check(control=False):
        out = want.astype(np.float16).astype(np.int64) if control else got
        return {"counts_off": float(np.abs(out - want).sum())}

    return r.outcome(
        metrics={"stream_items_per_s": k * chunk / (t_end - t0),
                 "device_peak_mib": peak / 2**20},
        attempted=k * chunk, failed=0, peak=peak, check=check, samples={},
        notes=[f"chunks={k}"])
''',
    "bench/work/toy-config.py": '''
def per_item(cfg):
    return {"flops": 0, "bytes": 8}
''',
    "bench/metrics/toy_device_us_per_kitem.toy.py": '''
from bench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes).get("toy_device_us_per_kitem")
''',
    "bench/kinds/toy.py": '''
import numpy as np

FAULTS = ("answer",)


def tiny(cfg, mix):
    return {"ids": 2048}, {"chunk": 256}


def control_numbers():
    want = np.bincount(np.random.default_rng(0).integers(0, 4, 20000))
    rounded = want.astype(np.float16).astype(np.int64)
    return {"counts_off": float(np.abs(rounded - want).sum())}


def plant(fault, monkeypatch):
    import jax.numpy as jnp
    orig = jnp.bincount
    monkeypatch.setattr(jnp, "bincount",
                        lambda x, *a, **k: orig(x, *a, **k).at[0].add(1))
''',
}
TOY_ENTRIES = {
    "configs": {"name": "toy-config",
                "source": "https://example.org/toy-deployment",
                "file": "bench/configs/toy-config.json", "reduced": ["ids"],
                "why": "a throwaway configuration of a kind of its own"},
    "workloads": {"name": "toy-cell", "config": "toy-config",
                  "traffic": "toy-mix", "chips": 1,
                  "why": "closed loop, chunks of 512 ids counted on the "
                         "device"},
    "per_layer": {"name": "toy_device_us_per_kitem.toy",
                  "unit": "us/kitem", "better": "lower",
                  "source": "device_trace",
                  "layer": "sketch ops on the device",
                  "moves": "stream_items_per_s", "workloads": ["toy-cell"]},
}
# test_bench.py's checks that pick the throwaway up.
PICKED_UP = {"test_cell_files_found_by_name[toy-cell]",
             "test_benchmark_json_contract",
             "test_kind_hooks_found_by_name[toy]",
             "test_control_fails_the_limits[toy-config]",
             "test_tiny_run_is_correct[toy-cell]",
             "test_tiny_traced_run_reads_its_metrics[toy-cell]",
             "test_planted_fault_is_not_correct[toy-cell-answer]"}
NEED_HOOKS = {"test_kind_hooks_found_by_name[toy]",
              "test_control_fails_the_limits[toy-config]",
              "test_tiny_run_is_correct[toy-cell]",
              "test_tiny_traced_run_reads_its_metrics[toy-cell]"}


def _copy_with_toy(dest, with_hooks: bool) -> None:
    """The benchmark's files, the throwaway's new files beside them, and its
    entries appended to ``BENCHMARK.json``; no other file is touched."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), dest / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), dest / "src")
    for rel, text in TOY_FILES.items():
        if rel.startswith("bench/kinds/") and not with_hooks:
            continue
        assert not (dest / rel).exists(), f"{rel} would edit a file"
        (dest / rel).write_text(text)
    for key, entry in TOY_ENTRIES.items():
        bench[key].append(entry)
    rate = {m["name"]: m for m in bench["end_to_end"]}["stream_items_per_s"]
    rate["workloads"].append("toy-cell")
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    for p in bench["paths"]:
        cmp = filecmp.dircmp(os.path.join(ROOT, p), dest / p,
                             ignore=["__pycache__"])
        assert not _changed(cmp), _changed(cmp)


def _changed(cmp) -> list:
    return cmp.diff_files + [f for sub in cmp.subdirs.values()
                             for f in _changed(sub)]


def _run_checks(dest):
    """({test name: "passed" | "failed" | "skipped"}, output) of
    ``test_bench.py``'s checks that name the throwaway, run in the copy."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(dest / "src"))
    xml = dest / "report.xml"
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "tests/bench/test_bench.py", "-k",
         "toy or contract", f"--basetemp={dest / 'tmp'}",
         f"--junitxml={xml}"],
        cwd=dest, env=env, capture_output=True, text=True, timeout=600)
    assert xml.exists(), p.stdout[-4000:] + p.stderr[-4000:]
    out = {}
    for case in ET.parse(xml).iter("testcase"):
        kids = {c.tag for c in case}
        out[case.get("name")] = ("failed" if kids & {"failure", "error"}
                                 else "skipped" if "skipped" in kids
                                 else "passed")
    return out, p.stdout


def test_new_kind_is_taken_by_files_alone(tmp_path):
    _copy_with_toy(tmp_path, with_hooks=True)
    got, log = _run_checks(tmp_path)
    assert {k: got.get(k) for k in PICKED_UP} == dict.fromkeys(
        PICKED_UP, "passed"), log[-4000:]


def test_new_kind_without_hooks_fails_by_name(tmp_path):
    _copy_with_toy(tmp_path, with_hooks=False)
    got, log = _run_checks(tmp_path)
    assert {k: got.get(k) for k in NEED_HOOKS} == dict.fromkeys(
        NEED_HOOKS, "failed"), log[-4000:]
    assert "kind 'toy' has no hooks" in log
    assert got["test_benchmark_json_contract"] == "passed"
    assert got["test_cell_files_found_by_name[toy-cell]"] == "passed"
    assert not any(k.startswith("test_planted_fault") for k in got)
