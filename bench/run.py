"""Runs one cell of the benchmark once, on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is the file that entry's configuration names, its traffic mix
is ``bench/traffic/<traffic>.json``, and the mix names the driver
``bench/traffic/<driver>.py`` that builds the system under test, makes its
inputs from the seed, warms up, and drives the measured window.  Per-layer
metrics are read, in ``--trace 1`` runs, by ``bench/metrics/<metric>.py``.

The run refuses (exit code other than 0, no result) a machine whose JAX finds
no TPU or fewer chips than the cell asks for, and a checkout without the
program's ``src/``.  A compile inside the measured window is an error.  After
the window the outputs are compared with the plain reference; each number
compared is printed with its limit as the last lines on standard error.  The
last line on standard output is the result, one JSON object.

Everything is found by the names in ``BENCHMARK.json``, so what follows is
added as new files and new entries there, and no file that exists is edited.

* A configuration: its entry in ``configs``; the file it names,
  ``bench/configs/<config>.json``, with its ``kind``, its sizes, its
  ``limits`` (number compared -> limit), and, for each key it lists in
  ``reduced``, the source's value under ``published``;
  ``bench/work/<config>.py``, whose ``per_item(cfg)`` gives the algorithm's
  flops and bytes per item; and, for a new ``kind``,
  ``bench/kinds/<kind>.py``, the hooks the tests find by the kind's name
  (``tiny``, ``control_numbers``, ``FAULTS``, ``plant``), a driver
  ``bench/traffic/<driver>.py`` whose ``run(r)`` returns an ``Outcome``, and
  its plain reference under ``bench/reference/``.
* A cell: its entry in ``workloads``, its name added to the ``workloads``
  of each end-to-end metric it reports, and its mix
  ``bench/traffic/<traffic>.json``, data only, naming its driver.
* A per-layer metric: its entry in ``per_layer`` and
  ``bench/metrics/<metric>.py``, whose ``read(ctx)`` takes a ``Context``
  and returns a number, or ``None`` where it finds nothing to read.  A
  device scope ``ace/<phase>`` of the program is read as
  ``bench.scopes.readings(ctx.scopes)["<phase>_device_us_per_kitem"]``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common  # noqa: E402
from bench.trace import Tracer, reduce as reduce_trace  # noqa: E402


class Refused(Exception):
    """The run cannot be made here; nothing is printed on standard output."""


@dataclasses.dataclass
class Outcome:
    metrics: dict              # end-to-end metric -> value
    attempted: int
    failed: int
    peak: int                  # peak device bytes on the fullest chip
    check: object              # (control=False) -> {number: value}
    samples: dict              # host-clock samples for per-layer readers
    notes: list


@dataclasses.dataclass
class Run:
    """What a driver gets: the cell, its seed and window, and the hooks."""

    workload: str
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    spans: common.Spans
    tracer: Tracer
    compiles: common.CompileCounter

    def window_begins(self) -> None:
        """Called as the measured window opens: set-up ends here.  The
        objects set-up made are frozen and the cyclic collector is off
        until the window closes, so that no collection over the whole
        heap (JAX's included) stalls the window."""
        gc.collect()
        gc.freeze()
        gc.disable()
        self._compiles0 = self.compiles.count
        self.setup_s = common.clock() - T_START

    def window_ends(self, trace_count: int) -> None:
        """Called as the window closes; ``trace_count`` is the program's own
        count of the traces of its entry, which must be 1."""
        self.tracer.window_ends()
        gc.enable()
        built = self.compiles.count - self._compiles0
        if built or trace_count != 1:
            raise RuntimeError(
                f"{built} executables built and a trace count of "
                f"{trace_count} inside the measured window")

    def read_peak(self) -> int:
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())

    def outcome(self, **kw) -> Outcome:
        return Outcome(**kw)


def benchmark(pending: bool = False) -> dict:
    """``BENCHMARK.json``; with ``pending``, also the cells of
    ``bench/pending.json``, defined but not admitted (their tools and tests
    still run them)."""
    bench = common.load_json(ROOT, "BENCHMARK.json")
    if pending:
        more = common.load_json(common.BENCH, "pending.json")
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + more[key]
    return bench


def cell_spec(name: str, bench: dict):
    """(workload entry, configuration dict, mix dict) of a cell, by name."""
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = common.load_json(ROOT, conf["file"])
    mix = common.load_json(common.BENCH, "traffic", w["traffic"] + ".json")
    return w, cfg, mix


def cell_metrics(name: str, bench: dict, trace: bool) -> list:
    """The metric entries this cell reports in a run of this kind."""
    reported = [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
    if not trace:
        return reported
    e2e = {m["name"] for m in reported}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader gets."""

    trace: object              # bench.trace.TraceSummary
    scopes: object             # bench.scopes.ScopeSummary
    samples: dict
    cfg: dict
    mix: dict
    work: object               # bench/work/<config>.py
    peaks: dict                # the device's row of bench/peaks.json


def _device_check(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", file=sys.stderr,
          flush=True)
    if require_tpu and dev.platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devices)}")
    return devices


def _compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def reduce_scopes(path: str):
    """The program's scopes and spans in a trace (``bench/scopes.py``)."""
    from bench import scopes
    return scopes.reduce(path)


@dataclasses.dataclass
class Driven:
    """A cell driven once: its specification, the run and its outcome."""

    bench: dict
    workload: dict
    cfg: dict
    mix: dict
    devices: list
    run: Run
    out: Outcome
    trace: object              # TraceSummary of a traced run, else None
    scopes: object             # ScopeSummary of a traced run, else None
    peaks: dict


def drive(name: str, seed: int, seconds: float, trace: bool, *,
          require_tpu: bool = True, cfg_override: dict | None = None,
          mix_override: dict | None = None, pending: bool = False) -> Driven:
    """Set up a cell, drive its window and reduce its trace."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused(f"no src/repro in {ROOT}: run from a checkout")
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    bench = benchmark(pending)
    w, cfg, mix = cell_spec(name, bench)
    cfg.update(cfg_override or {})
    mix.update(mix_override or {})
    devices = _device_check(w["chips"], require_tpu)
    peaks = common.load_json(common.BENCH, "peaks.json")
    if trace and require_tpu and devices[0].device_kind not in peaks:
        raise Refused(f"no peaks for device kind {devices[0].device_kind!r}"
                      " in bench/peaks.json")
    cache = _compile_cache()
    if trace:
        # Without the op names in the key, the cache may hand a traced run
        # an executable built from the same program with other op names
        # (another commit's, without the scopes), and its trace carries
        # those.
        import jax
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    print(f"workload={name} seed={seed} seconds={seconds} trace={int(trace)} "
          f"compile_cache={cache} devices_ready_s="
          f"{common.clock() - T_START:.3f}", file=sys.stderr, flush=True)

    spans = common.Spans()
    r = Run(workload=name, cfg=cfg, mix=mix, seed=seed, seconds=seconds,
            spans=spans, tracer=Tracer(trace, seconds, spans),
            compiles=common.CompileCounter())
    driver = common.load_module(os.path.join(
        common.BENCH, "traffic", mix["driver"] + ".py"))
    try:
        out = driver.run(r)
        path = r.tracer.stop()
        summary = reduce_trace(path) if trace else None
        scoped = reduce_scopes(path) if trace else None
    finally:
        r.tracer.cleanup()
    return Driven(bench=bench, workload=w, cfg=cfg, mix=mix, devices=devices,
                  run=r, out=out, trace=summary, scopes=scoped,
                  peaks=peaks.get(devices[0].device_kind, {}))


def per_layer(d: Driven, wanted: list) -> dict:
    """The per-layer metrics of a traced run, each read by its file;
    those whose reader finds nothing to read are left out."""
    ctx = Context(trace=d.trace, scopes=d.scopes, samples=d.out.samples,
                  cfg=d.cfg, mix=d.mix, work=common.load_module(os.path.join(
                      common.BENCH, "work", d.workload["config"] + ".py")),
                  peaks=d.peaks)
    metrics = {}
    for m in wanted:
        v = common.load_module(os.path.join(
            common.BENCH, "metrics", m["name"] + ".py")).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return metrics


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             **kw) -> dict:
    """One run of a cell; returns the result object.  Tests call this with
    ``require_tpu=False`` and small sizes."""
    d = drive(name, seed, seconds, trace, **kw)
    out, dev = d.out, d.devices[0]
    metrics = {}
    wanted = cell_metrics(name, d.bench, trace)
    if trace:
        metrics = per_layer(d, wanted)
    else:
        values = dict(out.metrics, setup_s=d.run.setup_s)
        for m in wanted:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    numbers = out.check()
    limits = d.cfg["limits"]
    checks = {k: {"value": float(numbers[k]), "limit": limits[k]}
              for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(d.devices), "memory_peak_bytes": out.peak}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = d.trace.busy_s
        device["window_s"] = d.trace.window_s
        result["breakdown"] = d.trace.breakdown()
    result["checks"] = checks
    for line in out.notes:
        print(line, file=sys.stderr)
    for k, v in numbers.items():
        if k not in checks:
            print(f"not compared: {k} = {float(v)!r}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
