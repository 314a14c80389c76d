"""The program's own marks in a profiler trace, and the readings they give.

``repro.core.obs`` names the stream path's phases twice: as device scopes
``ace/<phase>`` (``op_name`` metadata of every op traced inside them) and as
host spans ``ace:<name>`` with arguments.  This module reduces a run's
``.xplane.pb`` to what those marks say, beside ``bench/trace.py``'s reduction
of the same file (which it leaves as it is):

* each device op's phase: the outermost ``ace/<phase>`` in the op's ``tf_op``
  statistic (the op's event metadata on a ``/device:*`` plane).  Where XLA
  dropped an op's metadata, as it does for the scatter it rebuilds, ``tf_op``
  names only the loop around the op; such an op takes the one phase that the
  instructions fused into it carry, read from the module's HLO proto on the
  ``/host:metadata`` plane.  An op with neither, or whose event name stands
  for ops of different phases, is ``unscoped``;
* self seconds per phase inside the traced window (``bench:window``), the
  way ``bench/trace.py`` takes them per op, averaged over the devices;
* the ``ace:`` host spans begun in the window, with their arguments;
* the execution intervals of each module on the first device's ``XLA
  Modules`` line, and the harness's ``bench:pull`` spans.

``jax.profiler.ProfileData`` gives an event's name and its own statistics,
not its metadata's, so the metadata is read from the file's protobuf wire
format here, with the field numbers of ``xplane.proto`` (TensorFlow's
``tsl/profiler/protobuf``) and ``hlo.proto`` (XLA).  Nothing here imports
TensorFlow or xprof.

``readings`` turns a reduction into per-layer numbers by name, one per
``ace/<phase>`` found and a few of the spans, and ``bench/run.py`` hands the
reduction of a traced run to the metric readers (``Context.scopes``).  Run
on a cell, traced:

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> \\
        [--keep <dir>]

prints one JSON line: the readings, the self seconds per phase, the cell's
per-layer metrics as its readers give them, the idle share, the run's own
numbers (profiler on) and ``bench/trace.py``'s breakdown; ``--keep`` copies
the trace file into ``<dir>``.  ``python3 bench/scopes.py --file <trace>``
reduces a kept file alone and prints its readings.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import json
import os
import re
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common  # noqa: E402
from bench import trace as btrace  # noqa: E402

UNSCOPED = "unscoped"
CONSUME_SPAN = "ace:stream/consume"
CONSUME_MODULE = "jit__consume_impl"
PULL_SPAN = "bench:pull"
_PHASE = re.compile(r"(?:^|/)ace/([\w.-]+)")
_PROGRAM = re.compile(r"\((\d+)\)$")

# xplane.proto: XSpace, XPlane, XEventMetadata, XStatMetadata, XStat; a map
# entry holds its value in field 2.
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
ENTRY_VALUE = 2
EVENT_NAME, EVENT_DISPLAY_NAME, EVENT_STATS = 2, 4, 5
STATMETA_ID, STATMETA_NAME = 1, 2
STAT_METADATA_ID, STAT_UINT64, STAT_INT64 = 1, 3, 4
STAT_STR, STAT_BYTES, STAT_REF = 5, 6, 7
# hlo.proto: HloProto, HloModuleProto, HloComputationProto,
# HloInstructionProto, OpMetadata.
HLO_MODULE, MODULE_COMPUTATIONS = 1, 3
COMPUTATION_INSTRUCTIONS, COMPUTATION_ID = 2, 5
INSTRUCTION_NAME, INSTRUCTION_OPCODE = 1, 2
INSTRUCTION_METADATA, INSTRUCTION_CALLS = 7, 38
METADATA_OP_NAME = 2


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf: bytes, i: int = 0, j: int | None = None):
    """(field number, value) of the protobuf message in ``buf[i:j]``: an int
    for a varint, a (start, end) slice of ``buf`` for a length-delimited
    field.  Fixed-width fields are skipped."""
    j = len(buf) if j is None else j
    while i < j:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} before byte {i}")
        yield num, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def phase_of(op_name: str) -> str | None:
    """The outermost ``ace/<phase>`` scope in an op's name, if any."""
    m = _PHASE.search(op_name)
    return m.group(1) if m else None


def hlo_phases(buf: bytes, span=None) -> dict:
    """{instruction name: phase or None} of one module's serialized
    ``HloProto``: the instruction's own ``op_name`` scope; where it has no
    ``op_name`` at all, the one phase that the instructions of the
    computations it calls carry (their parameters aside; an instruction
    there with an ``op_name`` outside every scope counts against it)."""
    span = span or (0, len(buf))
    comps = {}                  # id -> [(name, opcode, op_name, calls)]
    for num, module in fields(buf, *span):
        if num != HLO_MODULE:
            continue
        for num2, comp in fields(buf, *module):
            if num2 != MODULE_COMPUTATIONS:
                continue
            cid, instrs = None, []
            for num3, v in fields(buf, *comp):
                if num3 == COMPUTATION_ID:
                    cid = v
                elif num3 == COMPUTATION_INSTRUCTIONS:
                    instrs.append(_instruction(buf, v))
            comps[cid] = instrs

    memo = {}

    def votes(cid):
        """Phases (None for an unscoped name) of a computation's named
        instructions, and of those its instructions call (computations
        call one another without cycles)."""
        if cid not in memo:
            memo[cid] = set()
            for _, opcode, op_name, calls in comps.get(cid, ()):
                if op_name and opcode != "parameter":
                    memo[cid].add(phase_of(op_name))
                for c in calls:
                    memo[cid] |= votes(c)
        return memo[cid]

    phases = {}
    for instrs in comps.values():
        for name, _, op_name, calls in instrs:
            if op_name:
                phases[name] = phase_of(op_name)
                continue
            found = set().union(*(votes(c) for c in calls))
            phases[name] = found.pop() if len(found) == 1 else None
    return phases


def _instruction(buf: bytes, span):
    name, opcode, op_name, calls = "", "", "", []
    for num, v in fields(buf, *span):
        if num == INSTRUCTION_NAME:
            name = _text(buf, v)
        elif num == INSTRUCTION_OPCODE:
            opcode = _text(buf, v)
        elif num == INSTRUCTION_METADATA:
            for num2, v2 in fields(buf, *v):
                if num2 == METADATA_OP_NAME:
                    op_name = _text(buf, v2)
        elif num == INSTRUCTION_CALLS:
            if isinstance(v, int):
                calls.append(v)
            else:                                   # packed
                i = v[0]
                while i < v[1]:
                    c, i = _varint(buf, i)
                    calls.append(c)
    return name, opcode, op_name, calls


def _plane_metadata(buf: bytes, plane):
    """(name, [(event name, display name, {stat name: value})]) of a plane;
    a statistic's value is its int, its string, or the (start, end) slice of
    its bytes."""
    name, events, stat_names, raw = "", [], {}, []
    for num, v in fields(buf, *plane):
        if num == PLANE_NAME:
            name = _text(buf, v)
        elif num == PLANE_STAT_METADATA:
            for num2, entry in fields(buf, *v):
                if num2 == ENTRY_VALUE:
                    sid, sname = 0, ""
                    for num3, v3 in fields(buf, *entry):
                        if num3 == STATMETA_ID:
                            sid = v3
                        elif num3 == STATMETA_NAME:
                            sname = _text(buf, v3)
                    stat_names[sid] = sname
        elif num == PLANE_EVENT_METADATA:
            raw.append(v)
    for v in raw:
        for num2, meta in fields(buf, *v):
            if num2 != ENTRY_VALUE:
                continue
            ename, display, stats = "", "", {}
            for num3, v3 in fields(buf, *meta):
                if num3 == EVENT_NAME:
                    ename = _text(buf, v3)
                elif num3 == EVENT_DISPLAY_NAME:
                    display = _text(buf, v3)
                elif num3 == EVENT_STATS:
                    sid, value = 0, None
                    for num4, v4 in fields(buf, *v3):
                        if num4 == STAT_METADATA_ID:
                            sid = v4
                        elif num4 in (STAT_UINT64, STAT_INT64):
                            value = v4
                        elif num4 == STAT_STR:
                            value = _text(buf, v4)
                        elif num4 == STAT_BYTES:
                            value = v4
                        elif num4 == STAT_REF:
                            value = stat_names.get(v4, "")
                    stats[stat_names.get(sid, "")] = value
            events.append((ename, display, stats))
    return name, events


def op_phases(buf: bytes) -> dict:
    """{device op event name, as ``ProfileData`` gives it: phase or None}
    over every ``/device:*`` plane of a serialized ``XSpace``."""
    planes = [_plane_metadata(buf, v) for num, v in fields(buf)
              if num == SPACE_PLANES]
    protos = {}                                  # program id -> bytes slice
    for name, events in planes:
        if name == "/host:metadata":
            for ename, _, stats in events:
                m = _PROGRAM.search(ename)
                if m and isinstance(stats.get("Hlo Proto"), tuple):
                    protos[int(m.group(1))] = stats["Hlo Proto"]
    modules = {}                                 # program id -> hlo_phases
    found = collections.defaultdict(set)
    for name, events in planes:
        if not name.startswith("/device:"):
            continue
        for ename, display, stats in events:
            phase = phase_of(str(stats.get("tf_op", "")))
            program = stats.get("program_id")
            if phase is None and program in protos:
                if program not in modules:
                    modules[program] = hlo_phases(buf, protos[program])
                phase = modules[program].get(display)
            found[ename].add(phase)
    return {k: (v.pop() if len(v) == 1 else None) for k, v in found.items()}


@dataclasses.dataclass
class ScopeSummary:
    window_s: float
    busy_s: float                   # averaged over the devices
    scope_self_s: dict              # phase or "unscoped" -> seconds
    spans: list                     # (start_ns, end_ns, name, {arg: value})
    modules: dict                   # module -> [(start_ns, end_ns)]
    pulls: list                     # (start_ns, end_ns) of bench:pull


def reduce(path: str) -> ScopeSummary:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        buf = f.read()
    phases = op_phases(buf)
    pd = ProfileData.from_serialized_xspace(buf)
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for ln in plane.lines:
                host += [(e.start_ns, e.end_ns, e.name, e.stats)
                         for e in ln.events
                         if e.name.startswith(("ace:", "bench:"))]
    host.sort(key=lambda h: h[:3])
    wins = [(s, e) for s, e, n, _ in host if n == btrace.WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {btrace.WINDOW_SPAN} span in {path}")
    w0, w1 = wins[0]
    inside = [h for h in host if w0 <= h[0] < w1]
    self_s, busy, devices, modules = collections.defaultdict(float), 0.0, \
        0, {}
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        if not lines.get("XLA Ops"):
            continue
        devices += 1
        ops = [(max(e.start_ns, w0), min(e.end_ns, w1),
                phases.get(e.name) or UNSCOPED)
               for e in lines["XLA Ops"] if e.end_ns > w0 and e.start_ns < w1]
        busy += sum(e - s for s, e in btrace._union(
            (s, e) for s, e, _ in ops)) * 1e-9
        for k, v in btrace._self_times(ops).items():
            self_s[k] += v * 1e-9
        if devices == 1:
            for e in lines.get("XLA Modules", []):
                modules.setdefault(btrace._MODULE.sub("", e.name), []).append(
                    (e.start_ns, e.end_ns))
    n = max(devices, 1)
    return ScopeSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
        scope_self_s={k: v / n for k, v in self_s.items()},
        spans=[(s, e, name, dict(st)) for s, e, name, st in inside
               if name.startswith("ace:")],
        modules={k: sorted(v) for k, v in modules.items()},
        pulls=[(s, e) for s, e, name, _ in inside if name == PULL_SPAN])


def pull_after_ready(dispatches, executions, pulls) -> list:
    """Nanoseconds from the end of a chunk's execution to the end of the
    pull of its verdicts, one per pull that can be paired.

    ``dispatches``: (start, chunk) of each chunk's dispatch span, in order;
    ``executions``: (start, end) of the consume program's runs, in order;
    ``pulls``: (start, end) of the pull spans.  Dispatches and executions
    pair in order from the last one back, since a trace may begin with runs
    of chunks dispatched before it.  The harness keeps one chunk in flight:
    the pull after the dispatch of chunk s pulls chunk s - 1, and a second
    pull after the same dispatch (the last, after the window's loop) pulls
    chunk s."""
    dispatches, executions = sorted(dispatches), sorted(executions)
    ready = {c: e for (_, c), (_, e) in zip(reversed(dispatches),
                                            reversed(executions))}
    starts = [s for s, _ in dispatches]
    pulled, out = set(), []
    for p0, p1 in sorted(pulls):
        i = bisect.bisect_left(starts, p0) - 1
        if i < 0:
            continue
        chunk = dispatches[i][1] if i in pulled else dispatches[i][1] - 1
        pulled.add(i)
        if chunk in ready:
            out.append(p1 - ready[chunk])
    return out


def readings(s: ScopeSummary) -> dict:
    """The per-layer readings of a reduction, by name: for every
    ``ace/<phase>`` found, ``<phase>_device_us_per_kitem``, its self time
    per 1,000 items of the ``ace:stream/consume`` spans; the share of the
    device's self time in no scope (``unscoped_device_share``, in percent);
    the median consume span (``runner_dispatch_us``) and the median time
    from a chunk's execution's end to the end of the pull of its verdicts
    (``pull_after_ready_ms``).  A reading with nothing to read (a program
    without the scopes or the span) is left out; each
    ``bench/metrics/<name>.py`` picks its own."""
    consume = [(a, b, st) for a, b, name, st in s.spans
               if name == CONSUME_SPAN]
    items = sum(int(st.get("items", 0)) for _, _, st in consume)
    scoped = {k: v for k, v in s.scope_self_s.items() if k != UNSCOPED}
    out = {}
    if items:
        for phase, seconds in scoped.items():
            out[f"{phase}_device_us_per_kitem"] = 1e9 * seconds / items
    total = sum(s.scope_self_s.values())
    if scoped and total > 0:
        out["unscoped_device_share"] = \
            100.0 * s.scope_self_s.get(UNSCOPED, 0.0) / total
    if consume:
        out["runner_dispatch_us"] = 1e-3 * common.median(
            [b - a for a, b, _ in consume])
    gaps = pull_after_ready(
        [(a, int(st["chunk"])) for a, _, st in consume if "chunk" in st],
        s.modules.get(CONSUME_MODULE, []), s.pulls)
    if gaps:
        out["pull_after_ready_ms"] = 1e-6 * common.median(gaps)
    return out


def _summary(s: ScopeSummary) -> dict:
    return {"readings": readings(s), "scope_self_s": s.scope_self_s,
            "busy_s": s.busy_s, "window_s": s.window_s}


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--keep", default="")
    ap.add_argument("--file", default="",
                    help="reduce this kept trace file instead of a run")
    args = ap.parse_args(argv)
    if args.file:
        print(json.dumps(dict(file=args.file, **_summary(reduce(args.file)))),
              flush=True)
        return 0
    if args.workload is None or args.seed is None or args.seconds is None:
        ap.error("--workload, --seed and --seconds, or --file")
    if args.keep:
        def reduce_and_keep(path):
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(path, os.path.join(
                args.keep, f"{args.workload}.{args.seed}.xplane.pb"))
            return reduce(path)

        run.reduce_scopes = reduce_and_keep
    d = run.drive(args.workload, args.seed, args.seconds, True)
    print(json.dumps(dict(
        workload=args.workload, seed=args.seed, **_summary(d.scopes),
        metrics=run.per_layer(d, run.cell_metrics(args.workload, d.bench,
                                                  True)),
        idle_share=d.trace.idle_share, traced_metrics=d.out.metrics,
        span_counts=d.trace.span_counts,
        breakdown=d.trace.breakdown())), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
