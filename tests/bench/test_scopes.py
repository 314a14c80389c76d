"""CPU tests of ``bench/scopes.py``: the reader of the program's device scopes
(``tf_op`` metadata, and the HLO proto where XLA dropped it), the reduction
of its host spans, the pairing of pulls with executions, the readings, and
that ``bench/trace.py``'s reduction of a trace does not change when the
program's ``ace:`` spans are in it."""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common  # noqa: E402
from bench import scopes  # noqa: E402
from bench import trace as btrace  # noqa: E402


# -- protobuf by hand ------------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _ld(num: int, payload) -> bytes:
    """A length-delimited field."""
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _vi(num: int, value: int) -> bytes:
    """A varint field."""
    return _varint(num << 3) + _varint(value)


def _instr(name, opcode="fusion", op_name=None, calls=()):
    meta = _ld(7, _ld(2, op_name)) if op_name is not None else b""
    return _ld(1, name) + _ld(2, opcode) + meta + b"".join(
        _vi(38, c) for c in calls)


def _computation(cid, *instrs):
    return _ld(1, f"c{cid}") + b"".join(_ld(2, i) for i in instrs) \
        + _vi(5, cid)


HLO = _ld(1, _ld(1, "jit_f") + b"".join(_ld(3, c) for c in [
    _computation(1,
                 _instr("fusion.9", calls=[2]),     # metadata dropped
                 _instr("fusion.8", calls=[3]),     # dropped, mixed inside
                 _instr("add.1", "add", "jit(f)/ace/threshold/add")),
    _computation(2,
                 _instr("p.0", "parameter", "scatter-add"),
                 _instr("reshape.2", "reshape",
                        "jit(f)/while/body/ace/insert/broadcast_in_dim"),
                 _instr("scatter.3", "scatter", calls=[4])),
    _computation(4, _instr("add.5", "add", "ace/insert/add")),
    _computation(3,
                 _instr("gather.6", "gather", "jit(f)/ace/score/gather"),
                 _instr("and.7", "and", "jit(f)/while/body/and"))]))


def _octal(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


# Device op metadata: (key, event name, display name, tf_op as a string or
# as a reference to stat metadata 9).
OPS = [
    (1, "%fusion.1 = s32[8] fusion(s32[64] %p)", "fusion.1",
     'str_value: "jit(f)/while/body/ace/score/gather:"'),
    (2, "%fusion.2 = s32[8] fusion(s32[64] %q)", "fusion.2", "ref_value: 9"),
    (3, "%and.3 = pred[8] and(pred[8] %a, pred[8] %b)", "and.3",
     'str_value: "jit(f)/while/body/and:"'),
    (4, "%copy.4 = s32[8] copy(s32[8] %c)", "copy.4",
     'str_value: "jit(f)/ace/hash/copy:"'),
    (5, "%copy.4 = s32[8] copy(s32[8] %c)", "copy.4",
     'str_value: "jit(f)/ace/score/copy:"'),
    (6, "%fusion.9 = s32[64] fusion(s32[64] %d)", "fusion.9",
     'str_value: "jit(f)/while:"'),
    (7, "%fusion.8 = s32[8] fusion(s32[64] %e)", "fusion.8",
     'str_value: "jit(f)/while:"'),
    (8, "%copy-start.5 = s32[8] copy-start(s32[8] %f)", "copy-start.5",
     'str_value: "jit(f)/ace/score/copy-start:"'),
    (10, "%copy-start.5 = s32[8] copy-start(s32[8] %f)", "async-copy",
     'str_value: "jit(f)/ace/score/copy-start:"'),
    (11, "%while.1 = s32[64] while(s32[64] %g)", "while.1",
     'str_value: "jit(f)/while:"'),
]
WANT = {"%fusion.1 = s32[8] fusion(s32[64] %p)": "score",
        "%fusion.2 = s32[8] fusion(s32[64] %q)": "rescore",
        "%and.3 = pred[8] and(pred[8] %a, pred[8] %b)": None,
        "%copy.4 = s32[8] copy(s32[8] %c)": None,          # two phases
        "%fusion.9 = s32[64] fusion(s32[64] %d)": "insert",
        "%fusion.8 = s32[8] fusion(s32[64] %e)": None,
        "%copy-start.5 = s32[8] copy-start(s32[8] %f)": "score",
        "%while.1 = s32[64] while(s32[64] %g)": None}


def _device_plane(ops_line: str = "", modules_line: str = "") -> str:
    metas = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{name}" '
        f'display_name: "{disp}" stats {{ metadata_id: 1 {tf} }} '
        f'stats {{ metadata_id: 2 uint64_value: 42 }} }} }}\n'
        for k, name, disp, tf in OPS)
    metas += ('event_metadata { key: 20 value { id: 20 '
              'name: "jit__consume_impl(42)" } }\n')
    return (
        'planes { id: 2 name: "/device:TPU:0"\n'
        f'{modules_line}{ops_line}{metas}'
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n'
        'stat_metadata { key: 2 value { id: 2 name: "program_id" } }\n'
        'stat_metadata { key: 9 value { id: 9 '
        'name: "jit(f)/ace/rescore/ace/score/gather:" } } }\n')


METADATA_PLANE = (
    'planes { id: 3 name: "/host:metadata"\n'
    'event_metadata { key: 1 value { id: 1 name: "jit_f(42)" '
    f'stats {{ metadata_id: 1 bytes_value: "{_octal(HLO)}" }} }} }}\n'
    'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } } }\n')


def _xspace(text: str) -> bytes:
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(text)


def _line(name: str, events) -> str:
    """A line of (metadata key, start ns, end ns) events."""
    evs = "".join(f"events {{ metadata_id: {k} offset_ps: {s * 1000} "
                  f"duration_ps: {(e - s) * 1000} }}\n" for k, s, e in events)
    return f'lines {{ id: 1 name: "{name}" timestamp_ns: 0\n{evs}}}\n'


# -- the reader -------------------------------------------------------------------

def test_hlo_phases_by_hand():
    got = scopes.hlo_phases(HLO)
    assert got["fusion.9"] == "insert"       # the parameter's name is no vote
    assert got["fusion.8"] is None           # score and unscoped inside
    assert got["add.1"] == "threshold"
    assert got["reshape.2"] == "insert" and got["and.7"] is None


def test_reader_finds_each_ops_phase():
    buf = _xspace(_device_plane() + METADATA_PLANE)
    assert scopes.op_phases(buf) == dict(WANT, **{      # a module's run
        "jit__consume_impl(42)": None})


def test_reader_without_the_hlo_proto_leaves_dropped_metadata_unscoped():
    got = scopes.op_phases(_xspace(_device_plane()))
    assert got["%fusion.9 = s32[64] fusion(s32[64] %d)"] is None
    assert got["%fusion.1 = s32[8] fusion(s32[64] %p)"] == "score"


@pytest.mark.parametrize("name,want", [
    ("jit(f)/while/body/closed_call/ace/score/gather:", "score"),
    ("ace/insert/add", "insert"),
    ("jit(f)/ace/rescore/ace/score/gather", "rescore"),
    ("jit(f)/while/body/reduce_and:", None),
    ("jit(f)/space/score/gather", None),
    ("", None)])
def test_phase_of(name, want):
    assert scopes.phase_of(name) == want


# -- the reduction and its readings ---------------------------------------------

# Host events (metadata key, start ns, end ns); the ace: spans carry args.
HOST_META = {1: "bench:window", 2: "bench:consume", 3: "bench:pull",
             4: "ace:stream/consume"}
HOST_EVENTS = [(1, 100, 1100), (2, 145, 165), (4, 148, 158, 5),
               (3, 165, 400), (2, 405, 425), (4, 408, 418, 6),
               (3, 425, 700), (3, 705, 900)]
# Executions of the consume program: the first, of chunk 4, began before
# chunk 5's dispatch span; ops inside each run under a loop.
MODULES = [(20, 50, 140), (20, 170, 390), (20, 430, 690)]
OPS_EVENTS = [(11, 170, 390), (1, 170, 250), (6, 250, 300), (2, 300, 380),
              (3, 380, 390),
              (11, 430, 650), (1, 430, 510), (6, 510, 560), (2, 560, 640),
              (3, 640, 650)]


def _host_plane(with_program_spans: bool) -> str:
    evs = []
    for ev in HOST_EVENTS:
        k, s, e = ev[:3]
        if k == 4 and not with_program_spans:
            continue
        stats = (f"stats {{ metadata_id: 1 int64_value: {ev[3]} }} "
                 f"stats {{ metadata_id: 2 int64_value: 100 }} "
                 if k == 4 else "")
        evs.append(f"events {{ metadata_id: {k} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000} {stats}}}\n")
    metas = "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                    f'name: "{n}" }} }}\n' for k, n in HOST_META.items()
                    if with_program_spans or k != 4)
    return ('planes { id: 1 name: "/host:CPU"\n'
            f'lines {{ id: 1 name: "python" timestamp_ns: 0\n'
            f'{"".join(evs)}}}\n{metas}'
            'stat_metadata { key: 1 value { id: 1 name: "chunk" } }\n'
            'stat_metadata { key: 2 value { id: 2 name: "items" } } }\n')


def _trace_file(tmp_path, with_program_spans: bool = True) -> str:
    text = (_host_plane(with_program_spans)
            + _device_plane(_line("XLA Ops", OPS_EVENTS),
                            _line("XLA Modules", MODULES))
            + METADATA_PLANE)
    path = tmp_path / f"t{int(with_program_spans)}.xplane.pb"
    path.write_bytes(_xspace(text))
    return str(path)


def test_reduce_of_a_recorded_trace(tmp_path):
    s = scopes.reduce(_trace_file(tmp_path))
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(440e-9)
    assert s.scope_self_s == pytest.approx(
        {"score": 160e-9, "insert": 100e-9, "rescore": 160e-9,
         "unscoped": 20e-9})
    assert [(a, b, n, st) for a, b, n, st in s.spans] == [
        (148, 158, "ace:stream/consume", {"chunk": 5, "items": 100}),
        (408, 418, "ace:stream/consume", {"chunk": 6, "items": 100})]
    assert s.modules == {"jit__consume_impl": [(50, 140), (170, 390),
                                               (430, 690)]}
    assert s.pulls == [(165, 400), (425, 700), (705, 900)]
    assert scopes.readings(s) == pytest.approx({
        "score_device_us_per_kitem": 0.8,
        "rescore_device_us_per_kitem": 0.8,
        "insert_device_us_per_kitem": 0.5,
        "unscoped_device_share": 100 * 20 / 440,
        "runner_dispatch_us": 0.01,
        "pull_after_ready_ms": 260e-6})


# The admitted metrics that read the program's marks, and their readings.
SCOPE_METRICS = {
    "score_device_us_per_kitem.stream": "score_device_us_per_kitem",
    "rescore_device_us_per_kitem.stream": "rescore_device_us_per_kitem",
    "insert_device_us_per_kitem.stream": "insert_device_us_per_kitem",
    "unscoped_device_share.stream": "unscoped_device_share",
    "runner_dispatch_us.stream": "runner_dispatch_us",
    "pull_after_ready_ms.stream": "pull_after_ready_ms"}


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_metric_file_reads_its_key_from_the_scopes(metric, tmp_path):
    """Each reader takes its reading from ``Context.scopes``, the reduction
    ``bench/run.py`` hands it, and reads nothing from a program without
    the marks."""
    from bench import run
    read = common.load_module(os.path.join(
        common.BENCH, "metrics", metric + ".py")).read
    s = scopes.reduce(_trace_file(tmp_path))
    ctx = run.Context(trace=None, scopes=s, samples={}, cfg={}, mix={},
                      work=None, peaks={})
    assert read(ctx) == pytest.approx(scopes.readings(s)[
        SCOPE_METRICS[metric]])
    bare = scopes.ScopeSummary(window_s=1.0, busy_s=0.5,
                               scope_self_s={"unscoped": 0.5}, spans=[],
                               modules={}, pulls=[])
    assert read(dataclasses.replace(ctx, scopes=bare)) is None


def test_readings_take_any_phase_found():
    """A scope the program adds later (``ace/rotate``) is read by its name,
    with no change here."""
    span = (0, 10, scopes.CONSUME_SPAN, {"chunk": 1, "items": 2000})
    s = scopes.ScopeSummary(window_s=1.0, busy_s=0.5,
                            scope_self_s={"rotate": 1e-3, "score": 3e-3,
                                          "unscoped": 1e-3},
                            spans=[span], modules={}, pulls=[])
    assert scopes.readings(s) == pytest.approx({
        "rotate_device_us_per_kitem": 500.0,
        "score_device_us_per_kitem": 1500.0,
        "unscoped_device_share": 20.0,
        "runner_dispatch_us": 0.01})


def test_main_reduces_a_kept_file(tmp_path, capsys):
    path = _trace_file(tmp_path)
    assert scopes.main(["--file", path]) == 0
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert got["readings"] == pytest.approx(
        scopes.readings(scopes.reduce(path)))
    assert got["busy_s"] == pytest.approx(440e-9)


def test_reader_imports_neither_tensorflow_nor_xprof(tmp_path):
    """The chip's host may have neither: the reduction and its readings run
    in a process that never loads them."""
    code = ("import sys; from bench import scopes; "
            f"scopes.readings(scopes.reduce({_trace_file(tmp_path)!r})); "
            "print(sorted({m.split('.')[0] for m in sys.modules} "
            "& {'tensorflow', 'xprof', 'tensorboard_plugin_profile'}))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=dict(
        os.environ, PYTHONPATH=ROOT), capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_existing_reduction_ignores_program_spans_by_hand(tmp_path):
    """The same recorded trace with and without the ``ace:`` spans (one is
    open at the middle of an idle gap) reduces alike in bench/trace.py."""
    a = btrace.reduce(_trace_file(tmp_path, True))
    b = btrace.reduce(_trace_file(tmp_path, False))
    assert a == b
    assert a.span_counts == {"consume": 2, "pull": 3}
    assert a.breakdown() == b.breakdown()


def test_readings_of_a_program_without_marks():
    """The parent program has no scopes and no span: nothing to read, and
    no error."""
    s = scopes.ScopeSummary(window_s=1.0, busy_s=0.5,
                            scope_self_s={"unscoped": 0.5}, spans=[],
                            modules={"jit__consume_impl": [(0, 10)]},
                            pulls=[(20, 30)])
    assert scopes.readings(s) == {}


@pytest.mark.parametrize("case", ["steady", "window_edges", "late_start",
                                  "no_pull"])
def test_pull_after_ready_by_hand(case):
    # dispatch spans of chunks 7, 8, 9; executions in order
    dispatches = [(100, 7), (200, 8), (300, 9)]
    runs = [(110, 190), (210, 290), (310, 390)]
    if case == "steady":
        # pull after chunk 8's span pulls 7, after 9's pulls 8, then 9
        pulls = [(205, 250), (305, 350), (395, 420)]
        want = [250 - 190, 350 - 290, 420 - 390]
    elif case == "window_edges":
        # the first pull pulls chunk 6, dispatched before the trace, whose
        # run is in it: it is left out, and pairs nothing out of place
        runs = [(20, 95)] + runs
        pulls = [(105, 108), (205, 250), (305, 350), (395, 420)]
        want = [250 - 190, 350 - 290, 420 - 390]
    elif case == "late_start":
        # chunk 8 ran late, after chunk 9's dispatch began
        runs = [(110, 190), (305, 330), (335, 390)]
        pulls = [(205, 331), (340, 360), (395, 420)]
        want = [331 - 190, 360 - 330, 420 - 390]
    else:
        pulls = [(50, 60)]                    # before every dispatch
        want = []
    assert scopes.pull_after_ready(dispatches, runs, pulls) == want


# -- a CPU trace of the stream path ------------------------------------------------

@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A traced window of six chunks through ``StreamRunner.consume``, with
    the harness's spans and one chunk in flight, as bench/traffic/stream.py
    drives it."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import AceDataFilter
    from repro.stream import StreamRunner

    filt = AceDataFilter(d_model=8, num_bits=6, num_tables=4,
                         warmup_items=16.0, insert_all=True)
    runner = StreamRunner(filt, chunk_T=2, topk=4, return_masks=True)
    feats = jnp.asarray(np.abs(common.rng(3, 1).normal(size=(2, 32, 9))),
                        jnp.float32)
    scratch, w = runner.init()
    jax.device_get(runner.consume(scratch, w, feats)[1:])     # chunk 0
    state, _ = runner.init()
    spans = common.Spans()
    tr = btrace.Tracer(True, 0.0, spans)
    try:
        tr.poll(0.0)
        pending = None
        for _ in range(6):
            with spans("consume"):
                state, summ, keep = runner.consume(state, w, feats)
            if pending is not None:
                with spans("pull"):
                    jax.device_get(pending)
            pending = (summ, keep)
        with spans("pull"):
            jax.device_get(pending)
        tr.window_ends()
        path = str(tmp_path_factory.mktemp("trace") / "cpu.xplane.pb")
        shutil.copy(tr.stop(), path)
    finally:
        tr.cleanup()
    return path


def _walk(buf: bytes, i: int, j: int):
    """(field number, wire type, payload) of a message, payload raw."""
    while i < j:
        key, i = scopes._varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            _, k = scopes._varint(buf, i)
        elif kind == 2:
            n, i = scopes._varint(buf, i)
            k = i + n
        else:
            k = i + (8 if kind == 1 else 4)
        yield num, kind, buf[i:k]
        i = k


def _field(num: int, kind: int, payload: bytes) -> bytes:
    head = _varint(num << 3 | kind)
    return head + (_varint(len(payload)) if kind == 2 else b"") + payload


def _strip_events(buf: bytes, prefix: str) -> bytes:
    """The serialized XSpace less every event whose name starts with
    ``prefix`` (XSpace.planes 1; XPlane.lines 3, .event_metadata 4;
    XLine.events 4; XEvent.metadata_id 1)."""
    out = b""
    for num, kind, plane in _walk(buf, 0, len(buf)):
        if num == 1:
            drop = {k for k, (name, _, _) in _metadata_ids(plane).items()
                    if name.startswith(prefix)}
            new = b""
            for n2, k2, v2 in _walk(plane, 0, len(plane)):
                if n2 == 3:
                    v2 = b"".join(
                        _field(n3, k3, v3)
                        for n3, k3, v3 in _walk(v2, 0, len(v2))
                        if not (n3 == 4 and _event_meta(v3) in drop))
                new += _field(n2, k2, v2)
            plane = new
        out += _field(num, kind, plane)
    return out


def _metadata_ids(plane: bytes) -> dict:
    _, events = scopes._plane_metadata(plane, (0, len(plane)))
    ids = []
    for num, kind, entry in _walk(plane, 0, len(plane)):
        if num == 4:
            ids.append(next(v for n, v in scopes.fields(entry) if n == 1))
    return dict(zip(ids, events))


def _event_meta(event: bytes) -> int:
    return next((v for n, v in scopes.fields(event) if n == 1), 0)


def _host_events(path: str) -> list:
    from jax.profiler import ProfileData
    return [(e.start_ns, e.end_ns, e.name, dict(e.stats))
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host") for ln in p.lines
            for e in ln.events]


def test_cpu_trace_carries_the_consume_spans(cpu_trace):
    evs = _host_events(cpu_trace)
    (w0, w1), = [(s, e) for s, e, n, _ in evs if n == btrace.WINDOW_SPAN]
    consume = sorted((s, e, st) for s, e, n, st in evs
                     if n == scopes.CONSUME_SPAN)
    assert [st["chunk"] for _, _, st in consume] == [1, 2, 3, 4, 5, 6]
    assert all(st["items"] == 64 for _, _, st in consume)
    assert all(w0 <= s <= e <= w1 for s, e, _ in consume)
    s = scopes.reduce(cpu_trace)
    assert [st for _, _, _, st in s.spans] == [st for *_, st in consume]
    assert len(s.pulls) == 6


def test_existing_reduction_ignores_program_spans(cpu_trace, tmp_path):
    """bench/trace.py reduces a trace with the program's spans as it does
    the same trace without them."""
    stripped = tmp_path / "stripped.xplane.pb"
    with open(cpu_trace, "rb") as f:
        stripped.write_bytes(_strip_events(f.read(), "ace:"))
    assert not any(n.startswith("ace:") for *_, n, _ in
                   _host_events(str(stripped)))
    a, b = btrace.reduce(cpu_trace), btrace.reduce(str(stripped))
    assert a.span_counts == b.span_counts == {"consume": 6, "pull": 6}
    assert a.idle_by_span == b.idle_by_span
    assert a.breakdown() == b.breakdown()
    assert a == b


def test_program_spans_share_the_device_clock(cpu_trace):
    """The program's spans and the device's operations are on one clock:
    each chunk's run begins after its dispatch span begins, and ends
    before the pull of its verdicts ends (on the CPU backend the device
    operations are host events with an ``hlo_op`` statistic)."""
    evs = _host_events(cpu_trace)
    runs = collections.defaultdict(list)
    for s, e, _, st in evs:
        if st.get("hlo_module") == scopes.CONSUME_MODULE:
            runs[st["run_id"]] += [s, e]
    runs = sorted((min(v), max(v)) for v in runs.values())
    dispatches = sorted((s, st["chunk"]) for s, _, n, st in evs
                        if n == scopes.CONSUME_SPAN)
    pulls = [(s, e) for s, e, n, _ in evs if n == "bench:pull"]
    assert len(runs) == len(dispatches) == 6
    assert all(r0 >= d0 for (r0, _), (d0, _) in zip(runs, dispatches))
    gaps = scopes.pull_after_ready(dispatches, runs, pulls)
    assert len(gaps) == 6 and min(gaps) >= 0


def test_hlo_phases_of_the_cpu_consume_module(cpu_trace):
    """The phases in the HLO proto of the consume program that ran in the
    trace.  The profiler lists the proto of every executable alive in the
    process, so another runner's, left by an earlier test, may be there
    too: the one that ran is found by its program id."""
    with open(cpu_trace, "rb") as f:
        buf = f.read()
    (ran,) = {st["program_id"] for *_, st in _host_events(cpu_trace)
              if st.get("hlo_module") == scopes.CONSUME_MODULE}
    (proto,) = [st["Hlo Proto"]
                for num, v in scopes.fields(buf) if num == 1
                for name, events in [scopes._plane_metadata(buf, v)]
                if name == "/host:metadata"
                for ename, _, st in events
                if ename == f"{scopes.CONSUME_MODULE}({ran})"]
    phases = set(scopes.hlo_phases(buf, proto).values())
    assert {"hash", "score", "threshold", "insert", "rescore",
            "summary"} <= phases
