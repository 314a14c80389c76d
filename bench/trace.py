"""The profiler trace of a run, and its reduction to device numbers.

A ``--trace 1`` run traces the last part of its window (``Tracer``).  The
reduction (``reduce``) reads the ``.xplane.pb`` the profiler writes:

* device operations: the ``XLA Ops`` line of each ``/device:*`` plane; a
  trace with no such plane (the CPU backend, in tests) takes the host
  events that carry an ``hlo_op`` statistic instead;
* busy time: the union of the operations' intervals inside the traced
  window, averaged over the devices; idle share = 1 - busy / window;
* each operation's self time (its time less that of operations nested in
  it, as a while loop holds its body), grouped by module and HLO op name;
* each idle gap, labelled by the innermost harness span (``bench:<what>``)
  open on the host at the gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import shutil
import tempfile

WINDOW_SPAN = "bench:window"
_SHAPE = re.compile(r"^([a-z0-9]+\[[^\]]*\])")
_MODULE = re.compile(r"\(\d+\)$")


class Tracer:
    """Traces the last min(window / 2, 4) seconds of a window, when
    enabled."""

    def __init__(self, enabled: bool, seconds: float, spans):
        self.enabled = enabled
        self.start_at = max(seconds - min(seconds / 2, 4.0), 0.0)
        self.spans = spans
        self.active = False
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if enabled else None
        self._window = None

    def poll(self, elapsed: float) -> None:
        """Called from the window's loop with the seconds since it began."""
        if self.enabled and not self.active and elapsed >= self.start_at:
            import jax
            jax.profiler.start_trace(self.dir)
            self.active = self.spans.on = True
            self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._window.__enter__()

    def window_ends(self) -> None:
        if self.active and self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None
            self.spans.on = False

    def stop(self) -> str | None:
        """Ends the trace; returns the path of the trace file."""
        if not self.active:
            return None
        import jax
        jax.profiler.stop_trace()
        self.active = False
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                   # averaged over the devices
    devices: int
    op_self_s: dict                 # "module/op kind type" -> seconds
    idle_by_span: dict              # host span -> idle device seconds
    span_counts: dict               # host span -> spans begun in the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(   # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:n]]
        return {"device_ops": top(self.op_self_s),
                "idle_gaps": top(self.idle_by_span)}


def _op_key(name: str, module: str) -> str:
    """``module/op kind shape`` from an HLO op's text, such as
    ``%fusion.3 = s32[12450]{0:T(1024)} fusion(...)``; a tuple-shaped op
    (a while loop, a sort) keeps its name and kind only."""
    op, sep, rest = name.partition(" = ")
    if not sep:
        return f"{module}/{name}"
    shape = ""
    if rest.startswith("("):               # a tuple: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        m = _SHAPE.match(rest)
        shape = m.group(1) if m else ""
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    kind = rest.strip().split("(", 1)[0]
    return f"{module}/{op.lstrip('%')} {kind} {shape}".strip()


def _device_ops(pd):
    """{device: [(start, end, key)]} over the trace's device operations."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        ops = lines.get("XLA Ops")
        if not ops:
            continue
        mods = sorted((e.start_ns, e.end_ns, _MODULE.sub("", e.name))
                      for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        evs = []
        for e in ops:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= e.start_ns else "?"
            evs.append((e.start_ns, e.end_ns, _op_key(e.name, mod)))
        out[plane.name] = evs
    if out:
        return out
    evs = []
    for plane in pd.planes:
        for ln in plane.lines:
            for e in ln.events:
                st = dict(e.stats)
                if "hlo_op" in st:
                    evs.append((e.start_ns, e.end_ns, _op_key(
                        e.name, str(st.get("hlo_module", "?")))))
    return {"host": evs} if evs else {}


def _host_spans(pd):
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            out += [(e.start_ns, e.end_ns, e.name) for e in ln.events
                    if e.name.startswith("bench:")]
    return sorted(out)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(evs):
    """Self time of each op: its span less the spans of ops nested in it."""
    out = collections.defaultdict(float)
    stack = []                                     # [start, end, key, self]
    for s, e, key in sorted(evs, key=lambda v: (v[0], -v[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out[top[2]] += top[3]
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append([s, e, key, e - s])
    for top in stack:
        out[top[2]] += top[3]
    return out


def _label_gaps(gaps, spans):
    """Idle seconds per innermost host span open at each gap's middle."""
    marks = []
    for i, (s, e, name) in enumerate(spans):
        if name != WINDOW_SPAN:
            marks += [(s, 1, i), (e, 0, i)]
    marks.sort()
    out = collections.defaultdict(float)
    open_spans, j = [], 0
    for g0, g1 in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (g0 + g1)
        while j < len(marks) and marks[j][0] <= mid:
            _, is_start, i = marks[j]
            if is_start:
                open_spans.append(i)
            elif i in open_spans:
                open_spans.remove(i)
            j += 1
        label = (spans[open_spans[-1]][2][len("bench:"):] if open_spans
                 else "no harness span")
        out[label] += (g1 - g0) * 1e-9
    return dict(out)


def reduce(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = _host_spans(pd)
    wins = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    w0, w1 = wins[0]
    devices = _device_ops(pd)
    if not devices:
        raise ValueError(f"no device operations in {path}")
    busy, op_self, idle = 0.0, collections.defaultdict(float), \
        collections.defaultdict(float)
    for evs in devices.values():
        clipped = [(max(s, w0), min(e, w1), k) for s, e, k in evs
                   if e > w0 and s < w1]
        merged = _union((s, e) for s, e, _ in clipped)
        busy += sum(e - s for s, e in merged) * 1e-9
        for k, v in _self_times(clipped).items():
            op_self[k] += v * 1e-9 / len(devices)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for k, v in _label_gaps(gaps, spans).items():
            idle[k] += v / len(devices)
    counts = collections.Counter(n[len("bench:"):] for s, _, n in spans
                                 if w0 <= s < w1 and n != WINDOW_SPAN)
    return TraceSummary(window_s=(w1 - w0) * 1e-9,
                        busy_s=busy / len(devices), devices=len(devices),
                        op_self_s=dict(op_self), idle_by_span=dict(idle),
                        span_counts=dict(counts))
