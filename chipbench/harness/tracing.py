"""Capture a profiler trace of the window and reduce it to numbers.

The reduction reads, from the profiler's ``.xplane.pb``:

* device op intervals (the ``XLA Ops`` line of each device plane) and
  program executions (the ``XLA Modules`` line);
* the harness's own host spans (``jax.profiler.TraceAnnotation`` names
  starting with ``bench.``), among them ``bench.window`` that bounds the
  traced window.

From these: busy time as the union of op intervals inside the window,
averaged over the devices used; the idle share; device time by op name;
device time and count of each program; and every idle gap, named by the
innermost harness span open at the gap's middle.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
from typing import Iterable

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = tuple[float, float, str]  # start_ns, end_ns, name


@dataclasses.dataclass
class TraceEvents:
    ops: dict[str, list[Interval]]
    modules: dict[str, list[Interval]]
    spans: list[Interval]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def events_from_profile(pd) -> TraceEvents:
    """Pull op, program and span intervals out of a ``ProfileData``."""
    ops: dict[str, list[Interval]] = {}
    modules: dict[str, list[Interval]] = {}
    spans: list[Interval] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = ops if line.name == OPS_LINE else modules
                dest.setdefault(plane.name, []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                )
    return TraceEvents(ops, modules, spans)


def read_trace(log_dir: str) -> TraceEvents:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return events_from_profile(ProfileData.from_file(paths[-1]))


class Spans:
    """The harness's host spans (``bench.<what>``), written into the
    profiler's trace only when the run is traced."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if self.on:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the block; the block opens :data:`WINDOW_SPAN` itself."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in iv if e > lo and s < hi]


def leaves(ops: list[Interval]) -> list[Interval]:
    """The ops that hold no other op: a device line nests the ops of a
    loop's body inside the loop's own event, which would count twice."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [o for o, nxt in zip(ops, ops[1:] + [None]) if nxt is None or nxt[0] >= o[1]]


_HLO = re.compile(r"^(%[\w.\-]+) = ([a-z0-9]+\[[^\]]*\])(?:\{[^}]*\})? ([\w\-]+)\(")


def short_name(op: str) -> str:
    """``%name = type[shape] opcode`` of an HLO instruction's text."""
    m = _HLO.match(op)
    if m:
        return f"{m.group(1)} = {m.group(2)} {m.group(3)}"
    return op.split(" = ")[0][:120]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the devices with ops
    devices: int
    op_s: dict[str, float]  # device time by op name (ops holding no other op), summed over devices
    gaps: list[tuple[str, float]]  # (span open in the gap, seconds), every gap
    module_runs: dict[str, list[float]]  # program name -> durations (s), inside the window
    ops_in_modules: dict[str, dict[str, float]]  # program name -> op name -> seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, needle: str) -> tuple[int, float]:
        """Runs and summed device seconds of every program whose name
        holds ``needle``."""
        runs = [d for name, ds in self.module_runs.items() if needle in name for d in ds]
        return len(runs), sum(runs)

    def op_time_in(self, module_needle: str, op_needles: tuple[str, ...]) -> float:
        """Device seconds of ops whose name holds every one of
        ``op_needles``, inside programs whose name holds ``module_needle``."""
        total = 0.0
        for mod, ops in self.ops_in_modules.items():
            if module_needle in mod:
                total += sum(t for op, t in ops.items() if all(n in op for n in op_needles))
        return total

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time and the longest idle gaps,
        each ``[name, seconds]``, ops named ``%name = type opcode``."""
        by_short: dict[str, float] = {}
        for n, t in self.op_s.items():
            by_short[short_name(n)] = by_short.get(short_name(n), 0.0) + t
        ops = sorted(by_short.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": [[n, t] for n, t in gaps]}


class _SpanIndex:
    """Innermost harness span at a time, for spans that nest (one host
    thread's annotations): the latest-starting span before ``t``, or the
    nearest of its enclosing spans that is still open at ``t``."""

    def __init__(self, spans: list[Interval]):
        self.spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
        self.starts = [sp[0] for sp in self.spans]
        self.parent: list[int] = []
        stack: list[int] = []
        for i, (s, _, _) in enumerate(self.spans):
            while stack and self.spans[stack[-1]][1] <= s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            s, e, n = self.spans[i]
            if t < e:
                return n
            i = self.parent[i]
        return "none"


def reduce(ev: TraceEvents) -> Summary:
    windows = [(s, e) for s, e, n in ev.spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    spans = _SpanIndex(_clip(ev.spans, lo, hi))
    busy, op_s, gaps = [], {}, []
    module_runs: dict[str, list[float]] = {}
    ops_in_modules: dict[str, dict[str, float]] = {}
    for dev, ops in sorted(ev.ops.items()):
        ops = _clip(ops, lo, hi)
        if not ops:
            continue
        merged = union((s, e) for s, e, _ in ops)
        busy.append(sum(e - s for s, e in merged))
        ops = leaves(ops)
        for s, e, n in ops:
            op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((spans.at((s + e) / 2), (e - s) / 1e9))
        mods = sorted(m for m in ev.modules.get(dev, []) if m[0] >= lo and m[1] <= hi)
        for s, e, n in mods:
            module_runs.setdefault(n, []).append((e - s) / 1e9)
        # attribute each op to the program running when it started
        starts = [m[0] for m in mods]
        for s, e, n in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                d = ops_in_modules.setdefault(mods[i][2], {})
                d[n] = d.get(n, 0.0) + (e - s) / 1e9
    if not busy:
        raise ValueError("no device op ran inside the traced window")
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        devices=len(busy),
        op_s=op_s,
        gaps=gaps,
        module_runs=module_runs,
        ops_in_modules=ops_in_modules,
    )
