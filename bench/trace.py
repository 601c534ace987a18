"""Reduce a JAX profiler trace to what the per-layer metrics read.

The trace is the `.xplane.pb` that `jax.profiler.trace` writes, read with
`jax.profiler.ProfileData`. Device planes are `/device:TPU:<n>`; on each,
the "XLA Modules" line holds one event per program execution, named
after the jitted function (`jit_seg(<id>)`), and the "XLA Ops" line the
operations inside them. Host planes hold the host threads, with the
benchmark's own `TraceAnnotation` spans among their events.

`reduce` returns a `TraceSummary`:

- busy: per chip, the length of the union of its program executions
  inside the window (an operation only runs inside its program);
- programs: device seconds per program name, summed over chips;
- ops: device seconds per operation, summed over chips;
- idle_gaps: device idle time inside the window, by what the host was
  doing: each gap of the first chip is named after the innermost host
  event that covers its midpoint.

The window is the benchmark's span named `window_span` where the trace
holds it, and the extent of the device events otherwise.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")
MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: List[float]                  # per chip
    programs: Dict[str, float]           # seconds, summed over chips
    program_calls: Dict[str, int]
    ops: Dict[str, float]
    idle_gaps: Dict[str, float]          # seconds, by host activity

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0

    def program_s(self, names) -> Optional[float]:
        """Device seconds of the programs named, or None where the trace
        holds none of them."""
        hit = [self.programs[n] for n in names if n in self.programs]
        return sum(hit) if hit else None


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def program_name(event_name: str) -> str:
    """`jit_seg(1234)` -> `jit_seg`."""
    return _SUFFIX.sub("", event_name)


def op_name(event_name: str) -> str:
    """An "XLA Ops" event is named by its HLO instruction,
    `%fusion.12 = s32[256]{...} fusion(...)`: keep `%fusion.12`."""
    return event_name.split(" = ", 1)[0]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _innermost(events, points) -> List[str]:
    """For each time point (ascending), the name of the shortest event
    (start, end, name) that covers it: a sweep with a heap of the events
    begun so far, ordered by length, whose ended entries are dropped as
    they surface."""
    events = sorted(events)
    out, heap, k = [], [], 0
    for t in points:
        while k < len(events) and events[k][0] <= t:
            s, e, name = events[k]
            heapq.heappush(heap, (e - s, e, name))
            k += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else "(no host event)")
    return out


def reduce(path: str, window_span: str = "bench.window") -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(2))] = plane
        elif plane.name.startswith("/host:"):
            host.append(plane)

    window = None
    host_events = []
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if ev.name == window_span:
                    window = (s, s + d)
                elif d > 0:
                    host_events.append((s, s + d, ev.name))

    per_chip_modules = {}
    per_chip_ops = {}
    for ordinal, plane in sorted(devices.items()):
        mods, ops = [], []
        for line in plane.lines:
            if line.name == MODULES:
                mods = [(int(e.start_ns), int(e.duration_ns), e.name)
                        for e in line.events]
            elif line.name == OPS:
                ops = [(int(e.start_ns), int(e.duration_ns),
                        op_name(e.name)) for e in line.events]
        per_chip_modules[ordinal] = mods
        per_chip_ops[ordinal] = ops

    if window is None:
        spans = [(s, s + d) for mods in per_chip_modules.values()
                 for s, d, _ in mods]
        window = (min(s for s, _ in spans), max(e for _, e in spans)) \
            if spans else (0, 0)
    lo, hi = window

    busy, programs, calls = [], collections.Counter(), collections.Counter()
    ops_s = collections.Counter()
    first_busy = None
    for ordinal in sorted(per_chip_modules):
        mods = per_chip_modules[ordinal]
        iv = _clip(_union([(s, s + d) for s, d, _ in mods]), lo, hi)
        if first_busy is None:
            first_busy = iv
        busy.append(sum(e - s for s, e in iv) / 1e9)
        for s, d, name in mods:
            cut = min(s + d, hi) - max(s, lo)
            if cut > 0:
                programs[program_name(name)] += cut / 1e9
                calls[program_name(name)] += 1
        for s, d, name in per_chip_ops[ordinal]:
            cut = min(s + d, hi) - max(s, lo)
            if cut > 0:
                ops_s[name] += cut / 1e9

    gaps = collections.Counter()
    if first_busy is not None:
        edges = [lo] + [t for iv in first_busy for t in iv] + [hi]
        spans = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                 if g1 > g0]
        for (g0, g1), label in zip(spans, _innermost(
                host_events, [(g0 + g1) // 2 for g0, g1 in spans])):
            gaps[label] += (g1 - g0) / 1e9

    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy,
                        programs=dict(programs), program_calls=dict(calls),
                        ops=dict(ops_s), idle_gaps=dict(gaps))


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The `breakdown` of a traced result line: the device operations
    that took most time and the idle time by host activity."""
    def best(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": best(summary.ops or summary.programs),
            "idle_gaps": best(summary.idle_gaps)}
