"""The program's own spans in a traced window, beside the device planes.

    python3 bench/spans.py --workload <cell> --seeds <n> [<n> ...]
        [--seconds S] [--hlo-trace 0|1] [--out DIR]

The fleet engine and the planner open `jax.profiler.TraceAnnotation`
spans at their layer boundaries: `fleet.job`, `fleet.static`,
`fleet.stream`, `fleet.dispatch`, `fleet.sync`, `fleet.restock`,
`fleet.upload`, `fleet.checkpoint`, `fleet.drain`, `fleet.report`, and
`sweep.whatif`, `sweep.prepare`, `sweep.step`, `sweep.readback`,
`sweep.finish`. The benchmark's own `bench.window` and `bench.request`
sit around them. All land on the profiler's host plane, on the clock of
the `/device:TPU:<n>` planes.

`reduce_spans` turns a trace into a `SpanSummary`:

- spans: for each span name in the window with one of the prefixes, its
  count, total seconds and self seconds (the total less the part its
  child spans of those prefixes cover);
- idle_by_span: the first chip's idle time, each gap put down to the
  innermost program span that covers its midpoint, or to
  `(outside program spans)`;
- the raw spans and device program runs, for `segments`.

`span_metrics` derives the per-segment, per-job and per-what-if host
costs from it; `accounting` says how much of the window and of the
stream loop the spans cover; `segments` lines each stream iteration's
spans up with the `jit_refill` and `jit_seg` runs it dispatched.

Run as a script, it drives one traced window per seed of the cell's
traffic on the cell's chips, as `bench/run.py --trace 1` does (no
Python tracer, programs compiled without per-operation trace points
unless `--hlo-trace 1`), and prints one JSON line per seed. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
PREFIXES = ("bench.", "fleet.", "sweep.")
OUTSIDE = "(outside program spans)"
# the stream loop's parts: their union should cover `fleet.stream`
LOOP_PARTS = ("fleet.sync", "fleet.dispatch", "fleet.restock",
              "fleet.upload", "fleet.drain", "fleet.checkpoint")
HOST_WORK = ("fleet.dispatch", "fleet.restock", "fleet.upload")

Span = Tuple[int, int, str]          # start ns, end ns, name


def _load(name: str, file: str):
    """A harness module loaded by path, under the name the harness and
    its tests give it (`trace` and `run` are standard library names)."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, file))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _bench_trace():
    return _load("bench_trace", "trace.py")


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    spans: Dict[str, Dict[str, float]]   # name -> count, total_s, self_s
    idle_by_span: Dict[str, float]       # seconds of chip 0's idle time
    events: List[Span]                   # program spans, clipped, sorted
    device: Dict[int, List[Span]]        # per chip: program runs, sorted

    def total_s(self, name: str) -> float:
        return self.spans.get(name, {}).get("total_s", 0.0)

    def self_s(self, name: str) -> float:
        return self.spans.get(name, {}).get("self_s", 0.0)

    def count(self, name: str) -> int:
        return int(self.spans.get(name, {}).get("count", 0))


def self_times(line: List[Span]) -> List[float]:
    """Nanoseconds of each span of one thread not covered by its child
    spans; spans on one thread nest or are disjoint."""
    order = sorted(range(len(line)),
                   key=lambda i: (line[i][0], -line[i][1]))
    own = [float(e - s) for s, e, _ in line]
    stack: List[int] = []
    for i in order:
        s, e, _ = line[i]
        while stack and line[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def inside(spans: List[Span], outer: List[Span]) -> List[Span]:
    """The spans that lie within some span of `outer`."""
    return [x for x in spans
            if any(o[0] <= x[0] and x[1] <= o[1] for o in outer)]


def union_s(spans: List[Span], within: List[Span]) -> float:
    """Seconds of the union of `spans`, cut to the union of `within`."""
    bt = _bench_trace()
    a = bt._union([(s, e) for s, e, _ in spans])
    b = bt._union([(s, e) for s, e, _ in within])
    cut = 0
    for lo, hi in b:
        cut += sum(e - s for s, e in bt._clip(a, lo, hi))
    return cut / 1e9


def reduce_spans(path: str, prefixes=PREFIXES,
                 window_span: str = "bench.window") -> SpanSummary:
    from jax.profiler import ProfileData
    bt = _bench_trace()
    pd = ProfileData.from_file(path)
    lines, device, window = [], {}, None
    for plane in pd.planes:
        m = bt._DEVICE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == bt.MODULES:
                    device[int(m.group(2))] = sorted(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns),
                         bt.program_name(e.name)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ours = []
                for e in line.events:
                    s, d = int(e.start_ns), int(e.duration_ns)
                    if e.name == window_span:
                        window = (s, s + d)
                    if e.name.startswith(tuple(prefixes)) and d > 0:
                        ours.append((s, s + d, e.name))
                if ours:
                    lines.append(ours)
    if window is None:
        every = [x for ln in lines for x in ln] \
            + [x for runs in device.values() for x in runs]
        window = (min(s for s, _, _ in every),
                  max(e for _, e, _ in every)) if every else (0, 0)
    lo, hi = window
    device = {c: [x for x in runs if x[1] > lo and x[0] < hi]
              for c, runs in device.items()}

    spans = collections.defaultdict(
        lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0})
    events: List[Span] = []
    for line in lines:
        cut = [(max(s, lo), min(e, hi), n) for s, e, n in line
               if e > lo and s < hi]
        for (s, e, n), own in zip(cut, self_times(cut)):
            spans[n]["count"] += 1
            spans[n]["total_s"] += (e - s) / 1e9
            spans[n]["self_s"] += own / 1e9
        events += cut
    events.sort()

    idle = collections.Counter()
    if device:
        first = device[min(device)]
        busy = bt._clip(bt._union([(s, e) for s, e, _ in first]), lo, hi)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        # program spans only: the window span would cover every gap
        named = [x for x in events if x[2] != window_span]
        for (g0, g1), label in zip(gaps, bt._innermost(
                named, [(g0 + g1) // 2 for g0, g1 in gaps])):
            idle[OUTSIDE if label == "(no host event)" else label] += \
                (g1 - g0) / 1e9
    return SpanSummary(window_s=(hi - lo) / 1e9, spans=dict(spans),
                       idle_by_span=dict(idle), events=events,
                       device=device)


def _named(summary: SpanSummary, name: str) -> List[Span]:
    return [x for x in summary.events if x[2] == name]


def _stream_syncs(summary: SpanSummary, outside=()) -> List[Span]:
    """`fleet.sync` spans inside `fleet.stream` and outside the spans
    named in `outside`."""
    syncs = inside(_named(summary, "fleet.sync"),
                   _named(summary, "fleet.stream"))
    skip = set(inside(syncs, [x for n in outside
                              for x in _named(summary, n)]))
    return [x for x in syncs if x not in skip]


def span_metrics(summary: SpanSummary, counters: dict) -> dict:
    """The host costs the spans give, None where their spans are absent:

    - sync_wait_us_per_segment: `fleet.sync` inside `fleet.stream` and
      not inside `fleet.drain`, over `n_segments`;
    - host_us_per_segment: self time of `fleet.dispatch`,
      `fleet.restock` and `fleet.upload`, over `n_segments`;
    - job_host_ms: `fleet.job` less `fleet.stream`, per job;
    - readback_ms_per_whatif: `sweep.readback` per `sweep.whatif`;
    - whatif_host_ms: `sweep.whatif` less `sweep.readback`, per what-if.
    """
    out = dict.fromkeys(("sync_wait_us_per_segment", "host_us_per_segment",
                         "job_host_ms", "readback_ms_per_whatif",
                         "whatif_host_ms"))
    n_seg = counters.get("n_segments")
    if summary.count("fleet.stream") and n_seg:
        wait = sum(e - s for s, e, _ in _stream_syncs(summary,
                                                       ("fleet.drain",)))
        out["sync_wait_us_per_segment"] = wait / 1e3 / n_seg
        out["host_us_per_segment"] = sum(
            summary.self_s(n) for n in HOST_WORK) * 1e6 / n_seg
    if summary.count("fleet.job"):
        out["job_host_ms"] = (summary.total_s("fleet.job")
                              - summary.total_s("fleet.stream")) \
            * 1e3 / summary.count("fleet.job")
    n_whatif = summary.count("sweep.whatif")
    if n_whatif:
        out["readback_ms_per_whatif"] = \
            summary.total_s("sweep.readback") * 1e3 / n_whatif
        out["whatif_host_ms"] = (summary.total_s("sweep.whatif")
                                 - summary.total_s("sweep.readback")) \
            * 1e3 / n_whatif
    return out


def accounting(summary: SpanSummary) -> dict:
    """Shares the spans cover: jobs and what-ifs of the window, and the
    union of the loop's parts of `fleet.stream`."""
    w = summary.window_s or float("nan")
    out = {}
    if summary.count("fleet.job"):
        out["job_share_of_window"] = summary.total_s("fleet.job") / w
    stream = _named(summary, "fleet.stream")
    if stream:
        parts = [x for x in summary.events if x[2] in LOOP_PARTS]
        out["loop_parts_share_of_stream"] = \
            union_s(parts, stream) / summary.total_s("fleet.stream")
    if summary.count("sweep.whatif"):
        out["whatif_share_of_window"] = summary.total_s("sweep.whatif") / w
    return out


def segments(summary: SpanSummary, chip: Optional[int] = None) -> dict:
    """Each iteration of the resident stream loop against the device.

    Iteration i dispatches `jit_refill` i and `jit_seg` i, then blocks in
    one `fleet.sync` that reads refill i's stats. Loop syncs (inside
    `fleet.stream`, outside `fleet.drain` and `fleet.checkpoint`) and
    the chip's refill runs are matched in order. The offset of each
    sync's end from its refill's end is runtime and transfer time, and
    can never be negative on one clock. The segment's own run, the
    host's work and the iteration's length say which sets the pace."""
    if not summary.device:
        return {}
    chip = min(summary.device) if chip is None else chip
    runs = summary.device[chip]
    refills = [x for x in runs if x[2] == "jit_refill"]
    segs = [x for x in runs if x[2] == "jit_seg"]
    syncs = _stream_syncs(summary, ("fleet.drain", "fleet.checkpoint"))
    dispatch = inside(_named(summary, "fleet.dispatch"),
                      _named(summary, "fleet.stream"))
    out = {"chip": chip, "loop_syncs": len(syncs),
           "dispatches": len(dispatch), "jit_refill_runs": len(refills),
           "jit_seg_runs": len(segs)}
    if not syncs or len(refills) != len(syncs):
        out["matched"] = False
        return out
    out["matched"] = True
    offset = [(s[1] - r[1]) / 1e3 for s, r in zip(syncs, refills)]
    ends = [s[1] for s in syncs]
    per = {"sync_end_after_refill_end_us": offset,
           "sync_wait_us": [(e - s) / 1e3 for s, e, _ in syncs],
           "iteration_us": [(b - a) / 1e3 for a, b in zip(ends, ends[1:])],
           "jit_seg_us": [(e - s) / 1e3 for s, e, _ in segs],
           "jit_refill_us": [(e - s) / 1e3 for s, e, _ in refills]}
    host = collections.defaultdict(float)
    for s, e, n in summary.events:
        if n in HOST_WORK:
            host[n] += (e - s) / 1e3
    out["host_us_per_iteration"] = {n: v / len(syncs)
                                    for n, v in host.items()}
    out["median"] = {k: statistics.median(v) for k, v in per.items() if v}
    out["max"] = {k: max(v) for k, v in per.items() if v}
    out["min"] = {k: min(v) for k, v in per.items() if v}
    out["per_iteration"] = per
    return out


# ------------------------------------------------- traced windows
def _run_module():
    return _load("bench_run", "run.py")


def traced_window(spec: dict, session, seconds: float, keep: str = None,
                  tag: str = "", whole_programs: bool = True) -> dict:
    """One traced window of the cell's closed loop, as `bench/run.py`
    runs it: the trace reduced by `reduce_spans` and, where programs ran
    without per-operation trace points, by `bench/trace.py` and the
    cell's per-layer readers (a trace of every operation holds millions
    of events that `bench/trace.py` would read one by one)."""
    import jax
    bench_run = _run_module()
    counter = bench_run.CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    records = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    counter.armed = True
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(bench_run.WINDOW_SPAN):
            start = time.perf_counter()
            deadline = start + seconds
            i = 0
            while time.perf_counter() < deadline:
                with jax.profiler.TraceAnnotation("bench.request"):
                    records.append(session.request(i))
                i += 1
            window_s = time.perf_counter() - start
    finally:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        counter.armed = False
        t_stop = time.perf_counter() - t_stop
    try:
        path = _bench_trace().find_xplane(trace_dir)
        size = os.path.getsize(path)
        summary = _bench_trace().reduce(path, bench_run.WINDOW_SPAN) \
            if whole_programs else None
        spans = reduce_spans(path)
        if keep and size <= 6 << 20:
            shutil.copy(path, os.path.join(keep, f"{tag}.xplane.pb"))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    counters = session.counters(records)
    out = {"requests": len(records), "window_s": window_s,
           "compiled_in_window": counter.compiled, "trace_bytes": size,
           "trace_write_s": t_stop, "traced_window_s": spans.window_s,
           "counters": counters}
    if summary is not None:
        layer = {m["name"]: spec["readers"][m["name"]].read(summary,
                                                             counters)
                 for m in spec["per_layer"]}
        out.update(busy_s=summary.busy_s, programs=summary.programs,
                   program_calls=summary.program_calls,
                   per_layer={k: v for k, v in layer.items()
                              if v is not None})
    out["device_programs"] = {
        chip: dict(collections.Counter(n for _, _, n in runs))
        for chip, runs in spans.device.items()}
    return {**out, "span_metrics": span_metrics(spans, counters),
            "accounting": accounting(spans), "spans": spans.spans,
            "idle_by_span": spans.idle_by_span,
            "segments": segments(spans)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--hlo-trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for each seed's full JSON and trace")
    args = ap.parse_args(argv)
    bench_run = _run_module()
    if not args.hlo_trace:
        bench_run.trace_programs_whole()
    try:
        spec = bench_run.resolve(args.workload)
        sys.path.insert(0, bench_run.program_root())
        devices = bench_run.require_chips(spec["cell"]["chips"])
    except bench_run.BenchError as e:
        print(f"spans: {e}", file=sys.stderr)
        return 2
    if not args.hlo_trace:       # programs with trace points stay apart
        bench_run.enable_compile_cache(traced=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    for k, seed in enumerate(args.seeds):
        session = spec["entry"].Session(spec["config"], spec["traffic"],
                                        seed, devices)
        if k == 0:
            session.warmup()      # the later seeds reuse its programs
        tag = f"{args.workload}-{seed}-hlo{args.hlo_trace}"
        res = traced_window(spec, session, args.seconds, keep=args.out,
                            tag=tag, whole_programs=not args.hlo_trace)
        session.release()
        res.update(workload=args.workload, seed=seed,
                   hlo_trace=args.hlo_trace,
                   device=bench_run.device_info(devices))
        if args.out:
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(res, f)
        seg = dict(res["segments"])
        seg.pop("per_iteration", None)
        short = {k: v for k, v in res.items() if k not in ("spans",
                                                           "segments")}
        short["segments"] = seg
        print(json.dumps(short), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
