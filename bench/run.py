"""Chip benchmark of the fleet engine and the carbon planner.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout. A cell of `BENCHMARK.json` names a
configuration (`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`); the mix names the entry that drives the
program (`bench/entries/<entry>.py`), and each per-layer metric is read
by `bench/metrics/<metric>.py`. Everything is found by name, so a new
configuration, mix, entry or metric is a new file.

A run sets up (imports, compile cache, the entry's session, one small
warm-up request of the cell's own shapes), then drives a closed loop:
one client submits its next request when the previous one has returned,
until `--seconds` have passed; requests started before then run to
their end, and the window closes when the last returns. Then it reads
the device's peak memory, frees the program's state, and checks the
answers against the plain references under `bench/reference/`. The
last line of standard output is the result as JSON; the numbers
compared, each with its limit, are also the last lines of standard
error. With `--trace 1` the window runs under the JAX profiler and the
result carries the per-layer metrics instead of the end-to-end ones.
A traced run compiles its programs without per-operation trace points
(`--xla_enable_hlo_trace=false`) and keeps them in a compile cache of
their own: the per-layer metrics read only whole programs, and a trace
of every operation of a fleet job takes minutes to write.

It refuses to measure without a TPU holding the chips the cell asks for.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

WINDOW_SPAN = "bench.window"


class BenchError(Exception):
    """The run cannot be measured: no result is printed."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no such file: {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def resolve(name: str, root: str = ROOT) -> dict:
    """Everything a cell needs, found by name under `root`."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell named {name!r} in BENCHMARK.json")
    cell = cells[name]
    bdir = os.path.join(root, "bench")
    config = load_json(os.path.join(bdir, "configs", cell["config"] + ".json"))
    traffic = load_json(os.path.join(bdir, "traffic",
                                     cell["traffic"] + ".json"))
    entry = load_module(os.path.join(bdir, "entries",
                                     traffic["entry"] + ".py"),
                        "bench_entry_" + traffic["entry"])

    def applies(m):
        return name in m.get("workloads", [name])
    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    per_layer = [m for m in bench["per_layer"] if applies(m)]
    readers = {m["name"]: load_module(
        os.path.join(bdir, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")) for m in per_layer}
    return {"cell": cell, "config": config, "traffic": traffic,
            "entry": entry, "end_to_end": end_to_end,
            "per_layer": per_layer, "readers": readers}


def program_root(root: str = ROOT) -> str:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program is not in this checkout ({src})")
    return src


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_chips(n: int):
    """The cell's chips; a run without a TPU holding them is refused."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform} "
                         f"({devices[0].device_kind}) x{len(devices)}")
    if len(devices) < n:
        raise BenchError(f"the cell needs {n} chips; JAX found "
                         f"{len(devices)} {devices[0].device_kind}")
    return devices[:n]


HLO_TRACE_OFF = "--xla_enable_hlo_trace=false"


def trace_programs_whole():
    """Compile without per-operation trace points; call before JAX
    starts its TPU backend."""
    args = os.environ.get("LIBTPU_INIT_ARGS", "")
    os.environ["LIBTPU_INIT_ARGS"] = f"{args} {HLO_TRACE_OFF}".strip()


def enable_compile_cache(root: str = ROOT, traced: bool = False) -> str:
    """JAX's persistent cache at `JAX_COMPILATION_CACHE_DIR`, else at the
    fixed `<checkout>/.jax_cache`, and for traced runs, whose programs
    are compiled otherwise, in its subdirectory `traced`; every program
    is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(root, ".jax_cache")
    if traced:
        path = os.path.join(path, "traced")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs lowered and compiled while armed."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed:
            if event == self.LOWER:
                self.lowered += 1
            elif event == self.COMPILE:
                self.compiled += 1


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(spec: dict, *, seed: int, seconds: float, trace: bool, devices,
        t0: float = _T0, log=None) -> dict:
    """One measured run of a resolved cell on `devices`: the result."""
    import jax
    log = log or sys.stderr
    session = spec["entry"].Session(spec["config"], spec["traffic"], seed,
                                    devices)
    session.warmup()
    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    records, latencies = [], []
    setup_s = time.perf_counter() - t0
    counter.armed = True
    if trace:
        # no Python tracer: it would time every call of the host loop
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            start = time.perf_counter()
            deadline = start + seconds
            i = 0
            while time.perf_counter() < deadline:
                r0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.request"):
                    records.append(session.request(i))
                latencies.append(time.perf_counter() - r0)
                i += 1
            window_s = time.perf_counter() - start
    finally:
        t_stop = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        counter.armed = False
        t_stop = time.perf_counter() - t_stop
    print(f"window: {len(records)} requests in {window_s:.3f} s; "
          f"programs lowered in the window: {counter.lowered}, "
          f"compiled: {counter.compiled}", file=log, flush=True)

    peak = memory_peak(devices)
    counters = session.counters(records)
    metrics = {}
    breakdown = None
    info = device_info(devices)
    info["memory_peak_bytes"] = peak
    if trace:
        # loaded by path: the standard library has a module named `trace`
        bench_trace = load_module(os.path.join(BENCH, "trace.py"),
                                  "bench_trace")
        t_read = time.perf_counter()
        try:
            path = bench_trace.find_xplane(trace_dir)
            size = os.path.getsize(path)
            summary = bench_trace.reduce(path, WINDOW_SPAN)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: {size} bytes, written in {t_stop:.1f} s, read in "
              f"{time.perf_counter() - t_read:.1f} s", file=log, flush=True)
        info["busy_s"] = summary.busy_mean_s
        info["window_s"] = summary.window_s
        for m in spec["per_layer"]:
            v = spec["readers"][m["name"]].read(summary, counters)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = bench_trace.breakdown(summary)
        print(f"trace: window {summary.window_s:.3f} s, busy per chip "
              f"{summary.busy_s}, programs {summary.programs}",
              file=log, flush=True)
    else:
        values = session.end_to_end(records, latencies, window_s)
        values["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    print(f"counters: {counters}", file=log, flush=True)

    session.release()
    c0 = time.perf_counter()
    checks, failed, n_checked = session.check(records)
    print(f"check: {n_checked} answers against the reference in "
          f"{time.perf_counter() - c0:.3f} s", file=log, flush=True)
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} limit {limit}", file=log, flush=True)
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.trace:
        trace_programs_whole()
    try:
        spec = resolve(args.workload)
        sys.path.insert(0, program_root())
        devices = require_chips(spec["cell"]["chips"])
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(traced=bool(args.trace))
    result = run(spec, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), devices=devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
