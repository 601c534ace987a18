"""The trace reduction on a small trace recorded on one TPU v5e chip.

`data/tiny_v5e.xplane.pb` holds a window span `bench.window` in which a
host loop ran three times: a jitted `seg` (a 2,000-step loop over a
256 x 1024 array), a jitted `refill`, a wait for the result, then a
50 ms `time.sleep` inside a `host.sleep` span."""
import importlib.util
import os
import sys

import pytest

from bench_helpers import BENCH

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")


def _load():
    spec = importlib.util.spec_from_file_location(
        "bench_trace", os.path.join(BENCH, "trace.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_trace"] = mod
    spec.loader.exec_module(mod)
    return mod


bench_trace = _load()


@pytest.fixture(scope="module")
def raw():
    """Events straight from the file: the window span and the modules."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TRACE)
    window, modules, sleeps = None, [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.window":
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name == "host.sleep":
                    sleeps.append((e.start_ns, e.start_ns + e.duration_ns))
                elif plane.name == "/device:TPU:0" \
                        and line.name == "XLA Modules":
                    modules.append((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name))
    return window, modules, sleeps


@pytest.fixture(scope="module")
def summary():
    return bench_trace.reduce(TRACE)


def test_window_is_the_benchmark_span(raw, summary):
    (lo, hi), _, _ = raw
    assert summary.window_s == pytest.approx((hi - lo) / 1e9, abs=1e-9)
    assert len(summary.busy_s) == 1          # one chip


def test_busy_is_the_union_of_program_runs_in_the_window(raw, summary):
    (lo, hi), modules, _ = raw
    # the recorded programs do not overlap: the union is their sum,
    # cut to the window
    ends = sorted((s, e) for s, e, _ in modules)
    assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))
    want = sum(max(0, min(e, hi) - max(s, lo)) for s, e in ends) / 1e9
    assert summary.busy_s == [pytest.approx(want, abs=1e-9)]
    assert 0 < summary.busy_mean_s < summary.window_s


def test_device_time_per_program(raw, summary):
    assert summary.program_calls == {"jit_seg": 3, "jit_refill": 3}
    assert summary.programs["jit_seg"] > 100 * summary.programs["jit_refill"]
    assert summary.program_s(["jit_seg", "jit_refill"]) == pytest.approx(
        summary.busy_s[0], rel=1e-9)
    assert summary.program_s(["jit_other"]) is None
    top = bench_trace.breakdown(summary)["device_ops"]
    assert top[0][0] == "%while" and len(top) <= 10
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


def test_idle_gaps_are_named_by_the_host_activity(raw, summary):
    _, _, sleeps = raw
    idle = summary.window_s - summary.busy_s[0]
    assert sum(summary.idle_gaps.values()) == pytest.approx(idle, abs=1e-9)
    # the three sleeps are the long gaps; the innermost host event in
    # them is the sleep call itself, inside the `host.sleep` span
    label, secs = bench_trace.breakdown(summary)["idle_gaps"][0]
    assert label == "$time sleep"
    slept = sum(e - s for s, e in sleeps) / 1e9
    # a gap runs from the end of one program to the start of the next,
    # a little longer than the sleep in it
    assert 0.9 * slept < secs < 1.1 * slept


def test_innermost_event_covering_each_point():
    events = [(0, 100, "outer"), (10, 20, "a"), (30, 60, "b"),
              (40, 50, "c")]
    got = bench_trace._innermost(events, [5, 15, 25, 35, 45, 55, 150])
    assert got == ["outer", "a", "outer", "b", "c", "b", "(no host event)"]
