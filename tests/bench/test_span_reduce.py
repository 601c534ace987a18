"""The span reduction of `bench/spans.py`: on the small trace recorded on
one TPU v5e chip (see test_trace_reduce.py), and on traced tiny-size
windows of the fleet and planner entries on the CPU, whose host plane
carries the program's own `fleet.*` and `sweep.*` spans."""
import importlib.util
import os
import sys

import jax
import pytest

from bench_helpers import BENCH, load_harness, tiny

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")


def _load():
    spec = importlib.util.spec_from_file_location(
        "bench_spans", os.path.join(BENCH, "spans.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_spans"] = mod
    spec.loader.exec_module(mod)
    return mod


bench_spans = _load()
bench_run = load_harness()


@pytest.fixture(scope="module")
def sleeps():
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            out += [e.duration_ns / 1e9 for e in line.events
                    if e.name == "host.sleep"]
    return out


@pytest.fixture(scope="module")
def host_spans():
    return bench_spans.reduce_spans(TRACE, prefixes=("host.",))


def test_span_count_total_and_self_time(sleeps, host_spans):
    got = host_spans.spans["host.sleep"]
    assert got["count"] == len(sleeps) == 3
    assert got["total_s"] == pytest.approx(sum(sleeps), abs=1e-9)
    assert 0.14 < got["total_s"] < 0.2           # three 50 ms sleeps
    # nothing with the prefix nests inside a sleep
    assert got["self_s"] == pytest.approx(got["total_s"], abs=1e-9)
    assert set(host_spans.spans) == {"host.sleep"}


def test_idle_time_is_put_down_to_the_covering_span(sleeps, host_spans):
    summary = bench_spans._bench_trace().reduce(TRACE)
    idle = summary.window_s - summary.busy_s[0]
    assert sum(host_spans.idle_by_span.values()) == pytest.approx(
        idle, abs=1e-9)
    # a gap runs from the end of one program to the start of the next,
    # a little longer than the sleep in it
    slept = sum(sleeps)
    assert 0.9 * slept < host_spans.idle_by_span["host.sleep"] \
        < 1.1 * slept
    assert set(host_spans.idle_by_span) <= {"host.sleep",
                                            bench_spans.OUTSIDE}


def test_a_parent_span_loses_its_children_in_self_time(sleeps):
    got = bench_spans.reduce_spans(TRACE, prefixes=("bench.", "host."))
    window = got.spans["bench.window"]
    assert window["count"] == 1
    assert window["total_s"] == pytest.approx(got.window_s, abs=1e-9)
    assert window["self_s"] == pytest.approx(
        window["total_s"] - sum(sleeps), abs=1e-9)
    # with the default prefixes the sleeps are not program spans
    plain = bench_spans.reduce_spans(TRACE)
    assert set(plain.spans) == {"bench.window"}
    assert plain.spans["bench.window"]["self_s"] == pytest.approx(
        plain.window_s, abs=1e-9)
    assert set(plain.idle_by_span) == {bench_spans.OUTSIDE}


def test_self_times_of_nested_spans():
    line = [(0, 100, "a"), (10, 40, "b"), (20, 30, "c"), (50, 60, "d"),
            (200, 210, "e")]
    assert bench_spans.self_times(line) == [60.0, 20.0, 10.0, 10.0, 10.0]


def test_loop_parts_are_measured_as_a_union():
    stream = [(0, 100, "fleet.stream")]
    parts = [(0, 30, "fleet.drain"), (10, 20, "fleet.sync"),
             (50, 150, "fleet.restock")]
    assert bench_spans.union_s(parts, stream) == pytest.approx(80e-9)


FLEET = ("sync_wait_us_per_segment", "host_us_per_segment", "job_host_ms")
PLANNER = ("readback_ms_per_whatif", "whatif_host_ms")


@pytest.mark.parametrize("cell,names", [("fs-fleet", FLEET),
                                        ("table2-planner", PLANNER)])
def test_a_traced_tiny_window_reads_every_span_metric(cell, names):
    spec = tiny(bench_run.resolve(cell))
    session = spec["entry"].Session(spec["config"], spec["traffic"],
                                    2**33 + 11, jax.devices()[:1])
    session.warmup()
    res = bench_spans.traced_window(spec, session, seconds=0.2)
    session.release()
    assert res["requests"] >= 1 and res["compiled_in_window"] == 0
    values = res["span_metrics"]
    assert all(values[n] > 0 for n in names), values
    assert all(values[n] is None for n in set(values) - set(names))
    spans = res["spans"]
    assert spans["bench.request"]["count"] == res["requests"]
    if cell == "fs-fleet":
        assert spans["fleet.job"]["count"] == res["requests"]
        assert spans["fleet.sync"]["count"] >= res["counters"]["n_segments"]
        share = res["accounting"]
        assert 0 < share["job_share_of_window"] <= 1
        assert 0 < share["loop_parts_share_of_stream"] <= 1
    else:
        assert spans["sweep.whatif"]["count"] == res["requests"]
        assert spans["sweep.readback"]["count"] == \
            spans["sweep.step"]["count"]
        assert 0 < res["accounting"]["whatif_share_of_window"] <= 1
    # the CPU has no device plane: nothing to line the segments up with
    assert res["segments"] == {} and res["idle_by_span"] == {}
