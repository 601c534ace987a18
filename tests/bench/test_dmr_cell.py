"""The `pt-dmr-fleet` cell on the CPU at a tiny size: PT jobs of 6 items
under transient register faults with DMR.

The fault rate is raised for these tests to the top of the band in which
the carbon planner still picks DMR on HERV for PT: a base rate of 6e-6
(the configuration's 4e-6 times 1.5), so 2.4e-5 flips a retired
instruction on HERV's 8-bit datapath, and a 6-item job of about 400k
useful instructions on 12 lanes meets mismatches at many boundaries."""
import ast
import dataclasses
import io
import json
import os
import re

import jax
import pytest

from bench_helpers import BENCH, ROOT, load_harness

bench_run = load_harness()
CELL = "pt-dmr-fleet"
SEED = 2**33 + 17
BASE_RATE = 6e-6
RATE = 2.4e-5

# what the cell adds to BENCHMARK.json
CONFIG = {
    "name": "pt-dmr",
    "source": "https://arxiv.org/abs/2509.08193 (Table 2: PT, package "
              "tracking, 3 weeks at 72 executions a day; Table 7: HERV); "
              "DMR chosen by the repo's carbon model at a 4e-6 fault rate",
    "file": "bench/configs/pt-dmr.json", "reduced": ["fleet_items"],
    "why": "PT (108-word code, 610-word memory, items of about 67k "
           "instructions) on HERV under transient register faults, every "
           "item on a DMR lane pair"}
WORKLOAD = {
    "name": CELL, "config": "pt-dmr", "traffic": "jobs-dmr-384", "chips": 1,
    "why": "closed loop of 384-item PT jobs on 128 DMR lane pairs, a fresh "
           "fault seed a job, 1.6e-5 flips an instruction; pairs compared "
           "and rolled back every 32 steps inside 4,096-step segments"}
SHARED = ("sim_minstr_per_s", "seg_ns_per_lane_step", "lane_occupancy",
          "device_idle.fleet")
PER_LAYER = [
    {"name": "dmr_refill_us_per_segment", "unit": "us/segment",
     "better": "lower", "source": "device_trace",
     "layer": "DMR compare, rollback and pair refill",
     "moves": "sim_minstr_per_s", "workloads": [CELL]},
    {"name": "dmr_discard_share", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "DMR rollback",
     "moves": "sim_minstr_per_s", "workloads": [CELL]}]


def without_cell(bench: dict) -> dict:
    """BENCHMARK.json's contents with what the cell adds taken off."""
    bench = json.loads(json.dumps(bench))
    bench["configs"] = [c for c in bench["configs"] if c != CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w != WORKLOAD]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m not in PER_LAYER]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in SHARED:
            m["workloads"] = [w for w in m["workloads"] if w != CELL]
    return bench


@pytest.fixture(scope="module")
def root():
    return ROOT


def resolve(root):
    return bench_run.resolve(CELL, root=root)


def tiny_dmr(root, check_per_group=2):
    spec = resolve(root)
    spec["traffic"].update(items_per_group=6,
                           check_per_group=check_per_group)
    spec["config"].update(fault_base_rate=BASE_RATE,
                          fault_rate_injected=RATE)
    return spec


def load(rel, name):
    return bench_run.load_module(os.path.join(BENCH, rel), name)


@pytest.fixture(scope="module")
def traced(root):
    """One traced tiny run: (its result, the log it wrote)."""
    log = io.StringIO()
    res = bench_run.run(tiny_dmr(root), seed=SEED, seconds=0.1, trace=True,
                        devices=jax.devices()[:1], log=log)
    return res, log.getvalue()


def test_a_tiny_run_is_correct_and_every_job_met_a_mismatch(traced):
    res, log = traced
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["checks"]["jobs_without_mismatch"] == {"value": 0,
                                                      "limit": 0}
    counters = ast.literal_eval(re.search(r"counters: (\{.*\})",
                                          log).group(1))
    assert counters["detected"] > 0 and counters["corrected"] > 0
    # each job's fault seed is new, and the window compiles nothing
    assert "programs lowered in the window: 0," in log


def test_both_new_metrics_read_on_a_traced_window(traced):
    res, log = traced
    counters = ast.literal_eval(re.search(r"counters: (\{.*\})",
                                          log).group(1))
    share = res["metrics"]["dmr_discard_share"]["value"]
    assert share == pytest.approx(
        100.0 * counters["discarded"] / counters["lane_steps"])
    assert 0 < share < 100
    assert 0 < res["metrics"]["lane_occupancy"]["value"] <= 50
    # the CPU has no device plane: the refill reader is given a summary
    # that holds the program, as a chip's trace does
    trace = load("trace.py", "bench_trace")
    summary = trace.TraceSummary(
        window_s=1.0, busy_s=[0.9], programs={"jit_refill_dmr": 0.002},
        program_calls={"jit_refill_dmr": counters["n_segments"]}, ops={},
        idle_gaps={})
    refill = load("metrics/dmr_refill_us_per_segment.py", "t_dmr_refill")
    assert refill.read(summary, counters) == pytest.approx(
        2000.0 / counters["n_segments"])
    assert "dmr_refill_us_per_segment" not in res["metrics"]


def test_the_metrics_give_nothing_where_the_program_has_nothing_to_read():
    """A program without the DMR refill or the `discarded` counter, as
    before them, gives no value and raises nothing."""
    trace = load("trace.py", "bench_trace")
    summary = trace.TraceSummary(
        window_s=1.0, busy_s=[0.9], programs={"jit_refill": 0.002},
        program_calls={"jit_refill": 5}, ops={}, idle_gaps={})
    counters = {"busy_steps": 10, "lane_steps": 40, "n_segments": 5,
                "detected": 1, "corrected": 1, "quarantined": 0}
    refill = load("metrics/dmr_refill_us_per_segment.py", "t_dmr_refill")
    share = load("metrics/dmr_discard_share.py", "t_dmr_share")
    assert refill.read(summary, counters) is None
    assert share.read(summary, counters) is None


def test_the_control_is_not_correct(root):
    """The same job run under the same fault schedule without
    redundancy gives answers that differ from the reference."""
    spec = tiny_dmr(root, check_per_group=6)
    session = spec["entry"].Session(spec["config"], spec["traffic"], SEED,
                                    jax.devices()[:1])
    records = [session.request(0)]
    sound, _, _ = session.check(records)
    control, _, _ = session.check(records, answer=session.control(records))
    assert all(v <= lim for v, lim in sound.values()), sound
    assert control["sampled_items_differing"][0] > 0, control


@pytest.mark.parametrize("edit", [
    {"planner_choice": {"redundancy": "dmr", "core": "QERV"}},
    {"planner_choice": {"redundancy": "none", "core": "HERV"}},
    {"redundancy": "none"},
    {"fault_rate_injected": 3.2e-5},
    {"fault_base_rate": 1e-7},
])
def test_the_planner_guard_refuses_an_edited_configuration(root, edit):
    spec = resolve(root)
    config = dict(spec["config"], **edit)
    with pytest.raises(RuntimeError, match="planner|fault_rate_injected"):
        spec["entry"].Session(config, spec["traffic"], SEED,
                              jax.devices()[:1])


def test_the_planner_guard_refuses_another_core(root):
    spec = resolve(root)
    config = dict(spec["config"])
    config["workloads"] = {"PT": dict(config["workloads"]["PT"],
                                      core="SERV")}
    with pytest.raises(RuntimeError, match="planner picks"):
        spec["entry"].Session(config, spec["traffic"], SEED,
                              jax.devices()[:1])


@pytest.mark.parametrize("steps", [64, 4096])
def test_set_up_refuses_another_compare_interval(root, steps):
    """The configuration records the program's compare interval; a
    program that compares pairs otherwise, such as at segment
    boundaries alone, runs another deployment."""
    spec = resolve(root)
    assert spec["config"]["compare_steps"] == 32
    config = dict(spec["config"], compare_steps=steps)
    with pytest.raises(RuntimeError, match="compares DMR pairs every 32"):
        spec["entry"].Session(config, spec["traffic"], SEED,
                              jax.devices()[:1])


def test_each_job_draws_a_fault_seed_of_its_own(root):
    spec = tiny_dmr(root)
    entry = spec["entry"]
    session = entry.Session(spec["config"], spec["traffic"], SEED,
                            jax.devices()[:1])
    plans = [session.plan(j, 6) for j in (0, 1)]
    seeds = [p.faults.seed for p in plans]
    assert seeds[0] != seeds[1] and all(0 <= s < 2**31 for s in seeds)
    assert dataclasses.replace(plans[0].faults, seed=seeds[1]) \
        == plans[1].faults
    assert plans[0].faults.rate == RATE
    assert plans[0].redundancy == "dmr"
    assert session.plan(0, 6, redundancy="none").faults == plans[0].faults


def test_the_cell_is_added_by_appends_alone():
    """Each entry the cell adds is the last of its list, and it is the
    last cell of each metric list it joins."""
    added = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = without_cell(added)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        n = len(bench[key])
        assert added[key][:n] == [
            dict(m, workloads=m["workloads"] + [CELL])
            if m["name"] in SHARED else m for m in bench[key]]
    assert added["configs"][-1] == CONFIG
    assert added["workloads"][-1] == WORKLOAD
    assert added["per_layer"][-2:] == PER_LAYER
    assert len(WORKLOAD["why"]) <= 200 and len(CONFIG["source"]) <= 200


@pytest.mark.parametrize("metric", [m["name"] for m in PER_LAYER])
def test_each_new_metric_reader_states_its_entry(metric):
    m = next(x for x in PER_LAYER if x["name"] == metric)
    reader = load("metrics/" + metric + ".py", "t_" + metric)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == \
        (m["layer"], m["unit"], m["source"], m["moves"])
