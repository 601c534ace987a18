"""Fast-tier gate over the committed BENCH_fleet.json artifact.

The fleet benchmark itself runs on main only (CI full tier), which used
to mean a regression in a recorded perf invariant — the §9.7 fusion
proof, the §9.10 timing overhead — only surfaced after merge, as an
artifact nobody opened. This gate validates the *committed* numbers on
every push: whoever regenerates BENCH_fleet.json with a regressed
tentpole metric fails fast-tier CI right in their PR. (Wall-clock rows
are machine-dependent; the gates below are exactly the invariants the
benchmark itself enforces on exit, evaluated on the recorded run.)
"""
import json
import os

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_BENCH = os.path.join(_ROOT, "BENCH_fleet.json")


@pytest.fixture(scope="module")
def bench():
    assert os.path.exists(_BENCH), (
        "BENCH_fleet.json missing at the repo root — regenerate with "
        "PYTHONPATH=src python benchmarks/fleet.py")
    with open(_BENCH) as f:
        return json.load(f)


def test_bench_has_all_studies(bench):
    for key in ("streaming_vs_monolithic", "fusion_proof",
                "timing_overhead", "fault_overhead", "planner_sweep",
                "flexilint", "device_scaling"):
        assert key in bench, f"BENCH_fleet.json lost the {key} study"


def test_fusion_proof_invariant(bench):
    """§9.7: the fused-segment module's top level must stay >=10x
    smaller than the branchless step body x seg_steps it replaces."""
    fp = bench["fusion_proof"]
    assert float(fp["top_level_ratio"]) >= 10.0, fp["top_level_ratio"]
    assert int(fp["pallas"]["entry_ops"]) < \
        int(fp["branchless"]["dispatched_ops_per_segment"])


def test_timing_overhead_invariant(bench):
    """§9.10: the per-lane cycle layer must be architecturally invisible
    (bit-exact on vs off) and cheap — cycles-on segment wall within
    1.5x of cycles-off even with full dynamic cost rows."""
    to = bench["timing_overhead"]
    assert to["bit_exact"] is True
    assert float(to["overhead_ratio"]) <= 1.5, to["overhead_ratio"]
    assert float(to["mean_cycles_per_item"]) > 0


def test_fault_overhead_invariant(bench):
    """§9.14: a rate-0 fault schedule must be bit-exact with faults-off
    (injection graph architecturally invisible), DMR must recover the
    fault-free outputs exactly under a nonzero schedule, and the DMR
    wall clock must stay within 2.5x of faults-off (two copies per
    item plus rollback re-execution). The recorded unprotected run must
    show a nonzero SDC rate — that silent corruption is the carbon
    model's whole case for pricing redundancy."""
    fo = bench["fault_overhead"]
    assert fo["bit_exact"] is True
    assert fo["dmr_recovered"] is True
    assert float(fo["dmr_overhead_ratio"]) <= 2.5, (
        fo["dmr_overhead_ratio"])
    assert 0.0 < float(fo["sdc_rate"]) <= 1.0, fo["sdc_rate"]
    assert int(fo["detected"]) > 0
    assert int(fo["corrected"]) > 0
    assert int(fo["corrupted_items"]) > 0


def test_planner_sweep_invariant(bench):
    """§9.13: the fused device sweep must price >=1e6 scenarios/s on
    CPU and hold a >=100x margin over the per-scenario python loop,
    with the Pallas A/B bit-exact and the float64 point-mass run pinned
    exactly to the numpy total_grid/selection_map oracles."""
    ps = bench["planner_sweep"]
    assert float(ps["scenarios_per_s"]) >= 1e6, ps["scenarios_per_s"]
    assert float(ps["python_loop_speedup"]) >= 100.0, (
        ps["python_loop_speedup"])
    assert ps["bit_exact"] is True
    assert ps["oracle_exact"] is True
    assert int(ps["n_scenarios"]) >= 100_000
    assert int(ps["n_cells"]) * int(ps["draws"]) == int(ps["n_scenarios"])


def test_flexilint_invariant(bench):
    """§9.11: every FlexiBench workload must analyze with zero lint
    errors and a finite WCET, and the recorded certificate must
    dominate the PyISS-measured ticks (WCET/measured >= 1 — below 1 is
    a soundness bug, not a perf regression)."""
    fl = bench["flexilint"]
    per = fl["per_workload"]
    assert len(per) == 11, sorted(per)
    assert int(fl["total_errors"]) == 0
    assert fl["all_bounded"] is True
    for key, p in per.items():
        assert float(p["analysis_wall_ms"]) > 0, key
        assert p["wcet_ticks"] is not None, key
        assert int(p["measured_max_ticks"]) > 0, key
        assert float(p["wcet_over_measured"]) >= 1.0, (
            key, p["wcet_over_measured"])
        assert int(p["min_steps"]) <= int(p["wcet_steps"]), key


def test_device_scaling_invariant(bench):
    """§9.12: the shard-local resident engine's weak-scaling curve must
    be monotonically increasing with >=2.5x at 4 devices (replay basis:
    per-shard dedicated-device wall — the legitimate node throughput of
    a collective-free loop), every shard replay must be bit-exact with
    the sharded run, the oversubscribed wall-clock must hold the >=0.6
    efficiency floor, and each recorded point must carry the sync
    accounting (host_syncs/sync_wait_s)."""
    sc = bench["device_scaling"]
    assert sc["bit_exact"] is True
    sp = [float(s) for s in sc["speedup_vs_1dev"]]
    devs = [int(p["n_devices"]) for p in sc["points"]]
    assert devs == sorted(devs) and len(devs) >= 3
    assert all(b > a for a, b in zip(sp, sp[1:])), sp
    assert 4 in devs
    assert sp[devs.index(4)] >= 2.5, sp
    assert float(sc["min_oversubscribed_efficiency"]) >= 0.6
    for p in sc["points"]:
        assert int(p["host_syncs"]) > 0
        assert float(p["sync_wait_s"]) >= 0.0
        assert int(p["n_shards"]) == int(p["n_devices"])
        assert float(p["shard_wall_s"]) > 0.0
        assert float(p["speedup_vs_1dev"]) > 0.0
