"""Streaming fleet-execution engine (DESIGN.md §9).

Replaces the one-shot `flexibits.fleet.run_fleet_sharded` hot path with a
chunked, segment-early-exit, heterogeneity-aware engine:

- `engine.run_stream`   — chunked streaming executor (host memory O(chunk))
- `engine.run_packed`   — packed multi-program runtime: every group of a
                          heterogeneous plan in ONE stream (program bank,
                          per-lane prog_id, admission scheduler, §9.8),
                          device-resident (on-device retire/refill +
                          async sync, optional adaptive supersteps, §9.9)
- `plan.FleetPlan`      — heterogeneous (workload, core) sub-fleets;
                          `run_plan` runs them through the packed runtime
- `report.FleetReport`  — per-group cycle/energy tallies priced through
                          core/carbon.py and core/planner.py, with the
                          packed stream's whole-run stats
"""
from repro.fleet.engine import (STEPPERS, FleetResult, PackedGroup,
                                PackedStats, array_source, run_packed,
                                run_stream, run_workload_stream,
                                workload_source)
from repro.fleet.plan import FleetGroup, FleetPlan, run_plan
from repro.fleet.report import FleetReport, GroupReport

__all__ = [
    "STEPPERS", "FleetResult", "PackedGroup", "PackedStats",
    "array_source", "run_packed", "run_stream", "run_workload_stream",
    "workload_source",
    "FleetGroup", "FleetPlan", "run_plan", "FleetReport", "GroupReport",
]
