"""Heterogeneous fleet plans: (workload, core) sub-fleets in one run.

The paper's fleet is not uniform — items differ in workload, datapath
width, deployment lifetime, and task frequency (1000X lifetime variation,
Table 2). A `FleetPlan` expresses that: each `FleetGroup` pins a
FlexiBench workload to a FLEXIBITS core and a deployment profile, and
`run_plan` drives every group through the same streaming engine
(DESIGN.md §9.3), collecting per-group cycle/energy tallies for the
carbon report.

Plans are statically checked before anything runs (DESIGN.md §9.11):
FlexiLint's shortest-path-to-HALT bound rejects `max_steps` budgets
that provably cannot reach the ecall (`BudgetError`), `max_steps=
"static"` derives the budget from the program's WCET instead of a
hand-picked number, and `subset_source="static"` specializes the
steppers with the analyzer's reachable-only opcode subset. Each group
also carries a certified worst-case cycle bound into the report so
the carbon table prints proved ceilings next to measured means.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro.flexibench import base as fb
from repro.flexibits import analyze
from repro.flexibits.cycles import CORES, TICKS_PER_CYCLE, Core, cost_row
from repro.flexibits.faults import FaultSpec
from repro.fleet import engine
from repro.fleet.report import FleetReport, build_group_report


class BudgetError(ValueError):
    """A group's `max_steps` budget is statically proved insufficient:
    FlexiLint's shortest path to HALT (`Analysis.min_steps`, a sound
    lower bound on retirements) already exceeds the budget, so every
    lane would be cut off before the ecall."""

    def __init__(self, name: str, budget: int, min_steps: int):
        self.name = name
        self.budget = budget
        self.min_steps = min_steps
        super().__init__(
            f"workload {name!r}: max_steps budget {budget} cannot reach "
            f"HALT — the statically shortest path to the ecall retires "
            f"{min_steps} instructions (FlexiLint min_steps, §9.11)")


@dataclasses.dataclass(frozen=True)
class FleetGroup:
    """One homogeneous sub-fleet: n_items of one workload on one core.

    `max_steps` is the per-item retirement budget: None takes the
    workload's hand-set value, an int overrides it, and the string
    "static" derives it from FlexiLint's WCET instruction bound —
    a budget *proved* sufficient for every input (errors out if the
    program has no finite static bound)."""
    workload: str                         # FlexiBench key (WQ, MC, ...)
    core: str = "SERV"                    # FLEXIBITS core name
    n_items: int = 1024
    seed: int = 0
    lifetime_s: Optional[float] = None    # default: workload Table-2 value
    execs_per_day: Optional[float] = None
    max_steps: Union[int, str, None] = None   # int | "static" | None

    def resolve(self) -> Tuple[fb.Workload, Core, float, float]:
        w = fb.get(self.workload)
        core = CORES[self.core]
        life = self.lifetime_s if self.lifetime_s is not None \
            else w.lifetime_s
        freq = self.execs_per_day if self.execs_per_day is not None \
            else w.execs_per_day
        return w, core, life, freq

    def resolve_max_steps(self, w: fb.Workload,
                          analysis: analyze.Analysis) -> int:
        """The group's effective per-item step budget (see class doc)."""
        if self.max_steps == "static":
            if analysis.wcet_steps is None:
                raise ValueError(
                    f"workload {w.key!r}: max_steps='static' needs a "
                    f"finite FlexiLint WCET, but the analysis has none "
                    f"(degraded: {analysis.degraded!r})")
            return analysis.wcet_steps
        if self.max_steps is not None:
            return int(self.max_steps)
        return w.max_steps


@dataclasses.dataclass(frozen=True)
class FleetPlan:
    """A full heterogeneous fleet plus engine tuning knobs.

    Every group runs in ONE packed multi-program stream — program bank
    + per-lane prog_id, freed lanes backfilled from any pending group
    (DESIGN.md §9.8) — on the resident runtime (§9.9). `stepper` names
    the segment interpreter: "branchless" (lane-parallel stepper with
    per-workload opcode-subset specialization, §9.5) or "pallas"
    (fused-segment kernel, §9.7). None (default) is the engine's
    choice: "pallas" on a TPU when the plan has no fault schedule,
    "branchless" otherwise (`engine._choose_stepper`); the report's
    results name the stepper that ran. `prefetch` enables
    double-buffered async host refill (§9.6). `adaptive` turns on the
    superstep controller (§9.9): each segment's step bound is picked
    from a bounded ladder under `seg_steps` by the observed halt
    cadence (deterministic for a given plan, bit-exact with fixed
    segmentation).

    `timing` turns on the per-lane cycle layer (DESIGN.md §9.10): each
    group's lanes accumulate ticks from its core's cost row
    (`cycles.cost_row`) and the carbon report prices the group from the
    *measured* mean cycles instead of the two-bucket analytic model.
    "base" prices only the per-(stage, class) table — numerically
    identical to the analytic model, an end-to-end consistency mode —
    while "dynamic" additionally prices taken-branch refetch, serial
    shift amount, and subword read-modify-write. None (default) keeps
    the cycles-off graphs and analytic pricing.

    `validate_budgets` (default on) runs FlexiLint over every group
    before launch and raises `BudgetError` when a `max_steps` budget is
    statically proved unable to reach HALT; `subset_source` picks the
    steppers' opcode-subset oracle — "text" (default) scans the encoded
    words as data (`iss.opcode_subset`), "static" uses the analyzer's
    reachable-only subset (DESIGN.md §9.11), which can be strictly
    smaller when dead code carries opcode classes the program never
    retires. Results are bit-exact either way (tests/test_flexilint.py
    pins it).

    `faults`/`redundancy`/`max_retries` turn on the FlexiFault
    resilience layer (DESIGN.md §9.14): a deterministic counter-based
    fault schedule injected into every lane, and — with
    `redundancy="dmr"` — shadow-lane detection with rollback to the
    last pair compare (`engine.compare_steps`) and quarantine.
    `faults=None` with `redundancy="none"` (the default) keeps the
    fault-free graphs bit-exact. The report prices each group
    under the plan's redundancy mode (`carbon.redundant_*`), so DMR
    plans show the spare-area + re-execution carbon they'd pay in
    deployment."""
    groups: Sequence[FleetGroup]
    chunk: int = 256
    seg_steps: int = 4096
    intensity: float = 0.367              # kg CO2e/kWh (US grid)
    clock_hz: float = 10_000.0
    stepper: Optional[str] = None         # None: the engine's choice
    prefetch: bool = True
    adaptive: bool = False
    timing: Optional[str] = None          # None | "base" | "dynamic"
    validate_budgets: bool = True         # FlexiLint min-steps gate
    subset_source: str = "text"           # "text" | "static"
    faults: Optional[FaultSpec] = None    # FlexiFault schedule (§9.14)
    redundancy: str = "none"              # "none" | "dmr"
    max_retries: int = 2                  # DMR rollbacks before quarantine

    @property
    def n_items(self) -> int:
        return sum(g.n_items for g in self.groups)


def _group_cost(plan: FleetPlan, core: Core):
    """The group's engine cost row under the plan's timing mode."""
    if plan.timing is None:
        return None
    if plan.timing not in ("base", "dynamic"):
        raise ValueError('timing must be None, "base", or "dynamic"')
    return cost_row(core, dynamic=plan.timing == "dynamic")


def _static_pass(plan: FleetPlan, g: FleetGroup, w: fb.Workload,
                 core: Core):
    """FlexiLint pre-flight for one group (DESIGN.md §9.11): resolve the
    step budget (possibly WCET-derived), reject provably-insufficient
    budgets, pick the stepper subset, and price the certified
    worst-case cycle bound for the report.

    The certificate always uses the *dynamic* cost row — the bound must
    hold on real hardware, where taken-branch refetch, serial shifts,
    and subword RMW all cost ticks — so a "base"-timing run's measured
    mean sits under it a fortiori."""
    if plan.subset_source not in ("text", "static"):
        raise ValueError('subset_source must be "text" or "static"')
    analysis = analyze.analyze_workload(w)
    max_steps = g.resolve_max_steps(w, analysis)
    if plan.validate_budgets and analysis.min_steps is not None \
            and max_steps < analysis.min_steps:
        raise BudgetError(w.key, max_steps, analysis.min_steps)
    subset = analysis.subset if plan.subset_source == "static" else None
    wcet_ticks = analysis.bound_ticks(cost_row(core, dynamic=True),
                                      max_steps)
    wcet_cycles = None if wcet_ticks is None \
        else wcet_ticks / TICKS_PER_CYCLE
    return max_steps, subset, wcet_cycles


def _packed_groups(plan: FleetPlan):
    """Lower FleetGroups to engine-level PackedGroups (one bank row per
    group — two groups sharing a workload still get their own rows, so
    prog_id doubles as the group id for accounting)."""
    lowered = []
    resolved = []
    for g in plan.groups:
        w, core, lifetime_s, execs_per_day = g.resolve()
        max_steps, subset, wcet_cycles = _static_pass(plan, g, w, core)
        resolved.append((w, core, lifetime_s, execs_per_day, wcet_cycles))
        lowered.append(engine.PackedGroup(
            code=w.program.code, source=engine.workload_source(w, g.seed),
            n_items=g.n_items, max_steps=max_steps,
            mem_words=w.total_mem_words, out_addr=w.out_addr,
            cost=_group_cost(plan, core), subset=subset))
    return lowered, resolved


def run_plan(plan: FleetPlan, mesh: Optional[Mesh] = None,
             keep_state: bool = False,
             checkpoint_dir: Optional[str] = None,
             checkpoint_every: int = 0) -> FleetReport:
    """Execute the plan and price it through the carbon report.

    Every group runs in ONE packed stream (engine.run_packed) and
    `fleet/report.py` demuxes the per-lane tallies back into per-group
    `GroupReport`s. Under a mesh the resident stream is shard-local
    (DESIGN.md §9.12) and the returned `FleetReport.packed` carries
    per-shard retirement/lane-step stats; `checkpoint_dir`/
    `checkpoint_every` make the stream durable (mid-flight checkpoint +
    bit-exact auto-resume). A plan with no groups returns an empty
    report.

    The job is a `fleet.job` span on the profiler's host plane, holding
    the `fleet.static` pass, the engine's `fleet.stream` and the
    `fleet.report` demux and pricing (engine `_SyncClock`).
    """
    if not plan.groups:
        return FleetReport(groups=[], intensity=plan.intensity)
    with TraceAnnotation("fleet.job"):
        with TraceAnnotation("fleet.static"):
            lowered, resolved = _packed_groups(plan)
        results, stats = engine.run_packed(
            lowered, chunk=plan.chunk, seg_steps=plan.seg_steps,
            keep_state=keep_state, mesh=mesh, stepper=plan.stepper,
            prefetch=plan.prefetch, adaptive=plan.adaptive,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, faults=plan.faults,
            redundancy=plan.redundancy, max_retries=plan.max_retries)
        with TraceAnnotation("fleet.report"):
            group_reports = [
                build_group_report(
                    group=g, workload=w, core=core, result=res,
                    lifetime_s=lifetime_s, execs_per_day=execs_per_day,
                    intensity=plan.intensity, clock_hz=plan.clock_hz,
                    wcet_cycles=wcet_cycles, redundancy=plan.redundancy,
                    fault_rate=0.0 if plan.faults is None
                    else plan.faults.rate)
                for g, (w, core, lifetime_s, execs_per_day,
                        wcet_cycles), res
                in zip(plan.groups, resolved, results)]
        return FleetReport(groups=group_reports,
                           intensity=plan.intensity, packed=stats)
