"""Entry `fleet_dmr`: the `fleet` entry's jobs under a transient fault
schedule, with DMR (dual modular redundancy).

A job is the fleet entry's job (`entries/fleet.py`) with the
configuration's fault schedule injected into every lane under a fault
seed of its own, drawn from (seed, job), and
`redundancy="dmr"`: each item runs on a pair of lanes whose state and
tallies are compared every `compare_steps` steps; a pair that
disagrees rolls back to the previous compare's snapshot, and one that
disagrees `max_retries + 1` times in a row is quarantined and its item
resumed from the pair's last agreed state on another pair. The engine
is given the configuration's `fault_rate_injected`, its base rate
scaled to the core's width; the segment length, the compare interval
it derives from the rate, and the retry limit are the engine's
defaults.

Set-up refuses a configuration whose recorded `planner_choice` (the
redundancy and core that the carbon planner picks for the workload at
its lifetime, frequency and base fault rate) the planner no longer
makes, or whose core, redundancy or injected rate do not follow from
it, as the fleet entry refuses a changed firmware or core. It also
refuses a program that does not compare each pair every
`compare_steps` steps of the configuration's segments, as the
configuration records: a program that compares at segment boundaries
alone runs another deployment.

DMR promises fault-free answers, so the fleet entry's five checks hold
against the same fault-free reference (`reference/rv32e.py`).
`jobs_without_mismatch` counts jobs in which no pair ever disagreed: a
run whose faults did nothing cannot pass. A pair's first mismatch is
always rolled back, so a job with a mismatch has a rollback too. The
control is the same jobs run under the same fault schedule without
redundancy.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional

import numpy as np

from entries import fleet


def fault_seed(seed: int, job: int) -> int:
    """The fault schedule's seed of job `job`: 31 bits, so that
    `jax.random.PRNGKey` takes it without 64-bit mode."""
    return int(np.random.SeedSequence([seed, job, 0xFA17])
               .generate_state(1, np.uint32)[0] >> 1)


def planner_choice(config: dict) -> dict:
    """The redundancy and core the carbon planner picks for the
    configuration's one workload at its lifetime, task frequency and
    base fault rate (`core.selection.redundancy_selection_map`)."""
    from repro.core.carbon import REDUNDANCY_MODES, DeviceProfile
    from repro.core.selection import redundancy_selection_map
    from repro.flexibits.cycles import CORES

    (key, wc), = config["workloads"].items()
    p = config["planner"]["profiles"][key]
    prof = DeviceProfile(n_one_stage=p["n_one_stage"],
                         n_two_stage=p["n_two_stage"], vm_kb=p["vm_kb"],
                         nvm_kb=p["nvm_kb"], events=tuple(p["events"]))
    cores = [CORES[name] for name in config["cores"]]
    r, c = redundancy_selection_map(
        prof, np.array([wc["lifetime_s"]]),
        np.array([float(wc["execs_per_day"])]),
        fault_rate=config["fault_base_rate"],
        intensity=config["planner"]["intensity"], cores=cores)
    return {"redundancy": REDUNDANCY_MODES[int(r[0, 0])],
            "core": cores[int(c[0, 0])].name}


def check_planner_choice(config: dict) -> None:
    from repro.flexibits.cycles import CORES
    from repro.flexibits.faults import width_scaled_rate

    got = planner_choice(config)
    if got != config["planner_choice"]:
        raise RuntimeError(f"the planner picks {got}, not the "
                           f"configuration's {config['planner_choice']}")
    (key, wc), = config["workloads"].items()
    if (wc["core"], config["redundancy"]) != (got["core"],
                                              got["redundancy"]):
        raise RuntimeError(f"{key} runs {config['redundancy']} on "
                           f"{wc['core']}, the planner picks {got}")
    rate = width_scaled_rate(config["fault_base_rate"],
                             CORES[wc["core"]].width)
    if not math.isclose(rate, config["fault_rate_injected"],
                        rel_tol=1e-12):
        raise RuntimeError(f"fault_rate_injected is "
                           f"{config['fault_rate_injected']}, the base "
                           f"rate scaled to {wc['core']} is {rate}")


def check_compare_steps(config: dict, seg_steps: int) -> None:
    """The program compares each DMR pair every `compare_steps` steps
    of a `seg_steps` segment at the injected rate, as recorded."""
    from repro.fleet import engine
    from repro.flexibits.faults import FaultSpec

    derive = getattr(engine, "compare_steps", None)
    spec = FaultSpec(rate=config["fault_rate_injected"],
                     targets=tuple(config["fault_targets"]),
                     mode=config["fault_mode"])
    got = derive(spec, seg_steps) if derive is not None else seg_steps
    if got != config["compare_steps"]:
        raise RuntimeError(f"the program compares DMR pairs every {got} "
                           f"steps, the configuration every "
                           f"{config['compare_steps']}")


@dataclasses.dataclass
class DmrJob(fleet.Job):
    index: int = 0
    detected: int = 0
    corrected: int = 0
    quarantined: int = 0
    discarded: Optional[int] = None   # None: the program does not count it


class Session(fleet.Session):
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        super().__init__(config, traffic, seed, devices)
        from repro.flexibits.faults import FaultSpec

        check_planner_choice(config)
        check_compare_steps(config, self._plan.seg_steps)
        self._spec = lambda job: FaultSpec(
            rate=config["fault_rate_injected"], seed=fault_seed(seed, job),
            targets=tuple(config["fault_targets"]),
            mode=config["fault_mode"])
        self._stats = None
        run = self._run

        def run_and_keep(plan, mesh=None):
            rep = run(plan, mesh=mesh)
            self._stats = rep.packed
            return rep
        self._run = run_and_keep

    def plan(self, job: int, n_items: int, redundancy: Optional[str] = None):
        return dataclasses.replace(
            super().plan(job, n_items), faults=self._spec(job),
            redundancy=redundancy or self.config["redundancy"])

    def request(self, i: int) -> DmrJob:
        job = super().request(i)
        st = self._stats
        return DmrJob(**{f.name: getattr(job, f.name)
                         for f in dataclasses.fields(job)},
                      index=i, detected=st.detected,
                      corrected=st.corrected, quarantined=st.quarantined,
                      discarded=getattr(st, "discarded", None))

    @staticmethod
    def counters(jobs: List[DmrJob]) -> dict:
        out = fleet.Session.counters(jobs)
        for k in ("detected", "corrected", "quarantined"):
            out[k] = sum(getattr(j, k) for j in jobs)
        if all(j.discarded is not None for j in jobs):
            out["discarded"] = sum(j.discarded for j in jobs)
        return out

    def control(self, jobs: List[DmrJob]) -> Callable:
        """The control's answers: each job run again under the same
        fault schedule, without redundancy."""
        runs = {}

        def answer(job: DmrJob, g: int, i: int) -> tuple:
            if job.index not in runs:
                rep = self._run(self.plan(job.index, job.n_items[0],
                                          redundancy="none"),
                                mesh=self.mesh)
                runs[job.index] = [grp.result for grp in rep.groups]
            r = runs[job.index][g]
            return (int(r.out[i]), bool(r.halted[i]), int(r.n_instr[i]),
                    int(r.n_two_stage[i]), int(r.n_cycles[i]))
        return answer

    def check(self, jobs: List[DmrJob],
              answer: Optional[Callable] = None) -> tuple:
        checks, failed, n = super().check(jobs, answer)
        checks["jobs_without_mismatch"] = (
            sum(j.detected == 0 for j in jobs), 0)
        return checks, failed, n
