"""Entry `fleet`: one request is one fleet job through `repro.fleet.run_plan`.

A job simulates `items_per_group` items of every workload the
configuration deploys, each on its configured core, with the
configuration's timing mode and step budget, exactly as a user builds a
`FleetPlan`: the engine's own defaults choose the stepper, the lane
pool, the segment length and the refill path. Group g of job j draws
its item inputs from the seed sequence (seed, j, g). With more than one
chip the job runs shard-local over a ("fleet",) mesh of them.

Correctness is judged on the answers the window's jobs returned: every
item must halt, each group's instruction mix must add up to its retired
instructions, every item asked for must be returned, and a sample of
items drawn from the seed, holding each group's longest item, must match
the plain interpreter in `reference/rv32e.py` in its output word, halt
flag, retired and two-stage instruction counts and cycle ticks, item by
item and in each group's totals over the sample.

The reference builds each sampled item's memory itself, by the stream's
definition: item i of a group seeded s takes row i % B of the workload's
dataset generator drawn with `default_rng([s, i // B])` for B items,
written over the firmware's memory image. B is the configuration's
`input_block`; the generator and the image are pinned by the
configuration's firmware hash. So the engine's own input stream is on
the timed side only.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, List, Optional

import numpy as np

from reference import rv32e

WARMUP_JOB = 0xFFFFFFFF


def group_seed(seed: int, job: int, group: int) -> int:
    return int(np.random.SeedSequence([seed, job, group])
               .generate_state(1, np.uint64)[0])


def memory_image(w) -> np.ndarray:
    """The firmware's memory image, inputs left at zero."""
    return np.asarray(w.initial_memory(np.zeros(w.n_inputs, np.int32)),
                      np.int32)


def input_block(w, seed: int, blk: int, block: int) -> np.ndarray:
    """Memory images of items blk * block ... (blk + 1) * block - 1 of a
    group seeded `seed`: the dataset generator's rows over the image."""
    xs = np.asarray(w.gen_inputs(np.random.default_rng([seed, blk]), block),
                    np.int32)
    mem = np.repeat(memory_image(w)[None], block, axis=0)
    mem[:, :w.n_inputs] = xs
    return mem


def firmware_sha256(w, block: int) -> str:
    """Identity of a workload as simulated: code, memory image, layout
    and the first block of seed-0 inputs."""
    h = hashlib.sha256()
    h.update(np.asarray(w.program.code, np.uint32).tobytes())
    h.update(memory_image(w).tobytes())
    h.update(np.asarray([w.n_inputs, w.out_addr, w.total_mem_words],
                        np.int64).tobytes())
    h.update(input_block(w, 0, 0, block).tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class Job:
    seeds: List[int]
    n_items: List[int]                 # asked for, per group
    out: List[np.ndarray]
    halted: List[np.ndarray]
    n_instr: List[np.ndarray]
    n_two_stage: List[np.ndarray]
    n_cycles: List[np.ndarray]
    mix: List[np.ndarray]
    busy_steps: int
    lane_steps: int
    n_segments: int


class Session:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        import jax
        from repro.fleet import FleetGroup, FleetPlan, run_plan
        from repro.flexibench.base import get
        from repro.flexibits.cycles import CORES

        self.config, self.traffic, self.seed = config, traffic, seed
        self._group, self._plan, self._run = FleetGroup, FleetPlan, run_plan
        self.keys = list(config["workloads"])
        self.workloads = [get(k) for k in self.keys]
        self._blocks = {}
        for k, w in zip(self.keys, self.workloads):
            want = config["workloads"][k]["firmware_sha256"]
            if firmware_sha256(w, config["input_block"]) != want:
                raise RuntimeError(f"workload {k} differs from the one the "
                                   f"configuration records")
        for name, c in config["cores"].items():
            core = CORES[name]
            got = {"width": core.width, "area_mm2": core.area_mm2,
                   "power_mw": core.power_mw, "a": core.a, "b": core.b}
            if got != c:
                raise RuntimeError(f"core {name} differs from Table 7: {got}")
        self.mesh = None
        if len(devices) > 1:
            self.mesh = jax.make_mesh((len(devices),), ("fleet",),
                                      devices=devices)

    def plan(self, job: int, n_items: int):
        groups = tuple(
            self._group(workload=k, core=self.config["workloads"][k]["core"],
                        n_items=n_items, seed=group_seed(self.seed, job, g),
                        max_steps=self.config["max_steps"])
            for g, k in enumerate(self.keys))
        return self._plan(groups=groups, timing=self.config["timing"])

    def warmup(self) -> None:
        """One job of the window's own size: the engine sizes some of its
        programs by the number of items in a job."""
        self._run(self.plan(WARMUP_JOB, self.traffic["items_per_group"]),
                  mesh=self.mesh)

    def request(self, i: int) -> Job:
        plan = self.plan(i, self.traffic["items_per_group"])
        rep = self._run(plan, mesh=self.mesh)
        res = [g.result for g in rep.groups]

        def col(f):
            return [np.asarray(getattr(r, f)) for r in res]
        return Job(seeds=[g.seed for g in plan.groups],
                   n_items=[g.n_items for g in plan.groups],
                   out=col("out"), halted=col("halted"),
                   n_instr=col("n_instr"), n_two_stage=col("n_two_stage"),
                   n_cycles=col("n_cycles"), mix=col("mix"),
                   busy_steps=int(rep.busy_steps),
                   lane_steps=int(rep.packed.lane_steps),
                   n_segments=int(rep.packed.n_segments))

    @staticmethod
    def counters(jobs: List[Job]) -> dict:
        return {"busy_steps": sum(j.busy_steps for j in jobs),
                "lane_steps": sum(j.lane_steps for j in jobs),
                "n_segments": sum(j.n_segments for j in jobs)}

    @staticmethod
    def end_to_end(jobs: List[Job], latencies: List[float],
                   window_s: float) -> dict:
        return {"sim_minstr_per_s":
                sum(j.busy_steps for j in jobs) / window_s / 1e6}

    def release(self) -> None:
        self.mesh = None

    # ---------------------------------------------------------- checking
    def sample(self, jobs: List[Job]):
        """(job, group, item) triples: per group `check_per_group` items
        drawn from the seed, and the group's longest item."""
        rng = np.random.default_rng([self.seed, 0x5A17])
        picks = []
        for g in range(len(self.keys)):
            sizes = [len(j.n_instr[g]) for j in jobs]
            total = sum(sizes)
            if total == 0:
                continue
            starts = np.cumsum([0] + sizes)
            longest = max(((int(j.n_instr[g].max()), -jx, int(
                np.argmax(j.n_instr[g]))) for jx, j in enumerate(jobs)
                if sizes[jx]))
            chosen = {(-longest[1], longest[2])}
            k = min(self.traffic["check_per_group"], total)
            for flat in rng.choice(total, size=k, replace=False):
                jx = int(np.searchsorted(starts, flat, side="right") - 1)
                chosen.add((jx, int(flat - starts[jx])))
            picks += [(jx, g, i) for jx, i in sorted(chosen)]
        return picks

    def reference(self, job: Job, g: int, i: int, dynamic: Optional[bool]
                  = None) -> tuple:
        """The reference's answer for item i of group g of `job`."""
        k, w = self.keys[g], self.workloads[g]
        wc = self.config["workloads"][k]
        if dynamic is None:
            dynamic = self.config["timing"] == "dynamic"
        block = self.config["input_block"]
        blk = (g, job.seeds[g], i // block)
        if blk not in self._blocks:
            self._blocks[blk] = input_block(w, job.seeds[g], i // block, block)
        mem = self._blocks[blk][i % block]
        item = rv32e.run_item(
            w.program.code, mem, out_addr=w.out_addr,
            max_steps=wc["wcet_steps"],
            cost=rv32e.cost_row(self.config["cores"][wc["core"]], dynamic))
        return item.as_tuple()

    def control(self, jobs: List[Job]) -> Callable:
        """The control's answers: the reference with the dynamic timing
        terms the configuration states left out."""
        dynamic = self.config["timing"] == "dynamic"
        return lambda job, g, i: self.reference(job, g, i,
                                                dynamic=not dynamic)

    @staticmethod
    def answer(job: Job, g: int, i: int) -> tuple:
        return (int(job.out[g][i]), bool(job.halted[g][i]),
                int(job.n_instr[g][i]), int(job.n_two_stage[g][i]),
                int(job.n_cycles[g][i]))

    def check(self, jobs: List[Job],
              answer: Optional[Callable] = None) -> tuple:
        """(numbers compared, each with its limit; failed jobs; answers
        checked)."""
        answer = answer or self.answer
        bad_jobs = set()
        missing = unhalted = mix_gap = 0
        for jx, j in enumerate(jobs):
            for g in range(len(self.keys)):
                got = [len(a[g]) for a in (j.out, j.halted, j.n_instr,
                                           j.n_two_stage, j.n_cycles)]
                miss = sum(j.n_items[g] - n for n in got)
                halt = int(j.n_items[g] - np.count_nonzero(j.halted[g]))
                gap = abs(int(np.sum(j.mix[g], dtype=np.int64))
                          - int(np.sum(j.n_instr[g], dtype=np.int64)))
                missing += miss
                unhalted += halt
                mix_gap += gap
                if miss or halt or gap:
                    bad_jobs.add(jx)
        mismatched = 0
        sampled = self.sample(jobs)
        # per group: retired, two-stage and cycle totals over the sample
        totals = np.zeros((2, len(self.keys), 3), np.int64)
        for jx, g, i in sampled:
            try:
                got = answer(jobs[jx], g, i)
            except IndexError:                  # a field came back short
                got = None
            want = self.reference(jobs[jx], g, i)
            totals[1, g] += want[2:]
            if got is not None:
                totals[0, g] += got[2:]
            if got != want:
                mismatched += 1
                bad_jobs.add(jx)
        checks = {
            "items_missing": (missing, 0),
            "items_unhalted": (unhalted, 0),
            "mix_gap": (mix_gap, 0),
            "sampled_items_differing": (mismatched, 0),
            "sampled_totals_gap": (int(np.abs(totals[0] - totals[1]).sum()),
                                   0),
        }
        return checks, len(bad_jobs), len(sampled)
