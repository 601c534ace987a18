"""Chip smoke run: the fleet engine and the carbon planner on a TPU.

    python chip_smoke.py              # one chip: phases a, b, c
    python chip_smoke.py --chips 4    # four chips: phase d only

Phases, all in this one process (a chip belongs to one process):

  a  the mixed Table-2 fleet — all 11 FlexiBench workloads in one packed
     `FleetPlan`, each on the core `selection.optimal_core` picks for
     its Table-2 lifetime and task frequency, dynamic timing, WCET step
     budgets — through `run_plan` with the branchless stepper. Every item's
     output must equal `Workload.ref`; the first and last item of each
     group must match the PyISS oracle's instruction, two-stage, cycle
     and mix tallies.
  b  the same plan with the fused Pallas stepper: every per-item result
     bit-identical to (a), and the segment program the engine compiled
     must hold the Mosaic kernel (`tpu_custom_call`).
  c  the planner sweep (506,880 scenarios, float32) through `run_sweep`
     on both paths, and a point-mass sweep on both paths checked against
     the float64 numpy oracle (`selection.total_grid`/`selection_map`).
  d  (--chips 4) plan (a) shard-local over a 4-chip ("fleet",) mesh
     against the same plan on one chip: bit-identical per item.

Wall-clock seconds are printed for orientation only; they are not a
measured speed. Any failed check raises and the script exits non-zero;
the last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

# the f32 planner against the f64 oracle: every total is a handful of
# f32 roundings (the f32-rounded anchors, three multiplies and an add)
# plus the TPU's f32 divide of the lifetime draw into days, which is
# not correctly rounded — each worth a few 2^-24 — and a transcendental
# lowering that differs from XLA:CPU's. 1e-5 bounds that with room; the
# measured worst case is printed next to it.
SWEEP_RTOL = 1e-5

# items per workload group: 11 groups of 1,024 run in well under the
# time limit on one chip
ITEMS = 1024

# state every per-item comparison covers (keep_state results)
ITEM_FIELDS = ("out", "n_instr", "n_two_stage", "n_cycles", "halted",
               "mems", "regs", "pc", "mix_items")


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_tag(n_devices: int) -> str:
    """Platform, kind and the devices a phase ran on, for its line."""
    info = device_info()
    return (f"platform={info['platform']} kind={info['kind']!r} "
            f"devices={n_devices}")


def mixed_plan(stepper: str = "branchless"):
    """All 11 workloads, each on its carbon-optimal core for its Table-2
    deployment (PyISS-profiled device profiles, as the planner uses)."""
    from repro.core.selection import optimal_core
    from repro.core.sweep import LifetimeDist, workload_spec
    from repro.flexibench.base import get
    from repro.fleet import FleetGroup, FleetPlan

    spec = workload_spec(dists=(LifetimeDist.point(86_400.0),),
                         execs_per_day=(1.0,), intensities=(0.367,))
    groups = []
    for i, (key, prof) in enumerate(zip(spec.workloads, spec.profiles)):
        w = get(key)
        core, _ = optimal_core(prof, lifetime_s=w.lifetime_s,
                               execs_per_day=w.execs_per_day)
        groups.append(FleetGroup(workload=key, core=core.name,
                                 n_items=ITEMS, seed=i,
                                 max_steps="static"))
    return FleetPlan(groups=tuple(groups), timing="dynamic",
                     stepper=stepper)


def item_arrays(report):
    return [{f: getattr(g.result, f) for f in ITEM_FIELDS}
            for g in report.groups]


def checksum(report) -> str:
    h = hashlib.sha256()
    for g in report.groups:
        r = g.result
        for a in (r.out, r.n_instr, r.n_two_stage, r.n_cycles, r.halted,
                  r.mix):
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def assert_same_items(a, b, what):
    for g, (ga, gb) in enumerate(zip(item_arrays(a), item_arrays(b))):
        for f in ITEM_FIELDS:
            check(np.array_equal(ga[f], gb[f]),
                  f"{what}: group {g} field {f} differs")


def run_fleet(plan, mesh=None):
    """Warm-up on a small plan with the same pool shape (compiles the
    runners), then the full plan. Returns (report, warm_s, run_s); the
    report's per-item arrays are on the host, so run_s ends after the
    device finished."""
    from repro.fleet import run_plan

    small = dataclasses.replace(plan, groups=tuple(
        dataclasses.replace(g, n_items=24) for g in plan.groups))
    t0 = time.perf_counter()
    run_plan(small, mesh=mesh, keep_state=True)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    report = run_plan(plan, mesh=mesh, keep_state=True)
    return report, warm, time.perf_counter() - t0


def check_fleet(plan, report):
    """Every item against Workload.ref; first/last item of each group
    against the PyISS oracle."""
    from repro.fleet.engine import workload_source
    from repro.flexibench.base import get
    from repro.flexibits.cycles import CORES, cost_row
    from repro.flexibits.pyiss import PyISS

    n_oracle = 0
    for g, gr in zip(plan.groups, report.groups):
        w, r = get(g.workload), gr.result
        mems = workload_source(w, g.seed)(0, g.n_items)
        want = np.asarray(w.ref(mems[:, :w.n_inputs]), np.int64)
        check(np.array_equal(r.out.astype(np.int64), want),
              f"{g.workload}: outputs differ from Workload.ref on "
              f"{int((r.out != want).sum())} items")
        check(bool(r.halted.all()), f"{g.workload}: items did not halt")
        cost = cost_row(CORES[g.core], dynamic=True)
        for i in (0, g.n_items - 1):
            sim = PyISS(w.program.code, w.total_mem_words, mems[i],
                        cost=cost).run(w.max_steps)
            # per-class mix = one-stage + two-stage event counts
            want = (True, sim.n_instr, sim.n_two_stage, sim.n_cycles,
                    (sim.events[:8] + sim.events[8:16]).tolist())
            got = (bool(r.halted[i]), int(r.n_instr[i]),
                   int(r.n_two_stage[i]), int(r.n_cycles[i]),
                   r.mix_items[i].tolist())
            check(sim.halted and got == want,
                  f"{g.workload} item {i}: engine {got} != PyISS {want}")
            n_oracle += 1
    return n_oracle


def fleet_line(tag, plan, report, warm, run):
    st = report.packed
    return (f"phase {tag}: {device_tag(st.n_devices)} "
            f"stepper={st.stepper} "
            f"groups={len(plan.groups)} items={report.n_items} "
            f"({plan.groups[0].n_items}/group) "
            f"instructions_retired={report.busy_steps} "
            f"checksum={checksum(report)} "
            f"warmup_wall_s={warm:.3f} run_wall_s={run:.3f} "
            f"(wall clock, not a speed claim)")


def phase_ab():
    import jax
    from repro.fleet import engine
    from repro.flexibits import iss
    from repro.flexibits.cycles import N_COST

    plan = mixed_plan()
    rep_a, warm, run = run_fleet(plan)
    n_oracle = check_fleet(plan, rep_a)
    print(fleet_line("a", plan, rep_a, warm, run)
          + f" ref_items={rep_a.n_items} pyiss_items={n_oracle} PASS",
          flush=True)

    plan_b = dataclasses.replace(plan, stepper="pallas")
    rep_b, warm, run = run_fleet(plan_b)
    assert_same_items(rep_a, rep_b, "pallas vs branchless")
    # the engine's cached segment runner for this pool, lowered: it must
    # hold the compiled Mosaic kernel, not an interpreted one
    st = rep_b.packed
    mem_words = max(g.workload.total_mem_words for g in rep_b.groups)
    subset = frozenset().union(*(iss.opcode_subset(g.workload.program.code)
                                 for g in rep_b.groups))
    seg = engine._packed_segment_runner(
        "pallas", st.chunk, plan_b.seg_steps, mem_words, st.n_progs,
        st.bank_width, None, subset, True, None, True)
    n, i32 = st.chunk, np.int32

    def sds(*shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    lanes = iss.ISSState(sds(n, 16), sds(n), sds(n, mem_words),
                         sds(n, dtype=np.bool_), sds(n), sds(n),
                         sds(n, len(iss.MIX_CLASSES)), sds(n))
    text = seg.lower(sds(st.n_progs, st.bank_width), sds(st.n_progs),
                     sds(st.n_progs), sds(st.n_progs, N_COST),
                     iss.PackedState(lanes, sds(n), sds(n))).as_text()
    check("tpu_custom_call" in text,
          "pallas segment lowered without a Mosaic kernel")
    print(fleet_line("b", plan_b, rep_b, warm, run)
          + " bit_identical_to_a=True tpu_custom_call=True PASS",
          flush=True)


def phase_c():
    from benchmarks.fleet import planner_sweep_spec
    from repro.core.selection import selection_map, total_grid
    from repro.core.sweep import LifetimeDist, run_sweep

    fields = ("mean", "p50", "p90", "p99", "min", "max", "mean_emb",
              "mean_op", "fleet_mean", "counts", "hist")
    spec = planner_sweep_spec()
    res = {}
    for path in ("jnp", "pallas"):
        t0 = time.perf_counter()
        run_sweep(spec, path=path)
        warm = time.perf_counter() - t0
        r = run_sweep(spec, path=path)
        check(all(np.isfinite(getattr(r, f)).all() for f in fields[:9]),
              f"sweep {path}: non-finite reductions")
        check(int(r.hist.sum()) == r.n_scenarios
              and bool((r.counts.sum(-1) == spec.draws).all()),
              f"sweep {path}: scenario counts do not add up")
        res[path] = r
        print(f"phase c: {device_tag(1)} sweep path={path} "
              f"scenarios={r.n_scenarios} "
              f"cells={r.n_cells} dtype=float32 warmup_wall_s={warm:.3f} "
              f"run_wall_s={r.wall_s:.3f} (wall clock, not a speed "
              f"claim)", flush=True)
    same = {f: bool(np.array_equal(getattr(res["jnp"], f),
                                   getattr(res["pallas"], f)))
            for f in fields}
    same_par = all(np.array_equal(res["jnp"].pareto[k],
                                  res["pallas"].pareto[k])
                   for k in res["jnp"].pareto)
    print(f"phase c: {device_tag(1)} jnp vs pallas bit-identical: "
          f"{all(same.values()) and same_par} (per field "
          f"{json.dumps(same)}, pareto={same_par})", flush=True)

    # point masses: every draw of a cell is the same lifetime, so the
    # sweep's min/p50/max are the oracle's best total and its modal
    # core is the oracle's argmin (away from near-ties)
    day = 86_400.0
    lifes = np.array([1.0, 10.0, 100.0, 1000.0]) * day
    pspec = dataclasses.replace(
        spec, dists=tuple(LifetimeDist.point(s) for s in lifes),
        volumes=(1.0,), timing=("base", "dynamic"), wcet_cycles=None)
    freqs = np.asarray(pspec.execs_per_day)
    cores = list(pspec.cores)
    for path in ("jnp", "pallas"):
        r = run_sweep(pspec, path=path)
        worst, core_ok, near_ties = 0.0, 0, 0
        for wi, prof in enumerate(pspec.profiles):
            for ti, mode in enumerate(pspec.timing):
                p = dataclasses.replace(prof, dynamic=mode == "dynamic")
                for ii, inten in enumerate(pspec.intensities):
                    tg = total_grid(cores, p, lifes, freqs, inten)
                    best = tg.min(axis=0)
                    smap = selection_map(p, lifes, freqs, inten)
                    sl = np.s_[:, :, ii, 0, wi, ti, 0]
                    for f in ("min", "p50", "max"):
                        rel = np.abs(getattr(r, f)[sl] - best) / best
                        worst = max(worst, float(rel.max()))
                    srt = np.sort(tg, axis=0)
                    gap = (srt[1] - srt[0]) / srt[0]
                    agree = r.best_core[sl] == smap
                    check(bool((agree | (gap <= SWEEP_RTOL)).all()),
                          f"sweep {path}: core choice differs from "
                          f"selection_map away from a near-tie")
                    core_ok += int(agree.sum())
                    near_ties += int((~agree).sum())
        check(worst <= SWEEP_RTOL,
              f"sweep {path}: rel err {worst:.3g} > {SWEEP_RTOL:g}")
        print(f"phase c: {device_tag(1)} point-mass oracle path={path} "
              f"cells={r.n_cells} "
              f"max_rel_err={worst:.3e} rtol={SWEEP_RTOL:g} "
              f"core_choice_equal={core_ok} near_tie_differences="
              f"{near_ties} PASS", flush=True)


def phase_d():
    import jax
    check(len(jax.devices()) >= 4, "--chips 4 needs four devices")
    plan = mixed_plan()
    one, warm1, run1 = run_fleet(plan)
    mesh = jax.make_mesh((4,), ("fleet",), devices=jax.devices()[:4])
    four, warm4, run4 = run_fleet(plan, mesh=mesh)
    check(four.packed.n_shards == 4, "mesh run did not shard four ways")
    assert_same_items(one, four, "4-chip mesh vs one chip")
    n_oracle = check_fleet(plan, four)
    print(fleet_line("d/one-chip", plan, one, warm1, run1), flush=True)
    print(fleet_line("d/4-chip-mesh", plan, four, warm4, run4)
          + f" shard_retired={list(four.packed.shard_retired)}"
          + f" bit_identical_to_one_chip=True ref_items={four.n_items}"
          + f" pyiss_items={n_oracle} PASS", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    info = device_info()
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {info['platform']})")
    from repro import compile_cache
    cache = compile_cache.enable()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} compile_cache={cache}", flush=True)
    if args.chips == 4:
        phase_d()
    else:
        phase_ab()
        phase_c()
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
