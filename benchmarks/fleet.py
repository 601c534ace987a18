"""Streaming fleet engine benchmarks (DESIGN.md §9).

Seven studies on a skewed halt-time distribution (the paper's regime:
most items run short data-dependent paths, a tail runs long ones):

- streaming vs monolithic: total simulated lane-steps; the monolithic
  vmap(while_loop) occupies every lane until the slowest item halts,
  the streaming engine compacts halted items out between segments, so
  it should retire >=2X fewer — bit-exact final memories.
- fusion proof (§9.7): structural HLO op counts; the fused-segment
  module's top level must hold >=10x fewer ops than the branchless
  step body x seg_steps it replaces.
- planner sweep (§9.13): the device-resident Monte Carlo carbon-planner
  sweep — scenarios/second of the fused jitted evaluate-and-reduce over
  the (distribution x frequency x intensity x volume x workload x
  timing) planning space vs a per-scenario python loop, with the Pallas
  A/B bit-exact and the float64 point-mass run pinned exactly to the
  numpy total_grid/selection_map oracles.
- timing overhead (§9.10): segment wall-clock of the same stream with
  the per-lane cycle layer off (cost=None, DCE'd graph) vs on with full
  dynamic cost rows — bit-exact architectural state, <=1.5x overhead.
- device scaling (§9.12): weak-scaling curve of the shard-local
  resident engine as the host device count grows (1..8, subprocesses
  with forced CPU device counts). Forced host devices time-share the
  physical cores, so each point pairs the real oversubscribed run
  (wall, host_syncs, sync_wait) with a bit-exact per-shard
  replay on a dedicated device — the collective-free loop makes the
  replay wall the dedicated-node wall, and that is what must scale
  (monotone, >=2.5x at 4 devices).

Run:  PYTHONPATH=src python benchmarks/fleet.py [--items 1024]
      (writes BENCH_fleet.json at the repo root)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.flexibits import iss
from repro.flexibits.asm import Asm
from repro.fleet import array_source, run_stream

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def skew_program():
    """Counting loop: iterates mem[0] times, stores the count at mem[1]."""
    a = Asm(vm_reserved=32)
    a.lw(a.t0, a.zero, 0)
    a.li(a.t1, 0)
    a.label("loop")
    a.addi(a.t1, a.t1, 1)
    a.blt(a.t1, a.t0, "loop")
    a.sw(a.t1, a.zero, 4)
    a.halt()
    return a.assemble()


def skew_fleet(prog, n_items: int, *, short_iters: int = 64,
               long_iters: int = 4096, long_frac: float = 0.1,
               seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    iters = np.where(rng.random(n_items) < long_frac, long_iters,
                     short_iters).astype(np.int32)
    mems = np.tile(prog.initial_memory(32), (n_items, 1))
    mems[:, 0] = iters
    return mems


def fleet_streaming_vs_monolithic(n_items: int = 1024, chunk: int = 128,
                                  seg_steps: int = 512,
                                  max_steps: int = 100_000):
    prog = skew_program()
    mems = skew_fleet(prog, n_items)
    code = jnp.asarray(prog.code.view(np.int32))

    # monolithic: one vmap(while_loop) over the whole fleet (compile at the
    # full batch shape first, then time the steady-state execution)
    jmems = jnp.asarray(mems)
    iss.run_fleet(code, jmems, max_steps).halted.block_until_ready()
    t0 = time.perf_counter()
    mono = iss.run_fleet(code, jmems, max_steps)
    mono.halted.block_until_ready()
    mono_wall = time.perf_counter() - t0
    mono_steps = n_items * int(np.asarray(mono.n_instr).max())

    res = run_stream(prog.code, array_source(mems), n_items=n_items,
                     mem_words=32, max_steps=max_steps, chunk=chunk,
                     seg_steps=seg_steps, out_addr=1, keep_state=True)

    np.testing.assert_array_equal(res.mems, np.asarray(mono.mem))

    ratio = mono_steps / max(res.lane_steps, 1)
    busy = 100.0 * res.busy_steps / max(res.lane_steps, 1)
    rows = [
        ("fleet/lane_steps", res.lane_steps, mono_steps),
        ("fleet/items_per_s", round(res.items_per_s, 1),
         round(n_items / mono_wall, 1)),
        ("fleet/wall_s", round(res.wall_s, 3), round(mono_wall, 3)),
    ]
    derived = {
        "cycles_saved_ratio": ratio,
        "streaming_busy_pct": busy,
        "n_segments": res.n_segments,
        "bit_exact": True,
        "target": ">=2X fewer simulated cycles on skewed halt times",
    }
    return rows, derived


def fleet_fusion_proof(chunk: int = 128, seg_steps: int = 512,
                       max_steps: int = 100_000):
    """HLO op-count proof of the fused-segment kernel (DESIGN.md §9.7).

    Compiles the branchless and pallas segment runners at the same
    (chunk, seg_steps) and counts ops structurally (`op_counts`). The
    branchless segment is an XLA while_loop: its step body — the largest
    while body in the module — is a graph of dozens of ops that XLA
    re-dispatches once per architectural step, i.e. O(steps x ops) per
    segment. The fused pallas segment runs the whole step loop inside
    one kernel invocation, so the compiled module's top level collapses
    to a handful of ops around a single call unit (on TPU hardware: one
    custom call; under the interpret fallback the kernel body is
    discharged back into the module, recorded here for transparency).
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.iss_stepper import iss_segment
    from repro.launch.hlo_analysis import op_counts

    prog = skew_program()
    subset = iss.opcode_subset(prog.code)
    code = jnp.asarray(prog.code.view(np.int32))
    state = jax.vmap(iss.init_state)(
        jnp.asarray(np.tile(prog.initial_memory(32), (chunk, 1))))

    def lower(fn):
        return op_counts(jax.jit(fn).lower(code, state)
                         .compile().as_text())

    bl = lower(lambda c, s: iss.run_segment_lanes(
        c, s, seg_steps, max_steps, subset))
    pal = lower(lambda c, s: iss_segment(
        c, s, seg_steps=seg_steps, max_steps=max_steps, subset=subset))

    step_ops = bl["max_while_body_ops"]
    dispatched = step_ops * seg_steps
    top = pal["entry_ops"]
    ratio = dispatched / max(top, 1)
    rows = [
        ("fleet/fusion_top_ops", top, f"{dispatched} (={step_ops}"
                                      f"x{seg_steps})"),
        ("fleet/fusion_ratio", round(ratio, 1), ">=10x"),
    ]
    derived = {
        "seg_steps": seg_steps,
        "chunk": chunk,
        "branchless": {
            "entry_ops": bl["entry_ops"],
            "step_while_body_ops": step_ops,
            "dispatched_ops_per_segment": dispatched,
        },
        "pallas": {
            "entry_ops": top,
            # interpret-fallback transparency: the discharged kernel's
            # internal step loop still appears as a while body on CPU
            "interpret_kernel_body_ops": pal["max_while_body_ops"],
        },
        "top_level_ratio": ratio,
        "target": ">=10x fewer top-level ops than branchless step-body "
                  "x seg_steps",
    }
    return rows, derived


def fleet_timing_overhead(chunk: int = 128, seg_steps: int = 256,
                          max_steps: int = 100_000):
    """Cost of the per-lane timing layer (DESIGN.md §9.10).

    The same skewed stream run twice: cycles-off (cost=None — the
    timing graph is dead-code-eliminated, identical to the pre-§9.10
    engine) and cycles-on with a full *dynamic* cost row (base table
    plus taken-branch refetch, serial shift, subword RMW — the most
    expensive configuration). The timing layer adds one one-hot dot
    product and an int32 accumulate per lane-step, so the segment wall
    clock should move very little; gates: architectural results
    bit-exact on vs off, per-lane tallies populated only when on, and
    the recorded overhead ratio under 1.5x (best-of-`reps` each, after
    a compile warm-up per mode).
    """
    from repro.flexibits.cycles import QERV, TICKS_PER_CYCLE, cost_row

    prog = skew_program()
    reps = 3
    n_items = 8 * chunk
    mems = skew_fleet(prog, n_items, short_iters=48, long_iters=2048,
                      long_frac=0.1, seed=23)
    cost = cost_row(QERV, dynamic=True)
    kw = dict(n_items=n_items, mem_words=32, max_steps=max_steps,
              chunk=chunk, seg_steps=seg_steps, out_addr=1)

    def run(c):
        best = None
        for i in range(reps + 1):             # first rep is the warm-up
            r = run_stream(prog.code, array_source(mems), cost=c, **kw)
            if i > 0 and (best is None or r.wall_s < best.wall_s):
                best = r
        return best

    off = run(None)
    on = run(cost)
    np.testing.assert_array_equal(off.n_instr, on.n_instr)
    np.testing.assert_array_equal(off.out, on.out)
    assert off.n_cycles is None and on.n_cycles is not None
    overhead = on.wall_s / max(off.wall_s, 1e-12)
    mean_cycles = float(on.n_cycles.sum()) / n_items / TICKS_PER_CYCLE
    rows = [
        ("fleet/timing_wall_s", round(on.wall_s, 3), round(off.wall_s, 3)),
        ("fleet/timing_overhead", round(overhead, 3), "<=1.5x"),
        ("fleet/timing_cyc_per_item", round(mean_cycles, 1), "-"),
    ]
    derived = {
        "cycles_on_wall_s": on.wall_s,
        "cycles_off_wall_s": off.wall_s,
        "overhead_ratio": overhead,
        "mean_cycles_per_item": mean_cycles,
        "core": "QERV",
        "dynamic": True,
        "bit_exact": True,
        "target": "cycles-on segment wall <= 1.5x cycles-off "
                  "(dynamic rows, worst case)",
    }
    return rows, derived


def fleet_fault_overhead(chunk: int = 128, seg_steps: int = 256,
                         max_steps: int = 100_000):
    """Cost of the FlexiFault resilience layer (DESIGN.md §9.14).

    The same skewed stream run four ways: `faults=None` (the pre-§9.14
    graphs), a rate-0 schedule (injection graph compiled in — must stay
    bit-exact with faults-off), an unprotected nonzero schedule (which
    records the silent-data-corruption rate DMR exists to stop), and
    DMR detect/rollback (shadow pairs + segment re-execution). Gates:
    rate 0 bit-exact, DMR recovers the fault-free outputs exactly, and
    the DMR wall clock stays under 2.5x faults-off (two copies per
    item + retries + the non-donated rollback snapshot; best-of-`reps`
    after a compile warm-up per mode)."""
    from repro.flexibits.faults import FaultSpec
    from repro.fleet import engine

    prog = skew_program()
    reps = 3
    n_items = 4 * chunk
    mems = skew_fleet(prog, n_items, short_iters=48, long_iters=2048,
                      long_frac=0.1, seed=29)

    def run(**fkw):
        best = None
        for i in range(reps + 1):             # first rep is the warm-up
            group = engine.PackedGroup(
                code=prog.code, source=array_source(mems),
                n_items=n_items, max_steps=max_steps, mem_words=32,
                out_addr=1)
            res, st = engine.run_packed([group], chunk=chunk,
                                        seg_steps=seg_steps, **fkw)
            if i > 0 and (best is None or st.wall_s < best[1].wall_s):
                best = (res[0], st)
        return best

    spec = FaultSpec(rate=2e-4, seed=5, targets=("regs", "mem", "pc"))
    off, off_st = run()
    zero, _ = run(faults=FaultSpec(rate=0.0, seed=5))
    for f in ("n_instr", "out", "halted"):
        np.testing.assert_array_equal(getattr(off, f), getattr(zero, f),
                                      err_msg=f"rate-0 {f}")
    sdc, sdc_st = run(faults=spec)
    corrupted = int(np.sum((sdc.out != off.out)
                           | (sdc.n_instr != off.n_instr)
                           | (sdc.halted != off.halted)))
    dmr, dmr_st = run(faults=spec, redundancy="dmr", max_retries=6)
    dmr_recovered = bool(np.array_equal(dmr.out, off.out)
                         and np.array_equal(dmr.n_instr, off.n_instr)
                         and np.array_equal(dmr.halted, off.halted))
    overhead = dmr_st.wall_s / max(off_st.wall_s, 1e-12)
    sdc_rate = corrupted / n_items
    rows = [
        ("fleet/faults_off_wall_s", round(off_st.wall_s, 3), "baseline"),
        ("fleet/faults_on_wall_s", round(sdc_st.wall_s, 3), "-"),
        ("fleet/dmr_wall_s", round(dmr_st.wall_s, 3), "<=2.5x off"),
        ("fleet/dmr_overhead", round(overhead, 3), "<=2.5x"),
        ("fleet/sdc_rate", round(sdc_rate, 4), "unprotected"),
        ("fleet/dmr_detected", dmr_st.detected, ">0"),
        ("fleet/dmr_corrected", dmr_st.corrected, "==detected"),
        ("fleet/dmr_quarantined", dmr_st.quarantined, "-"),
    ]
    derived = {
        "faults_off_wall_s": off_st.wall_s,
        "faults_on_wall_s": sdc_st.wall_s,
        "dmr_wall_s": dmr_st.wall_s,
        "dmr_overhead_ratio": overhead,
        "rate": spec.rate,
        "targets": list(spec.targets),
        "sdc_rate": sdc_rate,
        "corrupted_items": corrupted,
        "n_items": n_items,
        "detected": dmr_st.detected,
        "corrected": dmr_st.corrected,
        "quarantined": dmr_st.quarantined,
        "bit_exact": True,               # rate-0 vs faults-off, asserted
        "dmr_recovered": dmr_recovered,
        "target": "rate-0 bit-exact; DMR recovers fault-free outputs "
                  "at <=2.5x faults-off wall",
    }
    return rows, derived


def fleet_flexilint(n_inputs: int = 3):
    """FlexiLint certificate study (DESIGN.md §9.11).

    Runs the static analyzer over every FlexiBench workload and records
    the analysis wall time, the certified WCET tick bound under the
    dynamic SERV cost row, and the maximum ticks the PyISS oracle
    actually measures over `n_inputs` generated inputs. The gates are
    the soundness contract: zero lint errors, a finite WCET for every
    workload, and WCET/measured >= 1 everywhere — a ratio below 1 means
    the certificate is wrong, not slow.
    """
    from repro.flexibench.base import all_workloads
    from repro.flexibits import analyze
    from repro.flexibits.cycles import SERV, cost_row
    from repro.flexibits.pyiss import PyISS

    cost = cost_row(SERV, dynamic=True)
    per = {}
    for w in all_workloads():
        t0 = time.perf_counter()
        a = analyze.analyze_code(w.program.code, w.total_mem_words,
                                 loop_bounds=w.program.loop_bounds,
                                 name=w.key)
        wall_ms = (time.perf_counter() - t0) * 1e3
        wcet = a.wcet_ticks(cost)
        rng = np.random.default_rng(0)
        measured = 0
        for x in w.gen_inputs(rng, n_inputs):
            sim = PyISS(w.program.code, mem_words=w.total_mem_words,
                        init_mem=w.initial_memory(x))
            sim.run(max_steps=w.max_steps)
            measured = max(measured, sim.ticks(cost))
        per[w.key] = {
            "analysis_wall_ms": wall_ms,
            "n_words": a.n_words,
            "errors": len(a.errors),
            "warnings": len(a.warnings),
            "min_steps": a.min_steps,
            "wcet_steps": a.wcet_steps,
            "wcet_ticks": wcet,
            "measured_max_ticks": measured,
            "wcet_over_measured":
                (wcet / measured) if (wcet and measured) else None,
        }
    rows = [(f"fleet/lint_{k}", round(p["analysis_wall_ms"], 1),
             p["wcet_ticks"], p["measured_max_ticks"],
             round(p["wcet_over_measured"], 2))
            for k, p in per.items()]
    derived = {
        "per_workload": per,
        "core": "SERV",
        "dynamic": True,
        "n_inputs": n_inputs,
        "total_errors": sum(p["errors"] for p in per.values()),
        "all_bounded": all(p["wcet_ticks"] is not None
                           for p in per.values()),
        "min_ratio": min(p["wcet_over_measured"] for p in per.values()),
        "target": "0 lint errors, finite WCET, WCET >= measured ticks "
                  "on every workload",
    }
    return rows, derived


SWEEP_FIELDS = ("mean", "p50", "p90", "p99", "min", "max", "mean_emb",
                "mean_op", "fleet_mean", "counts", "hist")


def planner_sweep_spec(draws: int = 64):
    """The planning space of the planner-sweep study: 4 lifetime
    distributions x 5 task frequencies x 4 grid intensities x 3 fleet
    volumes x all 11 workloads x 3 timing modes = 7,920 cells, times
    `draws` Monte Carlo lifetime draws (506,880 scenarios at 64)."""
    from repro.core.sweep import LifetimeDist, workload_spec

    day = 86_400.0
    dists = (
        LifetimeDist.point(30 * day),
        LifetimeDist.lognormal(100 * day, 1.8),
        LifetimeDist.weibull(300 * day, 1.5),
        LifetimeDist.mixture(
            [(LifetimeDist.point(10 * day), 0.5),
             (LifetimeDist.lognormal(1000 * day, 0.8), 0.5)]),
    )
    return workload_spec(
        dists=dists,
        execs_per_day=(1.0, 24.0, 96.0, 960.0, 8640.0),
        intensities=(0.05, 0.233, 0.367, 0.7),
        volumes=(1e3, 1e6, 1e9),
        timing=("base", "dynamic", "wcet"),
        draws=draws, seed=0)


def fleet_planner_sweep(draws: int = 64, tile_cells: int = 1024,
                        n_ref: int = 200):
    """Device-resident Monte Carlo carbon-planner sweep (DESIGN.md
    §9.13).

    One fused jitted program prices the paper's whole planning space —
    (lifetime distribution x task frequency x grid intensity x
    deployment volume x workload x timing mode) cells, each with Monte
    Carlo lifetime draws over the 1000X spread and an on-device
    core-selection argmin — streamed through buffer-donated accumulator
    tiles. Workload anchors are PyISS-measured event vectors (§9.10)
    and FlexiLint WCET certificates (§9.11) priced per candidate core.
    Recorded: fused-jnp scenarios/second (warm, best of `reps`); the
    Pallas-kernel A/B on a subset spec (bit-exact gate); a per-scenario
    python-loop reference (`selection.optimal_core` per scenario — the
    pre-§9.13 way to answer the same question) for the speedup; and the
    float64 point-mass pin against the numpy
    `selection.total_grid`/`selection_map` oracles (exact-equality
    gate).
    """
    import dataclasses

    import jax

    from repro.core.selection import optimal_core, selection_map, \
        total_grid
    from repro.core.sweep import LifetimeDist, SweepSpec, run_sweep
    from repro.flexibits.cycles import CORES

    day = 86_400.0
    reps = 3
    spec = planner_sweep_spec(draws)

    run_sweep(spec, path="jnp", tile_cells=tile_cells)  # compile warm-up
    res = None
    for _ in range(reps):
        r = run_sweep(spec, path="jnp", tile_cells=tile_cells)
        if res is None or r.wall_s < res.wall_s:
            res = r
    scn_s = res.scenarios_per_s

    # Pallas A/B (interpret fallback on CPU): bit-exact on a subset of
    # the same spec — the full-spec jnp/tiling/flush contracts are
    # pinned by tests/test_sweep.py on every push.
    sub = dataclasses.replace(spec, execs_per_day=(24.0,),
                              intensities=(0.367,), volumes=(1e6,))
    aj = run_sweep(sub, path="jnp", tile_cells=64)
    ap = run_sweep(sub, path="pallas", tile_cells=64)
    for f in SWEEP_FIELDS:
        np.testing.assert_array_equal(getattr(aj, f), getattr(ap, f), f)
    for k in aj.pareto:
        np.testing.assert_array_equal(aj.pareto[k], ap.pareto[k], k)

    # python-loop reference: the same per-scenario question answered the
    # host way (one `optimal_core` call per scenario)
    rng = np.random.default_rng(0)
    wi = rng.integers(0, len(spec.workloads), n_ref)
    lifes = rng.uniform(day, 4000 * day, n_ref)
    freqs = rng.choice(spec.execs_per_day, n_ref)
    intens = rng.choice(spec.intensities, n_ref)
    t0 = time.perf_counter()
    for i in range(n_ref):
        optimal_core(spec.profiles[wi[i]], lifetime_s=lifes[i],
                     execs_per_day=freqs[i], intensity=intens[i])
    py_wall = time.perf_counter() - t0
    py_scn_s = n_ref / py_wall
    speedup = scn_s / py_scn_s

    # float64 point-mass oracle pin: device totals ARE the numpy floats
    point_lifes = [day * d for d in (1, 10, 100, 1000)]
    pfreqs = (1.0, 24.0, 96.0)
    pspec = SweepSpec(
        workloads=spec.workloads[:1], profiles=spec.profiles[:1],
        dists=tuple(LifetimeDist.point(s) for s in point_lifes),
        execs_per_day=pfreqs, intensities=(0.367,), draws=8, seed=3)
    cores = list(CORES.values())
    tg = total_grid(cores, spec.profiles[0], np.asarray(point_lifes),
                    np.asarray(pfreqs))
    smap = selection_map(spec.profiles[0], np.asarray(point_lifes),
                         np.asarray(pfreqs))
    with jax.enable_x64(True):
        pres = run_sweep(pspec, path="jnp", tile_cells=5,
                         dtype=np.float64)
    sq = np.s_[:, :, 0, 0, 0, 0, 0]
    np.testing.assert_array_equal(pres.p50[sq], tg.min(axis=0))
    np.testing.assert_array_equal(pres.min[sq], tg.min(axis=0))
    np.testing.assert_array_equal(pres.best_core[sq], smap)

    front = res.frontier()
    rows = [
        ("fleet/sweep_scn_per_s", round(scn_s), round(py_scn_s, 1)),
        ("fleet/sweep_wall_ms", round(res.wall_s * 1e3, 2),
         round(py_wall * 1e3, 2)),
        ("fleet/sweep_scenarios", res.n_scenarios, n_ref),
    ]
    derived = {
        "n_cells": res.n_cells,
        "n_scenarios": res.n_scenarios,
        "draws": draws,
        "tile_cells": tile_cells,
        "axes": {"dists": [d.name for d in spec.dists],
                 "execs_per_day": list(spec.execs_per_day),
                 "intensities": list(spec.intensities),
                 "volumes": list(spec.volumes),
                 "workloads": list(spec.workloads),
                 "timing": list(spec.timing)},
        "wall_s": res.wall_s,
        "scenarios_per_s": scn_s,
        "python_loop_scn_per_s": py_scn_s,
        "python_loop_speedup": speedup,
        "python_loop_n_ref": n_ref,
        "bit_exact": True,          # pallas A/B asserted above
        "oracle_exact": True,       # f64 point-mass pin asserted above
        "frontier_points": len(front),
        "frontier_head": front[:4],
        "target": ">=1e6 scenarios/s fused jnp on CPU, >=100x over the "
                  "per-scenario python loop, Pallas A/B bit-exact, "
                  "numpy total_grid/selection_map pinned exactly",
    }
    return rows, derived


def _scaling_worker(spec: dict) -> dict:
    """One device-scaling measurement: run the shard-local resident
    engine over ALL host devices — or, with `spec["slice"]`, replay one
    shard's item slice alone on a dedicated device (the per-node
    basis, §9.12). Invoked in a subprocess with XLA_FLAGS forcing the
    device count."""
    import hashlib

    import jax

    from repro.fleet.engine import PackedGroup, run_packed
    n_dev = len(jax.devices())
    prog = skew_program()
    mems = skew_fleet(prog, spec["fleet_items"])
    lo, hi = spec.get("slice") or (0, spec["fleet_items"])
    mems = mems[lo:hi]
    n_items = hi - lo
    mesh = jax.make_mesh((n_dev,), ("fleet",)) if n_dev > 1 else None

    def one():
        g = [PackedGroup(code=prog.code, source=array_source(mems),
                         n_items=n_items, max_steps=100_000,
                         mem_words=32, out_addr=1)]
        return run_packed(g, chunk=spec["chunk"],
                          seg_steps=spec["seg_steps"], mesh=mesh)

    one()                                     # compile warm-up
    res, stats = one()
    r2, s2 = one()                            # best of 2 timed runs
    if s2.wall_s < stats.wall_s:
        res, stats = r2, s2
    ca, cb = spec.get("check") or (0, n_items)
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(res[0].n_instr[ca:cb]).tobytes())
    h.update(np.ascontiguousarray(res[0].out[ca:cb]).tobytes())
    return {"n_devices": n_dev, "n_items": n_items,
            "items_per_s": n_items / max(stats.wall_s, 1e-9),
            "wall_s": stats.wall_s, "chunk": stats.chunk,
            "n_segments": stats.n_segments,
            "host_syncs": stats.host_syncs,
            "sync_wait_s": stats.sync_wait_s,
            "n_shards": stats.n_shards, "check": h.hexdigest()}


def fleet_device_scaling(counts=(1, 2, 4, 8), items_per_dev: int = 256,
                         chunk_per_dev: int = 128, seg_steps: int = 256):
    """Weak-scaling curve of the shard-local resident engine (§9.12):
    items and lanes per device held fixed as the device count grows.

    jax pins the device count at first backend init, so every point
    runs in a subprocess with `--xla_force_host_platform_device_count`.
    Forced host devices TIME-SHARE the physical cores (CI runners and
    the dev box have fewer cores than 8 "devices"), so the raw
    oversubscribed wall-clock cannot exhibit device scaling no matter
    what the engine does. Each point therefore also REPLAYS shard 0's
    item slice alone on one dedicated device: the §9.12 segment loop is
    collective-free (HLO-pinned by tests/test_shard_local.py), so a
    shard's replay wall IS its dedicated-node wall, and

        speedup_vs_1dev = n x (shard_items/shard_wall) / tp_1dev

    is the aggregate throughput a fleet of n single-device nodes
    achieves — the deployment shape that matters at item-level scale.
    The replay must also be BIT-EXACT with the sharded run's shard-0
    slice (checksummed per point), and the raw oversubscribed wall is
    recorded with per-point host_syncs/sync_wait_s and
    gated by an efficiency floor, so a return of per-segment global
    coordination still fails even time-shared.
    """
    def worker(n_dev: int, spec: dict) -> dict:
        env = dict(os.environ)
        # forced host devices are a CPU construct; pinning the child to
        # the CPU backend also keeps it off an accelerator this process
        # may already hold
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={n_dev}")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(_ROOT, "src"), _ROOT,
             env.get("PYTHONPATH", "")])
        cmd = [sys.executable, os.path.abspath(__file__),
               "--scale-worker", json.dumps(spec)]
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=900)
        if proc.returncode != 0:
            raise RuntimeError(f"scaling worker (n={n_dev}) failed:\n"
                               f"{proc.stderr[-2000:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    points, speedups, effs = [], [], []
    bit_exact = True
    base_node_tp = base_ips = None
    for n in counts:
        fleet = n * items_per_dev
        full = worker(n, {"fleet_items": fleet,
                          "chunk": n * chunk_per_dev,
                          "seg_steps": seg_steps, "slice": None,
                          "check": [0, items_per_dev]})
        if n == 1:
            shard = full
        else:
            # shard 0 of the contiguous balanced partition owns items
            # [0, items_per_dev) — replay them on a dedicated device
            shard = worker(1, {"fleet_items": fleet,
                               "chunk": chunk_per_dev,
                               "seg_steps": seg_steps,
                               "slice": [0, items_per_dev],
                               "check": [0, items_per_dev]})
        bit_exact = bit_exact and (full["check"] == shard["check"])
        node_tp = items_per_dev / max(shard["wall_s"], 1e-9)
        if base_node_tp is None:
            base_node_tp, base_ips = node_tp, full["items_per_s"]
        sp = n * node_tp / base_node_tp
        eff = full["items_per_s"] / max(base_ips, 1e-9)
        speedups.append(sp)
        effs.append(eff)
        point = {k: full[k] for k in
                 ("n_devices", "n_items", "items_per_s", "wall_s",
                  "chunk", "n_segments", "host_syncs", "sync_wait_s",
                  "n_shards")}
        point.update(shard_items=items_per_dev,
                     shard_wall_s=shard["wall_s"],
                     speedup_vs_1dev=sp, oversubscribed_efficiency=eff)
        points.append(point)
    rows = [(f"fleet/scale_{p['n_devices']}dev",
             round(p["speedup_vs_1dev"], 2),
             round(p["oversubscribed_efficiency"], 2))
            for p in points]
    derived = {
        "points": points, "speedup_vs_1dev": speedups,
        "bit_exact": bit_exact,
        "min_oversubscribed_efficiency": min(effs),
        "platform": "cpu (forced host devices)",
        "basis": "weak scaling; speedup from per-shard dedicated-device "
                 "replay (collective-free loop => replay wall == "
                 "dedicated-node wall, DESIGN.md §9.12); raw "
                 "oversubscribed wall recorded per point",
        "target": "monotone speedup, >=2.5x at 4 devices, shard replay "
                  "bit-exact, oversubscribed efficiency >= 0.6"}
    return rows, derived


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, default=1024)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--seg-steps", type=int, default=512)
    ap.add_argument("--json", default=os.path.join(_ROOT,
                                                   "BENCH_fleet.json"))
    ap.add_argument("--scale-worker", default=None, metavar="SPEC_JSON",
                    help="internal: emit one device-scaling point as JSON")
    ap.add_argument("--skip-scaling", action="store_true",
                    help="skip the subprocess device-scaling sweep")
    args = ap.parse_args()

    if args.scale_worker:
        print(json.dumps(_scaling_worker(json.loads(args.scale_worker))))
        return

    bench = {}
    rows, derived = fleet_streaming_vs_monolithic(
        args.items, args.chunk, args.seg_steps)
    bench["streaming_vs_monolithic"] = derived
    print(f"{'metric':<20} {'streaming':>14} {'monolithic':>14}")
    for name, s, m in rows:
        print(f"{name:<20} {s:>14} {m:>14}")
    print(f"cycles saved: {derived['cycles_saved_ratio']:.2f}x "
          f"(lane busy {derived['streaming_busy_pct']:.1f}%, "
          f"{derived['n_segments']} segments, bit-exact memories)")

    fp_rows, fp = fleet_fusion_proof(chunk=max(args.chunk, 64),
                                     seg_steps=args.seg_steps)
    bench["fusion_proof"] = fp
    print(f"\n{'metric':<22} {'pallas':>16} {'branchless':>22}")
    for name, p, b in fp_rows:
        print(f"{name:<22} {p:>16} {b:>22}")
    print(f"fused-segment module: {fp['pallas']['entry_ops']} top-level "
          f"ops vs {fp['branchless']['dispatched_ops_per_segment']} "
          f"step-dispatched ops ({fp['top_level_ratio']:.0f}x)")

    to_rows, to = fleet_timing_overhead(chunk=max(args.chunk, 64),
                                        seg_steps=args.seg_steps)
    bench["timing_overhead"] = to
    print(f"\n{'metric':<26} {'cycles-on':>14} {'cycles-off':>14}")
    for name, on_v, off_v in to_rows:
        print(f"{name:<26} {on_v:>14} {off_v:>14}")
    print(f"timing layer: {to['overhead_ratio']:.3f}x segment wall with "
          f"dynamic {to['core']} rows on ({to['mean_cycles_per_item']:.0f} "
          f"measured cycles/item, bit-exact architectural state)")

    fo_rows, fo = fleet_fault_overhead(chunk=max(args.chunk, 64),
                                       seg_steps=256)
    bench["fault_overhead"] = fo
    print(f"\n{'metric':<26} {'value':>14} {'target':>14}")
    for name, v, t in fo_rows:
        print(f"{name:<26} {v:>14} {t:>14}")
    print(f"fault layer (§9.14): DMR {fo['dmr_overhead_ratio']:.3f}x "
          f"faults-off wall, unprotected SDC rate "
          f"{fo['sdc_rate']:.1%} at {fo['rate']:g}/instr, "
          f"{fo['detected']} detected / {fo['corrected']} corrected / "
          f"{fo['quarantined']} quarantined, recovered outputs "
          f"bit-exact={fo['dmr_recovered']}")

    ps_rows, ps = fleet_planner_sweep()
    bench["planner_sweep"] = ps
    print(f"\n{'metric':<24} {'device sweep':>14} {'python loop':>14}")
    for name, d, p in ps_rows:
        print(f"{name:<24} {d:>14} {p:>14}")
    print(f"planner sweep (§9.13): {ps['scenarios_per_s']/1e6:.2f}M "
          f"scenarios/s over {ps['n_cells']} cells x {ps['draws']} "
          f"draws, {ps['python_loop_speedup']:.0f}x the per-scenario "
          f"python loop (Pallas A/B bit-exact, f64 numpy oracles "
          f"pinned, {ps['frontier_points']} frontier points)")

    fl_rows, fl = fleet_flexilint()
    bench["flexilint"] = fl
    print(f"\n{'metric':<18} {'wall ms':>9} {'wcet ticks':>12} "
          f"{'measured':>12} {'ratio':>7}")
    for name, ms, wc, ms_t, ratio in fl_rows:
        print(f"{name:<18} {ms:>9} {wc:>12} {ms_t:>12} {ratio:>7}")
    print(f"flexilint: {len(fl['per_workload'])} workloads, "
          f"{fl['total_errors']} errors, tightest certificate "
          f"{fl['min_ratio']:.2f}x measured (SERV dynamic rows)")

    sc = None
    if not args.skip_scaling:
        sc_rows, sc = fleet_device_scaling(
            items_per_dev=max(64, args.items // 4),
            seg_steps=args.seg_steps)
        bench["device_scaling"] = sc
        print(f"\n{'metric':<22} {'speedup':>14} {'oversub eff':>14}")
        for name, sp, eff in sc_rows:
            print(f"{name:<22} {sp:>14} {eff:>14}")
        print(f"device scaling (§9.12, CPU forced host devices): "
              f"replay-basis speedups "
              f"{[round(s, 2) for s in sc['speedup_vs_1dev']]}, "
              f"bit-exact={sc['bit_exact']}, min oversubscribed "
              f"efficiency {sc['min_oversubscribed_efficiency']:.2f}")

    with open(args.json, "w") as f:
        json.dump(bench, f, indent=1, default=str)
    print(f"\nwrote {args.json}")

    failures = []
    if derived["cycles_saved_ratio"] < 2.0 and args.items >= 4 * args.chunk:
        failures.append(f"streaming target NOT met: "
                        f"{derived['cycles_saved_ratio']:.2f}x < 2X")
    if fp["top_level_ratio"] < 10.0:
        failures.append(f"fusion proof target NOT met: "
                        f"{fp['top_level_ratio']:.1f}x < 10x")
    if to["overhead_ratio"] > 1.5:
        failures.append(f"timing overhead target NOT met: "
                        f"{to['overhead_ratio']:.3f}x > 1.5x")
    if not fo["dmr_recovered"]:
        failures.append("fault overhead target NOT met: DMR did not "
                        "recover the fault-free outputs")
    if fo["dmr_overhead_ratio"] > 2.5:
        failures.append(f"fault overhead target NOT met: "
                        f"{fo['dmr_overhead_ratio']:.3f}x > 2.5x "
                        f"DMR wall vs faults-off")
    if ps["scenarios_per_s"] < 1e6:
        failures.append(f"planner sweep target NOT met: "
                        f"{ps['scenarios_per_s']:.3g} scenarios/s < 1e6")
    if ps["python_loop_speedup"] < 100.0:
        failures.append(f"planner sweep speedup target NOT met: "
                        f"{ps['python_loop_speedup']:.1f}x < 100x vs "
                        f"python loop")
    if fl["total_errors"] > 0:
        failures.append(f"flexilint target NOT met: "
                        f"{fl['total_errors']} lint errors")
    if not fl["all_bounded"]:
        failures.append("flexilint target NOT met: unbounded WCET")
    if fl["min_ratio"] < 1.0:
        failures.append(f"flexilint SOUNDNESS violated: "
                        f"WCET/measured {fl['min_ratio']:.3f}x < 1")
    if sc is not None:
        sp = sc["speedup_vs_1dev"]
        devs = [p["n_devices"] for p in sc["points"]]
        if not sc["bit_exact"]:
            failures.append("device scaling target NOT met: shard replay "
                            "not bit-exact with the sharded run")
        if any(b <= a for a, b in zip(sp, sp[1:])):
            failures.append(f"device scaling NOT monotone: "
                            f"{[round(s, 2) for s in sp]}")
        if 4 in devs and sp[devs.index(4)] < 2.5:
            failures.append(f"device scaling target NOT met: "
                            f"{sp[devs.index(4)]:.2f}x < 2.5x at 4 devices")
        if sc["min_oversubscribed_efficiency"] < 0.6:
            failures.append(
                f"device scaling efficiency floor NOT met: "
                f"{sc['min_oversubscribed_efficiency']:.2f} < 0.6 "
                f"oversubscribed")
    if derived["cycles_saved_ratio"] < 2.0 and args.items < 4 * args.chunk:
        print(f"note: fleet too small to exploit skew "
              f"(--items {args.items} < 4x --chunk {args.chunk}); "
              f">=2X target applies at streaming scale")
    if failures:
        sys.exit("; ".join(failures))


if __name__ == "__main__":
    main()
