"""Resident packed runtime (DESIGN.md §9.9): full-state parity with the
monolithic per-group `iss.run_fleet` oracle (both steppers, timing and
adaptive supersteps on and off), the banked Pallas refill swap,
adaptive-superstep determinism and bit-exactness, sync-stats
accounting, the on-demand flush of the int32 mix rows, and the
4-device shard_map path."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.fleet import skew_fleet, skew_program
from repro.flexibench.base import get
from repro.fleet import engine
from repro.fleet.engine import (PackedGroup, _SuperstepController,
                                run_packed)
from repro.fleet.plan import FleetGroup, FleetPlan, run_plan
from repro.fleet.report import build_group_report
from repro.flexibits import iss
from repro.flexibits.cycles import CORES, cost_row
from repro.flexibits.faults import FaultSpec
from repro.kernels.iss_stepper import iss_refill

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_STATE_FIELDS = ("n_instr", "n_two_stage", "halted", "out", "mix",
                 "mems", "regs", "pc", "mix_items")


def _skew_mems(n_a=40, n_b=24):
    prog = skew_program()
    return prog, (
        skew_fleet(prog, n_a, short_iters=8, long_iters=400,
                   long_frac=0.2, seed=13),
        skew_fleet(prog, n_b, short_iters=16, long_iters=300,
                   long_frac=0.3, seed=14))


def _skew_groups(n_a=40, n_b=24, max_steps_b=100_000, costs=(None, None)):
    prog, (mems_a, mems_b) = _skew_mems(n_a, n_b)
    return [
        PackedGroup(code=prog.code, source=engine.array_source(mems_a),
                    n_items=n_a, max_steps=100_000, mem_words=32,
                    out_addr=1, cost=costs[0]),
        PackedGroup(code=prog.code, source=engine.array_source(mems_b),
                    n_items=n_b, max_steps=max_steps_b, mem_words=32,
                    out_addr=1, cost=costs[1]),
    ]


def _oracle(code, mems, max_steps, out_addr, cost=None):
    """One group's final state from the monolithic `iss.run_fleet`
    (every item to its halt or budget in one vmapped while_loop), in
    `FleetResult` field names."""
    st = iss.run_fleet(jnp.asarray(np.asarray(code).view(np.int32)),
                       jnp.asarray(mems), max_steps,
                       None if cost is None else jnp.asarray(cost))
    mem = np.asarray(st.mem)
    mix_items = np.asarray(st.mix)
    return {"n_instr": np.asarray(st.n_instr),
            "n_two_stage": np.asarray(st.n_two_stage),
            "halted": np.asarray(st.halted), "out": mem[:, out_addr],
            "mix": mix_items.astype(np.int64).sum(0), "mems": mem,
            "regs": np.asarray(st.regs), "pc": np.asarray(st.pc),
            "mix_items": mix_items, "n_cycles": np.asarray(st.n_cycles)}


def _assert_matches_oracle(results, groups, mems, timing=False):
    for r, g, m in zip(results, groups, mems):
        want = _oracle(g.code, m, g.max_steps, g.out_addr, g.cost)
        for f in _STATE_FIELDS + (("n_cycles",) if timing else ()):
            np.testing.assert_array_equal(getattr(r, f), want[f],
                                          err_msg=f)


@pytest.mark.parametrize("adaptive", [False, True],
                         ids=["fixed", "adaptive"])
@pytest.mark.parametrize("timing", [False, True], ids=["off", "dynamic"])
@pytest.mark.parametrize("stepper", ["branchless", "pallas"])
def test_resident_bit_exact_with_host_refill(stepper, timing, adaptive):
    """Full-state parity: the resident runtime retires, demuxes, and
    keeps final state (and, with the timing layer on, every cycle tally)
    bit-exactly equal to each group's monolithic `iss.run_fleet` —
    including a group whose budget, not halting, ends its items."""
    costs = (cost_row(CORES["SERV"], dynamic=True),
             cost_row(CORES["HERV"], dynamic=True)) if timing \
        else (None, None)
    groups = _skew_groups(max_steps_b=200, costs=costs)
    res, stats = run_packed(groups, chunk=16, seg_steps=64,
                            keep_state=True, stepper=stepper,
                            adaptive=adaptive)
    assert stats.stepper == stepper and stats.adaptive == adaptive
    _assert_matches_oracle(res, groups, _skew_mems()[1], timing)
    assert not res[1].halted[res[1].n_instr == 200].any()
    assert (res[1].n_instr == 200).any()              # budget-exhausted


def test_resident_plan_report_matches_host_refill():
    """run_plan's report (carbon, energy, profiles) is what the report
    path makes of each group's monolithic `iss.run_fleet` tallies, to
    the bit: the demux feeds it the oracle's numbers."""
    groups = (
        FleetGroup(workload="WQ", core="SERV", n_items=40, seed=1),
        FleetGroup(workload="MC", core="HERV", n_items=24, seed=2),
    )
    rep = run_plan(FleetPlan(groups=groups, chunk=16, seg_steps=128))
    for g, gr in zip(groups, rep.groups):
        w = get(g.workload)
        mems = engine.workload_source(w, g.seed)(0, g.n_items)
        want = _oracle(w.program.code, mems, w.max_steps, w.out_addr)
        for f in ("n_instr", "n_two_stage", "halted", "out", "mix"):
            np.testing.assert_array_equal(getattr(gr.result, f), want[f],
                                          err_msg=f)
        ref = build_group_report(
            group=g, workload=w, core=CORES[g.core],
            result=dataclasses.replace(
                gr.result, n_instr=want["n_instr"].astype(np.int64),
                n_two_stage=want["n_two_stage"].astype(np.int64),
                mix=want["mix"]),
            lifetime_s=gr.lifetime_s, execs_per_day=gr.execs_per_day,
            intensity=rep.intensity, clock_hz=10_000.0,
            wcet_cycles=gr.wcet_cycles)
        assert gr.profile == ref.profile
        assert gr.energy_j_per_exec == ref.energy_j_per_exec
        assert gr.total_kg == ref.total_kg
    assert "sync stats" in rep.format()


@pytest.mark.parametrize("stepper", ["branchless", "pallas"])
def test_adaptive_supersteps_bit_exact_and_deterministic(stepper):
    """Same plan + seed: two adaptive runs produce the identical segment
    schedule and results; adaptive results are bit-exact with the fixed
    schedule; the schedule actually adapts (more than one rung used on
    a churny skewed fleet) and stays within the ladder."""
    groups = _skew_groups()
    kw = dict(chunk=16, seg_steps=64, keep_state=True, stepper=stepper)
    fixed, sf = run_packed(_skew_groups(), **kw)
    run1, s1 = run_packed(_skew_groups(), adaptive=True, **kw)
    run2, s2 = run_packed(groups, adaptive=True, **kw)
    assert s1.adaptive and s1.seg_schedule == s2.seg_schedule
    assert len(s1.seg_schedule) == s1.n_segments
    assert sf.seg_schedule == (64,) * sf.n_segments
    ladder = _SuperstepController(64, 16, True).ladder
    assert set(s1.seg_schedule) <= set(ladder)
    assert len(set(s1.seg_schedule)) > 1, "controller never adapted"
    for a, b, c in zip(fixed, run1, run2):
        for f in _STATE_FIELDS:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                          err_msg=f)
            np.testing.assert_array_equal(getattr(b, f), getattr(c, f),
                                          err_msg=f)


def test_superstep_controller_ladder_and_policy():
    """The ladder is bounded (bounded retraces), capped at seg_steps,
    and the policy moves the right way: high observed churn shrinks the
    next segment, a quiet pool decays back to the cap."""
    c = _SuperstepController(4096, 256, True)
    assert c.ladder == (256, 512, 1024, 2048, 4096)
    assert c.next_seg() == 4096          # no signal yet -> cap
    for _ in range(4):
        c.record(n_retired=200, steps=256)   # heavy churn
    assert c.next_seg() == 256
    for _ in range(12):
        c.record(n_retired=0, steps=4096)    # long-tail quiet pool
    assert c.next_seg() == 4096
    assert c.schedule == [4096, 256, 4096]
    # disabled controller always returns the configured seg_steps
    off = _SuperstepController(4096, 256, False)
    off.record(n_retired=200, steps=256)
    assert off.next_seg() == 4096


def test_refill_take_assigns_staged_rows_in_lane_order():
    free = jnp.asarray([True, False, True, True, False, True])
    take, src = iss.refill_take(free, jnp.asarray(2, iss.I32))
    np.testing.assert_array_equal(
        np.asarray(take), [True, False, True, False, False, False])
    np.testing.assert_array_equal(np.asarray(src)[[0, 2]], [0, 1])
    # staged batch larger than the free set: every free lane takes
    take, src = iss.refill_take(free, jnp.asarray(6, iss.I32))
    np.testing.assert_array_equal(np.asarray(take), np.asarray(free))
    np.testing.assert_array_equal(np.asarray(src)[[0, 2, 3, 5]],
                                  [0, 1, 2, 3])


def test_pallas_refill_swap_matches_jnp_swap():
    """The banked Pallas compaction/scatter kernel (`iss_refill`) is
    bit-identical to the shared jnp helper (`iss.refill_lanes`) over a
    randomized pool + staged batch, including un-taken lanes."""
    rng = np.random.default_rng(7)
    n, m, s = 256, 16, 150          # two 128-lane tiles
    lanes = iss.ISSState(
        regs=jnp.asarray(rng.integers(-9, 9, (n, 16)), iss.I32),
        pc=jnp.asarray(rng.integers(0, 64, n), iss.I32),
        mem=jnp.asarray(rng.integers(-99, 99, (n, m)), iss.I32),
        halted=jnp.asarray(rng.random(n) < 0.5),
        n_instr=jnp.asarray(rng.integers(0, 50, n), iss.I32),
        n_two_stage=jnp.asarray(rng.integers(0, 20, n), iss.I32),
        mix=jnp.asarray(rng.integers(0, 9, (n, 8)), iss.I32),
        n_cycles=jnp.asarray(rng.integers(0, 999, n), iss.I32))
    ps = iss.PackedState(
        lanes=lanes,
        prog_id=jnp.asarray(rng.integers(0, 3, n), iss.I32),
        max_steps=jnp.asarray(rng.integers(1, 99, n), iss.I32))
    free = jnp.asarray(rng.random(n) < 0.6)
    take, src = iss.refill_take(free, jnp.asarray(s, iss.I32))
    smem = jnp.asarray(rng.integers(-99, 99, (n, m)), iss.I32)
    sprog = jnp.asarray(rng.integers(0, 3, n), iss.I32)
    sms = jnp.asarray(rng.integers(1, 99, n), iss.I32)
    a = iss.refill_lanes(ps, take, src, smem, sprog, sms)
    b = jax.jit(iss_refill)(
        ps, take, src, smem, sprog, sms)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_resident_syncs_fewer_than_host_refill():
    """On a churny skewed fleet the resident loop makes one small stats
    read per loop iteration (each segment plus the trailing one) and
    five drain reads, and nothing else; its sync stats are populated
    sanely."""
    _, sd = run_packed(_skew_groups(), chunk=16, seg_steps=64)
    assert sd.host_syncs == sd.n_segments + 1 + 5
    assert sd.sync_wait_s >= 0.0 and sd.refill_wall_s >= 0.0
    assert len(sd.seg_schedule) == sd.n_segments


def _flat_group(n, max_steps, mems=None):
    prog = skew_program()
    if mems is None:
        mems = skew_fleet(prog, n, short_iters=8, long_iters=400,
                          long_frac=0.2, seed=13)
    return PackedGroup(code=prog.code, source=engine.array_source(mems),
                       n_items=n, max_steps=max_steps, mem_words=32,
                       out_addr=1)


def test_resident_falls_back_to_host_past_safety_bounds():
    """A plan whose n_items x max_steps passes 2^31 - 1 (a group that
    COULD retire more instructions than an int32 mix row holds), but
    whose lanes-a-shard x max_steps does not, runs on the resident loop
    and matches the same fleet under a small budget."""
    n, chunk, big = 16, 4, 2**28
    assert n * big > engine._RESIDENT_MIX_LIMIT >= chunk * big
    res, _ = run_packed([_flat_group(n, big)], chunk=chunk, seg_steps=64)
    ref, _ = run_packed([_flat_group(n, 100_000)], chunk=chunk,
                        seg_steps=64)
    assert res[0].halted.all()
    for f in ("n_instr", "n_two_stage", "halted", "out", "mix"):
        np.testing.assert_array_equal(getattr(res[0], f),
                                      getattr(ref[0], f), err_msg=f)


_MILD = FaultSpec(rate=0.0008, seed=5, targets=("regs", "mem", "pc"))


@pytest.mark.parametrize("mode", ["branchless", "pallas", "faults", "dmr",
                                  "mesh"])
def test_mix_flush_is_bit_exact(monkeypatch, mode):
    """With the mix-row bound lowered above lanes x budget but far below
    items x budget, the loop flushes the rows to the host several times
    a run, no row it reads ever passes the bound, and every result, the
    mix totals included, is bit-exact with the run that needs no
    flush."""
    n, chunk, seg, budget = 64, 16, 64, 2000
    kw = dict(chunk=chunk, seg_steps=seg, keep_state=True,
              stepper="pallas" if mode == "pallas" else "branchless",
              faults=_MILD if mode in ("faults", "dmr") else None,
              redundancy="dmr" if mode == "dmr" else "none",
              max_retries=6,
              mesh=jax.make_mesh((1,), ("fleet",)) if mode == "mesh"
              else None)
    ref, rs = run_packed([_flat_group(n, budget)], **kw)
    limit = chunk * budget + 3 * chunk * seg
    assert limit < n * budget // 3
    monkeypatch.setattr(engine, "_RESIDENT_MIX_LIMIT", limit)
    rows = []                  # the largest mix row of each read
    fetch = engine._SyncClock.fetch

    def spy(clock, x):
        out = fetch(clock, x)
        if out.ndim == 3:      # (n_shards, n_groups, 8): the mix rows
            rows.append(int(out.max()))
        return out

    monkeypatch.setattr(engine._SyncClock, "fetch", spy)
    res, st = run_packed([_flat_group(n, budget)], **kw)
    assert st.host_syncs - rs.host_syncs == len(rows) - 1 >= 3
    assert max(rows) <= limit
    assert st.n_segments == rs.n_segments
    for f in _STATE_FIELDS:
        np.testing.assert_array_equal(getattr(res[0], f),
                                      getattr(ref[0], f), err_msg=f)


def test_dmr_plan_past_the_old_mix_bound_runs():
    """A DMR plan under transient faults whose n_items x max_steps
    passes 2^31 - 1 runs on the resident loop and returns the
    fault-free answers."""
    n, chunk, big = 64, 16, 2**26
    assert n * big > engine._RESIDENT_MIX_LIMIT >= chunk * big
    gold, _ = run_packed([_flat_group(n, 100_000)], chunk=16, seg_steps=64)
    dm, ds = run_packed([_flat_group(n, big)], chunk=chunk, seg_steps=64,
                        faults=_MILD, redundancy="dmr", max_retries=6)
    assert ds.detected > 0
    for f in ("n_instr", "n_two_stage", "halted", "out", "mix"):
        np.testing.assert_array_equal(getattr(dm[0], f),
                                      getattr(gold[0], f), err_msg=f)


@pytest.mark.parametrize("bound", ["keep_state", "mix_row"])
def test_resident_refuses_plans_past_its_bounds(monkeypatch, bound):
    """keep_state past its device-row bound, and a plan in which one
    refill could wrap an int32 mix row (lanes a shard x budget past
    2^31 - 1), are refused with the bound's name."""
    if bound == "keep_state":
        monkeypatch.setattr(engine, "_RESIDENT_KEEP_STATE_WORDS", 1000)
        with pytest.raises(ValueError, match="_RESIDENT_KEEP_STATE_WORDS"):
            run_packed([_flat_group(64, 2000)], chunk=16, keep_state=True)
    else:
        with pytest.raises(ValueError, match="_RESIDENT_MIX_LIMIT"):
            run_packed([_flat_group(4, 2**30)], chunk=4)


def test_resident_single_group_stream_parity():
    """run_stream (the single-group special case) is bit-exact with the
    monolithic `iss.run_fleet`, with fixed and adaptive supersteps,
    including keep_state."""
    prog = skew_program()
    mems = skew_fleet(prog, 50, short_iters=8, long_iters=600,
                      long_frac=0.25, seed=3)
    kw = dict(n_items=50, mem_words=32, max_steps=100_000, chunk=16,
              seg_steps=64, out_addr=1, keep_state=True)
    want = _oracle(prog.code, mems, 100_000, 1)
    for adaptive in (False, True):
        r = engine.run_stream(prog.code, engine.array_source(mems),
                              adaptive=adaptive, **kw)
        for f in _STATE_FIELDS:
            np.testing.assert_array_equal(getattr(r, f), want[f],
                                          err_msg=f)


@pytest.mark.slow
def test_resident_adaptive_sharded_multi_device_bit_exact():
    """Resident + adaptive streaming under shard_map on 4 forced host
    devices stays bit-exact with the single-device run for both
    steppers — FULL final state (mems/regs/pc/mix_items included) and
    per-group stats, not just the scalar tallies — and the adaptive
    schedule is identical across reruns. Shard-locally (§9.12): staged
    buffers shard per-device via `stage_shardings` (each device gets
    only its own slice), lane fields shard, the retire scatter lands in
    per-shard `ResidentAcc` row blocks, and the per-shard retired
    counts must cover every item with ONE host sync per segment.
    """
    script = r"""
import numpy as np, jax, json
from benchmarks.fleet import skew_fleet, skew_program
from repro.fleet import engine
from repro.fleet.engine import PackedGroup, run_packed
prog = skew_program()
mems_a = skew_fleet(prog, 40, short_iters=8, long_iters=400,
                    long_frac=0.2, seed=13)
mems_b = skew_fleet(prog, 24, short_iters=16, long_iters=300,
                    long_frac=0.3, seed=14)
groups = [
    PackedGroup(code=prog.code, source=engine.array_source(mems_a),
                n_items=40, max_steps=100_000, mem_words=32, out_addr=1),
    PackedGroup(code=prog.code, source=engine.array_source(mems_b),
                n_items=24, max_steps=100_000, mem_words=32, out_addr=1),
]
FIELDS = ("n_instr", "n_two_stage", "halted", "out", "mix",
          "mems", "regs", "pc", "mix_items")
refs, _ = run_packed(groups, chunk=16, seg_steps=64, keep_state=True)
mesh = jax.make_mesh((len(jax.devices()),), ("fleet",))
for stepper in ("branchless", "pallas"):
    scheds = []
    for _ in range(2):
        res, stats = run_packed(groups, chunk=16, seg_steps=64,
                                mesh=mesh, stepper=stepper,
                                adaptive=True,
                                keep_state=True)
        assert stats.n_devices == 4, stats.n_devices
        assert stats.n_shards == 4, stats.n_shards
        assert sum(stats.shard_retired) == 64, stats.shard_retired
        assert sum(stats.shard_lane_steps) == stats.lane_steps
        assert stats.host_syncs == stats.n_segments + 1 + 9, stats
        scheds.append(stats.seg_schedule)
        for r, ref in zip(res, refs):
            assert r.n_items == ref.n_items
            assert r.n_segments > 0 and r.lane_steps > 0
            for f in FIELDS:
                np.testing.assert_array_equal(getattr(r, f),
                                              getattr(ref, f),
                                              err_msg=f"{stepper}:{f}")
    assert scheds[0] == scheds[1], (stepper, scheds)
print(json.dumps({"ok": True}))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"]
