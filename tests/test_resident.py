"""Resident packed runtime (DESIGN.md §9.9): on-device refill parity
with the PR-4 host-refill baseline (full state, three steppers), the
banked Pallas refill swap, adaptive-superstep determinism and
bit-exactness, sync-stats accounting, and the 4-device shard_map path."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.fleet import skew_fleet, skew_program
from repro.fleet import engine
from repro.fleet.engine import (PackedGroup, _SuperstepController,
                                run_packed)
from repro.fleet.plan import FleetGroup, FleetPlan, run_plan
from repro.flexibits import iss
from repro.kernels.iss_stepper import iss_refill

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_STATE_FIELDS = ("n_instr", "n_two_stage", "halted", "out", "mix",
                 "mems", "regs", "pc", "mix_items")


def _skew_groups(n_a=40, n_b=24, max_steps_b=100_000):
    prog = skew_program()
    mems_a = skew_fleet(prog, n_a, short_iters=8, long_iters=400,
                        long_frac=0.2, seed=13)
    mems_b = skew_fleet(prog, n_b, short_iters=16, long_iters=300,
                        long_frac=0.3, seed=14)
    return [
        PackedGroup(code=prog.code, source=engine.array_source(mems_a),
                    n_items=n_a, max_steps=100_000, mem_words=32,
                    out_addr=1),
        PackedGroup(code=prog.code, source=engine.array_source(mems_b),
                    n_items=n_b, max_steps=max_steps_b, mem_words=32,
                    out_addr=1),
    ]


@pytest.mark.parametrize("stepper", ["switch", "branchless", "pallas"])
def test_resident_bit_exact_with_host_refill(stepper):
    """Full-state parity: the resident runtime retires, demuxes, and
    keeps final state bit-exactly equal to the host-refill baseline —
    including a group whose budget, not halting, ends its items."""
    groups = _skew_groups(max_steps_b=200)
    host, _ = run_packed(groups, chunk=16, seg_steps=64, keep_state=True,
                         refill="host", stepper=stepper)
    res, stats = run_packed(groups, chunk=16, seg_steps=64,
                            keep_state=True, refill="device",
                            stepper=stepper)
    assert stats.refill == "device"
    for a, b in zip(host, res):
        for f in _STATE_FIELDS:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                          err_msg=f)
        assert not a.halted[a.n_instr == 200].any()   # budget-exhausted


def test_resident_plan_report_matches_host_refill():
    """run_plan floats (carbon, energy, profiles) are identical between
    the resident and host-refill loops — the demux feeds the same
    report path bit-for-bit."""
    groups = (
        FleetGroup(workload="WQ", core="SERV", n_items=40, seed=1),
        FleetGroup(workload="MC", core="HERV", n_items=24, seed=2),
    )
    rep_d = run_plan(FleetPlan(groups=groups, chunk=16, seg_steps=128))
    rep_h = run_plan(FleetPlan(groups=groups, chunk=16, seg_steps=128,
                               refill="host"))
    assert rep_d.packed.refill == "device"
    assert rep_h.packed.refill == "host"
    for a, b in zip(rep_d.groups, rep_h.groups):
        np.testing.assert_array_equal(a.result.n_instr, b.result.n_instr)
        np.testing.assert_array_equal(a.result.mix, b.result.mix)
        assert a.profile == b.profile
        assert a.energy_j_per_exec == b.energy_j_per_exec
        assert a.total_kg == b.total_kg
    assert "sync stats (device-refill)" in rep_d.format()


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
    """On a churny skewed fleet the resident loop performs strictly
    fewer blocking host syncs (one small stats read per segment + one
    drain) than the host-refill loop (done-count scalar per segment +
    O(done) harvest pulls per finishing segment), and its sync stats
    are populated sanely."""
    _, sh = run_packed(_skew_groups(), chunk=16, seg_steps=64,
                       refill="host")
    _, sd = run_packed(_skew_groups(), chunk=16, seg_steps=64,
                       refill="device")
    assert sd.host_syncs < sh.host_syncs, (sd.host_syncs, sh.host_syncs)
    # one stats read per iteration (segments + trailing) + 5 drain pulls
    assert sd.host_syncs == sd.n_segments + 1 + 5
    for s in (sh, sd):
        assert s.sync_wait_s >= 0.0 and s.refill_wall_s >= 0.0
        assert len(s.seg_schedule) == s.n_segments


def test_run_packed_rejects_bad_refill():
    groups = _skew_groups()
    with pytest.raises(ValueError):
        run_packed(groups, refill="telepathy")


def test_resident_falls_back_to_host_past_safety_bounds():
    """Past the int32 mix-counter bound (a group that COULD retire 2^31
    instructions) or the keep_state device-row budget, the engine runs
    the host loop instead of overflowing/allocating silently — and says
    so in PackedStats.refill."""
    prog = skew_program()
    mems = skew_fleet(prog, 4, short_iters=4, long_iters=8,
                      long_frac=0.5, seed=1)
    big_budget = PackedGroup(code=prog.code,
                             source=engine.array_source(mems), n_items=4,
                             max_steps=2**30, mem_words=32, out_addr=1)
    res, stats = run_packed([big_budget], chunk=4, seg_steps=32)
    assert stats.refill == "host"
    assert res[0].halted.all()
    # a same-shape run under the bound stays resident
    ok = PackedGroup(code=prog.code, source=engine.array_source(mems),
                     n_items=4, max_steps=100_000, mem_words=32,
                     out_addr=1)
    _, stats = run_packed([ok], chunk=4, seg_steps=32)
    assert stats.refill == "device"


def test_resident_single_group_stream_parity():
    """run_stream (the single-group special case) is bit-exact between
    the resident and host-refill loops, including keep_state."""
    prog = skew_program()
    mems = skew_fleet(prog, 50, short_iters=8, long_iters=600,
                      long_frac=0.25, seed=3)
    kw = dict(n_items=50, mem_words=32, max_steps=100_000, chunk=16,
              seg_steps=64, out_addr=1, keep_state=True)
    a = engine.run_stream(prog.code, engine.array_source(mems),
                          refill="host", **kw)
    b = engine.run_stream(prog.code, engine.array_source(mems),
                          refill="device", **kw)
    c = engine.run_stream(prog.code, engine.array_source(mems),
                          refill="device", adaptive=True, **kw)
    for f in _STATE_FIELDS:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f)
        np.testing.assert_array_equal(getattr(a, f), getattr(c, f),
                                      err_msg=f)


@pytest.mark.slow
def test_resident_adaptive_sharded_multi_device_bit_exact():
    """Resident + adaptive streaming under shard_map on 4 forced host
    devices stays bit-exact with the host-refill baseline for all three
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
refs, _ = run_packed(groups, chunk=16, seg_steps=64, refill="host",
                     keep_state=True)
mesh = jax.make_mesh((len(jax.devices()),), ("fleet",))
for stepper in ("branchless", "pallas", "switch"):
    scheds = []
    for _ in range(2):
        res, stats = run_packed(groups, chunk=16, seg_steps=64,
                                mesh=mesh, stepper=stepper,
                                refill="device", adaptive=True,
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
