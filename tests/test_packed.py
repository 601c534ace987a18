"""Packed multi-program fleet runtime (DESIGN.md §9.8): banked-fetch
stepper parity against per-program monolithic runs, three-way
(switch/branchless/pallas) engine packed-parity with the sequential
baseline, heterogeneous per-lane step budgets, the proportional
admission scheduler, and sharded multi-device packed streaming."""
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
from repro.flexibits import iss
from repro.flexibits.faults import FaultSpec
from repro.fleet import engine
from repro.fleet.engine import PackedGroup, _apportion, run_packed
from repro.fleet.plan import FleetGroup, FleetPlan, run_plan
from repro.kernels.iss_stepper import iss_segment_banked

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _packed_pool(workloads, pids, seed=5):
    """Interleaved lane pool: lane i runs workloads[pids[i]]."""
    n = len(pids)
    mem_words = max(w.total_mem_words for w in workloads)
    mems = np.zeros((n, mem_words), np.int32)
    ms = np.zeros(n, np.int32)
    for i, p in enumerate(pids):
        w = workloads[p]
        rng = np.random.default_rng([seed, i])
        m = w.initial_memory(w.gen_inputs(rng, 1)[0])
        mems[i, :len(m)] = m
        ms[i] = w.max_steps
    lanes = iss.ISSState(
        regs=jnp.zeros((n, 16), iss.I32), pc=jnp.zeros((n,), iss.I32),
        mem=jnp.asarray(mems), halted=jnp.zeros((n,), bool),
        n_instr=jnp.zeros((n,), iss.I32),
        n_two_stage=jnp.zeros((n,), iss.I32),
        mix=jnp.zeros((n, len(iss.MIX_CLASSES)), iss.I32),
        n_cycles=jnp.zeros((n,), iss.I32))
    ps = iss.PackedState(lanes=lanes, prog_id=jnp.asarray(pids, iss.I32),
                         max_steps=jnp.asarray(ms))
    refs = []
    for i, p in enumerate(pids):
        w = workloads[p]
        code = jnp.asarray(w.program.code.view(np.int32))
        refs.append(iss.run(code, jnp.asarray(
            mems[i, :w.total_mem_words]), w.max_steps))
    return ps, refs


def _assert_lanes_match_refs(st: iss.PackedState, refs, workloads, pids):
    for i, p in enumerate(pids):
        w = workloads[p]
        ref = refs[i]
        np.testing.assert_array_equal(
            np.asarray(st.lanes.n_instr)[i], np.asarray(ref.n_instr),
            err_msg=f"lane {i}")
        np.testing.assert_array_equal(
            np.asarray(st.lanes.n_two_stage)[i],
            np.asarray(ref.n_two_stage), err_msg=f"lane {i}")
        np.testing.assert_array_equal(
            np.asarray(st.lanes.mem)[i, :w.total_mem_words],
            np.asarray(ref.mem), err_msg=f"lane {i}")
        np.testing.assert_array_equal(
            np.asarray(st.lanes.regs)[i], np.asarray(ref.regs),
            err_msg=f"lane {i}")
        np.testing.assert_array_equal(
            np.asarray(st.lanes.mix)[i], np.asarray(ref.mix),
            err_msg=f"lane {i}")


def test_pack_programs_pads_and_measures():
    a = np.arange(3, dtype=np.uint32)
    b = np.arange(7, dtype=np.uint32)
    bank, clen = iss.pack_programs([a, b])
    assert bank.shape == (2, 7) and bank.dtype == np.int32
    np.testing.assert_array_equal(clen, [3, 7])
    np.testing.assert_array_equal(bank[0, 3:], 0)   # padding
    np.testing.assert_array_equal(bank[1], b.view(np.int32))


def test_fetch_banked_clamps_per_program():
    """A pc past a short program's end fetches that program's OWN last
    word (jax clamp-on-read against the row's code_len), never the
    bank's padding or another row."""
    bank, clen = iss.pack_programs(
        [np.array([10, 11], np.uint32), np.array([20, 21, 22], np.uint32)])
    bank_j, clen_j = jnp.asarray(bank), jnp.asarray(clen)
    got = jax.jit(iss.fetch_banked)(
        bank_j, clen_j, jnp.asarray([0, 0, 1], iss.I32),
        jnp.asarray([4, 8, 11 * 4], iss.I32))
    np.testing.assert_array_equal(np.asarray(got), [11, 11, 22])


@pytest.mark.parametrize("mode", ["branchless", "pallas", "switch"])
def test_banked_segments_match_per_program_monolithic(mode):
    """Interleaved lanes running two different workloads from one bank
    retire exactly what each lane's own single-program `iss.run` does —
    for all three banked segment steppers."""
    workloads = (get("WQ"), get("MC"))
    pids = [i % 2 for i in range(8)]
    ps, refs = _packed_pool(workloads, pids)
    bank_np, clen_np = iss.pack_programs(
        [w.program.code for w in workloads])
    bank, clen = jnp.asarray(bank_np), jnp.asarray(clen_np)
    sub = frozenset().union(
        *(iss.opcode_subset(w.program.code) for w in workloads))

    if mode == "branchless":
        seg = jax.jit(lambda b, c, s: iss.run_segment_lanes_banked(
            b, c, s, 64, sub))
    elif mode == "pallas":
        seg = jax.jit(lambda b, c, s: iss_segment_banked(
            b, c, s, seg_steps=64, subset=sub))
    else:
        seg = jax.jit(lambda b, c, s: iss.PackedState(
            lanes=jax.vmap(lambda p, m, l: iss.run_segment_banked(
                b, c, p, m, l, 64))(s.prog_id, s.max_steps, s.lanes),
            prog_id=s.prog_id, max_steps=s.max_steps))

    st = ps
    for _ in range(10_000):
        st = seg(bank, clen, st)
        if bool(np.asarray(st.lanes.halted).all()):
            break
    _assert_lanes_match_refs(st, refs, workloads, pids)


@pytest.mark.parametrize("stepper", ["switch", "branchless", "pallas"])
def test_packed_engine_bit_exact_with_sequential(stepper):
    """run_packed demuxes per-group results bit-exactly equal to what
    run_stream produces for each group alone — full final state, per-item
    tallies, and outputs — for all three steppers."""
    specs = (("WQ", 1, 40), ("MC", 2, 17))
    groups = []
    for key, seed, n in specs:
        w = get(key)
        groups.append(PackedGroup(
            code=w.program.code, source=engine.workload_source(w, seed),
            n_items=n, max_steps=w.max_steps,
            mem_words=w.total_mem_words, out_addr=w.out_addr))
    res, stats = run_packed(groups, chunk=16, seg_steps=128,
                            keep_state=True, stepper=stepper)
    assert stats.n_groups == 2 and stats.chunk == 16
    for (key, seed, n), r in zip(specs, res):
        w = get(key)
        ref = engine.run_workload_stream(
            w, n, seed=seed, chunk=16, seg_steps=128, keep_state=True,
            stepper=stepper)
        np.testing.assert_array_equal(r.n_instr, ref.n_instr)
        np.testing.assert_array_equal(r.n_two_stage, ref.n_two_stage)
        np.testing.assert_array_equal(r.halted, ref.halted)
        np.testing.assert_array_equal(r.out, ref.out)
        np.testing.assert_array_equal(r.mix, ref.mix)
        np.testing.assert_array_equal(r.mems, ref.mems)
        np.testing.assert_array_equal(r.regs, ref.regs)
        np.testing.assert_array_equal(r.pc, ref.pc)
        np.testing.assert_array_equal(r.mix_items, ref.mix_items)
        assert r.stepper == stepper and r.halted.all()
        # the demuxed outputs also match the functional reference
        src = engine.workload_source(w, seed)(0, n)
        np.testing.assert_array_equal(r.out, w.ref(src[:, :w.n_inputs]))


def test_packed_plan_report_matches_sequential():
    """run_plan(packed=True) reports the same per-group carbon numbers
    (to the bit — same floats) as the sequential baseline, plus packed
    whole-run stats."""
    groups = (
        FleetGroup(workload="WQ", core="SERV", n_items=40, seed=1),
        FleetGroup(workload="MC", core="HERV", n_items=24, seed=2),
    )
    rep_p = run_plan(FleetPlan(groups=groups, chunk=16, seg_steps=128))
    rep_s = run_plan(FleetPlan(groups=groups, chunk=16, seg_steps=128,
                               packed=False))
    assert rep_p.packed is not None and rep_p.packed.n_groups == 2
    assert rep_s.packed is None
    for a, b in zip(rep_p.groups, rep_s.groups):
        np.testing.assert_array_equal(a.result.n_instr, b.result.n_instr)
        np.testing.assert_array_equal(a.result.mix, b.result.mix)
        assert a.profile == b.profile
        assert a.energy_j_per_exec == b.energy_j_per_exec
        assert a.operational_kg == b.operational_kg
        assert a.embodied_kg == b.embodied_kg
        assert a.total_kg == b.total_kg
        assert a.recommended_core == b.recommended_core
    assert "packed runtime: 2 groups" in rep_p.format()


def test_packed_heterogeneous_step_budgets():
    """Groups with different max_steps in ONE pool: each budget-exhausted
    item retires with n_instr == its OWN group's budget and halted=False,
    exactly as in its group's sequential run."""
    prog = skew_program()
    mems_a = skew_fleet(prog, 12, short_iters=4, long_iters=5000,
                        long_frac=0.5, seed=2)
    mems_b = skew_fleet(prog, 12, short_iters=4, long_iters=5000,
                        long_frac=0.5, seed=3)
    groups = [
        PackedGroup(code=prog.code, source=engine.array_source(mems_a),
                    n_items=12, max_steps=200, mem_words=32, out_addr=1),
        PackedGroup(code=prog.code, source=engine.array_source(mems_b),
                    n_items=12, max_steps=350, mem_words=32, out_addr=1),
    ]
    res, _ = run_packed(groups, chunk=8, seg_steps=64)
    for r, mems, budget in ((res[0], mems_a, 200), (res[1], mems_b, 350)):
        long_items = mems[:, 0] == 5000
        assert (~r.halted[long_items]).all()
        assert r.halted[~long_items].all()
        assert (r.n_instr[long_items] == budget).all()


def test_apportion_is_proportional_and_exact():
    """The admission split is deterministic, integral, never exceeds a
    group's backlog, and hands out exactly min(slots, total) lanes."""
    cases = [
        (10, [1, 100]), (100, [2, 2, 100]), (90, [1, 1, 1, 97]),
        (5, [2, 4]), (6, [1, 5]), (3, [0, 0, 7]), (7, [3, 3]),
        (0, [4, 4]), (16, [0, 0, 0]), (128, [1024, 128, 64, 64]),
    ]
    for slots, rem in cases:
        take = _apportion(slots, rem)
        assert take.sum() == min(slots, sum(rem)), (slots, rem, take)
        assert (take <= np.asarray(rem)).all(), (slots, rem, take)
        assert (take >= 0).all()
        np.testing.assert_array_equal(take, _apportion(slots, rem))
    # proportionality: the big group gets the lion's share
    take = _apportion(128, [1024, 128, 64, 64])
    assert take[0] > take[1] > 0 and take[2] > 0 and take[3] > 0


def test_packed_scheduler_beats_sequential_drain_on_skew():
    """On 8x-skewed group sizes with within-group halt-time skew, the
    packed stream needs fewer segments and fewer lane-step slots than
    draining the groups sequentially (freed lanes are backfilled from
    other groups instead of idling through each group's tail)."""
    prog = skew_program()
    sizes = (128, 16, 16)
    groups = []
    seq_segments = 0
    seq_lane_steps = 0
    for gi, n in enumerate(sizes):
        mems = skew_fleet(prog, n, short_iters=8, long_iters=1500,
                          long_frac=0.15, seed=31 + gi)
        g = PackedGroup(code=prog.code, source=engine.array_source(mems),
                        n_items=n, max_steps=100_000, mem_words=32,
                        out_addr=1)
        groups.append(g)
        r = engine.run_stream(prog.code, engine.array_source(mems),
                              n_items=n, mem_words=32, max_steps=100_000,
                              chunk=16, seg_steps=64, out_addr=1)
        seq_segments += r.n_segments
        seq_lane_steps += r.lane_steps
    _, stats = run_packed(groups, chunk=16, seg_steps=64)
    assert stats.n_segments < seq_segments, (stats.n_segments,
                                             seq_segments)
    assert stats.lane_steps < seq_lane_steps, (stats.lane_steps,
                                               seq_lane_steps)


@pytest.mark.parametrize("stepper", ["switch", "branchless", "pallas"])
def test_packed_preserves_oob_memory_semantics_per_group(stepper):
    """Data-memory out-of-range semantics are per-GROUP, not per-pool:
    a lane of a small-memory group packed next to a larger-memory group
    still clamps reads to ITS OWN last word and drops ITS OWN
    out-of-range stores (the data-port analogue of fetch_banked's
    per-program pc clamp), so even OOB-touching programs stay bit-exact
    with their sequential baseline."""
    from repro.flexibits.asm import Asm

    a = Asm(vm_reserved=32)
    a.li(a.t0, 99)
    a.sw(a.t0, a.zero, 80)    # word 20: OOB for an 8-word memory
    a.lw(a.t1, a.zero, 80)    # OOB load
    a.sw(a.t1, a.zero, 4)     # out at word 1
    a.halt()
    prog = a.assemble()

    def source(mem_words):
        mem = np.zeros((1, mem_words), np.int32)
        mem[0, :len(prog.initial_memory(mem_words))] = \
            prog.initial_memory(mem_words)
        mem[0, 7] = 1234          # sentinel at the small memory's last word
        return engine.array_source(mem)

    groups = [
        PackedGroup(code=prog.code, source=source(8), n_items=1,
                    max_steps=100, mem_words=8, out_addr=1),
        PackedGroup(code=prog.code, source=source(32), n_items=1,
                    max_steps=100, mem_words=32, out_addr=1),
    ]
    res, _ = run_packed(groups, chunk=2, seg_steps=16, stepper=stepper)
    for g in groups:
        ref = engine.run_stream(g.code, g.source, n_items=1,
                                mem_words=g.mem_words, max_steps=100,
                                chunk=1, seg_steps=16, out_addr=1,
                                stepper=stepper)
        r = res[0] if g.mem_words == 8 else res[1]
        np.testing.assert_array_equal(r.out, ref.out)
        np.testing.assert_array_equal(r.n_instr, ref.n_instr)
    # word 20 is OOB for the 8-word group: its store DROPS and its load
    # clamps to word 7's sentinel; for the 32-word group the same
    # addresses are in range, so the stored 99 reads back
    assert res[0].out[0] == 1234
    assert res[1].out[0] == 99


def test_run_packed_rejects_bad_args():
    prog = skew_program()
    g = PackedGroup(code=prog.code,
                    source=engine.array_source(np.zeros((4, 32), np.int32)),
                    n_items=4, max_steps=100, mem_words=32)
    with pytest.raises(ValueError):
        run_packed([])
    with pytest.raises(ValueError):
        run_packed([g], seg_steps=0)
    with pytest.raises(ValueError):
        run_packed([g], stepper="vliw")


# ---- the engine's choice of stepper ------------------------------------

_MILD = FaultSpec(rate=0.0008, seed=5, targets=("regs", "mem", "pc"))


def _skew_group(n=12):
    prog = skew_program()
    return PackedGroup(code=prog.code,
                       source=engine.array_source(skew_fleet(prog, n,
                                                             long_iters=300)),
                       n_items=n, max_steps=100_000, mem_words=32,
                       out_addr=1)


def _spy_runners(monkeypatch, backend):
    """`jax.default_backend()` reads `backend`, and both runner factories
    record what the engine asked of them — the segment stepper and the
    Pallas refill swap — and build the branchless and jnp runners, so no
    Pallas kernel runs (none could be compiled here for a TPU)."""
    asked = {"stepper": set(), "pallas_swap": set()}
    seg, refill = (engine._packed_segment_runner,
                   engine._resident_refill_runner)

    def seg_spy(stepper, *args):
        asked["stepper"].add(stepper)
        return seg("branchless", *args)

    def refill_spy(mesh, mem_words, n_groups, keep_state, use_pallas,
                   *args):
        asked["pallas_swap"].add(use_pallas)
        return refill(mesh, mem_words, n_groups, keep_state, False, *args)

    monkeypatch.setattr(engine.jax, "default_backend", lambda: backend)
    monkeypatch.setattr(engine, "_packed_segment_runner", seg_spy)
    monkeypatch.setattr(engine, "_resident_refill_runner", refill_spy)
    return asked


def _run_reports(groups, **kw):
    """Run and return the stepper every report names, checking they
    agree: `PackedStats.stepper` and each `FleetResult.stepper`."""
    res, stats = run_packed(groups, chunk=8, seg_steps=64, **kw)
    assert {r.stepper for r in res} == {stats.stepper}
    return stats.stepper


@pytest.mark.parametrize("with_mesh", [False, True], ids=["single", "mesh"])
def test_fault_free_default_on_tpu_is_pallas(monkeypatch, with_mesh):
    """On a TPU a fault-free run takes the fused Pallas stepper, with and
    without a mesh; the Pallas refill swap goes with it on one device
    only. A rate-0 schedule is no schedule."""
    mesh = jax.make_mesh((1,), ("fleet",)) if with_mesh else None
    asked = _spy_runners(monkeypatch, "tpu")
    assert _run_reports([_skew_group()], mesh=mesh) == "pallas"
    assert _run_reports([_skew_group()], mesh=mesh,
                        faults=FaultSpec(rate=0.0, seed=3)) == "pallas"
    assert asked == {"stepper": {"pallas"}, "pallas_swap": {not with_mesh}}


@pytest.mark.parametrize("redundancy", ["none", "dmr"])
def test_faulted_default_on_tpu_is_branchless(monkeypatch, redundancy):
    """A fault schedule, with or without DMR, keeps a TPU run on the
    branchless stepper: the Pallas fault transform does not compile
    there."""
    asked = _spy_runners(monkeypatch, "tpu")
    assert _run_reports([_skew_group()], faults=_MILD,
                        redundancy=redundancy, max_retries=6) \
        == "branchless"
    assert asked == {"stepper": {"branchless"}, "pallas_swap": {False}}


@pytest.mark.parametrize("faulted", [False, True], ids=["clean", "faults"])
@pytest.mark.parametrize("redundancy", ["none", "dmr"])
def test_default_off_tpu_is_branchless(faulted, redundancy):
    """On the CPU every default is the branchless stepper: the kernel
    would run interpreted."""
    assert _run_reports([_skew_group()],
                        faults=_MILD if faulted else None,
                        redundancy=redundancy, max_retries=6) \
        == "branchless"


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("faulted", [False, True], ids=["clean", "faults"])
@pytest.mark.parametrize("stepper", engine.STEPPERS)
def test_explicit_stepper_wins(monkeypatch, backend, faulted, stepper):
    """A named stepper runs whatever the backend and the schedule, but
    for the one combination the engine refuses: faults on the Pallas
    stepper on a TPU."""
    asked = _spy_runners(monkeypatch, backend)
    kw = dict(stepper=stepper, faults=_MILD if faulted else None)
    if faulted and stepper == "pallas" and backend == "tpu":
        with pytest.raises(ValueError, match="stepper='branchless'"):
            _run_reports([_skew_group()], **kw)
        return
    assert _run_reports([_skew_group()], **kw) == stepper
    assert asked["stepper"] == {stepper}


@pytest.mark.parametrize("packed", [True, False],
                         ids=["packed", "sequential"])
def test_plan_reports_the_stepper_that_ran(monkeypatch, packed):
    """`FleetPlan.stepper` None hands the choice to the engine on both
    runtimes, and the report's results name the stepper that ran."""
    _spy_runners(monkeypatch, "tpu")
    groups = (FleetGroup(workload="WQ", core="QERV", n_items=8, seed=1),)
    for faults, want in ((None, "pallas"), (_MILD, "branchless")):
        plan = FleetPlan(groups=groups, chunk=8, seg_steps=128,
                         packed=packed, faults=faults)
        rep = run_plan(plan)
        assert plan.stepper is None
        assert {g.result.stepper for g in rep.groups} == {want}
        if packed:
            assert rep.packed.stepper == want


@pytest.mark.slow
def test_packed_sharded_multi_device_bit_exact():
    """Packed streaming under shard_map on 4 forced host devices stays
    bit-exact with the sequential per-group baseline, for all three
    steppers (lane fields prog_id/max_steps shard over the mesh; the
    bank replicates)."""
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
refs = [engine.run_stream(g.code, g.source, n_items=g.n_items,
                          mem_words=32, max_steps=100_000, chunk=16,
                          seg_steps=64, out_addr=1) for g in groups]
mesh = jax.make_mesh((len(jax.devices()),), ("fleet",))
for stepper in ("branchless", "pallas", "switch"):
    res, stats = run_packed(groups, chunk=16, seg_steps=64, mesh=mesh,
                            stepper=stepper)
    assert stats.n_devices == 4, stats.n_devices
    for r, ref in zip(res, refs):
        np.testing.assert_array_equal(r.n_instr, ref.n_instr)
        np.testing.assert_array_equal(r.out, ref.out)
        np.testing.assert_array_equal(r.mix, ref.mix)
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
