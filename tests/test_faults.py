"""FlexiFault tests (DESIGN.md §9.14): deterministic counter-based fault
injection bit-identical across all three steppers and the PyISS
FaultOracle, rate-0 / faults=None bit-exactness with the fault-free
engine, DMR detect/rollback/quarantine recovery end-to-end, the
consecutive-retry quarantine semantics, golden-vs-faulty rate
measurement, redundancy-aware planner reproduction at rate 0, and the
FleetPlan wiring + resilience pricing."""
import hashlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.fleet import skew_fleet, skew_program
from repro.flexibits import faults, iss, pyiss
from repro.fleet import engine
from repro.kernels import iss_stepper as ks

_STATE_FIELDS = ("regs", "pc", "mem", "halted", "n_instr")
_RESULT_FIELDS = ("n_instr", "halted", "out", "mems", "regs", "pc")


def _fleet(n=8, seed=0):
    prog = skew_program()
    code = np.asarray(prog.code, np.uint32)
    mems = np.tile(prog.initial_memory(32), (n, 1))
    mems[:, 0] = np.random.default_rng(seed).integers(5, 60, size=n)
    return code, mems


def _group(code, mems, max_steps=400):
    return engine.PackedGroup(code=code, source=engine.array_source(mems),
                              n_items=len(mems), max_steps=max_steps,
                              mem_words=mems.shape[1], out_addr=1)


# ---- stepper-level identity -------------------------------------------


def test_faulty_trajectories_bit_identical_and_match_oracle():
    """A nonzero schedule produces BIT-IDENTICAL faulty trajectories on
    the branchless, lax.switch, and Pallas steppers, and each lane
    matches the PyISS FaultOracle exactly — the §9.13 counter-seeding
    discipline applied to corruption."""
    code, mems = _fleet(8)
    MAX = 400
    spec = faults.FaultSpec(rate=0.05, seed=3,
                            targets=("regs", "mem", "pc"))
    keys = faults.lane_keys(spec.seed, len(mems))
    kj, ej = jnp.asarray(keys), jnp.zeros(len(mems), jnp.int32)
    codej = jnp.asarray(code.view(np.int32))
    states = jax.vmap(lambda m: iss.init_state(m))(jnp.asarray(mems))

    out_b = iss.run_segment_lanes(codej, states, seg_steps=MAX,
                                  max_steps=MAX, faults=spec,
                                  lane_key=kj, epoch=ej)

    def run_switch(mem, k, e):
        def body(st):
            return iss.step(codej, st, faults=spec, lane_key=k, epoch=e)
        return lax.while_loop(
            lambda st: (~st.halted) & (st.n_instr < MAX), body,
            iss.init_state(mem))

    out_s = jax.vmap(run_switch)(jnp.asarray(mems), kj, ej)
    out_p = ks.iss_segment(codej, states, seg_steps=MAX, max_steps=MAX,
                           faults=spec, lane_key=kj, epoch=ej)
    for name, out in (("switch", out_s), ("pallas", out_p)):
        for f in _STATE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(out_b, f)),
                np.asarray(getattr(out, f)), err_msg=f"{name}.{f}")

    fired = 0
    for i in range(len(mems)):
        p = pyiss.PyISS(code, mems.shape[1], init_mem=mems[i])
        o = faults.FaultOracle(spec, int(keys[i]))
        p.post_commit = o
        p.run(MAX)
        fired += o.fired
        np.testing.assert_array_equal(
            np.asarray(out_b.regs[i]),
            np.array([np.int32(r) for r in p.regs]), err_msg=f"lane {i}")
        assert int(out_b.pc[i]) == np.int32(p.pc & 0xFFFFFFFF), i
        np.testing.assert_array_equal(
            np.asarray(out_b.mem[i], np.int64),
            np.asarray(p.mem, np.int64), err_msg=f"lane {i}")
        assert int(out_b.n_instr[i]) == p.n_instr, i
    assert fired > 0, "schedule never fired — the test proved nothing"


def test_rate_zero_bit_exact_with_faults_off():
    """rate=0 keeps the injection graph compiled in but must remain
    bit-exact with `faults=None` (every mask is all-false)."""
    code, mems = _fleet(8)
    codej = jnp.asarray(code.view(np.int32))
    states = jax.vmap(lambda m: iss.init_state(m))(jnp.asarray(mems))
    kw = dict(seg_steps=400, max_steps=400)
    off = iss.run_segment_lanes(codej, states, **kw)
    zero = iss.run_segment_lanes(
        codej, states, faults=faults.FaultSpec(rate=0.0),
        lane_key=jnp.asarray(faults.lane_keys(0, len(mems))),
        epoch=jnp.zeros(len(mems), jnp.int32), **kw)
    for f in _STATE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(off, f)),
                                      np.asarray(getattr(zero, f)),
                                      err_msg=f)


@pytest.mark.parametrize("mode", ["stuck", "dead"])
def test_defect_modes_bit_identical(mode):
    """stuck-at and dead-lane defects recur by construction (keyed
    below the epoch) and stay stepper- and oracle-identical."""
    code, mems = _fleet(8)
    sp = faults.FaultSpec(rate=1.0, seed=1, mode=mode)
    keys = faults.lane_keys(sp.seed, len(mems))
    kj, ej = jnp.asarray(keys), jnp.zeros(len(mems), jnp.int32)
    codej = jnp.asarray(code.view(np.int32))
    states = jax.vmap(lambda m: iss.init_state(m))(jnp.asarray(mems))
    kw = dict(seg_steps=400, max_steps=400, faults=sp, lane_key=kj,
              epoch=ej)
    ob = iss.run_segment_lanes(codej, states, **kw)
    op = ks.iss_segment(codej, states, **kw)
    for f in _STATE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(ob, f)),
                                      np.asarray(getattr(op, f)),
                                      err_msg=f)
    p = pyiss.PyISS(code, mems.shape[1], init_mem=mems[0])
    p.post_commit = faults.FaultOracle(sp, int(keys[0]))
    p.run(400)
    np.testing.assert_array_equal(
        np.asarray(ob.regs[0]), np.array([np.int32(r) for r in p.regs]))
    assert int(ob.n_instr[0]) == p.n_instr


# ---- packed engine ----------------------------------------------------


def test_packed_rate_zero_bit_exact_with_pre_fault_engine():
    code, mems = _fleet(40)
    gold, _ = engine.run_packed([_group(code, mems)], chunk=16,
                                seg_steps=64, keep_state=True)
    z, _ = engine.run_packed([_group(code, mems)], chunk=16, seg_steps=64,
                             keep_state=True,
                             faults=faults.FaultSpec(rate=0.0))
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(z[0], f), err_msg=f)


def test_packed_faulty_run_deterministic_and_stepper_identical():
    """A nonzero schedule is (1) reproducible run-to-run, (2) actually
    corrupting, and (3) bit-identical across the three steppers at the
    same (chunk, seg_steps) — faults are a function of the schedule,
    not of the execution strategy."""
    code, mems = _fleet(40)
    spec = faults.FaultSpec(rate=0.02, seed=5,
                            targets=("regs", "mem", "pc"))
    kw = dict(chunk=16, seg_steps=64, keep_state=True, faults=spec)
    gold, _ = engine.run_packed([_group(code, mems)], chunk=16,
                                seg_steps=64, keep_state=True)
    fb, _ = engine.run_packed([_group(code, mems)], **kw)
    fb2, _ = engine.run_packed([_group(code, mems)], **kw)
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(fb[0], f),
                                      getattr(fb2[0], f), err_msg=f)
    assert not np.array_equal(fb[0].mems, gold[0].mems), \
        "schedule never corrupted anything"
    for st in ("pallas", "switch"):
        fs, _ = engine.run_packed([_group(code, mems)], stepper=st, **kw)
        for f in _RESULT_FIELDS:
            np.testing.assert_array_equal(getattr(fb[0], f),
                                          getattr(fs[0], f),
                                          err_msg=f"{st}.{f}")


@pytest.mark.parametrize("stepper", ["branchless", "pallas", "switch"])
def test_dmr_recovers_golden_results(stepper):
    """DMR + retry recovers every detectable fault end-to-end: the
    drained results are bit-exact with the fault-free run."""
    code, mems = _fleet(40)
    gold, _ = engine.run_packed([_group(code, mems)], chunk=16,
                                seg_steps=64, keep_state=True)
    mild = faults.FaultSpec(rate=0.0008, seed=5,
                            targets=("regs", "mem", "pc"))
    dm, ds = engine.run_packed([_group(code, mems)], chunk=32,
                               seg_steps=64, keep_state=True,
                               faults=mild, redundancy="dmr",
                               max_retries=6, stepper=stepper)
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(dm[0], f), err_msg=f)
    assert ds.detected > 0 and ds.corrected > 0
    assert ds.corrected <= ds.detected


def test_dmr_fault_free_is_pure_overhead():
    code, mems = _fleet(40)
    gold, _ = engine.run_packed([_group(code, mems)], chunk=16,
                                seg_steps=64, keep_state=True)
    d0, d0s = engine.run_packed([_group(code, mems)], chunk=32,
                                seg_steps=64, keep_state=True,
                                redundancy="dmr")
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(d0[0], f), err_msg=f)
    assert d0s.detected == 0 and d0s.corrected == 0
    assert d0s.quarantined == 0


def test_dmr_dead_lanes_quarantine_and_backfill():
    """Dead-lane defects recur on retry, so the pair quarantines and
    its item is re-admitted on a healthy pair — results still golden."""
    code, mems = _fleet(40)
    gold, _ = engine.run_packed([_group(code, mems)], chunk=16,
                                seg_steps=64, keep_state=True)
    dead = faults.FaultSpec(rate=0.3, seed=5, mode="dead")
    dq, dqs = engine.run_packed([_group(code, mems)], chunk=32,
                                seg_steps=64, keep_state=True,
                                faults=dead, redundancy="dmr",
                                max_retries=1)
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(dq[0], f), err_msg=f)
    assert dqs.quarantined > 0


def test_dmr_long_items_accrue_transients_without_quarantine():
    """Regression: the retry counter must count CONSECUTIVE mismatching
    boundaries, resetting on every clean one. An item spanning ~100+
    segments legitimately accrues many independent transients over its
    lifetime; a lifetime-cumulative counter quarantined every pair and
    starved the pool (the bug showed up first on the CT workload's
    ~51k-instruction items)."""
    prog = skew_program()
    mems = skew_fleet(prog, 16, short_iters=64, long_iters=1500,
                      long_frac=0.5, seed=7)
    g = engine.PackedGroup(code=prog.code,
                           source=engine.array_source(mems), n_items=16,
                           max_steps=100_000, mem_words=32, out_addr=1)
    gold, _ = engine.run_packed([g], chunk=16, seg_steps=64,
                                keep_state=True)
    g2 = engine.PackedGroup(code=prog.code,
                            source=engine.array_source(mems), n_items=16,
                            max_steps=100_000, mem_words=32, out_addr=1)
    mild = faults.FaultSpec(rate=0.0008, seed=5,
                            targets=("regs", "mem", "pc"))
    dm, ds = engine.run_packed([g2], chunk=16, seg_steps=64,
                               keep_state=True, faults=mild,
                               redundancy="dmr", max_retries=6)
    # many independent detections, zero quarantines, golden results
    assert ds.detected > 10, ds.detected
    assert ds.quarantined == 0, ds.quarantined
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(dm[0], f), err_msg=f)


def _timed_group(code, mems):
    from repro.flexibits.cycles import CORES, cost_row
    return engine.PackedGroup(code=code, source=engine.array_source(mems),
                              n_items=len(mems), max_steps=400,
                              mem_words=mems.shape[1], out_addr=1,
                              cost=cost_row(CORES["HERV"], dynamic=True))


_TALLY_FIELDS = _RESULT_FIELDS + ("n_two_stage", "n_cycles", "mix",
                                  "mix_items")


@pytest.mark.parametrize("tally", ["n_two_stage", "n_cycles", "mix"])
@pytest.mark.parametrize("stepper", ["branchless", "pallas", "switch"])
def test_dmr_rolls_back_a_tally_only_corruption(monkeypatch, stepper,
                                                tally):
    """A corruption that leaves regs, pc, memory and n_instr alone and
    changes only what the item reports (its two-stage, cycle or mix
    tally), planted in the primary lane of one pair after the first
    segment, is caught by the pair compare and rolled back: the drain
    equals the fault-free one."""
    code, mems = _fleet(40)
    gold, _ = engine.run_packed([_timed_group(code, mems)], chunk=16,
                                seg_steps=64, keep_state=True)
    real = engine._packed_segment_runner
    planted = []

    def runner(*args):
        seg = real(*args)

        def plant(*a):
            state, *rest = seg(*a)         # DMR: (state, snap, ...)
            if planted:
                return (state, *rest)
            planted.append(True)
            lanes = state.lanes
            v = getattr(lanes, tally)
            v = v.at[0, 0].add(1) if v.ndim == 2 else v.at[0].add(1)
            return (state._replace(lanes=lanes._replace(**{tally: v})),
                    *rest)
        return plant

    monkeypatch.setattr(engine, "_packed_segment_runner", runner)
    dm, ds = engine.run_packed([_timed_group(code, mems)], chunk=32,
                               seg_steps=64, keep_state=True,
                               redundancy="dmr", stepper=stepper)
    assert planted
    assert ds.detected >= 1 and ds.corrected >= 1
    for f in _TALLY_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(dm[0], f), err_msg=f)


def _boundary_discards(monkeypatch):
    """Record, at each DMR boundary, n_instr - snapshot n_instr summed
    over both lanes of every pair the boundary rolled back (its retry
    count went up by one) or quarantined (its quarantine flag went
    up), read from the op's own inputs and outputs."""
    real = engine._resident_refill_runner
    want = []

    def runner(*args):
        refill = real(*args)

        def record(state, item_slot, epoch, retries, quar, snap, *rest):
            n = np.asarray(state.lanes.n_instr, np.int64)
            s = np.asarray(snap.n_instr, np.int64)
            r0, q0 = np.asarray(retries), np.asarray(quar)
            out = refill(state, item_slot, epoch, retries, quar, snap,
                         *rest)
            lost = np.repeat((np.asarray(out[3]) == r0 + 1)
                             | (np.asarray(out[4]) & ~q0), 2)
            want.append(int((n - s)[lost].sum()))
            return out
        return record

    monkeypatch.setattr(engine, "_resident_refill_runner", runner)
    return want


@pytest.mark.parametrize("spec,max_retries", [
    (faults.FaultSpec(rate=0.0008, seed=5, targets=("regs", "mem", "pc")),
     6),
    (faults.FaultSpec(rate=0.3, seed=5, mode="dead"), 1),
], ids=["transient", "dead"])
def test_dmr_counts_the_work_its_rollbacks_discard(monkeypatch, spec,
                                                   max_retries):
    """`PackedStats.discarded` is 0 where nothing mismatches, and under
    faults it equals n_instr - snapshot n_instr summed over both lanes
    of every mismatching pair: at each boundary, read from the refill's
    own inputs and outputs (a pair rolled back iff its retry count went
    up by one, and was quarantined iff its quarantine flag went up;
    dead lanes recur on retry, so their pairs quarantine), plus what
    the segment runner reports of its own compares (transients only:
    dead lanes are compared at the boundary alone)."""
    code, mems = _fleet(40)
    _, clean = engine.run_packed([_group(code, mems)], chunk=32,
                                 seg_steps=64, redundancy="dmr")
    assert clean.corrected == 0 and clean.discarded == 0

    want = _boundary_discards(monkeypatch)
    seen = _seg_counts(monkeypatch)
    _, ds = engine.run_packed([_group(code, mems)], chunk=32,
                              seg_steps=64, faults=spec, redundancy="dmr",
                              max_retries=max_retries)
    inside = int(np.sum(seen, axis=0)[1])
    assert ds.corrected > 0
    assert (ds.quarantined > 0) == (spec.mode == "dead")
    assert (inside > 0) == (spec.mode == "transient")
    assert ds.discarded == sum(want) + inside > 0


def test_dmr_quarantined_item_resumes_from_the_last_agreed_state(
        monkeypatch):
    """A quarantined pair parks at its snapshot, the state both lanes
    last agreed on, still holding its item; the free pair that takes
    the item over starts from that state on both lanes, not from the
    item's first instruction, and the drain stays golden. With
    `max_retries` 0 every mismatch quarantines, so items that ran clean
    segments first are parked part-way."""
    prog = skew_program()
    mems = skew_fleet(prog, 16, short_iters=64, long_iters=600,
                      long_frac=0.5, seed=7)

    def group():
        return engine.PackedGroup(code=prog.code,
                                  source=engine.array_source(mems),
                                  n_items=16, max_steps=100_000,
                                  mem_words=32, out_addr=1)

    gold, _ = engine.run_packed([group()], chunk=16, seg_steps=64,
                                keep_state=True)
    real = engine._resident_refill_runner
    held, resumed = {}, []
    fields = ("regs", "pc", "mem", "n_instr", "n_two_stage", "n_cycles",
              "mix")

    def runner(*args):
        refill = real(*args)

        def record(state, item_slot, epoch, retries, quar, snap, *rest):
            slot0, q0 = np.asarray(item_slot), np.asarray(quar)
            snap_h = {f: np.asarray(getattr(snap, f)) for f in fields}
            out = refill(state, item_slot, epoch, retries, quar, snap,
                         *rest)
            lanes, slot = out[0].lanes, np.asarray(out[1])
            for p in np.nonzero(np.asarray(out[4]) & ~q0)[0]:
                held[int(slot0[2 * p])] = {f: v[2 * p]
                                           for f, v in snap_h.items()}
            for lane in range(0, slot.size, 2):
                row = int(slot[lane])
                if row in held and int(slot0[lane]) != row:
                    for f in fields:
                        v = np.asarray(getattr(lanes, f))
                        np.testing.assert_array_equal(v[lane], held[row][f])
                        np.testing.assert_array_equal(v[lane + 1],
                                                      held[row][f])
                    assert not np.asarray(lanes.halted)[lane:lane + 2].any()
                    resumed.append(int(held.pop(row)["n_instr"]))
            return out
        return record

    monkeypatch.setattr(engine, "_resident_refill_runner", runner)
    mild = faults.FaultSpec(rate=0.0008, seed=5,
                            targets=("regs", "mem", "pc"))
    dq, dqs = engine.run_packed([group()], chunk=32, seg_steps=64,
                                keep_state=True, faults=mild,
                                redundancy="dmr", max_retries=0)
    assert dqs.quarantined == len(resumed) > 0 and not held
    assert max(resumed) > 0
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(dq[0], f), err_msg=f)


def _seg_counts(monkeypatch, frozen=None):
    """Record the [rollbacks, discarded] each DMR segment run reports
    of its own compares, and into `frozen` the pairs it returns halted
    on both lanes but disagreeing."""
    real = engine._packed_segment_runner
    seen = []

    def runner(*args):
        seg = real(*args)

        def record(*a):
            out = seg(*a)
            seen.append(np.asarray(out[4]).sum(0))
            if frozen is not None:
                lanes = out[0].lanes
                halted = np.asarray(lanes.halted).reshape(-1, 2).all(1)
                differ = np.zeros_like(halted)
                for f in ("regs", "pc", "mem", "n_instr"):
                    v = np.asarray(getattr(lanes, f))
                    v = v.reshape(halted.size, 2, -1)
                    differ |= (v[:, 0] != v[:, 1]).any(1)
                frozen.append(int((halted & differ).sum()))
            return out
        return record

    monkeypatch.setattr(engine, "_packed_segment_runner", runner)
    return seen


def test_compare_steps_follow_the_transient_rate():
    """Pairs compare often enough to expect at most COMPARE_FAULTS faults
    between compares, never more often than MIN_COMPARE_STEPS; without
    transients, once a segment."""
    assert engine.compare_steps(faults.FaultSpec(rate=1.6e-5), 4096) == 32
    assert engine.compare_steps(faults.FaultSpec(rate=1e-6), 4096) == 512
    assert engine.compare_steps(faults.FaultSpec(rate=0.0008), 4096) == 32
    assert engine.compare_steps(faults.FaultSpec(rate=0.0008), 100) == 32
    assert engine.compare_steps(faults.FaultSpec(rate=1e-9), 4096) == 4096
    assert engine.compare_steps(None, 4096) == 4096
    dead = faults.FaultSpec(rate=0.3, mode="dead")
    assert engine.compare_steps(dead, 4096) == 4096


@pytest.mark.parametrize("stepper", ["branchless", "pallas", "switch"])
def test_dmr_compares_inside_the_segment(monkeypatch, stepper):
    """With segments longer than the compare interval, pairs are compared
    and rolled back inside the segment: the drain equals the fault-free
    one, the segment's own rollbacks are counted as detected and
    corrected, and `discarded` adds the steps they threw away to the
    boundary's."""
    code, mems = _fleet(40)
    gold, _ = engine.run_packed([_group(code, mems)], chunk=16,
                                seg_steps=256, keep_state=True)
    seen = _seg_counts(monkeypatch)
    want = _boundary_discards(monkeypatch)
    mild = faults.FaultSpec(rate=0.0008, seed=5,
                            targets=("regs", "mem", "pc"))
    assert engine.compare_steps(mild, 256) == 32
    dm, ds = engine.run_packed([_group(code, mems)], chunk=32,
                               seg_steps=256, keep_state=True,
                               faults=mild, redundancy="dmr",
                               max_retries=6, stepper=stepper)
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(dm[0], f), err_msg=f)
    inside = np.sum(seen, axis=0)
    assert inside[0] > 0 and inside[1] > 0
    assert ds.corrected >= inside[0] and ds.detected >= ds.corrected
    assert ds.discarded == sum(want) + inside[1]


def test_dmr_pair_frozen_inside_the_segment_is_quarantined(monkeypatch):
    """A pair that disagrees once more after `max_retries` retries inside
    a segment stops there, halted, and the boundary quarantines it and
    resumes its item on a free pair from the state both lanes last
    agreed on: the drain stays golden."""
    prog = skew_program()
    mems = skew_fleet(prog, 16, short_iters=64, long_iters=600,
                      long_frac=0.5, seed=7)

    def group():
        return engine.PackedGroup(code=prog.code,
                                  source=engine.array_source(mems),
                                  n_items=16, max_steps=100_000,
                                  mem_words=32, out_addr=1)

    gold, _ = engine.run_packed([group()], chunk=16, seg_steps=256,
                                keep_state=True)
    frozen = []
    seen = _seg_counts(monkeypatch, frozen)
    mild = faults.FaultSpec(rate=0.0008, seed=5,
                            targets=("regs", "mem", "pc"))
    dq, dqs = engine.run_packed([group()], chunk=32, seg_steps=256,
                                keep_state=True, faults=mild,
                                redundancy="dmr", max_retries=0)
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(gold[0], f),
                                      getattr(dq[0], f), err_msg=f)
    # max_retries 0: no rollback anywhere, every mismatch quarantines
    assert dqs.quarantined == dqs.detected > 0 and dqs.corrected == 0
    assert np.sum(seen, axis=0)[0] == 0
    assert sum(frozen) > 0


_SEED_RUN = """
import sys
sys.path.insert(0, {tests!r})
from test_faults import _seed_run
print(_seed_run(int(sys.argv[1]))[0])
"""


def _seed_run(seed):
    """A faulty packed run under schedule seed `seed`: (a digest of its
    results, the segment runners it used)."""
    code, mems = _fleet(40)
    spec = faults.FaultSpec(rate=0.02, seed=seed,
                            targets=("regs", "mem", "pc"))
    real = engine._packed_segment_runner
    used = set()

    def runner(*args):
        fn = real(*args)
        used.add(fn)
        return fn

    engine._packed_segment_runner = runner
    try:
        res, _ = engine.run_packed([_group(code, mems)], chunk=16,
                                   seg_steps=64, keep_state=True,
                                   faults=spec)
    finally:
        engine._packed_segment_runner = real
    h = hashlib.sha256()
    for f in _RESULT_FIELDS:
        h.update(np.ascontiguousarray(getattr(res[0], f)).tobytes())
    return h.hexdigest(), used


def test_fault_seed_does_not_key_the_compiled_runner():
    """The schedule's seed enters only through the host-side lane keys,
    so schedules that differ only in seed share one compiled segment
    runner, and each run still equals a fresh process's run of its
    seed bit for bit."""
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    got = {}
    for seed in (11, 12):
        got[seed] = _seed_run(seed)
    assert got[11][1] == got[12][1] and len(got[11][1]) == 1
    assert got[11][0] != got[12][0], "the seed changed nothing"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(root, "src"), root,
                    os.environ.get("PYTHONPATH", "")]))
    for seed in (11, 12):
        p = subprocess.run(
            [sys.executable, "-c", _SEED_RUN.format(tests=here), str(seed)],
            cwd=root, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        assert p.stdout.split()[-1] == got[seed][0], seed


def test_resilience_requires_resident_loop():
    code, mems = _fleet(8)
    spec = faults.FaultSpec(rate=0.02, seed=5)
    with pytest.raises(ValueError, match="resident"):
        engine.run_packed([_group(code, mems)], refill="host",
                          faults=spec)
    with pytest.raises(ValueError, match="checkpoint"):
        engine.run_packed([_group(code, mems)], checkpoint_dir="/tmp/x",
                          faults=spec)


def test_faults_with_pallas_refused_on_tpu(monkeypatch):
    """The Pallas fault transform does not compile for the TPU: on a TPU
    backend the engine refuses the combination up front, before any
    compile, and still runs the same plan with the branchless stepper
    elsewhere."""
    code, mems = _fleet(8)
    spec = faults.FaultSpec(rate=0.02, seed=5)
    monkeypatch.setattr(engine.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="stepper='branchless'"):
        engine.run_packed([_group(code, mems)], stepper="pallas",
                          faults=spec)
    monkeypatch.undo()
    res, _ = engine.run_packed([_group(code, mems)], chunk=8,
                               stepper="pallas", faults=spec)
    assert len(res[0].n_instr) == 8


# ---- measurement and pricing ------------------------------------------


def test_measure_rates_classification():
    code, mems = _fleet(8, seed=2)
    spec = faults.FaultSpec(rate=0.05, seed=3,
                            targets=("regs", "mem", "pc"))
    rep = faults.measure_rates(code, mems, max_steps=400, spec=spec)
    assert rep.n_trials == 8
    assert rep.exposed > 0
    assert rep.masked + rep.derated + rep.sdc == rep.exposed
    assert rep.live_regs and all(0 <= r < 16 for r in rep.live_regs)
    quiet = faults.measure_rates(code, mems, max_steps=400,
                                 spec=faults.FaultSpec(rate=0.0))
    assert quiet.exposed == 0


def test_redundancy_selection_rate_zero_reproduces_selection_map():
    """The joint (redundancy x core) argmin at fault rate 0 must pick
    redundancy 'none' everywhere and reproduce `selection_map` exactly
    — spare copies only cost, never pay."""
    from repro.core import carbon
    from repro.core.selection import (redundancy_selection_map,
                                      selection_map)
    from repro.flexibench.base import get

    w = get("WQ")
    prof = carbon.DeviceProfile(n_one_stage=400.0, n_two_stage=130.0,
                                vm_kb=w.vm_kb(), nvm_kb=w.nvm_kb)
    L = np.logspace(np.log10(86_400.0 * 3), np.log10(86_400.0 * 1000), 9)
    F = np.array([1.0, 24.0, 960.0])
    r_idx, c_idx = redundancy_selection_map(prof, L, F, fault_rate=0.0)
    assert (r_idx == 0).all()
    np.testing.assert_array_equal(c_idx, selection_map(prof, L, F))
    # at a printing-grade rate the axis is live: protection wins cells
    r_hi, _ = redundancy_selection_map(prof, L, F, fault_rate=1e-3)
    assert (r_hi > 0).any()


def test_plan_wiring_prices_resilience():
    """FleetPlan(faults=..., redundancy='dmr') drains bit-exactly equal
    to the fault-free plan, prices strictly more carbon (spare area +
    re-execution), and the report prints the §9.14 resilience line."""
    from repro.fleet.plan import FleetGroup, FleetPlan, run_plan

    base = dict(groups=[FleetGroup("WQ", n_items=8)], chunk=16,
                seg_steps=128)
    r0 = run_plan(FleetPlan(**base))
    mild = faults.FaultSpec(rate=2e-4, seed=5,
                            targets=("regs", "mem", "pc"))
    r1 = run_plan(FleetPlan(**base, faults=mild, redundancy="dmr",
                            max_retries=6))
    for g0, g1 in zip(r0.groups, r1.groups):
        np.testing.assert_array_equal(g0.result.out, g1.result.out)
        np.testing.assert_array_equal(g0.result.n_instr,
                                      g1.result.n_instr)
        assert g1.total_kg > g0.total_kg
    assert r1.packed.redundancy == "dmr"
    assert "resilience (FlexiFault §9.14, dmr)" in r1.format()
    assert "resilience" not in r0.format()
