"""Cycle-accurate timing differential tests (DESIGN.md §9.10).

The per-lane timing layer must not disturb architectural state, and its
tick tallies must be *exact*: every stepper (the scalar lax.switch
reference, branchless one-hot, fused Pallas segment — including the
banked packed runtime with on-device refill) is stepped in lockstep
against the PyISS
cycle oracle on random instruction soups and on all 11 FlexiBench
workloads, comparing full architectural state AND per-lane cycle
counters bit-for-bit across all three core widths.

Also pins the Table-7 paper ratios under the timing layer's base case
(satellite of the same change): base-cost event pricing is *exactly*
the two-bucket analytic model, so the 3.15x/4.93x speedup and
2.65x/3.50x energy geomeans survive by construction.

`hypothesis` is optional (as in test_flexibits.py): without it the
single-instruction property test is skipped; the deterministic
spot-check fallbacks always run.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.flexibench.base import all_workloads
from repro.flexibits import isa, iss
from repro.flexibits.asm import Asm
from repro.flexibits.cycles import (CORES, N_COST, TAKEN_IDX,
                                    TICKS_PER_CYCLE, base_ticks, cost_row,
                                    event_cycles)
from repro.flexibits.pyiss import PyISS
from repro.fleet import engine

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

CORE_NAMES = ("SERV", "QERV", "HERV")
STEPPERS = ("branchless", "pallas")
MEM_WORDS = 128
# any u32 address in [MEM_WORDS*4, 2^31) exercises clamp-on-read /
# drop-on-write; bit-31 addresses are outside the contract (iss.py)
OOB_BASE = 2 ** 31 - 8192

R_NAMES = tuple(isa.R_OPS)
I_NAMES = ("addi", "slti", "sltiu", "xori", "ori", "andi")
SH_NAMES = tuple(isa.SHIFT_OPS)
MEM_NAMES = ("lw", "lh", "lb", "lhu", "lbu", "sw", "sh", "sb")
B_NAMES = tuple(isa.B_OPS)

_step_switch = jax.jit(lambda code, s, cost: iss.step(code, s, cost=cost))
_step_bl = jax.jit(
    lambda code, s, cost: iss.step_branchless(code, s, cost=cost))


def _u32(v):
    return int(v) & 0xFFFFFFFF


def _assert_state_matches(s, py, tag=""):
    """Full architectural state + cycle tally of one JAX state vs PyISS."""
    assert int(s.n_instr) == py.n_instr, tag
    assert int(s.n_two_stage) == py.n_two_stage, tag
    assert _u32(s.pc) == _u32(py.pc), tag
    np.testing.assert_array_equal(
        np.asarray(s.regs, np.int64), np.asarray(py.regs, np.int64),
        err_msg=tag)
    np.testing.assert_array_equal(
        np.asarray(s.mem, np.int64), py.mem, err_msg=tag)
    np.testing.assert_array_equal(
        np.asarray(s.mix, np.int64), py.events[:8] + py.events[8:16],
        err_msg=tag)
    assert int(s.n_cycles) == py.n_cycles, tag


# --------------------------------------------------------------- fixed point

def test_tick_table_exact_fixed_point():
    """Integer tick costs are EXACTLY TICKS_PER_CYCLE x the analytic
    per-instruction cycle counts — the SERV 38/70 anchors and the Table-7
    geomeans are preserved by construction, not by tolerance."""
    for core in CORES.values():
        one, two = base_ticks(core)
        assert one == TICKS_PER_CYCLE * core.cycles_one_stage()
        assert two == TICKS_PER_CYCLE * core.cycles_two_stage()
        base = cost_row(core)
        assert base.shape == (N_COST,)
        assert not base[TAKEN_IDX:].any()       # base case: no dynamic terms
        dyn = cost_row(core, dynamic=True)
        assert (dyn[TAKEN_IDX:] > 0).all()
        np.testing.assert_array_equal(base[:TAKEN_IDX], dyn[:TAKEN_IDX])
    assert base_ticks(CORES["SERV"]) == (760, 1400)     # 38 / 70 cycles


# ------------------------------------------------------------ lockstep steps

def _lockstep_program():
    """One program touching every opcode class and every dynamic timing
    event: taken + fall-through branches, varied serial shift amounts,
    subword RMW, jumps, upper immediates, and OOB clamp/drop accesses."""
    a = Asm(vm_reserved=MEM_WORDS * 4)
    a.li(3, 0)                        # in-range memory base
    a.li(4, OOB_BASE)                 # OOB base (clamp/drop)
    a.li(5, -3)
    a.li(6, 100)
    a.li(7, 0x1234_5678 - (1 << 32) // 2)
    a.lui(8, 0xABCDE)
    a.emit("auipc", 9, imm=0x7)
    a.add(10, 5, 6)
    a.sub(11, 6, 5)
    a.emit("xor", 12, 7, 8)
    a.emit("or", 13, 7, 8)
    a.emit("and", 14, 7, 8)
    a.emit("slt", 15, 5, 6)
    a.emit("sltu", 15, 5, 6)
    a.emit("slli", 10, 7, imm=1)
    a.emit("slli", 10, 7, imm=31)
    a.emit("srli", 11, 7, imm=17)
    a.emit("srai", 12, 5, imm=9)
    a.li(15, 13)
    a.emit("sll", 13, 6, 15)          # reg-amount shifts
    a.emit("srl", 13, 7, 15)
    a.emit("sra", 13, 5, 15)
    a.sw(7, 3, 16)
    a.emit("sh", 0, 3, 7, 18)         # subword RMW, unaligned half
    a.emit("sb", 0, 3, 8, 21)
    a.lw(10, 3, 16)
    a.emit("lh", 11, 3, imm=18)
    a.emit("lb", 12, 3, imm=21)
    a.emit("lhu", 11, 3, imm=18)
    a.emit("lbu", 12, 3, imm=21)
    a.lw(10, 4, 4)                    # OOB: clamps to last word
    a.emit("lbu", 11, 4, imm=7)
    a.sw(7, 4, 8)                     # OOB: dropped
    a.emit("sb", 0, 4, 7, 3)
    a.beq(5, 6, "skip1")              # not taken
    a.addi(14, 14, 1)
    a.label("skip1")
    a.blt(5, 6, "skip2")              # taken
    a.addi(14, 14, 2)
    a.label("skip2")
    a.bltu(5, 6, "skip3")             # -3 unsigned is huge: not taken
    a.addi(14, 14, 4)
    a.label("skip3")
    a.li(5, 0)                        # bounded backward loop (taken x3)
    a.label("loop")
    a.addi(5, 5, 1)
    a.emit("slti", 6, 5, imm=4)
    a.bne(6, 0, "loop")
    a.jal(1, "over")
    a.addi(14, 14, 8)
    a.label("over")
    a.jalr(2, 1, 8)                   # link reg + 8 = the next instruction
    for r in range(16):
        a.sw(r, 3, 4 * r)
    a.halt()
    return a.assemble()


@pytest.mark.parametrize("core_name", CORE_NAMES)
def test_single_step_lockstep(core_name):
    """iss.step and iss.step_branchless vs the oracle after EVERY retired
    instruction — state and cycle tally, dynamic cost row."""
    prog = _lockstep_program()
    cost = cost_row(CORES[core_name], dynamic=True)
    mem0 = prog.initial_memory(MEM_WORDS)
    py = PyISS(prog.code, MEM_WORDS, mem0, cost=cost)
    code = jnp.asarray(prog.code.view(np.int32))
    costj = jnp.asarray(cost)
    s_sw = iss.init_state(jnp.asarray(mem0))
    s_bl = s_sw
    for n in range(500):
        if py.halted:
            break
        py.step()
        s_sw = _step_switch(code, s_sw, costj)
        s_bl = _step_bl(code, s_bl, costj)
        _assert_state_matches(s_sw, py, f"switch step {n}")
        _assert_state_matches(s_bl, py, f"branchless step {n}")
    assert py.halted and bool(s_sw.halted) and bool(s_bl.halted)
    assert py.events[TAKEN_IDX] >= 4          # the soup really branched
    assert py.n_cycles > 0


# --------------------------------------------------- random instruction soups

def _timing_soup(rng):
    """Random halting program over the full ISA: forward branches, a
    bounded backward loop, subword + OOB memory traffic, jumps."""
    a = Asm(vm_reserved=MEM_WORDS * 4)
    a.li(3, 0)
    a.li(4, OOB_BASE)
    for r in range(5, 16):
        a.li(r, int(rng.integers(-2 ** 31, 2 ** 31)))
    a.li(5, 0)
    a.li(6, int(rng.integers(3, 9)))
    a.label("loop")
    a.addi(5, 5, 1)
    a.blt(5, 6, "loop")
    kinds = ("r", "i", "sh", "mem", "br", "jal", "ui")
    for i in range(int(rng.integers(30, 80))):
        kind = str(rng.choice(kinds))
        rd = int(rng.integers(5, 16))
        rs1 = int(rng.integers(0, 16))
        rs2 = int(rng.integers(0, 16))
        if kind == "r":
            a.emit(str(rng.choice(R_NAMES)), rd, rs1, rs2)
        elif kind == "i":
            a.emit(str(rng.choice(I_NAMES)), rd, rs1,
                   imm=int(rng.integers(-2048, 2048)))
        elif kind == "sh":
            a.emit(str(rng.choice(SH_NAMES)), rd, rs1,
                   imm=int(rng.integers(0, 32)))
        elif kind == "mem":
            name = str(rng.choice(MEM_NAMES))
            base = 4 if rng.random() < 0.25 else 3
            off = int(rng.integers(0, MEM_WORDS * 4 - 4))
            if name[0] == "s":
                a.emit(name, 0, base, rs2, off)
            else:
                a.emit(name, rd, base, imm=off)
        elif kind == "br":
            lbl = f"fwd{i}"
            getattr(a, str(rng.choice(B_NAMES)))(rs1, rs2, lbl)
            a.emit(str(rng.choice(I_NAMES)), int(rng.integers(5, 16)), rs1,
                   imm=int(rng.integers(-2048, 2048)))
            a.label(lbl)
        elif kind == "jal":
            lbl = f"j{i}"
            a.jal(rd, lbl)
            a.addi(int(rng.integers(5, 16)), 0, 1)
            a.label(lbl)
        elif rng.random() < 0.5:
            a.lui(rd, int(rng.integers(0, 1 << 20)))
        else:
            a.emit("auipc", rd, imm=int(rng.integers(0, 1 << 20)))
    for r in range(16):
        a.sw(r, 3, 4 * r)
    a.halt()
    return a.assemble()


@functools.lru_cache(maxsize=None)
def _soup_fixture():
    """(prog, mem0, core_name, cost, oracle) per soup — cores round-robin
    so one packed run exercises per-group heterogeneous cost rows."""
    out = []
    for i in range(6):
        prog = _timing_soup(np.random.default_rng(1000 + i))
        cost = cost_row(CORES[CORE_NAMES[i % 3]], dynamic=True)
        mem0 = prog.initial_memory(MEM_WORDS)
        py = PyISS(prog.code, MEM_WORDS, mem0, cost=cost).run(4096)
        assert py.halted
        out.append((prog, mem0, cost, py))
    return out


def _check_packed_vs_oracle(results, oracles, mem_words_of):
    for g, (res, py) in enumerate(zip(results, oracles)):
        mw = mem_words_of(g)
        assert res.n_cycles is not None
        for i in range(res.n_items):
            tag = f"group {g} item {i}"
            assert bool(res.halted[i]), tag
            assert int(res.n_instr[i]) == py.n_instr, tag
            assert _u32(res.pc[i]) == _u32(py.pc), tag
            np.testing.assert_array_equal(
                np.asarray(res.regs[i], np.int64),
                np.asarray(py.regs, np.int64), err_msg=tag)
            np.testing.assert_array_equal(
                np.asarray(res.mems[i][:mw], np.int64), py.mem[:mw],
                err_msg=tag)
            assert int(res.n_cycles[i]) == py.n_cycles, tag


@pytest.mark.parametrize("stepper", STEPPERS)
def test_soup_differential(stepper):
    """Whole random programs through the packed fleet runtime (banked
    fetch, on-device refill): final state + per-lane cycle tallies equal
    the oracle for every item, heterogeneous cost rows in one bank."""
    oracles = _soup_fixture()
    groups = [engine.PackedGroup(
        code=prog.code,
        source=engine.array_source(np.broadcast_to(
            mem0, (2, MEM_WORDS)).copy()),
        n_items=2, max_steps=4096, mem_words=MEM_WORDS, cost=cost)
        for (prog, mem0, cost, _) in oracles]
    results, _ = engine.run_packed(groups, chunk=8, seg_steps=256,
                                   keep_state=True, stepper=stepper)
    _check_packed_vs_oracle(results, [py for *_, py in oracles],
                            lambda g: MEM_WORDS)


# -------------------------------------------------- all FlexiBench workloads

@functools.lru_cache(maxsize=None)
def _workload_fixture():
    """Per (workload, item) oracle runs with dynamic cost rows, cores
    round-robin across the 11 workloads; inputs are the engine's own
    stream items (workload_source) so the packed run sees identical
    memory images."""
    n = 2
    ws = all_workloads()
    fixture = []
    for i, w in enumerate(ws):
        cost = cost_row(CORES[CORE_NAMES[i % 3]], dynamic=True)
        mems = np.asarray(engine.workload_source(w, seed=0)(0, n), np.int32)
        pys = []
        for j in range(n):
            py = PyISS(w.program.code, w.total_mem_words, mems[j],
                       cost=cost).run(w.max_steps)
            assert py.halted, w.key
            pys.append(py)
        fixture.append((w, cost, mems, pys))
    return fixture


@pytest.mark.parametrize("stepper", STEPPERS)
def test_workload_differential(stepper):
    """All 11 FlexiBench workloads in ONE packed bank per stepper: out
    words, full final state, and per-lane cycle tallies all equal the
    PyISS oracle, per item."""
    fixture = _workload_fixture()
    groups = [engine.PackedGroup(
        code=w.program.code, source=engine.array_source(mems),
        n_items=len(mems), max_steps=w.max_steps,
        mem_words=w.total_mem_words, out_addr=w.out_addr, cost=cost)
        for (w, cost, mems, _) in fixture]
    results, _ = engine.run_packed(groups, chunk=16, seg_steps=128,
                                   keep_state=True, stepper=stepper)
    for res, (w, _, _, pys) in zip(results, fixture):
        for j, py in enumerate(pys):
            assert int(res.out[j]) == int(np.int32(py.mem[w.out_addr])), \
                (w.key, j)
            assert int(res.n_instr[j]) == py.n_instr, (w.key, j)
            assert int(res.n_cycles[j]) == py.n_cycles, (w.key, j)
            np.testing.assert_array_equal(
                np.asarray(res.mems[j][:w.total_mem_words], np.int64),
                py.mem, err_msg=f"{w.key} item {j}")


# -------------------------------------------- single-instruction properties

def _single_instr_check(name, rd, rs1, rs2, a_val, b_val, imm):
    """One decoded instruction on a fresh state: PyISS vs step_branchless,
    full state + tick tally on every core (dynamic rows)."""
    code = np.array([isa.encode(name, rd, rs1, rs2, imm),
                     isa.encode("ecall")], np.uint32)
    mem0 = (np.arange(MEM_WORDS, dtype=np.int64) * 2654435761) \
        .astype(np.int32)
    regs = np.zeros(16, np.int64)
    if rs2 != 0:
        regs[rs2] = np.int32(b_val)
    if rs1 != 0:
        regs[rs1] = np.int32(a_val)        # rs1 wins on alias (addressing)
    codej = jnp.asarray(code.view(np.int32))
    for cname in CORE_NAMES:
        cost = cost_row(CORES[cname], dynamic=True)
        py = PyISS(code, MEM_WORDS, mem0, cost=cost)
        py.regs = [int(v) for v in regs]
        py.step()
        s0 = iss.init_state(jnp.asarray(mem0))._replace(
            regs=jnp.asarray(regs, iss.I32))
        s1 = _step_bl(codej, s0, jnp.asarray(cost))
        _assert_state_matches(s1, py, f"{name} on {cname}")


def _draw_operands(rng, name):
    rd = int(rng.integers(0, 16))
    rs1 = int(rng.integers(0, 16))
    rs2 = int(rng.integers(0, 16))
    imm = int(rng.integers(0, 32)) if name in isa.SHIFT_OPS \
        else int(rng.integers(-2048, 2048))
    b_val = int(rng.integers(-2 ** 31, 2 ** 31))
    if name in isa.S_OPS or (name in isa.I_OPS and name[0] == "l"):
        # target address in [0, 2^31): in-range or OOB clamp/drop zone
        addr = int(rng.integers(0, MEM_WORDS * 4)) if rng.random() < 0.5 \
            else int(rng.integers(MEM_WORDS * 4, 2 ** 31 - 4096))
        a_val = (addr - imm) & 0xFFFFFFFF
        if a_val >= 1 << 31:
            a_val -= 1 << 32
        if rs1 == 0:                  # x0 base: keep the address valid
            a_val, imm = 0, int(rng.integers(0, 2048))
    else:
        a_val = int(rng.integers(-2 ** 31, 2 ** 31))
    return rd, rs1, rs2, int(a_val), b_val, imm


if HAVE_HYPOTHESIS:
    @st.composite
    def single_instr(draw):
        name = draw(st.sampled_from(isa.ALL_OPS))
        seed = draw(st.integers(0, 2 ** 31 - 1))
        return (name,) + _draw_operands(np.random.default_rng(seed), name)

    @hypothesis.settings(max_examples=40, deadline=None)
    @hypothesis.given(single_instr())
    def test_single_instruction_matches_oracle(case):
        _single_instr_check(*case)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_single_instruction_matches_oracle():
        pass


def test_single_instruction_spot_checks():
    """Deterministic fallback: every opcode (incl. ecall/ebreak, x0
    destinations, OOB addresses) through the same differential check."""
    rng = np.random.default_rng(7)
    for name in isa.ALL_OPS:
        for _ in range(3):
            _single_instr_check(name, *_draw_operands(rng, name))
    # pinned edges: x0 write, OOB load clamp, OOB store drop
    _single_instr_check("addi", 0, 5, 0, 99, 0, 123)
    _single_instr_check("lw", 6, 5, 0, OOB_BASE, 0, 16)
    _single_instr_check("sb", 0, 5, 7, OOB_BASE, -1, 5)


# --------------------------------------------------------- Table-7 ratio pins

def test_base_event_pricing_equals_analytic():
    """Base-cost event pricing is the two-bucket analytic model; dynamic
    pricing is strictly costlier (it only adds nonnegative terms)."""
    from benchmarks.common import device_profile
    for w in all_workloads():
        prof = device_profile(w.key)
        for core in CORES.values():
            want = core.cycles(prof.n_one_stage, prof.n_two_stage)
            got = event_cycles(prof.events, core, dynamic=False)
            assert got == pytest.approx(want, rel=1e-12), (w.key, core.name)
            assert event_cycles(prof.events, core, dynamic=True) > got


def test_table7_geomeans_pinned():
    """Paper Table-7/Fig-9 ratios under the timing layer's base case:
    geomean speedups 3.15x (QERV) / 4.93x (HERV), energy gains
    2.65x / 3.50x."""
    from benchmarks.paper_tables import table7_fig9_ppa
    _, derived = table7_fig9_ppa()
    paper = derived["paper"]
    assert paper == {"qerv_speedup": 3.15, "herv_speedup": 4.93,
                     "qerv_energy": 2.65, "herv_energy": 3.50}
    assert derived["qerv_speedup_geomean"] == \
        pytest.approx(paper["qerv_speedup"], rel=0.06)
    assert derived["herv_speedup_geomean"] == \
        pytest.approx(paper["herv_speedup"], rel=0.06)
    assert derived["qerv_energy_gain_geomean"] == \
        pytest.approx(paper["qerv_energy"], rel=0.06)
    assert derived["herv_energy_gain_geomean"] == \
        pytest.approx(paper["herv_energy"], rel=0.06)
