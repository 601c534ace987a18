"""JAX RV32E instruction-set simulator — the paper's RTL characterization
loop re-thought for TPU: vmap-able over per-item memories (a *fleet* of
devices with different sensor inputs) and shard_map-able over the
production mesh (fleet/engine.py).

Two interpreters share the decode/commit semantics bit-exactly:

- `step` — scalar reference: decodes with bit ops, dispatches on opcode
  via lax.switch; `run`/`run_segment` wrap it in while_loops.
- `step_branchless`/`step_lanes` — the lane-parallel hot path
  (DESIGN.md §9.5): no switch, masked jnp.where/jnp.select commits, one
  shared memory port, one-hot register/mix updates, and a static
  opcode-subset mask for per-workload ISA specialization;
  `run_segment_lanes` steps a whole lane pool in one while_loop.

A third interpreter, the fused-segment Pallas stepper
(`kernels/iss_stepper.py`, DESIGN.md §9.7), ports the branchless commit
scheme into a single kernel per lane tile; any change to the commit
semantics here must be mirrored there (the instruction-soup tests in
tests/test_stepper.py pin all three against each other).

The branchless and Pallas steppers also run *banked* (DESIGN.md §9.8):
lanes fetch from a padded multi-program bank through `fetch_banked`
(per-program pc clamp), carry their program row and step budget in
`PackedState`, and retire exactly what a single-program pool running
their program would — the packed fleet runtime multiplexes a whole
heterogeneous plan through one lane pool on top of this.

Cycle accounting implements the paper's bit-serial timing model
(cycles.py): per retired instruction, one-stage or two-stage cost for the
configured datapath width. On top of the two-bucket counts every stepper
can carry a per-lane cycle tally (`ISSState.n_cycles`, DESIGN.md §9.10):
pass a `cost` row (cycles.cost_row) and each retired instruction adds its
(stage, mix-class) base ticks plus the dynamic terms the bucket model
cannot see — taken-branch refetch, per-bit serial shift, subword RMW.
With `cost=None` (the default) the timing layer is dropped from the
traced graph entirely and `n_cycles` passes through untouched.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.flexibits import faults as flexifault
from repro.flexibits import isa
from repro.flexibits.cycles import (MIX_CLASSES, SHIFT_IDX, SUBWORD_IDX,
                                    TAKEN_IDX)

I32 = jnp.int32
U32 = jnp.uint32

# mix categories (Fig. 2a) — canonical order lives in cycles.MIX_CLASSES
_MIX_IDX = {c: i for i, c in enumerate(MIX_CLASSES)}

_OPCODES = (isa.OP_LUI, isa.OP_AUIPC, isa.OP_JAL, isa.OP_JALR,
            isa.OP_BRANCH, isa.OP_LOAD, isa.OP_STORE, isa.OP_IMM,
            isa.OP_REG, isa.OP_SYSTEM)


class ISSState(NamedTuple):
    regs: jax.Array        # (16,) int32
    pc: jax.Array          # () int32 (byte address)
    mem: jax.Array         # (M,) int32 word-addressed RAM
    halted: jax.Array      # () bool
    n_instr: jax.Array     # () int32
    n_two_stage: jax.Array  # () int32
    mix: jax.Array         # (8,) int32 per-category retired counts
    n_cycles: jax.Array    # () int32 accumulated timing ticks (§9.10)


class PackedState(NamedTuple):
    """Lane pool executing a *bank* of programs (DESIGN.md §9.8).

    The packed fleet runtime multiplexes every group of a heterogeneous
    `FleetPlan` through one lane pool: each lane carries the bank row of
    the program it is executing (`prog_id`) and its own retirement
    budget (`max_steps`, groups differ in step budget), both refilled
    alongside the architectural state when the admission scheduler
    assigns the lane a new item.
    """
    lanes: ISSState        # lane-batched architectural state
    prog_id: jax.Array     # (lanes,) int32 bank row per lane
    max_steps: jax.Array   # (lanes,) int32 per-lane step budget


def pack_programs(codes) -> "tuple[np.ndarray, np.ndarray]":
    """Pad programs into a (n_progs, max_len) int32 bank + length vector.

    Rows are zero-padded; the pad words are unreachable because every
    banked fetch clamps the pc to the row's own `code_len` (the same
    clamp-on-read semantics a single-program fetch gets from jax
    gathers, applied per program — see `fetch_banked`).
    """
    rows = [np.asarray(c) for c in codes]
    rows = [r.view(np.int32) if r.dtype.itemsize == 4 else
            r.astype(np.uint32).view(np.int32) for r in rows]
    max_len = max(len(r) for r in rows)
    bank = np.zeros((len(rows), max_len), np.int32)
    for i, r in enumerate(rows):
        bank[i, :len(r)] = r
    return bank, np.array([len(r) for r in rows], np.int32)


def fetch_banked(bank: jax.Array, code_len: jax.Array, prog_id: jax.Array,
                 pc: jax.Array) -> jax.Array:
    """Fetch instruction word(s) from a program bank (uint32 out).

    Bit-exact with the single-program fetch `code[pc >> 2]` run against
    each lane's own program: the word index clamps to that program's
    `code_len`, not the padded bank width, so a pc past a short
    program's end reads the program's *own* last word exactly as jax's
    clamp-on-read gather would. Shape-polymorphic over () and (lanes,).
    """
    pword = (_u(pc) >> 2).astype(I32)
    pword = jnp.clip(pword, 0, code_len[prog_id] - 1)
    return bank[prog_id, pword].astype(U32)


def init_state(mem: jax.Array) -> ISSState:
    return ISSState(
        regs=jnp.zeros(16, I32),
        pc=jnp.zeros((), I32),
        mem=mem.astype(I32),
        halted=jnp.zeros((), bool),
        n_instr=jnp.zeros((), I32),
        n_two_stage=jnp.zeros((), I32),
        mix=jnp.zeros(len(MIX_CLASSES), I32),
        n_cycles=jnp.zeros((), I32),
    )


def _sx(v, bits):
    shift = 32 - bits
    return (v.astype(I32) << shift) >> shift


def _u(v):
    return v.astype(U32)


def step(code: jax.Array, s: ISSState, *,
         instr: jax.Array = None, mem_len: jax.Array = None,
         cost: jax.Array = None, faults=None, lane_key: jax.Array = None,
         epoch: jax.Array = None) -> ISSState:
    # `instr` overrides the fetch (banked runtimes fetch from a program
    # bank via `fetch_banked`); `mem_len` bounds the data-memory ports at
    # the lane's OWN word count, so a lane in a pool padded to a larger
    # memory keeps jax's clamp-on-read / drop-on-write semantics at ITS
    # program's boundary; `cost` (an (N_COST,) cycles.cost_row) turns on
    # the per-lane timing tally; `faults` (a faults.FaultSpec, with the
    # lane's traced uint32 `lane_key` and int32 retry `epoch`) turns on
    # the post-commit fault transform (DESIGN.md §9.14) — None keeps it
    # out of the traced graph. Everything else is identical.
    if instr is None:
        instr = code[(_u(s.pc) >> 2).astype(I32)].astype(U32)
    ii = instr.astype(I32)
    op = (ii & 0x7F)
    rd = (ii >> 7) & 0xF
    f3 = (ii >> 12) & 0x7
    rs1 = (ii >> 15) & 0xF
    rs2 = (ii >> 20) & 0xF
    f7 = (ii >> 25) & 0x7F
    sub_bit = (ii >> 30) & 1

    imm_i = _sx(_u(instr) >> 20, 12)
    imm_s = _sx(((_u(instr) >> 25) << 5).astype(I32)
                | ((ii >> 7) & 0x1F), 12)
    imm_b = _sx(((ii >> 31) & 1) << 12 | ((ii >> 7) & 1) << 11
                | ((ii >> 25) & 0x3F) << 5 | ((ii >> 8) & 0xF) << 1, 13)
    imm_u = ii & jnp.asarray(-4096, I32)  # 0xFFFFF000 as a signed mask
    imm_j = _sx(((ii >> 31) & 1) << 20 | ((ii >> 12) & 0xFF) << 12
                | ((ii >> 20) & 1) << 11 | ((ii >> 21) & 0x3FF) << 1, 21)

    a = s.regs[rs1]
    b = s.regs[rs2]
    au = _u(a)
    bu = _u(b)
    pc4 = s.pc + 4

    def alu(x, y, f3v, is_sub, is_sra):
        sh = (y & 31).astype(U32)
        return lax.switch(f3v, [
            lambda: jnp.where(is_sub, x - y, x + y),
            lambda: (x.astype(U32) << sh).astype(I32),
            lambda: (x < y).astype(I32),
            lambda: (_u(x) < _u(y)).astype(I32),
            lambda: x ^ y,
            lambda: jnp.where(is_sra, x >> (y & 31),
                              (_u(x) >> sh).astype(I32)),
            lambda: x | y,
            lambda: x & y,
        ])

    # LOAD: word RMW for sub-word
    def do_load():
        addr = (a + imm_i).astype(I32)
        widx = _u(addr).astype(I32) >> 2
        if mem_len is not None:          # per-program clamp-on-read
            widx = jnp.clip(widx, 0, mem_len - 1)
        word = s.mem[widx]
        sh8 = ((addr & 3) * 8).astype(U32)
        byte = (_u(word) >> sh8).astype(I32) & 0xFF
        half_sh = ((addr & 2) * 8).astype(U32)
        half = (_u(word) >> half_sh).astype(I32) & 0xFFFF
        val = lax.switch(jnp.clip(f3, 0, 5), [
            lambda: _sx(byte, 8),            # lb
            lambda: _sx(half, 16),           # lh
            lambda: word,                    # lw
            lambda: word,                    # (unused f3=3)
            lambda: byte,                    # lbu
            lambda: half,                    # lhu
        ])
        return val, pc4, s.mem, False

    def do_store():
        addr = (a + imm_s).astype(I32)
        widx = _u(addr).astype(I32) >> 2
        ridx = widx if mem_len is None \
            else jnp.clip(widx, 0, mem_len - 1)
        word = s.mem[ridx]
        sh8 = ((addr & 3) * 8).astype(U32)
        sh16 = ((addr & 2) * 8).astype(U32)
        bmask = (jnp.asarray(0xFF, U32) << sh8).astype(I32)
        hmask = (jnp.asarray(0xFFFF, U32) << sh16).astype(I32)
        neww = lax.switch(jnp.clip(f3, 0, 2), [
            lambda: (word & ~bmask) | (((b & 0xFF).astype(U32) << sh8
                                        ).astype(I32) & bmask),
            lambda: (word & ~hmask) | (((b & 0xFFFF).astype(U32) << sh16
                                        ).astype(I32) & hmask),
            lambda: b,
        ])
        if mem_len is not None:          # per-program drop-on-write
            neww = jnp.where(widx < mem_len, neww, s.mem[widx])
        return jnp.zeros((), I32), pc4, s.mem.at[widx].set(neww), False

    def do_branch():
        cond = lax.switch(f3, [
            lambda: a == b, lambda: a != b,
            lambda: jnp.zeros((), bool), lambda: jnp.zeros((), bool),
            lambda: a < b, lambda: a >= b,
            lambda: au < bu, lambda: au >= bu,
        ])
        return jnp.zeros((), I32), \
            jnp.where(cond, s.pc + imm_b, pc4), s.mem, False

    cases = [
        lambda: (imm_u, pc4, s.mem, False),                       # LUI
        lambda: (s.pc + imm_u, pc4, s.mem, False),                # AUIPC
        lambda: (pc4, s.pc + imm_j, s.mem, False),                # JAL
        lambda: (pc4, (a + imm_i) & ~1, s.mem, False),            # JALR
        do_branch,                                                # BRANCH
        do_load,                                                  # LOAD
        do_store,                                                 # STORE
        lambda: (alu(a, imm_i, f3,                                # OP-IMM
                     jnp.zeros((), bool),
                     (f3 == 5) & (sub_bit == 1)),
                 pc4, s.mem, False),
        lambda: (alu(a, b, f3, sub_bit == 1, sub_bit == 1),       # OP-REG
                 pc4, s.mem, False),
        lambda: (jnp.zeros((), I32), pc4, s.mem, True),           # SYSTEM
    ]
    case_idx = jnp.searchsorted(jnp.asarray(sorted(_OPCODES), I32), op)
    # map sorted position back to case order
    sorted_ops = sorted(_OPCODES)
    perm = [sorted_ops.index(o) for o in _OPCODES]
    inv = [0] * len(_OPCODES)
    for ci, po in enumerate(perm):
        inv[po] = ci
    wr, next_pc, mem, halt = lax.switch(case_idx,
                                        [cases[i] for i in inv])

    writes_rd = (op != isa.OP_BRANCH) & (op != isa.OP_STORE) \
        & (op != isa.OP_SYSTEM) & (rd != 0)
    regs = s.regs.at[rd].set(jnp.where(writes_rd, wr, s.regs[rd]))

    # ---- classification: two-stage + mix category
    is_shift_imm = (op == isa.OP_IMM) & ((f3 == 1) | (f3 == 5))
    is_shift_reg = (op == isa.OP_REG) & ((f3 == 1) | (f3 == 5))
    is_slt = ((op == isa.OP_IMM) | (op == isa.OP_REG)) \
        & ((f3 == 2) | (f3 == 3))
    two_stage = ((op == isa.OP_LOAD) | (op == isa.OP_STORE)
                 | (op == isa.OP_BRANCH) | (op == isa.OP_JAL)
                 | (op == isa.OP_JALR) | is_shift_imm | is_shift_reg
                 | is_slt)
    mix_idx = jnp.select(
        [op == isa.OP_LOAD, op == isa.OP_STORE, op == isa.OP_BRANCH,
         (op == isa.OP_JAL) | (op == isa.OP_JALR),
         is_shift_imm | is_shift_reg,
         (op == isa.OP_IMM) | (op == isa.OP_LUI) | (op == isa.OP_AUIPC),
         op == isa.OP_REG],
        [_MIX_IDX["loads"], _MIX_IDX["stores"], _MIX_IDX["branches"],
         _MIX_IDX["jumps"], _MIX_IDX["shifts"], _MIX_IDX["I-type"],
         _MIX_IDX["R-type"]],
        _MIX_IDX["system"])

    n_cycles = s.n_cycles
    if cost is not None:
        taken, shamt, subword = dynamic_terms(op, f3, a, b, imm_i)
        n_cycles = n_cycles + timing_ticks(cost, two_stage, mix_idx,
                                           taken, shamt, subword)

    out = ISSState(
        regs=regs,
        pc=next_pc.astype(I32),
        mem=mem,
        halted=s.halted | halt,
        n_instr=s.n_instr + 1,
        n_two_stage=s.n_two_stage + two_stage.astype(I32),
        mix=s.mix.at[mix_idx].add(1),
        n_cycles=n_cycles,
    )
    if faults is not None:
        # post-commit fault transform: this scalar stepper only runs a
        # step while live, so the gate is just post-commit ~halted
        out = flexifault.apply_faults(faults, lane_key, epoch, out,
                                      mem_len=mem_len)
    return out


# ---------------------------------------------------------------------------
# Lane-parallel branchless stepper (DESIGN.md §9.5)
#
# Under vmap, `step`'s lax.switch executes every opcode branch for every
# lane anyway (batched switch lowers to select-of-all-branches) — but each
# branch re-derives its own addresses and issues its own gather/scatter.
# The branchless stepper makes the all-branches cost explicit and amortized:
# one decode, ONE memory gather shared by loads and stores, ONE scatter,
# and masked jnp.where/jnp.select commits. A static opcode-subset mask
# (per-workload ISA subset, à la RISC-V instruction-subset processors)
# drops whole opcode classes from the graph at trace time, so XLA never
# even compiles classes a workload cannot retire.
# ---------------------------------------------------------------------------

FULL_SUBSET = frozenset(_OPCODES)


# Shape-polymorphic pieces of the branchless step, shared verbatim by the
# scalar `step_branchless` (vmapped by `step_lanes`) and the lane-tile
# vectorized Pallas kernel (kernels/iss_stepper.py): the arithmetic is
# elementwise, so one definition serves () and (lanes,) operands alike
# and the two steppers cannot drift.

class DecodedInstr(NamedTuple):
    op: jax.Array
    rd: jax.Array
    f3: jax.Array
    rs1: jax.Array
    rs2: jax.Array
    sub_bit: jax.Array
    imm_i: jax.Array
    imm_s: jax.Array
    imm_b: jax.Array
    imm_u: jax.Array
    imm_j: jax.Array


# The shared helpers compute in int32 only: the uint32 operations of
# `step` become logical shifts and sign-flipped compares on the same
# bits, which Mosaic lowers for the TPU (its uint32 support is partial).

def _srl(v, n):
    """Logical right shift of int32 bits (`step`'s uint32 `>>`)."""
    v, n = jnp.broadcast_arrays(v, jnp.asarray(n, I32))
    return lax.shift_right_logical(v, n)


def _ult(a, b):
    """Unsigned less-than of int32 bit patterns."""
    sign = jnp.asarray(-2**31, I32)
    return (a ^ sign) < (b ^ sign)


def _select(conds, vals, default):
    """First-match select (`jnp.select` semantics) as a chain of
    elementwise wheres; boolean values select through logic ops, which
    Mosaic lowers where a select between masks it does not."""
    out = default
    for c, v in zip(reversed(conds), reversed(vals)):
        if out.dtype == jnp.bool_:
            out = (c & v) | (~c & out)
        else:
            out = jnp.where(c, v, out)
    return out


def decode_fields(instr: jax.Array) -> DecodedInstr:
    """Bit-op decode of fetched instruction word(s) (uint32 or int32)."""
    ii = instr.astype(I32)
    return DecodedInstr(
        op=ii & 0x7F,
        rd=(ii >> 7) & 0xF,
        f3=(ii >> 12) & 0x7,
        rs1=(ii >> 15) & 0xF,
        rs2=(ii >> 20) & 0xF,
        sub_bit=(ii >> 30) & 1,
        imm_i=_sx(_srl(ii, 20), 12),
        imm_s=_sx((_srl(ii, 25) << 5) | ((ii >> 7) & 0x1F), 12),
        imm_b=_sx(((ii >> 31) & 1) << 12 | ((ii >> 7) & 1) << 11
                  | ((ii >> 25) & 0x3F) << 5 | ((ii >> 8) & 0xF) << 1, 13),
        imm_u=ii & jnp.asarray(-4096, I32),
        imm_j=_sx(((ii >> 31) & 1) << 20 | ((ii >> 12) & 0xFF) << 12
                  | ((ii >> 20) & 1) << 11 | ((ii >> 21) & 0x3FF) << 1, 21),
    )


def alu_result(a, y, f3, is_sub, is_sra):
    """Shared OP-IMM/OP-REG ALU: f3-selected branchless result."""
    sh = y & 31
    return _select(
        [f3 == 0, f3 == 1, f3 == 2, f3 == 3, f3 == 4, f3 == 5, f3 == 6],
        [jnp.where(is_sub, a - y, a + y),
         a << sh,
         (a < y).astype(I32),
         _ult(a, y).astype(I32),
         a ^ y,
         jnp.where(is_sra, a >> sh, _srl(a, sh)),
         a | y], a & y)


def branch_taken(a, b, f3):
    """BRANCH condition select (f3 in {2,3} never taken, as in `step`)."""
    false = jnp.zeros_like(a, bool)
    ult = _ult(a, b)
    return _select(
        [f3 == 0, f3 == 1, f3 == 2, f3 == 3, f3 == 4, f3 == 5, f3 == 6],
        [a == b, a != b, false, false, a < b, a >= b, ult], ~ult)


def load_value(word, addr, f3):
    """Sub-word load extraction from the fetched memory word."""
    byte = _srl(word, (addr & 3) * 8) & 0xFF
    half = _srl(word, (addr & 2) * 8) & 0xFFFF
    lf3 = jnp.clip(f3, 0, 5)       # matches step's clipped switch
    return _select(
        [lf3 == 0, lf3 == 1, lf3 == 4, lf3 == 5],
        [_sx(byte, 8), _sx(half, 16), byte, half], word)


def store_word(word, addr, b, f3):
    """Read-modify-write merge of the store value into the memory word."""
    sh8 = (addr & 3) * 8
    sh16 = (addr & 2) * 8
    bmask = jnp.left_shift(jnp.asarray(0xFF, I32), sh8)
    hmask = jnp.left_shift(jnp.asarray(0xFFFF, I32), sh16)
    sf3 = jnp.clip(f3, 0, 2)
    return _select(
        [sf3 == 0, sf3 == 1],
        [(word & ~bmask) | (((b & 0xFF) << sh8) & bmask),
         (word & ~hmask) | (((b & 0xFFFF) << sh16) & hmask)], b)


def branchless_commits(d: DecodedInstr, a, b, pc, subset, live, *,
                       read_word, write_word, cost=None):
    """Opcode-gated commit pipeline shared by `step_branchless` and the
    Pallas tile stepper (kernels/iss_stepper.py).

    Computes every commit value — next pc, rd write value/predicate,
    halt, timing class, mix category, and the updated memory — from the
    decoded fields and register operands. Only the memory *ports* are
    injected, because that is all that differs between the steppers
    (indexed gather/scatter vs masked one-hot):

      read_word(widx) -> word          fetched memory word per lane
      write_word(widx, word, neww, is_store) -> mem   committed memory

    `subset` (static) drops opcode classes from the traced graph;
    `live=False` freezes stores, rd writes, and counters. All arithmetic
    is shape-polymorphic over () and (lanes,) operands.

    Returns (next_pc, wr, writes_rd, mem, halt, two_stage, mix_idx,
    ticks); `mem` is None when the subset contains no stores, and
    `ticks` is None when `cost` is None (the timing layer contributes
    nothing to the traced graph when off — cycles-off is the unchanged
    PR-5 graph, not a zeroed tally).
    """
    sub = FULL_SUBSET if subset is None else frozenset(subset)

    def on(*ops):
        return any(o in sub for o in ops)

    op, rd, f3 = d.op, d.rd, d.f3
    pc4 = pc + 4
    false = jnp.zeros_like(live)
    zero = jnp.zeros_like(pc)

    is_load = (op == isa.OP_LOAD) if on(isa.OP_LOAD) else false
    is_store = ((op == isa.OP_STORE) & live) if on(isa.OP_STORE) else false

    # ---- shared memory word port: one read serves loads AND stores
    mem_val = zero
    mem = None
    if on(isa.OP_LOAD, isa.OP_STORE):
        addr = (a + jnp.where(is_store, d.imm_s, d.imm_i)).astype(I32)
        widx = jnp.where(is_load | is_store, addr >> 2, 0)
        word = read_word(widx)
        if on(isa.OP_LOAD):
            mem_val = load_value(word, addr, f3)
        if on(isa.OP_STORE):
            mem = write_word(widx, word, store_word(word, addr, b, f3),
                             is_store)

    # ---- shared ALU serves OP-IMM and OP-REG
    alu_res = zero
    if on(isa.OP_IMM, isa.OP_REG):
        is_reg = (op == isa.OP_REG) if on(isa.OP_REG) else false
        y = jnp.where(is_reg, b, d.imm_i)
        alu_res = alu_result(a, y, f3,
                             is_sub=is_reg & (d.sub_bit == 1),
                             is_sra=(f3 == 5) & (d.sub_bit == 1))

    # ---- next pc
    next_pc = pc4
    if on(isa.OP_BRANCH):
        next_pc = jnp.where(op == isa.OP_BRANCH,
                            jnp.where(branch_taken(a, b, f3),
                                      pc + d.imm_b, pc4), next_pc)
    if on(isa.OP_JAL):
        next_pc = jnp.where(op == isa.OP_JAL, pc + d.imm_j, next_pc)
    if on(isa.OP_JALR):
        next_pc = jnp.where(op == isa.OP_JALR, (a + d.imm_i) & ~1, next_pc)

    # ---- rd write value
    wr = zero
    if on(isa.OP_LUI):
        wr = jnp.where(op == isa.OP_LUI, d.imm_u, wr)
    if on(isa.OP_AUIPC):
        wr = jnp.where(op == isa.OP_AUIPC, pc + d.imm_u, wr)
    if on(isa.OP_JAL, isa.OP_JALR):
        wr = jnp.where((op == isa.OP_JAL) | (op == isa.OP_JALR), pc4, wr)
    if on(isa.OP_LOAD):
        wr = jnp.where(is_load, mem_val, wr)
    if on(isa.OP_IMM, isa.OP_REG):
        wr = jnp.where((op == isa.OP_IMM) | (op == isa.OP_REG),
                       alu_res, wr)

    writes_rd = (op != isa.OP_BRANCH) & (op != isa.OP_STORE) \
        & (op != isa.OP_SYSTEM) & (rd != 0) & live
    halt = (op == isa.OP_SYSTEM) if on(isa.OP_SYSTEM) else false
    two_stage, mix_idx = classify(op, f3)
    ticks = None
    if cost is not None:
        taken, shamt, subword = dynamic_terms(op, f3, a, b, d.imm_i,
                                              subset)
        ticks = timing_ticks(cost, two_stage, mix_idx, taken, shamt,
                             subword)
    return next_pc, wr, writes_rd, mem, halt, two_stage, mix_idx, ticks


def classify(op, f3):
    """(two_stage, mix_idx) per retired instruction — the paper's
    bit-serial timing classes and Fig. 2a mix categories. Identical
    arithmetic to the tail of `step`."""
    is_shift_imm = (op == isa.OP_IMM) & ((f3 == 1) | (f3 == 5))
    is_shift_reg = (op == isa.OP_REG) & ((f3 == 1) | (f3 == 5))
    is_slt = ((op == isa.OP_IMM) | (op == isa.OP_REG)) \
        & ((f3 == 2) | (f3 == 3))
    two_stage = ((op == isa.OP_LOAD) | (op == isa.OP_STORE)
                 | (op == isa.OP_BRANCH) | (op == isa.OP_JAL)
                 | (op == isa.OP_JALR) | is_shift_imm | is_shift_reg
                 | is_slt)
    mix_idx = _select(
        [op == isa.OP_LOAD, op == isa.OP_STORE, op == isa.OP_BRANCH,
         (op == isa.OP_JAL) | (op == isa.OP_JALR),
         is_shift_imm | is_shift_reg,
         (op == isa.OP_IMM) | (op == isa.OP_LUI) | (op == isa.OP_AUIPC),
         op == isa.OP_REG],
        [_MIX_IDX["loads"], _MIX_IDX["stores"], _MIX_IDX["branches"],
         _MIX_IDX["jumps"], _MIX_IDX["shifts"], _MIX_IDX["I-type"],
         _MIX_IDX["R-type"]],
        jnp.full_like(op, _MIX_IDX["system"]))
    return two_stage, mix_idx


def dynamic_terms(op, f3, a, b, imm_i, subset: frozenset = None):
    """Per-instruction dynamic timing events (DESIGN.md §9.10).

    The microarchitectural events the two-bucket model cannot see,
    mirrored verbatim by the PyISS oracle:

      taken   — a BRANCH whose condition held (refetch; jumps always
                redirect and are priced in their base class instead)
      shamt   — effective shift amount of a serial shift (0 otherwise)
      subword — lb/lh/lbu/lhu/sb/sh (read-modify-write word pass)

    `subset` drops the classes from the traced graph exactly like
    `branchless_commits` does. Shape-polymorphic over () and (lanes,).
    """
    sub = FULL_SUBSET if subset is None else frozenset(subset)

    def on(*ops):
        return any(o in sub for o in ops)

    false = jnp.zeros_like(op, bool)
    zero = jnp.zeros_like(op)

    taken = ((op == isa.OP_BRANCH) & branch_taken(a, b, f3)) \
        if on(isa.OP_BRANCH) else false

    shamt = zero
    if on(isa.OP_IMM, isa.OP_REG):
        is_shift = (((op == isa.OP_IMM) | (op == isa.OP_REG))
                    & ((f3 == 1) | (f3 == 5)))
        shamt = jnp.where(is_shift,
                          jnp.where(op == isa.OP_REG, b, imm_i) & 31, 0)

    subword = false
    if on(isa.OP_LOAD):
        lf3 = jnp.clip(f3, 0, 5)       # matches load_value's clip
        subword = subword | ((op == isa.OP_LOAD)
                             & (lf3 != 2) & (lf3 != 3))
    if on(isa.OP_STORE):
        sf3 = jnp.clip(f3, 0, 2)       # matches store_word's clip
        subword = subword | ((op == isa.OP_STORE) & (sf3 != 2))
    return taken, shamt, subword


def timing_ticks(cost, two_stage, mix_idx, taken, shamt, subword):
    """Ticks retired by one instruction under cost row `cost`.

    `cost[i]` is cost entry i, broadcastable against `mix_idx`: one
    (N_COST,) row (the XLA steppers, per lane under vmap), or a
    sequence of per-entry lane arrays (the Pallas stepper). The (stage,
    mix-class) base entry is picked by a select chain over the 8
    classes (no gathers, so the Pallas stepper runs it unchanged), then
    the dynamic entries are added in.
    """
    n = len(MIX_CLASSES)
    classes = [mix_idx == k for k in range(n)]
    zero = jnp.zeros_like(mix_idx)
    one_base = _select(classes, [cost[k] for k in range(n)], zero)
    two_base = _select(classes, [cost[n + k] for k in range(n)], zero)
    base = jnp.where(two_stage, two_base, one_base)
    return (base + taken.astype(I32) * cost[TAKEN_IDX]
            + shamt * cost[SHIFT_IDX]
            + subword.astype(I32) * cost[SUBWORD_IDX])


def opcode_subset(code, reachable_only: bool = False) -> frozenset:
    """Static host-side decode: the opcode classes present in a program.

    Only opcodes that appear in the program text can ever retire (the pc
    always fetches from `code`), so this is a sound per-workload ISA
    subset for `step_branchless`/`step_lanes`.

    `reachable_only=True` tightens the set to opcodes of CFG-reachable
    words via FlexiLint (DESIGN.md §9.11): dead code never retires
    *live* — halted lanes keep fetching the word after their ecall, but
    every commit (and tick tally) is `live`-masked, so dropping
    unreachable opcode classes stays bit-exact. Falls back to the text
    subset when the CFG degrades (indirect jumps etc.).
    """
    if reachable_only:
        from repro.flexibits import analyze
        return analyze.analyze_code(code, mem_words=1).subset
    words = np.asarray(code)
    words = words.view(np.uint32) if words.dtype.itemsize == 4 \
        else words.astype(np.uint32)
    present = {int(o) for o in np.unique(words & np.uint32(0x7F))}
    return frozenset(o for o in _OPCODES if o in present)


def step_branchless(code: jax.Array, s: ISSState,
                    subset: frozenset = None,
                    active: jax.Array = None, *,
                    instr: jax.Array = None,
                    mem_len: jax.Array = None,
                    cost: jax.Array = None, faults=None,
                    lane_key: jax.Array = None,
                    epoch: jax.Array = None) -> ISSState:
    """One branchless step: bit-exact with `step`, no lax.switch/cond.

    `subset` (static) keeps only those opcode classes in the traced graph;
    it must be a superset of `opcode_subset(code)` for bit-exactness.
    `active=False` freezes the state entirely (used by the segment loop to
    park halted lanes without a pytree-wide post-select). `instr`
    overrides the fetch (the packed runtime fetches from a program bank
    with `fetch_banked`) and `mem_len` bounds the memory ports at the
    lane's own word count (clamp-on-read / drop-on-write at the
    program's boundary even when the pool's memory is padded wider);
    the commit pipeline is shared either way.

    Bit-exactness is defined over programs whose fetched words decode to
    RV32E opcodes (everything asm.py / FlexiBench emit). For a word whose
    opcode is outside the ISA both interpreters are junk — `step`'s
    clamped searchsorted dispatches to an arbitrary neighboring class,
    this one retires a no-op — and neither behavior is contractual.
    """
    if instr is None:
        instr = code[(_u(s.pc) >> 2).astype(I32)].astype(U32)
    d = decode_fields(instr)
    a = s.regs[d.rs1]
    b = s.regs[d.rs2]
    live = jnp.ones((), bool) if active is None else active

    def read_word(widx):
        if mem_len is not None:
            widx = jnp.clip(widx, 0, mem_len - 1)
        return s.mem[widx]

    def write_word(widx, word, neww, is_store):
        # non-stores write word back to itself at index 0: a no-op,
        # so the scatter needs no predication beyond the value select.
        # With a per-lane mem bound, a store past the lane's OWN word
        # count also degrades to the no-op write-back (the padded pool
        # drop-on-write); the clamped-read `word` may land in the pad
        # region then, which nothing — port, fetch, or demux — ever
        # reads back.
        if mem_len is not None:
            is_store = is_store & (widx < mem_len)
        return s.mem.at[widx].set(jnp.where(is_store, neww, word))

    next_pc, wr, writes_rd, mem, halt, two_stage, mix_idx, ticks = \
        branchless_commits(d, a, b, s.pc, subset, live,
                           read_word=read_word, write_word=write_word,
                           cost=cost)
    mem = s.mem if mem is None else mem

    # one-hot commit instead of a scatter: an elementwise select over the
    # 16-entry register file fuses into the surrounding arithmetic, where
    # a 1-element scatter is a separate kernel per step on CPU/TPU
    regs = jnp.where((jnp.arange(16, dtype=I32) == d.rd) & writes_rd,
                     wr, s.regs)

    one = live.astype(I32)
    mix_onehot = (jnp.arange(len(MIX_CLASSES), dtype=I32)
                  == mix_idx).astype(I32) * one
    out = ISSState(
        regs=regs,
        pc=jnp.where(live, next_pc.astype(I32), s.pc),
        mem=mem,
        halted=s.halted | (halt & live),
        n_instr=s.n_instr + one,
        n_two_stage=s.n_two_stage + (two_stage & live).astype(I32),
        mix=s.mix + mix_onehot,
        n_cycles=s.n_cycles if ticks is None else s.n_cycles + ticks * one,
    )
    if faults is not None:
        # post-commit fault transform (DESIGN.md §9.14): gated on the
        # lane having retired live AND not halted on this very step —
        # parked lanes draw nothing, and a flip in the halting cycle is
        # architecturally unobservable (identical in every stepper and
        # in the PyISS oracle's post_commit hook)
        out = flexifault.apply_faults(faults, lane_key, epoch, out,
                                      live=live, mem_len=mem_len)
    return out


def step_lanes(code: jax.Array, states: ISSState,
               subset: frozenset = None,
               active: jax.Array = None,
               cost: jax.Array = None, faults=None,
               lane_key: jax.Array = None,
               epoch: jax.Array = None) -> ISSState:
    """Branchless step over a batch of lanes (leading lane axis).

    Decodes once per lane with pure bit ops; every opcode class commits
    via masked where/select, so vmap pays one shared gather + scatter
    instead of per-branch memory ports. Bit-exact with vmap(step).
    `cost` is one shared (N_COST,) row — homogeneous pools run one
    program on one core, so it closes over the vmap unbatched.
    `faults` turns on the per-lane post-commit fault transform
    (`lane_key`/`epoch` are (lanes,) arrays).
    """
    if faults is not None:
        act = jnp.ones(states.pc.shape, bool) if active is None else active
        return jax.vmap(
            lambda a, k, e, s: step_branchless(
                code, s, subset, active=a, cost=cost, faults=faults,
                lane_key=k, epoch=e))(act, lane_key, epoch, states)
    if active is None:
        return jax.vmap(
            lambda s: step_branchless(code, s, subset, cost=cost))(states)
    return jax.vmap(
        lambda a, s: step_branchless(code, s, subset, active=a, cost=cost)
    )(active, states)


def run_segment_lanes(code: jax.Array, states: ISSState, seg_steps: int,
                      max_steps: int, subset: frozenset = None,
                      unroll: int = 1,
                      cost: jax.Array = None, faults=None,
                      lane_key: jax.Array = None,
                      epoch: jax.Array = None) -> ISSState:
    """Lane-parallel segment: up to `seg_steps` branchless steps per lane.

    One while_loop over the whole lane pool (not vmap of scalar loops):
    each iteration advances every still-active lane; lanes that halt or
    exhaust `max_steps` are frozen in place by the `active` mask. The body
    can unroll `unroll` steps per loop trip (substeps past `seg_steps`
    are masked out, so segment boundaries stay exact); the default is 1 —
    on CPU the one-hot-commit step body fuses into few kernels and
    unrolling only bloats codegen, but accelerators with costlier loop
    turnaround can profit. Execution retires the same instruction
    sequence as vmapped `run_segment`, so segmented execution stays
    bit-exact with `iss.run`.
    """
    unroll = max(1, min(unroll, seg_steps))

    def active_of(st: ISSState) -> jax.Array:
        return (~st.halted) & (st.n_instr < max_steps)

    def cond(c):
        k, st = c
        return (k < seg_steps) & active_of(st).any()

    def body(c):
        k, st = c
        for j in range(unroll):
            act = active_of(st) & (k + j < seg_steps)
            st = step_lanes(code, st, subset, active=act, cost=cost,
                            faults=faults, lane_key=lane_key, epoch=epoch)
        return k + unroll, st

    _, out = lax.while_loop(cond, body, (jnp.zeros((), I32), states))
    return out


def step_lanes_banked(bank: jax.Array, code_len: jax.Array,
                      states: ISSState, prog_id: jax.Array,
                      subset: frozenset = None,
                      active: jax.Array = None,
                      mem_len: jax.Array = None,
                      cost: jax.Array = None, faults=None,
                      lane_key: jax.Array = None,
                      epoch: jax.Array = None) -> ISSState:
    """Branchless step over lanes executing *different* programs.

    One batched bank fetch (`fetch_banked`, per-program pc clamp), then
    the exact `step_branchless` commit pipeline per lane — so a lane
    retires precisely what it would retire in a single-program pool
    running its own program. `subset` must cover the union of the bank's
    opcode subsets for bit-exactness; `mem_len` (per-LANE word counts)
    bounds each lane's memory ports at its own program's size; `cost`
    (per-LANE (lanes, N_COST) rows — groups price on different cores)
    turns on the per-lane timing tally.
    """
    instr = fetch_banked(bank, code_len, prog_id, states.pc)
    act = jnp.ones(states.pc.shape, bool) if active is None else active
    if faults is not None:
        # per-lane fault keys/epochs batch; mem_len/cost stay optional
        # (None broadcasts through the vmap as an empty pytree)
        return jax.vmap(
            lambda i, a, m, c, k, e, s: step_branchless(
                bank, s, subset, active=a, instr=i, mem_len=m, cost=c,
                faults=faults, lane_key=k, epoch=e),
            in_axes=(0, 0, None if mem_len is None else 0,
                     None if cost is None else 0, 0, 0, 0),
        )(instr, act, mem_len, cost, lane_key, epoch, states)
    if mem_len is None and cost is None:
        return jax.vmap(
            lambda i, a, s: step_branchless(bank, s, subset, active=a,
                                            instr=i)
        )(instr, act, states)
    if cost is None:
        return jax.vmap(
            lambda i, a, m, s: step_branchless(bank, s, subset, active=a,
                                               instr=i, mem_len=m)
        )(instr, act, mem_len, states)
    if mem_len is None:
        return jax.vmap(
            lambda i, a, c, s: step_branchless(bank, s, subset, active=a,
                                               instr=i, cost=c)
        )(instr, act, cost, states)
    return jax.vmap(
        lambda i, a, m, c, s: step_branchless(bank, s, subset, active=a,
                                              instr=i, mem_len=m, cost=c)
    )(instr, act, mem_len, cost, states)


def run_segment_lanes_banked(bank: jax.Array, code_len: jax.Array,
                             ps: PackedState, seg_steps: int,
                             subset: frozenset = None,
                             mem_len: jax.Array = None,
                             cost: jax.Array = None, faults=None,
                             lane_key: jax.Array = None,
                             epoch: jax.Array = None) -> PackedState:
    """Packed segment: up to `seg_steps` banked steps for every lane.

    The packed-runtime counterpart of `run_segment_lanes`: one
    while_loop over the whole heterogeneous lane pool. Each lane runs
    its own program (`prog_id`) against its own retirement budget
    (`ps.max_steps`, a traced per-lane array rather than a static int,
    because groups in one pool have different budgets); lanes that halt
    or exhaust their budget are frozen by the `active` mask exactly as
    in the homogeneous segment loop. `mem_len` (per-PROGRAM word
    counts, like `code_len`) keeps each lane's memory semantics at its
    own program's boundary when the pool memory is padded wider; `cost`
    (per-PROGRAM (n_progs, N_COST) rows, like `mem_len`) prices each
    lane's retirements on its own program's core; `faults` (with
    per-LANE `lane_key`/`epoch` arrays — fault schedules belong to the
    physical lane, not the program) turns on the post-commit fault
    transform (DESIGN.md §9.14).
    """
    lane_mlen = None if mem_len is None else mem_len[ps.prog_id]
    lane_cost = None if cost is None else cost[ps.prog_id]

    def active_of(st: ISSState) -> jax.Array:
        return (~st.halted) & (st.n_instr < ps.max_steps)

    def cond(c):
        k, st = c
        return (k < seg_steps) & active_of(st).any()

    def body(c):
        k, st = c
        return k + 1, step_lanes_banked(bank, code_len, st, ps.prog_id,
                                        subset, active=active_of(st),
                                        mem_len=lane_mlen,
                                        cost=lane_cost, faults=faults,
                                        lane_key=lane_key, epoch=epoch)

    _, out = lax.while_loop(cond, body, (jnp.zeros((), I32), ps.lanes))
    return PackedState(lanes=out, prog_id=ps.prog_id,
                       max_steps=ps.max_steps)


# ---------------------------------------------------------------------------
# Device-side refill / compaction helpers (DESIGN.md §9.9)
#
# The resident packed runtime never ships lane state to the host between
# segments: retired lanes are detected, their tallies scattered into
# on-device result accumulators, and fresh items swapped in from a staged
# buffer — all inside one jitted, donated op (fleet/engine.py). The
# *semantics* of that swap live here, `branchless_commits`-style: one
# shape-polymorphic definition shared by every stepper, with a banked
# Pallas variant (`kernels/iss_stepper.py::iss_refill`) that reproduces
# the same swap through one-hot ports and must stay bit-identical
# (pinned by tests/test_resident.py).
# ---------------------------------------------------------------------------


def retire_mask(ps: PackedState, item_slot: jax.Array) -> jax.Array:
    """Lanes whose item just finished: occupied (`item_slot >= 0`) and
    halted or out of their OWN step budget. Parked lanes (slot -1) are
    free but have nothing to retire; padding lanes stay parked forever.
    """
    return (item_slot >= 0) & (ps.lanes.halted
                               | (ps.lanes.n_instr >= ps.max_steps))


def refill_take(free: jax.Array, n_staged: jax.Array):
    """Deterministic staged->lane assignment for an on-device refill.

    Free lanes are ranked in lane order (a cumsum compaction); the first `n_staged` of them take staged rows 0..n_staged-1
    in order, so the host — which built the staged batch and will learn
    only the *count* consumed — always knows exactly which item went
    where it matters (into the stream) without reading any lane state.

    Returns `(take, src)`: `take[l]` marks lanes that swap in a fresh
    item, `src[l]` is the staged row a taking lane reads (clipped for
    non-taking lanes, whose gathers are discarded).
    """
    rank = jnp.cumsum(free.astype(I32)) - 1
    take = free & (rank < n_staged)
    src = jnp.clip(rank, 0, free.shape[0] - 1)
    return take, src


def refill_lanes(ps: PackedState, take: jax.Array, src: jax.Array,
                 staged_mems: jax.Array, staged_prog: jax.Array,
                 staged_ms: jax.Array) -> PackedState:
    """Swap fresh items into `take` lanes from staged rows `src`.

    The jnp form of the resident swap (gather staged rows, masked
    reset of the architectural state) — used by the branchless
    stepper, and by the Pallas one under a mesh or DMR; the Pallas
    stepper's single-device variant
    (`kernels/iss_stepper.py::iss_refill`) expresses the same gather
    as a one-hot reduction and is bit-identical.
    """
    t1 = take[:, None]
    lanes = ps.lanes
    return PackedState(
        lanes=ISSState(
            regs=jnp.where(t1, 0, lanes.regs),
            pc=jnp.where(take, 0, lanes.pc),
            mem=jnp.where(t1, staged_mems[src], lanes.mem),
            halted=jnp.where(take, False, lanes.halted),
            n_instr=jnp.where(take, 0, lanes.n_instr),
            n_two_stage=jnp.where(take, 0, lanes.n_two_stage),
            mix=jnp.where(t1, 0, lanes.mix),
            n_cycles=jnp.where(take, 0, lanes.n_cycles)),
        prog_id=jnp.where(take, staged_prog[src], ps.prog_id),
        max_steps=jnp.where(take, staged_ms[src], ps.max_steps))


@functools.partial(jax.jit, static_argnums=(2,))
def run(code: jax.Array, mem: jax.Array, max_steps: int,
        cost: jax.Array = None) -> ISSState:
    """Run to ecall or max_steps. code: (P,) uint32; mem: (M,) int32."""
    s0 = init_state(mem)

    def cond(s):
        return (~s.halted) & (s.n_instr < max_steps)

    return lax.while_loop(cond, lambda s: step(code, s, cost=cost), s0)


def run_segment(code: jax.Array, s: ISSState, seg_steps: int,
                max_steps: int, cost: jax.Array = None) -> ISSState:
    """Resume an ISSState for up to `seg_steps` further instructions.

    The segment primitive of the streaming fleet engine (DESIGN.md §9):
    running `run_segment` repeatedly until `halted` (or `n_instr` reaches
    `max_steps`) retires the exact same instruction sequence as a single
    `run` call, so segmented execution is bit-exact with the monolithic
    while_loop. Not jitted here — fleet/engine.py jits the vmapped form
    with buffer donation.
    """
    def cond(c):
        k, st = c
        return (~st.halted) & (k < seg_steps) & (st.n_instr < max_steps)

    def body(c):
        k, st = c
        return k + 1, step(code, st, cost=cost)

    _, out = lax.while_loop(cond, body, (jnp.zeros((), I32), s))
    return out


def run_fleet(code: jax.Array, mems: jax.Array, max_steps: int,
              cost: jax.Array = None) -> ISSState:
    """vmap over a fleet of items with different memory images."""
    return jax.vmap(lambda m: run(code, m, max_steps, cost))(mems)
