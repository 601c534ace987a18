"""Plain RV32E interpreter with the FlexiBits cycle model: the fleet cells'
reference.

One item at a time, one instruction at a time, in Python integers. It
follows the simulator's published contract: 16 registers (register
fields are taken mod 16), word reads clamp to the last memory word,
writes past the end are dropped, fetch clamps to the code image, the
ecall halts, and the per-item tick tally wraps in int32. Timing events
follow the paper's two-stage split (section 4.2) and the dynamic terms
of the cycle model: a taken branch refetches, a serial shift pays per
shift-amount bit, a subword load or store pays a read-modify-write.

It shares no code with the system under test.
"""
from __future__ import annotations

import numpy as np

# opcode field values (RV32I base encoding)
LUI, AUIPC, JAL, JALR = 0x37, 0x17, 0x6F, 0x67
BRANCH, LOAD, STORE, IMM, REG, SYSTEM = 0x63, 0x03, 0x23, 0x13, 0x33, 0x73

# Fig. 2a mix classes, in the simulator's order
MIX = ("loads", "stores", "branches", "jumps", "shifts", "I-type", "R-type",
       "system")
N_MIX = len(MIX)
TICKS_PER_CYCLE = 20
# cost-row layout: [0:8] one-stage ticks per class, [8:16] two-stage,
# then taken-branch refetch, per-bit serial shift, subword RMW
TAKEN, SHIFT, SUBWORD = 2 * N_MIX, 2 * N_MIX + 1, 2 * N_MIX + 2
N_COST = 2 * N_MIX + 3


def cost_row(core: dict, dynamic: bool) -> np.ndarray:
    """Ticks per event for a core given as {width, a, b} (Table 7).

    A one-stage instruction takes 32/w + a cycles and a two-stage one
    64/w + b; with `dynamic` a taken branch adds 32/w, a shift 1/w per
    shift-amount bit and a subword access 32/w.
    """
    w = int(core["width"])
    row = np.zeros(N_COST, np.int64)
    row[:N_MIX] = 640 // w + round(TICKS_PER_CYCLE * core["a"])
    row[N_MIX:2 * N_MIX] = 1280 // w + round(TICKS_PER_CYCLE * core["b"])
    if dynamic:
        row[TAKEN] = 640 // w
        row[SHIFT] = 20 // w
        row[SUBWORD] = 640 // w
    return row


def _sx(v: int, bits: int) -> int:
    v &= (1 << bits) - 1
    return v - (1 << bits) if v >= 1 << (bits - 1) else v


def _s32(v: int) -> int:
    return _sx(v, 32)


def _u32(v: int) -> int:
    return v & 0xFFFFFFFF


def _decode(word: int):
    op = word & 0x7F
    rd = (word >> 7) & 0xF
    f3 = (word >> 12) & 7
    rs1 = (word >> 15) & 0xF
    rs2 = (word >> 20) & 0xF
    f7 = (word >> 25) & 0x7F
    if op in (LUI, AUIPC):
        imm = _s32(word & 0xFFFFF000)
    elif op == JAL:
        imm = _sx((((word >> 31) & 1) << 20) | (((word >> 12) & 0xFF) << 12)
                  | (((word >> 20) & 1) << 11)
                  | (((word >> 21) & 0x3FF) << 1), 21)
    elif op == BRANCH:
        imm = _sx((((word >> 31) & 1) << 12) | (((word >> 7) & 1) << 11)
                  | (((word >> 25) & 0x3F) << 5)
                  | (((word >> 8) & 0xF) << 1), 13)
    elif op == STORE:
        imm = _sx(((word >> 25) << 5) | ((word >> 7) & 0x1F), 12)
    else:
        imm = _sx(word >> 20, 12)
    return op, rd, f3, rs1, rs2, f7, imm


class Item:
    """The outcome of one item: what the simulator reports per item."""
    __slots__ = ("out", "halted", "n_instr", "n_two_stage", "n_cycles",
                 "mix")

    def as_tuple(self):
        return (self.out, self.halted, self.n_instr, self.n_two_stage,
                self.n_cycles)


def run_item(code, mem_image, *, out_addr: int, max_steps: int,
             cost: np.ndarray) -> Item:
    """Run one item from its initial memory image to the ecall or the
    step budget."""
    prog = [_decode(int(w)) for w in np.asarray(code, np.uint32)]
    n_code = len(prog)
    mem = [int(v) for v in np.asarray(mem_image, np.int64)]
    n_mem = len(mem)
    cost = [int(c) for c in cost]
    regs = [0] * 16
    pc = 0
    n = two_stage = ticks = 0
    mix = [0] * N_MIX
    halted = False

    def widx(addr):
        return _s32(addr) >> 2

    def load_word(addr):
        return _s32(mem[max(0, min(widx(addr), n_mem - 1))])

    def store_word(addr, val):
        i = widx(addr)
        if 0 <= i < n_mem:
            mem[i] = _s32(val)

    def sub_shift(addr, nbytes):
        return ((addr & 3) if nbytes == 1 else (addr & 2)) * 8

    while not halted and n < max_steps:
        op, rd, f3, rs1, rs2, f7, imm = prog[min(max(pc >> 2, 0), n_code - 1)]
        a, b = regs[rs1], regs[rs2]
        nxt = pc + 4
        wr = None
        two = False
        taken = subword = False
        shamt = 0
        if op == LUI:
            wr, cls = imm, 5
        elif op == AUIPC:
            wr, cls = _s32(pc + imm), 5
        elif op == JAL:
            wr, cls, two = pc + 4, 3, True
            nxt = pc + imm
        elif op == JALR:
            wr, cls, two = pc + 4, 3, True
            nxt = _u32(a + imm) & ~1
        elif op == BRANCH:
            cls, two = 2, True
            taken = {0: a == b, 1: a != b, 4: a < b, 5: a >= b,
                     6: _u32(a) < _u32(b), 7: _u32(a) >= _u32(b)}[f3]
            if taken:
                nxt = pc + imm
        elif op == LOAD:
            cls, two = 0, True
            addr = _u32(a + imm)
            if f3 == 2:
                wr = load_word(addr)
            else:
                nbytes = 1 if f3 in (0, 4) else 2
                subword = True
                v = (_u32(load_word(addr & ~3)) >> sub_shift(addr, nbytes)) \
                    & ((1 << 8 * nbytes) - 1)
                wr = _sx(v, 8 * nbytes) if f3 in (0, 1) else v
        elif op == STORE:
            cls, two = 1, True
            addr = _u32(a + imm)
            if f3 not in (0, 1):
                store_word(addr, b)
            else:
                nbytes = 1 if f3 == 0 else 2
                subword = True
                sh = sub_shift(addr, nbytes)
                mask = ((1 << 8 * nbytes) - 1) << sh
                w = _u32(load_word(addr & ~3))
                store_word(addr & ~3, (w & ~mask) | ((_u32(b) << sh) & mask))
        elif op in (IMM, REG):
            rhs = imm if op == IMM else b
            cls = 5 if op == IMM else 6
            if f3 == 0:
                sub = op == REG and f7 & 0x20
                wr = _s32(a - rhs if sub else a + rhs)
            elif f3 == 1:
                shamt = rhs & 31
                wr, cls, two = _s32(a << shamt), 4, True
            elif f3 == 2:
                wr, two = int(a < rhs), True
            elif f3 == 3:
                wr, two = int(_u32(a) < _u32(rhs)), True
            elif f3 == 4:
                wr = _s32(a ^ rhs)
            elif f3 == 5:
                shamt = rhs & 31
                cls, two = 4, True
                wr = a >> shamt if f7 & 0x20 else _s32(_u32(a) >> shamt)
            elif f3 == 6:
                wr = _s32(a | rhs)
            else:
                wr = _s32(a & rhs)
        elif op == SYSTEM:
            cls = 7
            halted = True
        else:
            raise ValueError(f"bad opcode {op:#x} at pc={pc}")
        if wr is not None and rd:
            regs[rd] = _s32(wr)
        pc = nxt
        n += 1
        two_stage += two
        mix[cls] += 1
        t = cost[cls + N_MIX * two] + shamt * cost[SHIFT]
        if taken:
            t += cost[TAKEN]
        if subword:
            t += cost[SUBWORD]
        ticks = _s32(ticks + t)
    item = Item()
    item.out = _s32(mem[out_addr]) if out_addr else 0
    item.halted = halted
    item.n_instr = n
    item.n_two_stage = two_stage
    item.n_cycles = ticks
    item.mix = mix
    return item
