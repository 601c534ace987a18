"""Fused-segment Pallas ISS stepper (DESIGN.md §9.7, packed bank §9.8).

`iss.run_segment_lanes` is plain XLA: every architectural step of the
segment `while_loop` re-materializes the full lane-pool `ISSState`
(regs, pc, mem, halted, counters) through the memory system and
re-dispatches the step body as dozens of separate HLO ops. This kernel
executes ALL `seg_steps` architectural steps of a lane tile inside ONE
`pl.pallas_call` invocation:

- the program *bank* and the tile's regs/pc/mem/halted/counters are read
  from their refs once, live in kernel-resident values (VMEM on TPU) for
  the whole segment, and are written back once at the end;
- the step body is the branchless one-hot commit scheme ported from
  `iss.step_branchless`, with every memory port expressed as a masked
  one-hot reduce/select instead of gather/scatter — the kernel body is
  pure elementwise/reduction work over (words, lanes) tiles, lanes on
  the TPU's 128-wide lane axis;
- the PR-2 opcode-subset DCE (`iss.opcode_subset`) is applied at kernel
  *build* time, so dead opcode classes are never emitted into the kernel
  for a given workload (the RISP specialization knob, one kernel per
  ISA subset);
- the grid runs over lane tiles; each tile's internal `while_loop`
  exits as soon as its own lanes are all halted, mirroring the per-device
  early exit of the shard_map path (§9.6) at tile granularity.

The packed fleet runtime (§9.8) generalizes the fetch: the kernel holds
the whole multi-program bank resident, every lane carries its `prog_id`
and its own `max_steps` budget, and the instruction fetch is a one-hot
selection (on the MXU, over bf16 byte planes) from the *flattened* bank
at index `prog_id * bank_width + clamp(pc >> 2, 0, code_len[prog_id] -
1)` — the per-program clamp of
`iss.fetch_banked`, so each lane retires exactly what it would retire in
a single-program pool running its own program. The single-program entry
point `iss_segment` is the 1-row special case of the same kernel, so the
two paths cannot drift.

Bit-exactness contract: identical to `step_branchless` (and therefore to
`iss.step`/`iss.run`) for programs whose fetched words decode to RV32E
opcodes — including the clamp-on-read / drop-on-write behavior of jax
gathers and scatters at out-of-range addresses, which the one-hot ports
reproduce explicitly (clipped match for the read port, unclipped match
for the write port). Pinned by the instruction-soup and segment-parity
tests in `tests/test_stepper.py` and the packed-parity tests in
`tests/test_packed.py`.

On a TPU backend the kernels always compile to Mosaic (a kernel that
does not compile raises); on any other backend they default to the
Pallas interpreter, so they run, and are tested bit-exact against the
XLA steppers, on the CPU. An explicit `interpret=` overrides either
way.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.flexibits import faults as flexifault
from repro.flexibits import iss
from repro.flexibits.cycles import N_COST
from repro.flexibits.iss import I32, ISSState, PackedState


def _pick_lane_tile(n_lanes: int) -> int:
    """Lanes per grid step. Lanes sit on the TPU's 128-wide lane axis, so
    a pool that is a multiple of 128 tiles at 128 and any other pool runs
    as one full-width block (the engine pads wide pools to a multiple of
    128)."""
    return 128 if n_lanes % 128 == 0 else n_lanes


def _to_bf16_bytes(words: jax.Array) -> jax.Array:
    """(..., K) int32 -> (4, ..., K) bfloat16 byte planes (exact: every
    byte value is an integer bf16 holds without rounding)."""
    return jnp.stack([(words >> (8 * k)) & 0xFF for k in range(4)]
                     ).astype(jnp.bfloat16)


def _from_bytes(rows: jax.Array, n: int) -> jax.Array:
    """Reassemble int32 words from a (4 * n, lanes) f32 byte-plane
    product (the inverse of `_to_bf16_bytes`)."""
    word = jnp.zeros((n, rows.shape[1]), I32)
    for k in range(4):
        word = word | (rows[k * n:(k + 1) * n].astype(I32) << (8 * k))
    return word


def _bank_planes(bank: jax.Array) -> jax.Array:
    """Byte planes of the flattened program bank for the one-hot fetch.

    Word `f` of the flattened bank sits at (hi, lo) = (f // 128,
    f % 128); plane k holds its byte k at row `k * 128 + lo`, column
    `hi`. The fetch then multiplies a one-hot over `hi` on the MXU and
    selects `lo` with a 128-row mask, instead of comparing every lane
    against every bank word.
    """
    flat = bank.reshape(-1).astype(I32)
    n_hi = -(-flat.shape[0] // 128)
    n_hi = -(-n_hi // 128) * 128            # MXU-aligned contraction
    flat = jnp.pad(flat, (0, n_hi * 128 - flat.shape[0]))
    planes = _to_bf16_bytes(flat.reshape(n_hi, 128))   # (4, n_hi, 128)
    return planes.transpose(0, 2, 1).reshape(4 * 128, n_hi)


def _fetch(planes, flat):
    """Instruction word at flat bank index `flat` ((1, TL) lanes)."""
    n_hi = planes.shape[1]
    t = flat.shape[1]
    hi_sel = lax.broadcasted_iota(I32, (n_hi, t), 0) == (flat >> 7)
    rows = jnp.dot(planes, hi_sel.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)     # (512, TL)
    lo_sel = lax.broadcasted_iota(I32, (128, t), 0) == (flat & 127)
    rows = jnp.concatenate(
        [jnp.sum(jnp.where(lo_sel, rows[k * 128:(k + 1) * 128], 0.0),
                 axis=0, keepdims=True) for k in range(4)])  # (4, TL)
    return _from_bytes(rows, 1)


def _step_tile(planes, lane_base, lane_len, lane_mlen, lane_cost,
               regs, pc, mem, halted, n_instr, n_two, mix, n_cyc,
               live, subset, faults=None, lane_key=None, epoch=None):
    """One branchless architectural step over a lane tile.

    Lanes run along the last axis: per-lane values are (1, TL), and the
    register file, memory and mix counters are (16, TL), (M, TL) and
    (8, TL). The opcode-gated commit pipeline is the SAME code as
    `iss.step_branchless` (`iss.branchless_commits` and its shared
    decode/ALU/branch/load-store/classify helpers), so the semantics
    cannot drift. What this function owns is only the data movement:
    the instruction fetch (`_fetch`), register reads, and the memory
    word ports are masked one-hot reductions/selects, so the kernel body
    contains no gather/scatter at all. The fetch reads the flattened
    program bank through each lane's `lane_base`/`lane_len` (both
    segment-constant), reproducing the per-program pc clamp of
    `iss.fetch_banked`; `lane_mlen` bounds the memory word ports at each
    lane's OWN word count, so clamp-on-read / drop-on-write happen at
    the lane's program boundary even when the pool memory is padded
    wider. `live=False` freezes a lane completely. `subset` is static
    — opcode classes outside it are dropped from the kernel at build
    time, and `lane_cost=None` (timing off) drops the whole cycle tally.
    """
    t = pc.shape[1]
    iota_mem = lax.broadcasted_iota(I32, mem.shape, 0)
    iota_reg = lax.broadcasted_iota(I32, (16, t), 0)
    iota_mix = lax.broadcasted_iota(I32, mix.shape, 0)

    # ---- fetch: per-program clipped index into the flattened bank ==
    # jax's clamp-on-read gather against each lane's own program
    flat = lane_base + jnp.clip(iss._srl(pc, 2), 0, lane_len - 1)
    d = iss.decode_fields(_fetch(planes, flat))

    # ---- register read port: one-hot over the 16-entry file
    def read_reg(idx):
        return jnp.sum(jnp.where(iota_reg == idx, regs, 0), axis=0,
                       keepdims=True)

    a = read_reg(d.rs1)
    b = read_reg(d.rs2)

    # ---- memory word ports: a clipped one-hot read (clamp-on-read, as
    # jax gathers) and an UNCLIPPED one-hot write select (out-of-range
    # stores drop, as jax scatters)
    def read_word(widx):
        rsel = iota_mem == jnp.clip(widx, 0, lane_mlen - 1)
        return jnp.sum(jnp.where(rsel, mem, 0), axis=0, keepdims=True)

    def write_word(widx, word, neww, is_store):
        wsel = (iota_mem == widx) & (is_store & (widx < lane_mlen))
        return jnp.where(wsel, neww, mem)

    next_pc, wr, writes_rd, new_mem, halt, two_stage, mix_idx, ticks = \
        iss.branchless_commits(d, a, b, pc, subset, live,
                               read_word=read_word, write_word=write_word,
                               cost=lane_cost)
    mem = mem if new_mem is None else new_mem

    # ---- one-hot register-file and mix commits (elementwise)
    regs = jnp.where((iota_reg == d.rd) & writes_rd, wr, regs)
    one = live.astype(I32)
    mix = mix + ((iota_mix == mix_idx) & live).astype(I32)
    pc = jnp.where(live, next_pc, pc)
    halted = halted | (halt & live)
    n_instr = n_instr + one
    if faults is not None:
        # post-commit fault transform (DESIGN.md §9.14): the SAME
        # one-hot arithmetic as the XLA steppers, on lane-major views,
        # gated exactly like their commits — live this step and not
        # halted by it. `lane_key`/`epoch` are segment constants.
        regs_l, pc_l, mem_l = flexifault.apply_fault_arrays(
            faults, lane_key[0], epoch[0], regs.T, pc[0], mem.T,
            n_instr[0], (live & ~halted)[0], mem_len=lane_mlen[0])
        regs, pc, mem = regs_l.T, pc_l[None], mem_l.T
    return (regs, pc, mem, halted, n_instr,
            n_two + (two_stage & live).astype(I32), mix,
            n_cyc if ticks is None else n_cyc + ticks * one)


def _segment_kernel(planes_ref, base_ref, len_ref, mlen_ref, ms_ref,
                    *refs, seg_steps: int, subset, timing: bool,
                    faults=None):
    """Mega-step: all `seg_steps` architectural steps of one lane tile.

    State is read from the refs ONCE, carried through the segment loop as
    kernel-resident values, and written back ONCE — the per-step state
    round-trip of the XLA steppers never leaves the kernel. The bank
    planes and each lane's fetch base/length, memory bound, step budget
    (and, with `timing`, cost row) are segment constants. `faults`
    (static) gates the post-commit fault transform: on, two extra
    per-lane refs (fault key, epoch) lead the state refs; off, they are
    not inputs at all.
    """
    refs = list(refs)
    lane_cost = None
    if timing:
        cost_ref = refs.pop(0)
        lane_cost = [cost_ref[i:i + 1, :] for i in range(N_COST)]
    lane_key = epoch = None
    if faults is not None:
        lane_key = refs.pop(0)[...]
        epoch = refs.pop(0)[...]
    (regs_ref, pc_ref, mem_ref, halt_ref, ni_ref, n2_ref, mix_ref,
     ncyc_ref, oregs_ref, opc_ref, omem_ref, ohalt_ref, oni_ref,
     on2_ref, omix_ref, oncyc_ref) = refs
    planes = planes_ref[...]
    lane_base = base_ref[...]
    lane_len = len_ref[...]
    lane_mlen = mlen_ref[...]
    max_steps = ms_ref[...]

    # `halted` rides the loop as int32: Mosaic cannot carry a mask
    carry = (jnp.zeros((), I32), regs_ref[...], pc_ref[...], mem_ref[...],
             halt_ref[...], ni_ref[...], n2_ref[...], mix_ref[...],
             ncyc_ref[...])

    def active_of(halted, n_instr):
        return (halted == 0) & (n_instr < max_steps)

    def cond(c):
        k, _, _, _, halted, n_instr, _, _, _ = c
        return (k < seg_steps) & jnp.any(active_of(halted, n_instr))

    def body(c):
        k, regs, pc, mem, halted, n_instr, n2, mix, ncyc = c
        act = active_of(halted, n_instr)
        regs, pc, mem, halted, n_instr, n2, mix, ncyc = _step_tile(
            planes, lane_base, lane_len, lane_mlen, lane_cost, regs,
            pc, mem, halted != 0, n_instr, n2, mix, ncyc, act, subset,
            faults=faults, lane_key=lane_key, epoch=epoch)
        return (k + 1, regs, pc, mem, halted.astype(I32), n_instr, n2,
                mix, ncyc)

    _, regs, pc, mem, halted, n_instr, n2, mix, ncyc = \
        lax.while_loop(cond, body, carry)
    oregs_ref[...] = regs
    opc_ref[...] = pc
    omem_ref[...] = mem
    ohalt_ref[...] = halted
    oni_ref[...] = n_instr
    on2_ref[...] = n2
    omix_ref[...] = mix
    oncyc_ref[...] = ncyc


def _lane_major(lanes: ISSState) -> list:
    """ISSState -> kernel layout: lanes on the last axis, `halted` as
    int32 (the kernel's refs are 2-D int32)."""
    return [lanes.regs.T, lanes.pc[None], lanes.mem.T,
            lanes.halted.astype(I32)[None], lanes.n_instr[None],
            lanes.n_two_stage[None], lanes.mix.T, lanes.n_cycles[None]]


def _from_lane_major(out) -> ISSState:
    regs, pc, mem, halted, n_instr, n_two, mix, n_cyc = out
    return ISSState(regs=regs.T, pc=pc[0], mem=mem.T, halted=halted[0] != 0,
                    n_instr=n_instr[0], n_two_stage=n_two[0], mix=mix.T,
                    n_cycles=n_cyc[0])


def _lane_specs(arrays, tile):
    """Lane-tiled BlockSpecs for 2-D lane-major arrays."""
    return [pl.BlockSpec((a.shape[0], tile), lambda i: (0, i))
            for a in arrays]


def iss_segment_banked(bank: jax.Array, code_len: jax.Array,
                       state: PackedState, *, seg_steps: int,
                       subset=None, mem_len: Optional[jax.Array] = None,
                       cost: Optional[jax.Array] = None, faults=None,
                       lane_key: Optional[jax.Array] = None,
                       epoch: Optional[jax.Array] = None,
                       interpret: Optional[bool] = None) -> PackedState:
    """Fused packed segment: every lane runs ITS OWN bank program.

    The packed-runtime counterpart of `iss_segment` (and the fused form
    of `iss.run_segment_lanes_banked`, bit-exact with it): the whole
    (n_progs, width) program bank is resident in the kernel as byte
    planes (`_bank_planes`), each lane tile carries its lanes' fetch
    base, code length, memory bound and `max_steps` budget, and the
    fetch is a per-program-clamped one-hot over the flattened bank.
    `mem_len` (per-program word counts, like `code_len`) bounds each
    lane's memory ports at its own program's size; None means the
    padded pool width is every program's true size. `cost` (per-program
    (n_progs, N_COST) rows, like `mem_len`) turns on the per-lane cycle
    tally — None keeps the timing layer out of the kernel entirely.
    `faults` (a faults.FaultSpec, with per-LANE `lane_key` uint32 keys
    and int32 retry `epoch`s) turns on the post-commit fault transform
    (DESIGN.md §9.14) — None adds neither the inputs nor any kernel
    code. `subset` must cover the union of the bank's opcode subsets —
    either the text-derived `iss.opcode_subset` per program, or
    FlexiLint's tighter reachable-only subsets (`analyze.Analysis.subset`,
    DESIGN.md §9.11): unreachable words are fetched at most by halted
    lanes, whose commits and tick tallies this kernel `live`-masks
    exactly like `step_branchless`, so the DCE stays bit-exact.

    The per-program tables are gathered per lane outside the kernel
    (`prog_id` is a segment constant), and the state crosses the kernel
    boundary lane-major; the kernel's state buffers are aliased
    input->output. `interpret=None` runs the compiled Mosaic kernel on a
    TPU and the Pallas interpreter on any other backend.
    """
    if seg_steps < 1:
        raise ValueError("seg_steps must be >= 1")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lanes = state.lanes
    n_lanes, mem_words = lanes.mem.shape
    n_progs, bank_width = bank.shape
    pid = state.prog_id
    lane_mlen = (jnp.full((n_lanes,), mem_words, I32) if mem_len is None
                 else mem_len[pid])
    consts = [(pid * bank_width)[None], code_len[pid][None],
              lane_mlen[None], state.max_steps[None]]
    timing = cost is not None
    if timing:
        consts.append(cost[pid].T)
    if faults is not None and not faults.off:
        consts += [lane_key[None], epoch.astype(I32)[None]]
    else:
        faults = None
    tile = _pick_lane_tile(n_lanes)
    sub = None if subset is None else frozenset(subset)
    planes = _bank_planes(bank)
    st = _lane_major(lanes)
    n_in = 1 + len(consts)

    out = pl.pallas_call(
        functools.partial(_segment_kernel, seg_steps=seg_steps,
                          subset=sub, timing=timing, faults=faults),
        grid=(n_lanes // tile,),
        in_specs=[pl.BlockSpec(planes.shape, lambda i: (0, 0))]
        + _lane_specs(consts + st, tile),
        out_specs=_lane_specs(st, tile),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in st],
        # state buffers update in place; the bank planes and the per-lane
        # constants are read-only
        input_output_aliases={n_in + j: j for j in range(len(st))},
        interpret=interpret,
    )(planes, *consts, *st)
    return PackedState(lanes=_from_lane_major(out), prog_id=pid,
                       max_steps=state.max_steps)


def _refill_kernel(take_ref, src_ref, planes_ref,
                   regs_ref, pc_ref, mem_ref, halt_ref, ni_ref, n2_ref,
                   mix_ref, ncyc_ref, pid_ref, ms_ref,
                   oregs_ref, opc_ref, omem_ref, ohalt_ref, oni_ref,
                   on2_ref, omix_ref, oncyc_ref, opid_ref, oms_ref):
    """One-hot staged->lane swap for a lane tile (DESIGN.md §9.9).

    The resident runtime's compaction/scatter expressed the way the
    fused stepper expresses its fetch: each taking lane's staged row
    (memory image, program row and step budget packed as one row of
    byte planes) is selected by a one-hot product on the MXU instead of
    a row gather. The take/src assignment itself (`iss.refill_take`, a
    pool-wide cumsum) is computed outside, because ranks cross lane
    tiles. Bit-identical to `iss.refill_lanes`.
    """
    take = take_ref[...] != 0
    src = src_ref[...]
    planes = planes_ref[...]
    n_rows = planes.shape[1]
    width = planes.shape[0] // 4
    mem_words = mem_ref.shape[0]
    onehot = (lax.broadcasted_iota(I32, (n_rows, take.shape[1]), 0)
              == src) & take
    rows = _from_bytes(
        jnp.dot(planes, onehot.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32), width)
    oregs_ref[...] = jnp.where(take, 0, regs_ref[...])
    opc_ref[...] = jnp.where(take, 0, pc_ref[...])
    omem_ref[...] = jnp.where(take, rows[:mem_words], mem_ref[...])
    ohalt_ref[...] = jnp.where(take, 0, halt_ref[...])
    oni_ref[...] = jnp.where(take, 0, ni_ref[...])
    on2_ref[...] = jnp.where(take, 0, n2_ref[...])
    omix_ref[...] = jnp.where(take, 0, mix_ref[...])
    oncyc_ref[...] = jnp.where(take, 0, ncyc_ref[...])
    opid_ref[...] = jnp.where(take, rows[mem_words:mem_words + 1],
                              pid_ref[...])
    oms_ref[...] = jnp.where(take, rows[mem_words + 1:mem_words + 2],
                             ms_ref[...])


def iss_refill(state: PackedState, take: jax.Array, src: jax.Array,
               staged_mems: jax.Array, staged_prog: jax.Array,
               staged_ms: jax.Array, *,
               interpret: Optional[bool] = None) -> PackedState:
    """Banked Pallas variant of `iss.refill_lanes` — same swap, one-hot
    ports, gridded over lane tiles with state aliased input->output so
    the donated lane pool updates in place. The staged batch is small
    (<= chunk rows), so its byte planes are replicated to every tile
    like the program bank in `iss_segment_banked`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lanes = state.lanes
    n_lanes, mem_words = lanes.mem.shape
    n_rows = staged_mems.shape[0]
    payload = jnp.concatenate(
        [staged_mems.astype(I32), staged_prog.astype(I32)[:, None],
         staged_ms.astype(I32)[:, None]], axis=1)
    width = -(-(mem_words + 2) // 8) * 8
    rows_p = -(-n_rows // 128) * 128                # MXU-aligned
    payload = jnp.pad(payload, ((0, rows_p - n_rows),
                                (0, width - mem_words - 2)))
    planes = _to_bf16_bytes(payload.T).reshape(4 * width, rows_p)
    tile = _pick_lane_tile(n_lanes)
    st = _lane_major(lanes) + [state.prog_id[None], state.max_steps[None]]
    lane_in = [take.astype(I32)[None], src.astype(I32)[None]]

    out = pl.pallas_call(
        _refill_kernel,
        grid=(n_lanes // tile,),
        in_specs=_lane_specs(lane_in, tile)
        + [pl.BlockSpec(planes.shape, lambda i: (0, 0))]
        + _lane_specs(st, tile),
        out_specs=_lane_specs(st, tile),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in st],
        # lane-pool state updates in place (take/src/staged planes,
        # inputs 0-2, are read-only refill constants)
        input_output_aliases={3 + j: j for j in range(len(st))},
        interpret=interpret,
    )(*lane_in, planes, *st)
    return PackedState(lanes=_from_lane_major(out[:8]), prog_id=out[8][0],
                       max_steps=out[9][0])


def iss_segment(code: jax.Array, state: ISSState, *, seg_steps: int,
                max_steps: int, subset=None,
                cost: Optional[jax.Array] = None, faults=None,
                lane_key: Optional[jax.Array] = None,
                epoch: Optional[jax.Array] = None,
                interpret: Optional[bool] = None) -> ISSState:
    """Fused-segment stepper: up to `seg_steps` steps for every lane.

    Drop-in replacement for `iss.run_segment_lanes` — bit-exact with it
    (and with `iss.run`) over RV32E programs. The grid runs over lane
    tiles (`_pick_lane_tile`); each tile's segment executes inside a
    single kernel invocation with state resident for the whole
    segment. State buffers are aliased input->output.

    Implemented as the 1-row special case of the packed-bank kernel
    (`iss_segment_banked`): a singleton bank, every lane on row 0 with a
    uniform `max_steps` budget — the flat one-hot fetch then clamps to
    `n_code - 1` exactly as the dedicated single-program fetch did, so
    the single- and multi-program paths share one kernel and cannot
    drift.

    `subset` is the static opcode subset (`iss.opcode_subset`): classes
    outside it are never emitted into the kernel. `interpret=None`
    resolves by backend — the compiled Mosaic kernel on TPU, the
    run-anywhere interpreter fallback elsewhere (the package's CPU
    convention); pass an explicit bool to override. Not jitted here —
    the fleet engine jits (and donates through) the wrapped call.
    """
    n_lanes = state.pc.shape[0]
    packed = PackedState(
        lanes=state,
        prog_id=jnp.zeros((n_lanes,), I32),
        max_steps=jnp.full((n_lanes,), max_steps, I32))
    out = iss_segment_banked(
        code[None, :], jnp.asarray([code.shape[0]], I32), packed,
        seg_steps=seg_steps, subset=subset,
        cost=None if cost is None else cost[None, :],
        faults=faults, lane_key=lane_key, epoch=epoch,
        interpret=interpret)
    return out.lanes
