"""FlexiFault — deterministic fault injection for the lane steppers
(DESIGN.md §9.14).

Flexible ICs run at far lower yield and far higher variability than
silicon; this module gives the fleet runtime an adversarial-state layer
with the same cannot-drift discipline as the steppers themselves. A
fault schedule is a pure function of

    (spec.seed, lane, epoch, n_instr)

with no sampler state to carry: per-lane base keys come from
`jax.random.fold_in` (host-side, cached), and every per-step draw is a
murmur3-finalizer hash (`mix32`) of the lane key, the lane's retry/refit
`epoch`, and the post-commit `n_instr` counter. The identical integer
arithmetic exists twice — shape-polymorphic jnp (used verbatim by the
switch, branchless, and Pallas steppers) and masked pure-Python (the
PyISS fault oracle) — so all four produce bit-identical faulty
trajectories for the same schedule (pinned by tests/test_faults.py).

Fault model (post-commit transform, applied after every *live* retired
instruction; the halting instruction itself is exempt — a flip in the
cycle the machine stops is architecturally unobservable):

- ``transient``: with probability `rate` per retired instruction, flip
  one bit in one enabled target — a register (x1..x15), a data-memory
  word (within the lane's own `mem_len`), or the pc (bits 2..11, so the
  pc stays word-aligned and the clamp-on-read fetch contract holds).
- ``stuck``: with probability `rate` per *lane*, one drawn register bit
  is forced to a drawn value after every live step (a manufacturing
  defect; epoch-independent, so retries cannot clear it).
- ``dead``: with probability `rate` per *lane*, the whole register file
  reads zero after every live step (a dead lane; epoch-independent).

The transform is elementwise one-hot arithmetic — no gather/scatter —
so the Pallas tile stepper runs it unchanged inside the fused kernel.
With `spec=None` (or a transient rate of exactly 0) the transform is
dropped from the traced graph entirely, keeping the fault-free graphs
byte-identical to the pre-FlexiFault steppers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

I32 = jnp.int32
U32 = jnp.uint32

_TARGETS = ("regs", "mem", "pc")
_MASK32 = 0xFFFFFFFF

# derivation salts (arbitrary odd constants, shared with the oracle)
_T1 = 0x9E3779B9      # fire draw -> index draw
_T2 = 0x632BE59B      # index draw -> bit draw
_STUCK = 0x27220A95   # per-lane stuck-at decision
_DEAD = 0x85157AF5    # per-lane dead-lane decision


def _u(v):
    return v.astype(U32)


def _c(v: int):
    """uint32 constant (python ints > 2**31 overflow weak int32)."""
    return jnp.asarray(v, U32)


def mix32(x):
    """murmur3 finalizer over uint32 (shape-polymorphic jnp).

    The one hash every draw is built from. Multiplications wrap mod
    2**32 (uint32 arithmetic); `mix32_py` is the bit-identical
    pure-Python mirror used by the PyISS fault oracle.
    """
    x = x ^ (x >> 16)
    x = x * jnp.asarray(0x85EBCA6B, U32)
    x = x ^ (x >> 13)
    x = x * jnp.asarray(0xC2B2AE35, U32)
    x = x ^ (x >> 16)
    return x


def mix32_py(x: int) -> int:
    """Pure-Python mirror of `mix32` (masked 32-bit arithmetic)."""
    x &= _MASK32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & _MASK32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & _MASK32
    x ^= x >> 16
    return x


def width_scaled_rate(rate: float, width: int) -> float:
    """Per-retired-instruction transient rate for a `width`-bit serial
    core: a narrower datapath holds each instruction in flight for more
    cycles (cycles/instr ~ 32/width, cycles.py), so its exposure window
    per retirement is proportionally longer."""
    return min(1.0, rate * (32.0 / float(width)))


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Static description of a fault schedule (hashable — it keys the
    jitted-runner caches in fleet/engine.py, so two streams with the
    same spec share compiled graphs).

    `rate` is per retired instruction for ``transient`` and per lane
    for ``stuck``/``dead``. `targets` picks the transient flip targets
    (canonical order; ignored by stuck/dead, which are register-file
    defects). Use `for_core` to derive the width-scaled rate of a
    specific core from a technology base rate.
    """
    rate: float
    seed: int = 0
    targets: Tuple[str, ...] = ("regs",)
    mode: str = "transient"

    def __post_init__(self):
        if self.mode not in ("transient", "stuck", "dead"):
            raise ValueError(f"unknown fault mode {self.mode!r}")
        bad = set(self.targets) - set(_TARGETS)
        if bad or not self.targets:
            raise ValueError(f"targets must be a non-empty subset of "
                             f"{_TARGETS}, got {self.targets!r}")
        # canonicalize target order so equal specs hash equal
        object.__setattr__(self, "targets",
                           tuple(t for t in _TARGETS if t in self.targets))
        if not 0.0 <= self.rate <= 1.0:
            raise ValueError(f"rate must be in [0, 1], got {self.rate}")

    @property
    def threshold(self) -> int:
        """uint32 fire threshold: draw < threshold fires."""
        return min(_MASK32, int(round(self.rate * 4294967296.0)))

    @property
    def always(self) -> bool:
        """rate >= 1: fire unconditionally (statically, no draw)."""
        return self.rate >= 1.0

    @property
    def off(self) -> bool:
        """A schedule that can never fire — the transform is dropped
        from the traced graph entirely (the fault-free graph)."""
        return self.threshold == 0 and not self.always

    def for_core(self, core) -> "FaultSpec":
        """Width-scaled copy of this spec for `core` (cycles.Core)."""
        return dataclasses.replace(
            self, rate=width_scaled_rate(self.rate, core.width))


@functools.lru_cache(maxsize=64)
def lane_keys(seed: int, n_lanes: int) -> np.ndarray:
    """Per-lane uint32 base keys: `fold_in(PRNGKey(seed), lane)`, both
    key words xored down to 32 bits. Host-side and cached — the engine
    derives them once per stream; the PyISS oracle calls the same
    function, so lane l's schedule is identical everywhere."""
    base = jax.random.PRNGKey(seed)
    kd = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(n_lanes, dtype=U32))
    kd = np.asarray(kd, np.uint32)
    out = kd[:, 0] ^ kd[:, 1]
    out.setflags(write=False)
    return out


# ---------------------------------------------------------------------------
# The post-commit transform (jnp, shape-polymorphic)
# ---------------------------------------------------------------------------


def apply_fault_arrays(spec: Optional[FaultSpec], lane_key, epoch,
                       regs, pc, mem, n_instr, gate, mem_len=None):
    """Post-commit fault transform over architectural arrays.

    Shape-polymorphic exactly like `iss.branchless_commits`: a scalar
    lane (`regs` (16,), `pc`/`n_instr`/`gate` (), `mem` (M,)) or a lane
    tile (leading lane axis on everything, `regs` (L, 16), `mem`
    (L, M)). `gate` must already exclude lanes that are halted *after*
    the commit; `mem_len` bounds the transient memory-word draw at the
    lane's own word count (None: the full pool width). All arithmetic
    is elementwise/one-hot — the Pallas kernel runs this unchanged.

    Returns (regs, pc, mem); with `spec=None` or an off schedule the
    inputs pass through untouched (nothing enters the traced graph).
    """
    if spec is None or spec.off:
        return regs, pc, mem
    key = _u(lane_key)
    thr = jnp.asarray(spec.threshold, U32)
    iota16 = jnp.arange(16, dtype=I32)

    if spec.mode == "dead":
        hit = mix32(key ^ _c(_DEAD)) < thr
        dead = gate if spec.always else (gate & hit)
        return jnp.where(dead[..., None], 0, regs), pc, mem

    if spec.mode == "stuck":
        sk = mix32(key ^ _c(_STUCK))
        hit = gate if spec.always else (gate & (sk < thr))
        s1 = mix32(sk ^ _c(_T1))
        reg = (1 + ((s1 >> 8) % 15)).astype(I32)
        mask = jnp.left_shift(jnp.asarray(1, U32), s1 % 32).astype(I32)
        sel = (iota16 == reg[..., None]) & hit[..., None]
        stuck_one = (s1 >> 5) & 1
        forced = jnp.where((stuck_one == 1)[..., None],
                           regs | mask[..., None],
                           regs & ~mask[..., None])
        return jnp.where(sel, forced, regs), pc, mem

    # ---- transient: one draw per retired instruction
    k = mix32(key ^ mix32(_u(epoch)))
    h0 = mix32(k ^ _u(n_instr))
    fire = gate if spec.always else (gate & (h0 < thr))
    h1 = mix32(h0 ^ _c(_T1))
    h2 = mix32(h1 ^ _c(_T2))
    t = h1 % len(spec.targets)
    bit = h2 % 32
    bmask = jnp.left_shift(jnp.asarray(1, U32), bit).astype(I32)

    if "regs" in spec.targets:
        f = fire & (t == spec.targets.index("regs"))
        reg = (1 + ((h1 >> 8) % 15)).astype(I32)
        sel = (iota16 == reg[..., None]) & f[..., None]
        regs = jnp.where(sel, regs ^ bmask[..., None], regs)
    if "mem" in spec.targets:
        f = fire & (t == spec.targets.index("mem"))
        mwords = mem.shape[-1]
        ml = jnp.asarray(mwords, U32) if mem_len is None else _u(mem_len)
        word = ((h1 >> 8) % ml).astype(I32)
        iota_mem = jnp.arange(mwords, dtype=I32)
        wsel = (iota_mem == word[..., None]) & f[..., None]
        mem = jnp.where(wsel, mem ^ bmask[..., None], mem)
    if "pc" in spec.targets:
        f = fire & (t == spec.targets.index("pc"))
        pmask = jnp.left_shift(jnp.asarray(1, U32),
                               2 + (h2 % 10)).astype(I32)
        pc = jnp.where(f, pc ^ pmask, pc)
    return regs, pc, mem


def apply_faults(spec: Optional[FaultSpec], lane_key, epoch, state,
                 live=None, mem_len=None):
    """ISSState-level wrapper over `apply_fault_arrays`.

    `state` is an `iss.ISSState` (scalar or lane-batched) *after* its
    commit; `live` is the pre-step active mask (None: all live). The
    gate excludes post-commit halted lanes — the halting instruction's
    own flip window is unobservable. Returns the state with regs/pc/mem
    possibly flipped; everything else passes through.
    """
    if spec is None or spec.off:
        return state
    gate = ~state.halted if live is None else (live & ~state.halted)
    regs, pc, mem = apply_fault_arrays(
        spec, lane_key, epoch, state.regs, state.pc, state.mem,
        state.n_instr, gate, mem_len=mem_len)
    return state._replace(regs=regs, pc=pc, mem=mem)


def arch_digest(regs, pc, mem, halted, n_instr, n_two_stage, n_cycles,
                mix):
    """Per-lane 32-bit digest of the architectural state and the tallies
    an item reports.

    The DMR pair compare (fleet/engine.py): two lanes that executed
    the same item fault-free have equal digests; any surviving state
    corruption shows up as an inequality. The two-stage, cycle and
    instruction-mix tallies are folded in too: a fault that only
    changes what an item reports (a flipped shift amount, overwritten
    before anything reads it, still costs its serial-shift ticks) must
    roll back like one that changes the state. Position-mixed so
    permuted corruption cannot cancel; uint32 sums wrap, which is fine
    — the digest is a determinism check, not cryptography.
    """
    rpos = mix32(_u(jnp.arange(16, dtype=I32)) + 1)
    mpos = mix32(_u(jnp.arange(mem.shape[-1], dtype=I32)) + 17)
    xpos = mix32(_u(jnp.arange(mix.shape[-1], dtype=I32)) + 0x3C6EF372)
    d = jnp.sum(mix32(_u(regs) ^ rpos), axis=-1)
    d = d + jnp.sum(mix32(_u(mem) ^ mpos), axis=-1)
    d = d + jnp.sum(mix32(_u(mix) ^ xpos), axis=-1)
    d = d + mix32(_u(pc) ^ _c(0x7FB5D329))
    d = d + mix32(_u(n_instr) ^ _c(0x2B7E1516))
    d = d + mix32(_u(n_two_stage) ^ _c(0x243F6A88))
    d = d + mix32(_u(n_cycles) ^ _c(0x13198A2E))
    return d + halted.astype(U32)


# ---------------------------------------------------------------------------
# PyISS fault oracle (pure Python, bit-identical draws)
# ---------------------------------------------------------------------------


def _s32(v: int) -> int:
    v &= _MASK32
    return v - 0x100000000 if v >= 0x80000000 else v


class FaultOracle:
    """Post-commit hook for `pyiss.PyISS` — the fault oracle.

    Attach as ``p.post_commit = FaultOracle(spec, lane_key)``; PyISS
    calls it after every non-halting retired instruction, exactly where
    the jnp steppers apply `apply_fault_arrays`, with bit-identical
    draws. `fired` counts transient fires (for stuck/dead it is 1 per
    application while the lane defect is active).
    """

    def __init__(self, spec: FaultSpec, lane_key: int, epoch: int = 0):
        self.spec = spec
        self.lane_key = int(lane_key) & _MASK32
        self.epoch = int(epoch) & _MASK32
        self.fired = 0
        # per-lane (epoch-independent) defect decisions
        sk = mix32_py(self.lane_key ^ _STUCK)
        self._stuck = spec.mode == "stuck" and \
            (spec.always or sk < spec.threshold)
        s1 = mix32_py(sk ^ _T1)
        self._stuck_reg = 1 + ((s1 >> 8) % 15)
        self._stuck_mask = 1 << (s1 % 32)
        self._stuck_one = (s1 >> 5) & 1
        dk = mix32_py(self.lane_key ^ _DEAD)
        self._dead = spec.mode == "dead" and \
            (spec.always or dk < spec.threshold)

    def __call__(self, iss):
        spec = self.spec
        if spec.off:
            return
        if spec.mode == "dead":
            if self._dead:
                iss.regs = [0] * 16
                self.fired += 1
            return
        if spec.mode == "stuck":
            if self._stuck:
                r = self._stuck_reg
                w = iss.regs[r] & _MASK32
                w = (w | self._stuck_mask) if self._stuck_one \
                    else (w & ~self._stuck_mask)
                iss.regs[r] = _s32(w)
                self.fired += 1
            return
        # ---- transient
        k = mix32_py(self.lane_key ^ mix32_py(self.epoch))
        h0 = mix32_py(k ^ (iss.n_instr & _MASK32))
        if not spec.always and h0 >= spec.threshold:
            return
        self.fired += 1
        h1 = mix32_py(h0 ^ _T1)
        h2 = mix32_py(h1 ^ _T2)
        t = spec.targets[h1 % len(spec.targets)]
        bmask = 1 << (h2 % 32)
        if t == "regs":
            r = 1 + ((h1 >> 8) % 15)
            iss.regs[r] = _s32((iss.regs[r] & _MASK32) ^ bmask)
        elif t == "mem":
            w = (h1 >> 8) % len(iss.mem)
            iss.mem[w] = _s32((int(iss.mem[w]) & _MASK32) ^ bmask)
        else:  # pc: flip a word-aligned bit (2..11)
            iss.pc = _s32((iss.pc & _MASK32) ^ (1 << (2 + (h2 % 10))))


# ---------------------------------------------------------------------------
# Measurement: SDC / derating vs the golden fault-free PyISS run
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FaultReport:
    """Per-workload resilience rates (AVF-style, DESIGN.md §9.14).

    Of `exposed` trials (>= 1 fault actually fired), each is one of:
    `masked` (architecturally invisible — final memory and every
    FlexiLint-live register match the golden run), `derated` (a
    detectable deviation: halt status or retirement count differ — what
    a watchdog/budget check catches), or `sdc` (silent data corruption:
    the run completes on time but the visible state is wrong). Flips
    that only land in provably-dead registers (never read by any
    CFG-reachable instruction) are masked by construction of the
    comparison, not counted as corruption.
    """
    n_trials: int
    exposed: int
    masked: int
    derated: int
    sdc: int
    live_regs: Tuple[int, ...]

    @property
    def sdc_rate(self) -> float:
        return self.sdc / self.exposed if self.exposed else 0.0

    @property
    def derate_rate(self) -> float:
        return self.derated / self.exposed if self.exposed else 0.0

    @property
    def avf(self) -> float:
        """Architectural vulnerability: visible failures / exposures."""
        return (self.sdc + self.derated) / self.exposed \
            if self.exposed else 0.0


def measure_rates(code, mems, *, max_steps: int, spec: FaultSpec,
                  analysis=None) -> FaultReport:
    """Golden-vs-faulty differential over a batch of items.

    Runs every item twice through PyISS — fault-free and with the
    item's lane schedule (`lane_keys(spec.seed, n_items)[i]`, epoch 0)
    — and classifies each exposed trial per `FaultReport`. Register
    comparison is masked by FlexiLint liveness: only registers read by
    some reachable instruction (`analyze.read_registers`) count; a CFG
    degrade falls back to all 15 (conservative — nothing masked).
    """
    from repro.flexibits import analyze, pyiss

    code = np.asarray(code)
    mems = np.asarray(mems)
    n_items, mem_words = mems.shape
    if analysis is None:
        analysis = analyze.analyze_code(code, mem_words)
    if analysis.degraded:
        live = tuple(range(1, 16))
    else:
        live = tuple(sorted(analyze.read_registers(analysis)))
    keys = lane_keys(spec.seed, n_items)

    exposed = masked = derated = sdc = 0
    for i in range(n_items):
        golden = pyiss.PyISS(code, mem_words, init_mem=mems[i])
        golden.run(max_steps)
        faulty = pyiss.PyISS(code, mem_words, init_mem=mems[i])
        oracle = FaultOracle(spec, int(keys[i]))
        faulty.post_commit = oracle
        faulty.run(max_steps)
        if oracle.fired == 0:
            continue
        exposed += 1
        if golden.halted != faulty.halted \
                or golden.n_instr != faulty.n_instr:
            derated += 1
        elif np.array_equal(golden.mem, faulty.mem) and all(
                golden.regs[r] == faulty.regs[r] for r in live):
            masked += 1
        else:
            sdc += 1
    return FaultReport(n_trials=n_items, exposed=exposed, masked=masked,
                       derated=derated, sdc=sdc, live_regs=live)
