"""Chunked streaming fleet executor with early-exit segmentation.

The monolithic path (`flexibits.fleet.run_fleet_sharded`) vmaps one
while_loop over the whole fleet: every SIMD lane is occupied until the
*slowest* item halts, and the host materializes all item memories at once.
This engine fixes both (DESIGN.md §9):

- **Chunked streaming.** Items flow through a fixed pool of `chunk` lanes;
  the host only ever holds O(chunk) memory images (the per-item *scalar*
  results — counts, halt flags, output words — are O(fleet), which is what
  makes 10M+ item runs feasible). Lane buffers are donated back to XLA
  between segments, so device memory is a single chunk-sized allocation.

- **Early-exit segmentation.** The interpreter runs in bounded cycle
  segments (default 4096). Between segments, halted lanes are harvested,
  compacted out, and refilled from the stream, so aggregate simulated
  lane-steps track the fleet's *actual* halt distribution instead of the
  worst case. Segmented execution retires the exact instruction sequence
  of `iss.run`, so final memories are bit-exact with the monolithic path.

- **Packed multi-program runtime** (`run_packed`, DESIGN.md §9.8). A
  heterogeneous `FleetPlan` no longer drains group by group: programs
  are padded into a bank, every lane carries its program row + step
  budget, and freed lanes are backfilled with items from ANY pending
  group, so one group's halt-time tail hides behind the others' backlog
  and the whole plan runs as one stream.

- **Resident runtime** (DESIGN.md §9.9). Retire/refill runs as one
  donated on-device op against an asynchronously staged batch, the
  per-segment host sync collapses to one small stats read overlapped
  with the next segment's execution, and an optional superstep
  controller (`adaptive=True`) adapts each segment's step bound to the
  observed halt cadence.

- **Shard-local multi-device streaming** (DESIGN.md §9.12). Under a
  mesh, every device shard owns its lanes, its slice of the staged
  refill batch, its admission/prefetch cursors, and its own block of
  `ResidentAcc` rows; retire/refill runs as a per-shard `shard_map`
  body and the per-segment host read is ONE stacked (n_shards, 3+G)
  stats vector — the segment loop contains zero cross-device
  collectives and per-item results are demuxed exactly once at drain.
  The single-device path is literally the 1-shard special case of the
  same code. Resident state (lane pool + accumulators + staging
  cursors) checkpoints mid-flight through `distributed/checkpoint.py`
  (`checkpoint_dir=`/`checkpoint_every=`) and resumes bit-exactly,
  including onto a different mesh shape.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import checkpoint as dckpt
from repro.distributed import sharding as dsharding
from repro.flexibench.base import Workload
from repro.flexibits import faults as flexifault
from repro.flexibits import iss
from repro.flexibits.cycles import N_COST
from repro.kernels import iss_stepper

STEPPERS = ("branchless", "pallas")
REDUNDANCY = ("none", "dmr")   # executed redundancy modes (§9.14; the
                               # carbon planner additionally PRICES tmr)

# resident-runtime bounds: the on-device per-group mix rows are int32
# (a plan that could pass this in one row flushes them to the host's
# int64 totals as it runs, see `_stream_resident`), and keep_state's
# O(fleet) device rows are capped (`run_packed` refuses a plan past it)
_RESIDENT_MIX_LIMIT = 2**31 - 1
_RESIDENT_KEEP_STATE_WORDS = 1 << 27   # ~512 MB of int32 device rows

# DMR pair compares inside a segment (§9.14): under a transient schedule
# a pair is compared often enough to expect at most COMPARE_FAULTS
# faults between compares, but never more often than every
# MIN_COMPARE_STEPS steps (a compare reads the whole lane pool)
COMPARE_FAULTS = 1 / 512
MIN_COMPARE_STEPS = 32

# source protocol: source(start, count) -> (count, mem_words) int32
Source = Callable[[int, int], np.ndarray]


def array_source(mems: np.ndarray) -> Source:
    """Stream an in-memory (n_items, M) array (parity tests, small fleets)."""
    mems = np.asarray(mems, np.int32)

    def src(start: int, count: int) -> np.ndarray:
        return mems[start:start + count]

    return src


def workload_source(w: Workload, seed: int = 0,
                    gen_block: int = 256) -> Source:
    """O(chunk) on-demand input generation for one workload.

    Generation is batched over fixed *aligned* blocks of `gen_block`
    items: item i's inputs are row `i % gen_block` of
    `w.gen_inputs(default_rng([seed, i // gen_block]), gen_block)`. The
    aligned block an item falls in is a pure function of its index, so
    the fleet is identical no matter how the engine's refill boundaries
    slice the stream (chunk/seg_steps are pure performance knobs) —
    while the host hot path pays one Generator construction and one
    vectorized `gen_inputs` call per block instead of per item.
    `gen_block` is part of the stream's identity (a different block size
    is a different — equally valid — fleet), not an engine tuning knob.

    The last generated block is cached: the engine consumes items in
    stream order, so a request straddling a block boundary reuses the
    cached block instead of regenerating it.
    """
    base = w.initial_memory(np.zeros(w.n_inputs, np.int32))
    gen_block = max(1, gen_block)
    cache = {"blk": -1, "xs": None}

    def block(blk: int) -> np.ndarray:
        if cache["blk"] != blk:
            rng = np.random.default_rng([seed, blk])
            cache["xs"] = np.asarray(w.gen_inputs(rng, gen_block), np.int32)
            cache["blk"] = blk
        return cache["xs"]

    def src(start: int, count: int) -> np.ndarray:
        if count <= 0:
            return np.zeros((0, base.size), np.int32)
        parts = []
        i = start
        while i < start + count:
            blk, off = divmod(i, gen_block)
            k = min(gen_block - off, start + count - i)
            parts.append(block(blk)[off:off + k])
            i += k
        xs = parts[0] if len(parts) == 1 else np.concatenate(parts)
        mems = np.tile(base, (count, 1))
        mems[:, :xs.shape[1]] = xs
        return mems

    return src


class _Prefetcher:
    """Double-buffered async host refill (DESIGN.md §9.6).

    Source generation is host work (per-item RNG, memory-image assembly);
    segment execution is device work. A one-worker executor keeps exactly
    one `block`-sized fetch in flight, so generating the next chunk of
    items overlaps the device segment instead of serializing after it.
    The engine consumes items strictly in stream order, so a single
    pending future is a full double buffer. `background=False` degrades
    to synchronous fetches (for sources that aren't thread-safe).
    """

    def __init__(self, source: Source, n_items: int, block: int,
                 background: bool = True):
        self._source = source
        self._n = n_items
        self._block = max(1, block)
        self._cursor = 0          # next un-requested item
        self._taken = 0           # items handed to the engine so far
        self._buf: Optional[np.ndarray] = None
        self._off = 0
        self._fut = None
        self._fut_span = (0, 0)   # [start, start+count) of the fetch
        self._err: Optional[BaseException] = None
        self._closed = False
        self._ex = concurrent.futures.ThreadPoolExecutor(max_workers=1) \
            if background else None
        if self._ex is not None:
            self._submit()

    def _submit(self):
        count = min(self._block, self._n - self._cursor)
        if count > 0:
            start = self._cursor
            self._cursor += count
            self._fut_span = (start, count)
            self._fut = self._ex.submit(self._source, start, count)
        else:
            self._fut = None

    def _fetch_failed(self, exc: BaseException, start: int,
                      count: int) -> RuntimeError:
        """Wrap a source exception with the stream context the bare
        traceback lacks (which source, which item span, where the
        engine's cursor was) and latch it: the background worker's
        error must surface on the *next* take(), never vanish with
        the future, and every later take() must keep failing."""
        self._err = exc
        self._fut = None
        return RuntimeError(
            f"prefetch source {self._source!r} raised while fetching "
            f"items [{start}:{start + count}) of {self._n} (stream "
            f"cursor {self._taken}): {exc!r}")

    def take(self, count: int) -> np.ndarray:
        """Next `count` item memories, in stream order.

        Requests past the declared stream length fail loudly with the
        full cursor state — "exhausted" alone is undebuggable when a
        plan/group/source disagrees with the engine about `n_items`.
        """
        if self._closed:
            raise RuntimeError("prefetcher is closed: take() after "
                               "close() at stream cursor "
                               f"{self._taken}, n_items={self._n}")
        if self._err is not None:
            raise RuntimeError(
                f"prefetch source {self._source!r} already failed "
                f"(stream cursor {self._taken}, n_items={self._n}); "
                f"the stream cannot continue") from self._err
        if self._taken + count > self._n:
            raise RuntimeError(
                f"source stream exhausted: requested {count} item(s) at "
                f"stream cursor {self._taken}, but the source holds only "
                f"{self._n} item(s) "
                f"({self._n - self._taken} item(s) remaining)")
        self._taken += count
        if self._ex is None:
            start = self._cursor
            self._cursor += count
            try:
                return np.asarray(self._source(start, count), np.int32)
            except Exception as e:
                raise self._fetch_failed(e, start, count) from e
        parts = []
        while count > 0:
            if self._buf is None or self._off >= len(self._buf):
                if self._fut is None:
                    raise RuntimeError(
                        f"source stream exhausted: no fetch in flight at "
                        f"stream cursor {self._taken}, request cursor "
                        f"{self._cursor}, n_items={self._n}")
                try:
                    self._buf = np.asarray(self._fut.result(), np.int32)
                except Exception as e:
                    raise self._fetch_failed(e, *self._fut_span) from e
                self._off = 0
                self._submit()          # refill the second buffer now
            k = min(count, len(self._buf) - self._off)
            parts.append(self._buf[self._off:self._off + k])
            self._off += k
            count -= k
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def close(self):
        """Cancel/drain the in-flight fetch and join the worker.
        Idempotent — the engine closes on every exit path (including
        unwinding from an exception that may itself have closed).

        `shutdown(wait=False)` would leave a running background fetch
        alive past close — a leaked non-daemon thread still calling the
        source after the engine returned (or raised). Cancel the pending
        future if it has not started; if it is already running, drain it
        (`wait=True`) so the source is never invoked after close().
        """
        if self._closed:
            return
        self._closed = True
        if self._ex is not None:
            self._ex.shutdown(wait=True, cancel_futures=True)
            self._fut = None


@dataclasses.dataclass
class FleetResult:
    """Per-item scalars plus engine-level accounting for one stream run."""
    n_items: int
    n_instr: np.ndarray          # (n,) retired instructions per item
    n_two_stage: np.ndarray      # (n,)
    halted: np.ndarray           # (n,) bool (False = max_steps exhausted)
    out: np.ndarray              # (n,) word at out_addr (0 if no out_addr)
    mix: np.ndarray              # (8,) retired-instruction mix, fleet total
    lane_steps: int              # SIMD lane-step slots the engine executed
    n_segments: int
    chunk: int
    seg_steps: int
    wall_s: float
    stepper: str = "branchless"
    n_devices: int = 1
    # full final state, only populated with keep_state=True (O(fleet) host
    # memory — for parity tests and the legacy ISSState wrapper)
    mems: Optional[np.ndarray] = None    # (n, M)
    regs: Optional[np.ndarray] = None    # (n, 16)
    pc: Optional[np.ndarray] = None      # (n,)
    mix_items: Optional[np.ndarray] = None  # (n, 8)
    # per-item accumulated timing ticks (§9.10) — populated when the
    # group ran with a cycle-cost row, None for cycles-off runs
    n_cycles: Optional[np.ndarray] = None   # (n,)

    @property
    def busy_steps(self) -> int:
        """Lane-steps that retired a real instruction (useful work)."""
        return int(self.n_instr.sum())

    @property
    def monolithic_lane_steps(self) -> int:
        """Cost of the one-shot vmap(while_loop) on the same fleet: every
        lane runs (masked) until the slowest item halts."""
        if self.n_items == 0:
            return 0
        return int(self.n_items) * int(self.n_instr.max())

    @property
    def items_per_s(self) -> float:
        return self.n_items / self.wall_s if self.wall_s > 0 else float("inf")


def run_stream(code: np.ndarray, source: Source, *, n_items: int,
               mem_words: int, max_steps: int, chunk: int = 256,
               seg_steps: int = 4096, out_addr: Optional[int] = None,
               keep_state: bool = False,
               mesh: Optional[Mesh] = None,
               stepper: Optional[str] = None,
               subset: Optional[frozenset] = None,
               prefetch: bool = True,
               adaptive: bool = False,
               cost: Optional[np.ndarray] = None,
               faults: Optional[flexifault.FaultSpec] = None,
               redundancy: str = "none",
               max_retries: int = 2) -> FleetResult:
    """Stream `n_items` memory images from `source` through `chunk` lanes.

    Returns per-item scalars in item order. With `keep_state=True` the
    full final state (memories, registers, pc) is also collected — O(fleet)
    host memory, so only use it for parity checks or small fleets.

    `stepper` picks the segment interpreter: "branchless" (lane-parallel
    masked-select stepper, DESIGN.md §9.5) or "pallas" (fused-segment
    kernel — the whole segment of a lane tile runs inside one kernel
    invocation with state resident, §9.7); None (the default) leaves
    the choice to `run_packed`. `subset` optionally pins the static
    opcode subset for the branchless/pallas steppers; by default it is
    derived from the program text (`iss.opcode_subset`), letting the
    compiler drop opcode classes the workload can never retire. With a
    `mesh`, lanes are sharded over every mesh axis and each device steps
    its shard independently via shard_map (DESIGN.md §9.6). `prefetch`
    overlaps host-side source generation with device segments (double
    buffering).

    Implemented as the single-group special case of the packed
    multi-program runtime (`run_packed`, DESIGN.md §9.8) — one stream
    loop serves both, so the sync/harvest/refill subtleties exist in
    exactly one place — with the run's whole-pool accounting (lane-step
    slots including padding lanes, segment count, measured wall clock)
    folded back into the returned `FleetResult`. `adaptive` turns on
    the superstep controller exactly as in `run_packed` (DESIGN.md
    §9.9); the per-segment host sync is one small async stats read.

    `cost` optionally turns on the per-lane timing layer (DESIGN.md
    §9.10): an (N_COST,) int32 cycle-cost row (`cycles.cost_row`) priced
    per retired instruction into each item's `n_cycles` tally.
    Architectural results are bit-identical with and without it.
    """
    results, stats = run_packed(
        [PackedGroup(code=code, source=source, n_items=n_items,
                     max_steps=max_steps, mem_words=mem_words,
                     out_addr=out_addr, cost=cost)],
        chunk=chunk, seg_steps=seg_steps, keep_state=keep_state,
        mesh=mesh, stepper=stepper, subset=subset, prefetch=prefetch,
        adaptive=adaptive, faults=faults, redundancy=redundancy,
        max_retries=max_retries)
    return dataclasses.replace(
        results[0], lane_steps=stats.lane_steps,
        n_segments=stats.n_segments, chunk=stats.chunk,
        wall_s=stats.wall_s)


# ---------------------------------------------------------------------------
# Packed multi-program fleet runtime (DESIGN.md §9.8)
#
# `run_stream` executes ONE program; a heterogeneous FleetPlan run group
# by group pays each group's tail idle (the last segments where only a
# few long-running items hold the whole lane pool), its own retrace, and
# its own host<->device round-trips. The packed runtime multiplexes every
# group through one stream: programs live in a padded program bank, each
# lane carries the bank row it is executing (`iss.PackedState.prog_id`)
# plus its own step budget, and the admission scheduler backfills every
# freed lane with an item from ANY pending group — proportional to the
# groups' remaining backlogs, so all groups drain together and the tail
# of one group is hidden behind the backlog of the others.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackedGroup:
    """One group's inputs to the packed runtime (engine-level: program +
    item source; fleet/plan.py builds these from a FleetPlan)."""
    code: np.ndarray                  # program words (uint32 or int32)
    source: Source
    n_items: int
    max_steps: int
    mem_words: int
    out_addr: Optional[int] = None
    # optional (N_COST,) int32 cycle-cost row (cycles.cost_row) — turns
    # on per-lane n_cycles accounting for this group's items (§9.10)
    cost: Optional[np.ndarray] = None
    # optional static opcode subset for this program (e.g. FlexiLint's
    # reachable-only subset, DESIGN.md §9.11). The packed bank shares
    # one traced graph, so the run uses the union over groups; None
    # falls back to the text-derived `iss.opcode_subset(code)`.
    subset: Optional[frozenset] = None


@dataclasses.dataclass
class PackedStats:
    """Whole-run accounting of one packed stream (the per-group
    `FleetResult`s carry only the lane-step slots attributable to their
    own active lanes; idle/padding slots belong to the run).

    The sync-stats fields (DESIGN.md §9.9) make the host<->device
    cadence a first-class output: `host_syncs` counts every blocking
    device->host read the run performed (the `fleet.sync` spans),
    `sync_wait_s` the host time spent inside them, and `refill_wall_s`
    the host time spent in the `fleet.restock` spans that stage items. How long the device itself sat idle is read from a
    profiler trace, where these spans share the device planes' clock.
    `seg_schedule` records the seg_steps actually used per segment —
    constant for a fixed run, the controller's trace for an adaptive
    one (pinned deterministic by tests/test_resident.py).

    The shard-local fields (DESIGN.md §9.12) attribute the run to the
    mesh: `n_shards` is the lane-pool shard count (1 single-device),
    and `shard_retired`/`shard_lane_steps` break
    items retired and lane-step slots down per shard, so a scaling
    regression is attributable from the stats alone."""
    n_groups: int
    n_progs: int
    bank_width: int
    lane_steps: int               # chunk x max-step-delta, summed
    n_segments: int
    chunk: int
    seg_steps: int
    wall_s: float
    stepper: str
    n_devices: int
    adaptive: bool = False
    host_syncs: int = 0           # blocking device->host reads
    sync_wait_s: float = 0.0      # host time blocked in those reads
    refill_wall_s: float = 0.0    # host time assembling/staging refills
    seg_schedule: tuple = ()      # seg_steps used, one entry per segment
    n_shards: int = 1             # lane-pool shards (§9.12)
    shard_retired: tuple = ()     # items retired per shard (resident)
    shard_lane_steps: tuple = ()  # lane-step slots per shard (resident)
    # resilience counters (§9.14) — populated by fault-injection / DMR
    # runs. `sdc` (silent data corruption) is structurally zero here:
    # only a golden fault-free cross-check can count corruptions the
    # detector missed (that measurement lives in
    # `flexibits.faults.measure_rates`); the field exists so callers
    # that DO hold a golden run can fill in one complete record.
    redundancy: str = "none"
    detected: int = 0             # DMR digest mismatches observed
    corrected: int = 0            # pair rollbacks that re-executed
    quarantined: int = 0          # pairs permanently retired from pool
    discarded: int = 0            # lane-steps mismatching pairs threw away
    sdc: int = 0


class _SyncClock:
    """The stream loop's host clock and its program spans.

    `span(name)` opens a `jax.profiler.TraceAnnotation`: with a profiler
    running it lands on the host plane, on the same clock as the device
    planes, and otherwise costs a few microseconds. The fleet's spans
    are `fleet.job`, `fleet.static` and `fleet.report` (`fleet/plan.py`)
    and, here, `fleet.stream` (first restock to the end of the drain),
    `fleet.dispatch` (the refill and segment calls), `fleet.sync` (one
    per blocking device->host read, opened by `fetch`), `fleet.restock`,
    `fleet.upload`, `fleet.checkpoint` and `fleet.drain`. `sync_wait_s`
    and `refill_wall_s` total the `fleet.sync` and `fleet.restock` spans
    (DESIGN.md §9.9)."""

    def __init__(self):
        self.host_syncs = 0
        self.sync_wait_s = 0.0
        self.refill_wall_s = 0.0

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                if name == "fleet.sync":
                    self.sync_wait_s += dt
                elif name == "fleet.restock":
                    self.refill_wall_s += dt

    def fetch(self, x) -> np.ndarray:
        with self.span("fleet.sync"):
            out = np.asarray(x)
        self.host_syncs += 1
        return out


class _SuperstepController:
    """Adaptive superstep sizing (DESIGN.md §9.9).

    Tracks an EMA of the pool's finish hazard (retirements per executed
    pool-step) and picks the next segment length from a small
    power-of-two ladder below the configured `seg_steps`: when churn is
    high, shorter segments return finished lanes to the admission
    scheduler sooner (a lane that halts early in a long segment sits
    frozen — wasted occupancy — until the segment ends); when the pool
    is all long-lived tails the hazard decays and segments grow back to
    the cap, keeping the sync count low. The ladder is bounded (<= 6
    values), so the lru-cached segment runners stay bounded too — one
    compile per ladder rung, ever. Decisions are a pure function of the
    observed (retired, steps) sequence, so a plan+seed reruns to an
    identical segment schedule.
    """

    LADDER_SPAN = 16       # smallest rung = seg_steps / 16
    TARGET_FRAC = 0.25     # aim for ~chunk/4 retirements per segment
    EMA = 0.5

    def __init__(self, seg_steps: int, chunk: int, enabled: bool):
        base = max(1, seg_steps)
        rungs = {base}
        v = base
        while v > max(1, base // self.LADDER_SPAN):
            v = max(1, v // 2)
            rungs.add(v)
        self.ladder = tuple(sorted(rungs))
        self.base = base
        self.enabled = enabled
        self.target = max(1.0, self.TARGET_FRAC * chunk)
        self.rate = 0.0            # EMA of retirements per pool-step
        self.schedule = []

    def record(self, n_retired: int, steps: int):
        if steps > 0:
            self.rate = (self.EMA * (n_retired / steps)
                         + (1.0 - self.EMA) * self.rate)

    def next_seg(self) -> int:
        seg = self.base
        if self.enabled:
            for s in self.ladder:  # smallest rung meeting the target
                if self.rate * s >= self.target:
                    seg = s
                    break
        self.schedule.append(seg)
        return seg


def _apportion(slots: int, remaining) -> np.ndarray:
    """Admission policy: split `slots` free lanes over groups
    proportionally to their remaining backlogs (largest-remainder
    rounding, ties to the lower group index — deterministic).

    Proportional shares keep every pending group flowing and drain all
    groups at roughly the same time, so no group is left to run its tail
    alone at the end of the stream. Per-group results do not depend on
    the policy at all (item i of group g is a pure function of the
    group's source), only wall-clock does.
    """
    remaining = np.asarray(remaining, np.int64)
    total = int(remaining.sum())
    slots = min(int(slots), total)
    take = np.zeros(len(remaining), np.int64)
    if slots <= 0:
        return take
    quota = slots * remaining / total
    take = np.minimum(np.floor(quota).astype(np.int64), remaining)
    left = slots - int(take.sum())
    if left > 0:
        frac = np.where(remaining > take, quota - take, -1.0)
        for g in np.argsort(-frac, kind="stable")[:left]:
            take[g] += 1
    return take


def _packed_state_specs(mesh: Mesh, mem_words: int):
    """Shard specs for a packed lane pool (`lane_specs` maps each leaf
    of the abstract `iss.PackedState` by its rank)."""
    abstract = jax.eval_shape(lambda: iss.PackedState(
        lanes=jax.vmap(iss.init_state)(jnp.zeros((1, mem_words), iss.I32)),
        prog_id=jnp.zeros(1, iss.I32), max_steps=jnp.zeros(1, iss.I32)))
    return dsharding.lane_specs(mesh, abstract)


def compare_steps(faults: Optional[flexifault.FaultSpec],
                  seg_steps: int) -> int:
    """Steps between DMR pair compares in a segment of `seg_steps`.

    Under a transient schedule, `seg_steps` halved until a pair (two
    lanes at `faults.rate` a step each) expects at most COMPARE_FAULTS
    faults between compares, stopping at MIN_COMPARE_STEPS; a rollback
    then discards at most that many steps, and three mismatches in a
    row (a quarantine at the default `max_retries`) stay out of reach
    of transients. Otherwise, and for stuck/dead defects, which recur
    on every retry, once a segment, at its boundary."""
    k = seg_steps
    if faults is not None and faults.mode == "transient":
        while k > MIN_COMPARE_STEPS and 2 * k * faults.rate > COMPARE_FAULTS:
            k = max(MIN_COMPARE_STEPS, -(-k // 2))
    return k


@functools.lru_cache(maxsize=None)
def _packed_segment_runner(stepper: str, chunk: int, seg_steps: int,
                           mem_words: int, n_progs: int, bank_width: int,
                           mesh: Optional[Mesh], subset, timing: bool,
                           faults: Optional[flexifault.FaultSpec] = None,
                           dmr: bool = False, max_retries: int = 0):
    """Compiled packed segment runner, cached per engine configuration.

    The bank, per-program code lengths, per-program memory bounds, and
    per-program cycle-cost rows are traced *inputs* (not closure
    constants), so two plans that share shapes and opcode subset reuse
    one compiled callable even with different programs. Per-lane
    `max_steps` lives in the state, so the budget never appears in the
    cache key at all — one compiled runner serves every heterogeneous
    budget mix. `timing` is static: with it off the cost operand is a
    dead argument and the compiled segment is the cycles-off graph.
    `faults` (§9.14) is static too — with it None the runner keeps the
    pre-FlexiFault signature and graph; with a schedule on, the runner
    takes the per-lane `lane_key`/`epoch` arrays as two extra traced
    inputs ahead of the donated state.

    With `dmr` (§9.14) the runner takes `(lane_key, epoch, retries)`
    ahead of the state and returns `(state, snap, epoch, retries,
    counts)`: it steps the pool in runs of `compare_steps(faults,
    seg_steps)` and, after each run but the last, compares every lane
    pair's digest. A pair that disagrees rolls back to `snap`, its
    state at the previous compare, with a bumped epoch (fresh draws),
    and counts one retry; one that disagrees after `max_retries`
    retries in a row is left as it is, halted, for the boundary op to
    quarantine. Pairs that agree reset their retries and become the
    new `snap`. The last run is compared at the boundary by
    `refill_dmr`, against the returned `snap`; `counts` is the
    shard's `[rollbacks, discarded lane-steps]` of the runs compared
    here.
    """
    def seg_body(bank, code_len, mem_len, cost, state,
                 lane_key=None, epoch=None, steps=seg_steps):
        cr = cost if timing else None
        if stepper == "pallas":
            return iss_stepper.iss_segment_banked(
                bank, code_len, state, seg_steps=steps, subset=subset,
                mem_len=mem_len, cost=cr, faults=faults,
                lane_key=lane_key, epoch=epoch)
        return iss.run_segment_lanes_banked(bank, code_len, state,
                                            steps, subset, mem_len,
                                            cr, faults=faults,
                                            lane_key=lane_key,
                                            epoch=epoch)

    def pair_compare(state, snap, epoch, retries, frozen, counts):
        """One compare of every lane pair inside the segment."""
        lanes = state.lanes
        d = flexifault.arch_digest(lanes.regs, lanes.pc, lanes.mem,
                                   lanes.halted, lanes.n_instr,
                                   lanes.n_two_stage, lanes.n_cycles,
                                   lanes.mix).reshape(-1, 2)
        mismatch = (d[:, 0] != d[:, 1]) & ~frozen
        freeze = mismatch & (retries >= max_retries)
        rollback = mismatch & ~freeze
        rb_l = jnp.repeat(rollback, 2)
        discarded = jnp.sum(jnp.where(rb_l, lanes.n_instr - snap.n_instr,
                                      0))

        def rb(a, b):
            m = rb_l.reshape(rb_l.shape + (1,) * (b.ndim - 1))
            return jnp.where(m, a, b)
        lanes = jax.tree.map(rb, snap, lanes)
        frozen = frozen | freeze
        fz_l = jnp.repeat(frozen, 2)
        lanes = lanes._replace(halted=lanes.halted | fz_l)

        def keep(a, b):
            m = fz_l.reshape(fz_l.shape + (1,) * (b.ndim - 1))
            return jnp.where(m, a, b)
        snap = jax.tree.map(keep, snap, lanes)
        one = jnp.asarray(1, iss.I32)
        epoch = jnp.where(rb_l, epoch + one, epoch)
        retries = jnp.where(rollback, retries + one,
                            jnp.where(frozen, retries,
                                      jnp.zeros_like(retries)))
        counts = counts + jnp.stack([rollback.sum().astype(iss.I32),
                                     discarded.astype(iss.I32)])
        return (state._replace(lanes=lanes), snap, epoch, retries, frozen,
                counts)

    k = compare_steps(faults, seg_steps)
    n_cmp = (seg_steps - 1) // k          # compares inside the segment
    lane = None if mesh is None else P(tuple(mesh.axis_names))
    if dmr:
        def seg(bank, code_len, mem_len, cost, lane_key, epoch, retries,
                state):
            def run(_, carry):
                st, snap, ep, rt, frozen, counts = carry
                st = seg_body(bank, code_len, mem_len, cost, st,
                              lane_key=lane_key, epoch=ep, steps=k)
                return pair_compare(st, snap, ep, rt, frozen, counts)
            carry = (state, state.lanes, epoch, retries,
                     jnp.zeros_like(retries, bool),
                     jnp.zeros(2, iss.I32))
            if n_cmp:
                carry = jax.lax.fori_loop(0, n_cmp, run, carry)
            state, snap, epoch, retries, _, counts = carry
            state = seg_body(bank, code_len, mem_len, cost, state,
                             lane_key=lane_key, epoch=epoch,
                             steps=seg_steps - n_cmp * k)
            return state, snap, epoch, retries, counts[None]
        donate = (5, 6, 7)
        extra_specs = (lane, lane, lane)
    elif faults is None:
        def seg(bank, code_len, mem_len, cost, state):
            return seg_body(bank, code_len, mem_len, cost, state)
        donate = (4,)
        extra_specs = ()
    else:
        def seg(bank, code_len, mem_len, cost, lane_key, epoch, state):
            return seg_body(bank, code_len, mem_len, cost, state,
                            lane_key=lane_key, epoch=epoch)
        donate = (6,)
        extra_specs = (lane, lane)

    if mesh is None:
        return jax.jit(seg, donate_argnums=donate)
    specs = _packed_state_specs(mesh, mem_words)
    bspecs = dsharding.bank_specs(mesh, (0, 0, 0, 0))
    out_specs = specs
    if dmr:
        out_specs = (specs, specs.lanes, lane, lane,
                     P(tuple(mesh.axis_names), None))
    fn = shard_map(seg, mesh=mesh, in_specs=(*bspecs, *extra_specs, specs),
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=donate)


class ResidentAcc(NamedTuple):
    """On-device result accumulators of the resident runtime (§9.9),
    laid out shard-locally (§9.12).

    Per-ITEM leaves hold `n_shards * cap` rows sharded on dim 0: shard
    s owns the block `[s*cap, (s+1)*cap)` and scatters ONLY the items
    it admitted (the host keeps the item->row table, `rowmap`), so the
    retire scatter never crosses a shard boundary. Rows are scattered
    once when the item's lane retires and fetched once at drain —
    per-item scalar results stay O(fleet), on the device until the
    stream ends.
    Single-device, `cap == total_items` and the row table is the
    identity. Per-GROUP mix totals accumulate in int32 per shard and
    are summed over shards into the host's int64 totals at drain, or
    earlier, by a flush, in a plan whose rows could pass 2^31 - 1
    (`_stream_resident`). `prev_instr` is the per-lane retired-count
    snapshot at the last refill, from which each segment's max step
    delta is measured. The keep_state leaves are None unless full final
    state was requested.
    """
    n_instr: jax.Array             # (n_shards*cap,) i32
    n_two: jax.Array               # (n_shards*cap,) i32
    n_cycles: jax.Array            # (n_shards*cap,) i32 timing ticks
    halted: jax.Array              # (n_shards*cap,) bool
    out: jax.Array                 # (n_shards*cap,) i32
    mix_g: jax.Array               # (n_shards, n_groups, 8) i32
    prev_instr: jax.Array          # (chunk,) i32
    mems: Optional[jax.Array]      # (n_shards*cap, mem_words) i32
    regs: Optional[jax.Array]      # (n_shards*cap, 16) i32
    pc: Optional[jax.Array]        # (n_shards*cap,) i32
    mix_items: Optional[jax.Array]  # (n_shards*cap, 8) i32


class InjectedFault(RuntimeError):
    """Raised by the resident loop's fault-injection knob
    (`run_packed(..., _crash_after_segments=n)`): the stream dies at
    the top of a loop iteration, so fault-tolerance tests can kill a
    run mid-flight at a segment boundary and resume it from its last
    checkpoint (DESIGN.md §9.12)."""


def shard_partition(counts, n_shards: int):
    """Static item->shard partition of the packed stream (§9.12).

    Returns `spans[g][s]`: a list of `(lo, hi)` half-open item-index
    ranges of group g owned by shard s — a contiguous balanced split
    (shard item counts differ by at most one). Each shard admits,
    stages, and retires ONLY its own items, which is what keeps the
    resident segment loop collective-free. Per-item results are pure
    functions of (group, item index), so ANY partition is bit-exact
    with the single-device stream, and `n_shards=1` degenerates to
    exactly the old global admission order.
    """
    spans = []
    for c in np.asarray(counts, np.int64):
        c = int(c)
        base, rem = divmod(c, n_shards)
        row, lo = [], 0
        for s in range(n_shards):
            k = base + (1 if s < rem else 0)
            row.append([(lo, lo + k)] if k else [])
            lo += k
        spans.append(row)
    return spans


def _span_items(spans) -> np.ndarray:
    """Flat item-index vector of a span list."""
    if not spans:
        return np.zeros(0, np.int64)
    return np.concatenate([np.arange(lo, hi, dtype=np.int64)
                           for lo, hi in spans])


def _items_to_spans(items):
    """Compress a sorted item-index vector back into (lo, hi) spans."""
    items = np.asarray(items, np.int64)
    if items.size == 0:
        return []
    brk = np.nonzero(np.diff(items) != 1)[0]
    starts = np.concatenate([[0], brk + 1])
    ends = np.concatenate([brk, [items.size - 1]])
    return [(int(items[a]), int(items[b]) + 1)
            for a, b in zip(starts, ends)]


def _split_spans(spans, n_shards: int):
    """Contiguous balanced split of a span list over `n_shards` — the
    elastic-resume generalization of `shard_partition` (the pending
    items of a restored stream are re-dealt to the new mesh's shards).
    """
    items = _span_items(spans)
    base, rem = divmod(items.size, n_shards)
    out, lo = [], 0
    for s in range(n_shards):
        k = base + (1 if s < rem else 0)
        out.append(_items_to_spans(items[lo:lo + k]))
        lo += k
    return out


def _span_source(source: Source, spans) -> Source:
    """View of `source` restricted to a span list: linear index i maps
    to the i-th item of the concatenated spans, fetched from the
    underlying source in contiguous runs (so per-shard prefetch keeps
    issuing block-sized reads against block-aligned sources)."""
    lens = np.array([hi - lo for lo, hi in spans], np.int64)
    offs = np.concatenate([np.zeros(1, np.int64), np.cumsum(lens)])

    def src(start: int, count: int) -> np.ndarray:
        parts = []
        i, end = int(start), int(start) + int(count)
        while i < end:
            k = int(np.searchsorted(offs, i, side="right")) - 1
            take = min(end - i, int(offs[k + 1]) - i)
            a = spans[k][0] + (i - int(offs[k]))
            parts.append(np.asarray(source(a, take), np.int32))
            i += take
        if not parts:
            return np.zeros((0, 0), np.int32)
        return parts[0] if len(parts) == 1 else np.concatenate(parts)
    return src


def _abstract_acc(keep_state: bool) -> ResidentAcc:
    """Rank-only ResidentAcc skeleton (leaf sizes are irrelevant:
    `lane_specs` maps each leaf by ndim only)."""
    def z(*shape):
        return jax.ShapeDtypeStruct(shape, np.int32)
    return ResidentAcc(
        n_instr=z(1), n_two=z(1), n_cycles=z(1),
        halted=jax.ShapeDtypeStruct((1,), np.bool_), out=z(1),
        mix_g=z(1, 1, 1), prev_instr=z(1),
        mems=z(1, 1) if keep_state else None,
        regs=z(1, 1) if keep_state else None,
        pc=z(1) if keep_state else None,
        mix_items=z(1, 1) if keep_state else None)


@functools.lru_cache(maxsize=None)
def _resident_refill_runner(mesh: Optional[Mesh], mem_words: int,
                            n_groups: int, keep_state: bool,
                            use_pallas: bool, faults_on: bool = False,
                            dmr: bool = False, max_retries: int = 0):
    """Compiled retire+refill op, shard-local end to end (§9.9/§9.12).

    One donated op retires and refills on the device: finished lanes
    are detected against their own budgets
    (`iss.retire_mask`), their tallies scattered into the `ResidentAcc`
    rows of the items they carried (dropped-out-of-range scatter — only
    retiring lanes write), and fresh items swapped in from the staged
    batch in lane-rank order (`iss.refill_take` + `iss.refill_lanes`,
    or the banked Pallas swap `iss_stepper.iss_refill` when the fused
    stepper runs single-device). The lane state never leaves the
    device.

    The body is written per-shard: staged leaves arrive with a leading
    shard dim — `(n_shards, spc, ...)` globally, `(1, spc, ...)` inside
    the shard — `n_staged` is a per-shard `(n_shards,)` vector, and
    `item_slot`/`staged_slot` carry shard-LOCAL accumulator rows, so
    the `refill_take` cumsum rank, the retire scatter, and the staged
    swap all stay inside the shard. Under a mesh the body runs through
    `shard_map` and the lowered module contains zero cross-device
    collectives (pinned by tests/test_shard_local.py); single-device it
    is jitted directly — the identical code at n_shards=1.

    Returns the refreshed (state, item_slot, acc) plus an int32
    `(n_shards, 3 + n_groups)` stats block — per shard: [n_retired,
    n_consumed, max step delta, active-lanes-per-group...] — describing
    the segment that just ran; that ONE stacked vector is all the host
    reads per segment, fetched asynchronously while the next segment
    executes.
    """
    def scatter_retired(state, item_slot, acc, out_addr, retired):
        """Scatter finished lanes' tallies at their (shard-local) item
        rows (shared by all three loop variants)."""
        lanes = state.lanes
        cap = acc.n_instr.shape[0]
        slot = jnp.where(retired, item_slot, cap)   # OOB rows drop

        def put(buf, val):
            return None if buf is None \
                else buf.at[slot].set(val, mode="drop")

        col = out_addr[state.prog_id]
        out_val = jnp.take_along_axis(
            lanes.mem, jnp.clip(col, 0, lanes.mem.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        out_val = jnp.where(col >= 0, out_val, 0)
        return acc._replace(
            n_instr=put(acc.n_instr, lanes.n_instr),
            n_two=put(acc.n_two, lanes.n_two_stage),
            n_cycles=put(acc.n_cycles, lanes.n_cycles),
            halted=put(acc.halted, lanes.halted),
            out=put(acc.out, out_val),
            mix_g=acc.mix_g[0].at[state.prog_id].add(
                jnp.where(retired[:, None], lanes.mix, 0))[None],
            mems=put(acc.mems, lanes.mem),
            regs=put(acc.regs, lanes.regs),
            pc=put(acc.pc, lanes.pc),
            mix_items=put(acc.mix_items, lanes.mix))

    def refill(state, item_slot, acc, staged_mems, staged_prog,
               staged_ms, staged_slot, n_staged, out_addr):
        lanes = state.lanes
        active = item_slot >= 0
        retired = iss.retire_mask(state, item_slot)

        # ---- accounting of the segment that just ran (host-free)
        delta = jnp.max(lanes.n_instr - acc.prev_instr, initial=0)
        act_g = jnp.zeros((n_groups,), iss.I32).at[state.prog_id].add(
            active.astype(iss.I32))

        acc = scatter_retired(state, item_slot, acc, out_addr, retired)

        # ---- refill freed lanes from this shard's staged batch, in
        # lane-rank order
        free = retired | ~active
        take, src = iss.refill_take(free, n_staged[0])
        swap = iss_stepper.iss_refill if use_pallas else iss.refill_lanes
        new_state = swap(state, take, src, staged_mems[0], staged_prog[0],
                         staged_ms[0])
        new_slot = jnp.where(take, staged_slot[0][src],
                             jnp.where(retired, -1, item_slot))
        acc = acc._replace(prev_instr=jnp.where(take, 0, lanes.n_instr))
        stats = jnp.concatenate([
            jnp.stack([retired.sum().astype(iss.I32),
                       take.sum().astype(iss.I32),
                       delta.astype(iss.I32)]), act_g])[None]
        return new_state, new_slot, acc, stats

    def refill_faults(state, item_slot, epoch, acc, staged_mems,
                      staged_prog, staged_ms, staged_slot, n_staged,
                      out_addr):
        """The base loop plus the per-lane fault `epoch` (§9.14): a
        lane taking a fresh item bumps its epoch so the new item draws
        a fresh schedule instead of replaying the last item's (draws
        key on (lane, epoch, n_instr) and n_instr restarts at 0)."""
        new_state, new_slot, acc, stats = refill(
            state, item_slot, acc, staged_mems, staged_prog, staged_ms,
            staged_slot, n_staged, out_addr)
        took = (new_slot != item_slot) & (new_slot >= 0)
        new_epoch = jnp.where(took, epoch + jnp.asarray(1, iss.I32),
                              epoch)
        return new_state, new_slot, new_epoch, acc, stats

    def refill_dmr(state, item_slot, epoch, retries, quar, snap,
                   seg_counts, acc, staged_mems, staged_prog, staged_ms,
                   staged_slot, n_staged, out_addr):
        """DMR shadow-lane retire/refill (§9.14).

        Lanes pair up as (2p primary, 2p+1 shadow); both run the SAME
        item image but draw independent fault schedules (different
        physical lane keys). At every refill boundary the pair's
        architectural digests are compared: a mismatch means at least
        one lane was hit since the segment's last compare, so the pair
        rolls back to `snap` (its state at that compare, returned by
        the segment runner — those steps re-execute) with a bumped
        epoch (fresh draws; a transient won't recur, a stuck-at/dead
        defect will). A pair
        that mismatches `max_retries + 1` times in a row is quarantined:
        it is rolled back to the snapshot, its last agreed state, and
        parked forever, still holding its item; the first free pair of
        this or a later boundary takes that state over and resumes the
        item where the pair agreed last, ahead of any staged item.
        Pairs whose digests agree retire/refill exactly as the base
        loop, at pair granularity (the shadow carries item row -1 and
        never scatters).
        The stats block adds, after the base loop's three columns,
        [mismatches, rollbacks, pairs quarantined, discarded] ahead of
        the per-group counts; `discarded` sums n_instr - snap.n_instr
        over both lanes of every mismatching pair, rolled back or
        quarantined: the lane-steps thrown away. The segment's own
        compares (`seg_counts`: [rollbacks, discarded]) are added in.
        """
        lanes = state.lanes
        one = jnp.asarray(1, iss.I32)
        n_pairs = quar.shape[0]
        slot_p = item_slot.reshape(-1, 2)[:, 0]
        # primaries of running pairs (shadows: -1; a quarantined pair's
        # primary keeps its row while it holds the item)
        active = (item_slot >= 0) & ~jnp.repeat(quar, 2)

        # ---- pair views: chunk % (2 * n_shards) == 0 (validated in
        # run_packed), so a pair never straddles a shard boundary
        d = flexifault.arch_digest(lanes.regs, lanes.pc, lanes.mem,
                                   lanes.halted, lanes.n_instr,
                                   lanes.n_two_stage, lanes.n_cycles,
                                   lanes.mix)
        d2 = d.reshape(-1, 2)
        pair_active = active.reshape(-1, 2)[:, 0]
        mismatch = pair_active & (d2[:, 0] != d2[:, 1])
        done_l = lanes.halted | (lanes.n_instr >= state.max_steps)
        pair_retire = (pair_active & done_l.reshape(-1, 2)[:, 0]
                       & ~mismatch)

        new_q = mismatch & (retries >= max_retries)
        rollback = mismatch & ~new_q

        # ---- accounting of the segment that just ran
        delta = jnp.max(lanes.n_instr - acc.prev_instr, initial=0)
        act_g = jnp.zeros((n_groups,), iss.I32).at[state.prog_id].add(
            active.astype(iss.I32))

        # ---- retire matching finished pairs (primary rows scatter)
        retired = iss.retire_mask(state, item_slot) \
            & jnp.repeat(pair_retire, 2)
        acc = scatter_retired(state, item_slot, acc, out_addr, retired)

        # ---- roll mismatching pairs back to the last agreed boundary;
        # park the quarantined ones there
        rb_l = jnp.repeat(mismatch, 2)
        q_l = jnp.repeat(new_q, 2)
        discarded = jnp.sum(jnp.where(rb_l, lanes.n_instr - snap.n_instr,
                                      0))

        def rb(a, b):
            m = rb_l.reshape(rb_l.shape + (1,) * (b.ndim - 1))
            return jnp.where(m, a, b)

        lanes2 = jax.tree.map(rb, snap, lanes)
        lanes2 = lanes2._replace(
            halted=jnp.where(q_l, True, lanes2.halted))

        # ---- free pairs resume held items first (a held item's state is
        # its pair's primary lane), then take staged items
        quar2 = quar | new_q
        held = quar2 & (slot_p >= 0)
        free_p = (pair_retire | ~pair_active) & ~quar2
        f_rank = jnp.cumsum(free_p.astype(iss.I32)) - 1
        resume_p = free_p & (f_rank < held.sum())
        held_idx = jnp.nonzero(held, size=n_pairs, fill_value=0)[0]
        from_l = 2 * jnp.repeat(
            held_idx[jnp.clip(f_rank, 0, n_pairs - 1)], 2)
        resume_l = jnp.repeat(resume_p, 2)
        released = held & (jnp.cumsum(held.astype(iss.I32)) - 1
                           < free_p.sum())

        def resume(x):
            m = resume_l.reshape(resume_l.shape + (1,) * (x.ndim - 1))
            return jnp.where(m, x[from_l], x)

        lanes3 = jax.tree.map(resume, lanes2)
        lanes3 = lanes3._replace(
            halted=jnp.where(resume_l, False, lanes3.halted))
        state = iss.PackedState(lanes=lanes3,
                                prog_id=resume(state.prog_id),
                                max_steps=resume(state.max_steps))

        # ---- refill the other free pairs; both lanes get the item
        # image, only the primary carries the accumulator row
        take_p, src_p = iss.refill_take(free_p & ~resume_p, n_staged[0])
        take_l = jnp.repeat(take_p, 2)
        src_l = jnp.repeat(src_p, 2)
        new_state = iss.refill_lanes(state, take_l, src_l,
                                     staged_mems[0], staged_prog[0],
                                     staged_ms[0])
        is_primary = (jnp.arange(item_slot.shape[0]) % 2) == 0
        new_slot = jnp.where(
            take_l & is_primary, staged_slot[0][src_l],
            jnp.where(resume_l & is_primary, item_slot[from_l],
                      jnp.where(retired | jnp.repeat(released, 2), -1,
                                item_slot)))
        new_epoch = jnp.where(take_l | rb_l | resume_l, epoch + one,
                              epoch)
        # consecutive-mismatch counter: any clean boundary resets it
        # (a long-lived item accrues many independent transients over
        # its lifetime; only an unrecoverable streak should quarantine)
        new_retries = jnp.where(rollback, retries + one,
                                jnp.where(new_q, retries,
                                          jnp.zeros_like(retries)))
        acc = acc._replace(prev_instr=jnp.where(
            take_l, 0, new_state.lanes.n_instr))
        stats = jnp.concatenate([
            jnp.stack([pair_retire.sum().astype(iss.I32),
                       take_p.sum().astype(iss.I32),
                       delta.astype(iss.I32),
                       mismatch.sum().astype(iss.I32) + seg_counts[0, 0],
                       rollback.sum().astype(iss.I32) + seg_counts[0, 0],
                       new_q.sum().astype(iss.I32),
                       discarded.astype(iss.I32) + seg_counts[0, 1]]),
            act_g])[None]
        return (new_state, new_slot, new_epoch, new_retries, quar2, acc,
                stats)

    if dmr:
        # snap (arg 5) is NOT donated: the new-state output already
        # reuses the state input's buffers, so snap's would go unused
        # (it is freed by refcount when the host drops the reference)
        fn, donate = refill_dmr, (0, 1, 2, 3, 4, 7)
    elif faults_on:
        fn, donate = refill_faults, (0, 1, 2, 3)
    else:
        fn, donate = refill, (0, 1, 2)
    if mesh is None:
        return jax.jit(fn, donate_argnums=donate)
    axes = tuple(mesh.axis_names)
    lane = P(axes)
    state_specs = _packed_state_specs(mesh, mem_words)
    acc_specs = dsharding.lane_specs(mesh, _abstract_acc(keep_state))
    st_specs = (P(axes, None, None), P(axes, None), P(axes, None),
                P(axes, None))
    if dmr:
        snap_specs = state_specs.lanes
        carry_in = (state_specs, lane, lane, lane, lane, snap_specs,
                    P(axes, None), acc_specs)
        carry_out = (state_specs, lane, lane, lane, lane, acc_specs)
    elif faults_on:
        carry_in = (state_specs, lane, lane, acc_specs)
        carry_out = (state_specs, lane, lane, acc_specs)
    else:
        carry_in = (state_specs, lane, acc_specs)
        carry_out = (state_specs, lane, acc_specs)
    fn = shard_map(
        fn, mesh=mesh,
        in_specs=(*carry_in, *st_specs, lane, P()),
        out_specs=(*carry_out, P(axes, None)),
        check_vma=False)
    return jax.jit(fn, donate_argnums=donate)


def _pool_lanes(chunk: int, stepper: str, n_dev: int, dmr: bool) -> int:
    """Lane-pool size for a requested `chunk`: a multiple of the device
    count (of 2x it under DMR, so a lane pair never straddles a shard),
    and for the fused stepper past 128 lanes a multiple of 128 — the
    kernel tiles lanes 128 at a time on the TPU's lane axis and runs a
    smaller pool as one full-width block."""
    round_to = 2 * n_dev if dmr else n_dev
    if stepper == "pallas" and chunk > 128:
        round_to = int(np.lcm(128, round_to))
    return -(-chunk // round_to) * round_to


def _choose_stepper(stepper: Optional[str],
                    faults: Optional[flexifault.FaultSpec]) -> str:
    """The segment stepper a run uses: `stepper` when one is named;
    otherwise the fused Pallas kernel on a TPU for a fault-free run, and
    the XLA branchless stepper elsewhere — on other backends the kernel
    runs interpreted, and its fault transform does not compile for the
    TPU. The two give bit-identical results, so the choice is speed
    only."""
    if stepper is not None:
        return stepper
    if faults is None and jax.default_backend() == "tpu":
        return "pallas"
    return "branchless"


def run_packed(groups, *, chunk: int = 256, seg_steps: int = 4096,
               keep_state: bool = False, mesh: Optional[Mesh] = None,
               stepper: Optional[str] = None,
               subset: Optional[frozenset] = None,
               prefetch: bool = True,
               adaptive: bool = False,
               checkpoint_dir: Optional[str] = None,
               checkpoint_every: int = 0,
               faults: Optional[flexifault.FaultSpec] = None,
               redundancy: str = "none", max_retries: int = 2,
               _crash_after_segments: Optional[int] = None):
    """Execute every `PackedGroup` through ONE packed stream.

    Returns `(results, stats)`: `results[g]` is a per-group `FleetResult`
    bit-exact with what `run_stream` would produce for group g alone —
    identical per-item instruction/timing/mix tallies and final state
    (`tests/test_packed.py` pins it) — and `stats` is the
    whole-run `PackedStats`.

    The program bank holds one padded row per group; every stepper
    fetches through the per-program clamp (`iss.fetch_banked`), bounds
    each lane's data-memory ports at its group's own `mem_words` (so
    clamp-on-read / drop-on-write happen at the program's boundary even
    though the pool memory is padded to the largest group's), and the
    branchless/pallas steppers compile ONE graph specialized to the
    *union* opcode subset of the bank (a superset of every row's subset,
    so per-group bit-exactness is preserved). Lane admission backfills
    freed lanes from any pending group (`_apportion`); per-group sources
    prefetch concurrently, each double-buffered as in `run_stream`.

    Per-group accounting: `lane_steps`/`n_segments` count only segments
    slots where the group had active lanes; `wall_s` splits the measured
    whole-run wall clock proportionally to retired instructions (the
    sums over groups match the run, up to idle-lane slots, which belong
    to `stats`).

    `stepper` names the segment interpreter (`STEPPERS`); None lets
    `_choose_stepper` pick it from the backend and the fault schedule,
    and `stats.stepper` and every `FleetResult.stepper` report the one
    that ran.

    The stream loop is the *resident* runtime (DESIGN.md §9.9):
    retire/refill happens in one donated on-device op against a staged
    batch that was uploaded asynchronously while the previous segment
    ran, and the only per-segment host read is one small stats vector
    fetched while the NEXT segment executes. `adaptive` turns
    on the superstep controller (§9.9): each segment's step bound is
    picked from a bounded power-of-two ladder under `seg_steps` by the
    observed halt cadence — deterministic for a given plan, bit-exact
    with any fixed schedule.

    `checkpoint_dir` makes the resident stream durable (§9.12): every
    `checkpoint_every` segments the loop writes an atomic, canonical
    (mesh-independent) snapshot of the resident state — lane pool,
    accumulated/done results, pending item spans, controller state —
    through `distributed/checkpoint.py`; when `checkpoint_dir` already
    holds a checkpoint the run auto-resumes from it, bit-exact with an
    uninterrupted run, even onto a different mesh shape (the elastic
    path re-deals surviving lanes and pending spans to the new shards).
    `_crash_after_segments` is the fault-injection knob used by
    tests/test_fault_tolerance.py: raise `InjectedFault` once that many
    segments have retired.

    `faults` (a `flexibits.faults.FaultSpec`, DESIGN.md §9.14) turns on
    deterministic fault injection: every lane applies the post-commit
    fault transform under its own `fold_in`-derived key, bit-identically
    across the steppers. `redundancy="dmr"` pairs lanes as
    primary+shadow running the same item under independent schedules,
    compares architectural digests every `compare_steps(faults,
    seg_steps)` steps (inside the segment runner, and at the segment
    boundary), rolls mismatching pairs back to the previous compare's
    snapshot (re-executing those steps under fresh draws), and after
    `max_retries` consecutive rollbacks quarantines the pair at its
    next mismatch — parking the defective lanes and resuming the item
    from the pair's last agreed state on the next free pair. Both are
    incompatible with `checkpoint_dir` (the rollback snapshots are not
    part of the durable snapshot schema); `faults=None` with
    `redundancy="none"` is bit-exact with the pre-FlexiFault engine
    (pinned by tests/test_faults.py).

    `keep_state` past `_RESIDENT_KEEP_STATE_WORDS` words of device rows
    is refused; a plan whose per-group mix rows could pass int32 runs
    with on-demand flushes, as long as one refill cannot (see
    `_stream_resident`).
    """
    groups = list(groups)
    if not groups:
        raise ValueError("run_packed needs at least one group")
    if seg_steps < 1:
        raise ValueError("seg_steps must be >= 1")
    if chunk < 1:
        raise ValueError("chunk must be >= 1")
    if redundancy not in REDUNDANCY:
        raise ValueError(f"redundancy must be one of {REDUNDANCY} "
                         f"(tmr is priced by the carbon planner but "
                         f"not executed), got {redundancy!r}")
    if faults is not None and faults.off:
        faults = None              # rate 0 IS the fault-free graph
    stepper = _choose_stepper(stepper, faults)
    if stepper not in STEPPERS:
        raise ValueError(f"stepper must be one of {STEPPERS}, got "
                         f"{stepper!r}")
    if (faults is not None or redundancy == "dmr") \
            and checkpoint_dir is not None:
        raise ValueError(
            "fault injection / DMR is incompatible with checkpoint_dir: "
            "epoch/retry/snapshot state is not part of the durable "
            "checkpoint schema")
    if (faults is not None and stepper == "pallas"
            and jax.default_backend() == "tpu"):
        raise ValueError(
            "fault injection with stepper='pallas' does not compile for "
            "the TPU: the kernel's fault transform works on lane-minor "
            "views; use stepper='branchless'")

    n_groups = len(groups)
    counts = np.array([g.n_items for g in groups], np.int64)
    total_items = int(counts.sum())
    if keep_state:
        # keep_state scatters full final state into O(fleet) device rows
        ks_words = total_items * (max(g.mem_words for g in groups) + 16
                                  + 1 + len(iss.MIX_CLASSES))
        if ks_words > _RESIDENT_KEEP_STATE_WORDS:
            raise ValueError(
                f"keep_state would hold {ks_words:,} words of device rows, "
                f"past the bound of {_RESIDENT_KEEP_STATE_WORDS:,} "
                f"(_RESIDENT_KEEP_STATE_WORDS): keep_state is for parity "
                f"checks and small fleets")
    if total_items == 0:
        empty = [FleetResult(
            n_items=0, n_instr=np.zeros(0, np.int64),
            n_two_stage=np.zeros(0, np.int64), halted=np.zeros(0, bool),
            out=np.zeros(0, np.int32),
            mix=np.zeros(len(iss.MIX_CLASSES), np.int64), lane_steps=0,
            n_segments=0, chunk=0, seg_steps=seg_steps, wall_s=0.0,
            stepper=stepper,
            n_cycles=None if g.cost is None else np.zeros(0, np.int64))
            for g in groups]
        return empty, PackedStats(
            n_groups=n_groups, n_progs=n_groups, bank_width=0,
            lane_steps=0, n_segments=0, chunk=0, seg_steps=seg_steps,
            wall_s=0.0, stepper=stepper, n_devices=1, adaptive=adaptive)
    mem_words = max(g.mem_words for g in groups)
    bank_np, code_len_np = iss.pack_programs([g.code for g in groups])
    if subset is None:
        subset = frozenset().union(
            *(g.subset if g.subset is not None
              else iss.opcode_subset(g.code) for g in groups))
    bank = jnp.asarray(bank_np)
    code_len = jnp.asarray(code_len_np)
    # per-program memory bounds: lanes of a small-memory group keep
    # clamp-on-read / drop-on-write at their OWN word count even though
    # the pool memory is padded to the largest group's
    mem_len = jnp.asarray([g.mem_words for g in groups], iss.I32)
    ms_of = np.array([g.max_steps for g in groups], np.int64)
    # per-program cycle-cost rows (§9.10): the timing layer is ON iff
    # any group carries a cost row. Cost-less groups in a mixed plan get
    # a zero row — their lanes share the timing-on graph but tally 0.
    timing = any(g.cost is not None for g in groups)
    cost_np = np.zeros((n_groups, N_COST), np.int32)
    for i, g in enumerate(groups):
        if g.cost is not None:
            cost_np[i] = np.asarray(g.cost, np.int32)
    cost = jnp.asarray(cost_np)

    dmr = redundancy == "dmr"
    # a DMR pair occupies two lanes per item, and a pair must never
    # straddle a shard: the pool rounds to 2 x n_dev
    chunk = min(chunk, max(total_items * (2 if dmr else 1), 1))
    n_dev = 1
    if mesh is not None:
        n_dev = int(np.prod(list(mesh.shape.values())))
    chunk = _pool_lanes(chunk, stepper, n_dev, dmr)

    clock = _SyncClock()
    controller = _SuperstepController(seg_steps, chunk, adaptive)
    t0 = time.perf_counter()
    # the resident loop owns per-(group, shard) prefetchers — the
    # item->shard partition decides what each one reads (§9.12)
    out = _stream_resident(
        groups, prefetch, counts, ms_of, bank, code_len, mem_len, cost,
        timing, bank_np, chunk, keep_state, mesh, stepper, subset,
        mem_words, controller, clock, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every, faults=faults,
        redundancy=redundancy, max_retries=max_retries,
        crash_after=_crash_after_segments)

    wall_s = time.perf_counter() - t0
    busy = np.array([r.sum() for r in out["r_instr"]], np.float64)
    busy_share = busy / max(busy.sum(), 1.0)
    results = []
    for g, grp in enumerate(groups):
        results.append(FleetResult(
            n_items=grp.n_items, n_instr=out["r_instr"][g],
            n_two_stage=out["r_two"][g],
            halted=out["r_halt"][g], out=out["r_out"][g],
            mix=out["r_mix"][g],
            lane_steps=int(out["g_lane_steps"][g]),
            n_segments=int(out["g_segments"][g]),
            chunk=chunk, seg_steps=seg_steps,
            wall_s=wall_s * float(busy_share[g]),
            stepper=stepper, n_devices=n_dev,
            mems=out["r_mem"][g] if keep_state else None,
            regs=out["r_regs"][g] if keep_state else None,
            pc=out["r_pc"][g] if keep_state else None,
            mix_items=out["r_mix_items"][g] if keep_state else None,
            n_cycles=out["r_cycles"][g] if grp.cost is not None else None,
        ))
    stats = PackedStats(
        n_groups=n_groups, n_progs=bank_np.shape[0],
        bank_width=bank_np.shape[1], lane_steps=out["lane_steps"],
        n_segments=out["n_segments"], chunk=chunk, seg_steps=seg_steps,
        wall_s=wall_s, stepper=stepper, n_devices=n_dev,
        adaptive=adaptive, host_syncs=clock.host_syncs,
        sync_wait_s=clock.sync_wait_s, refill_wall_s=clock.refill_wall_s,
        seg_schedule=tuple(controller.schedule[:out["n_segments"]]),
        n_shards=out["n_shards"],
        shard_retired=tuple(int(x) for x in out["shard_retired"]),
        shard_lane_steps=tuple(int(x) for x in out["shard_lane_steps"]),
        redundancy=redundancy, detected=out["detected"],
        corrected=out["corrected"], quarantined=out["quarantined"],
        discarded=out["discarded"])
    return results, stats


_CKPT_VALS = ("n_instr", "n_two", "n_cycles", "halted", "out")
_CKPT_KEEP = ("mems", "regs", "pc", "mix_items")
_CKPT_LANES = ("regs", "pc", "mem", "halted", "n_instr", "n_two",
               "mix", "n_cycles", "prog", "ms")


def _resident_ckpt_skeleton(n_groups: int, keep_state: bool) -> dict:
    """Flat-dict skeleton of a resident checkpoint — `restore` only
    needs the key set; shapes come from the stored arrays."""
    keys = ["counts", "done_mask", "mix_g", "pending", "counters",
            "ctrl", "sched", "g_lane_steps", "g_segments",
            "lane_item", "lane_prev"]
    keys += ["val_" + k for k in _CKPT_VALS]
    if keep_state:
        keys += ["val_" + k for k in _CKPT_KEEP]
    keys += ["lane_" + k for k in _CKPT_LANES]
    return {k: np.zeros(0, np.int64) for k in keys}


def _stream_resident(groups, prefetch, counts, ms_of, bank, code_len,
                     mem_len, cost, timing, bank_np, chunk, keep_state,
                     mesh, stepper, subset, mem_words,
                     controller: _SuperstepController,
                     clock: _SyncClock, checkpoint_dir=None,
                     checkpoint_every: int = 0, faults=None,
                     redundancy: str = "none", max_retries: int = 2,
                     crash_after=None):
    """The resident stream loop (DESIGN.md §9.9, shard-local §9.12).

    Pipeline per iteration, in device-queue order:

        refill_i  — donated on-device op: retire finished lanes into
                    the `ResidentAcc` rows, swap in staged items —
                    per-shard under a mesh, zero collectives
        seg_i     — the segment, at the controller's step bound
        (host)    — async-fetch refill_i's stacked per-shard stats
                    block, which blocks only until refill_i is done —
                    seg_i is already executing behind it; then restock
                    each shard's staged slice for refill_{i+1}
                    (per-shard prefetcher take + async device_put), all
                    overlapped with seg_i

    The host therefore performs exactly ONE small read per segment
    regardless of the device count, and the device queue never drains
    while the stream has backlog. The loop exits after the refill that
    retires the last item; the final trailing segment dispatch sees an
    all-parked pool and its while_loop exits without stepping. Per-item
    results and final state are fetched ONCE, at drain, and merged
    through the host-side item->row table.
    """
    n_groups = len(groups)
    total = int(counts.sum())
    n_mix = len(iss.MIX_CLASSES)
    slot_base = np.zeros(n_groups, np.int64)
    np.cumsum(counts[:-1], out=slot_base[1:])
    out_addr_np = np.asarray(
        [-1 if g.out_addr is None else g.out_addr for g in groups],
        np.int32)
    dmr = redundancy == "dmr"
    # the banked Pallas swap is the single-device fused-stepper path;
    # under a mesh the (bit-identical) jnp swap partitions per shard
    # (and the DMR op always uses the jnp swap — pair semantics)
    use_pallas = stepper == "pallas" and mesh is None and not dmr
    n_shards = 1
    if mesh is not None:
        n_shards = int(np.prod(list(mesh.shape.values())))
    spc = chunk // n_shards          # lanes (and staged rows) per shard
    # int32 mix rows: a plan where some group's n_items x max_steps can
    # pass _RESIDENT_MIX_LIMIT flushes `acc.mix_g` into the int64
    # `mix_base` as it runs. No row may pass 2^31 - 1 between two
    # flushes: after one, a row gains at most spc x max(ms_of) from the
    # work that items in flight then had done, plus the lane-steps run
    # since, so the loop flushes once those, with the next segment's
    # chunk x seg_steps, could pass the limit. `mix_room` is that
    # budget for the lane-steps, None where no flush is ever needed.
    mix_room = None
    if int((counts * ms_of).max()) > _RESIDENT_MIX_LIMIT:
        mix_room = _RESIDENT_MIX_LIMIT - spc * int(ms_of.max())
        if mix_room < 0:
            raise ValueError(
                f"{spc} lanes a shard x a {int(ms_of.max()):,}-step "
                f"budget could pass the int32 mix rows' bound of "
                f"{_RESIDENT_MIX_LIMIT:,} (_RESIDENT_MIX_LIMIT) in one "
                f"refill: lower chunk or max_steps")

    # ---- host-side merged results: items finished before a resume
    # live here and never get device rows again
    done_mask = np.zeros(total, bool)
    base = {"n_instr": np.zeros(total, np.int64),
            "n_two": np.zeros(total, np.int64),
            "n_cycles": np.zeros(total, np.int64),
            "halted": np.zeros(total, bool),
            "out": np.zeros(total, np.int32)}
    if keep_state:
        base.update(mems=np.zeros((total, mem_words), np.int32),
                    regs=np.zeros((total, 16), np.int32),
                    pc=np.zeros(total, np.int32),
                    mix_items=np.zeros((total, n_mix), np.int32))
    mix_base = np.zeros((n_groups, n_mix), np.int64)

    g_lane_steps = np.zeros(n_groups, np.int64)
    g_segments = np.zeros(n_groups, np.int64)
    shard_retired = np.zeros(n_shards, np.int64)
    shard_steps = np.zeros(n_shards, np.int64)
    lane_steps = 0
    n_segments = 0
    prev_seg = 0
    detected = corrected = quarantined = discarded = 0   # §9.14 counters
    n_quar = np.zeros(n_shards, np.int64)         # quarantined pairs

    # ---- resume? (canonical checkpoint — independent of the mesh and
    # chunk it was written under)
    resume = None
    if checkpoint_dir is not None \
            and dckpt.latest_step(checkpoint_dir) is not None:
        tree, _ = dckpt.restore(
            checkpoint_dir, _resident_ckpt_skeleton(n_groups, keep_state))
        resume = {k: np.asarray(v) for k, v in tree.items()}
        if not np.array_equal(resume["counts"], counts):
            raise ValueError(
                f"checkpoint in {checkpoint_dir} was written for group "
                f"sizes {resume['counts'].tolist()}, plan has "
                f"{counts.tolist()}")
        if int(resume["lane_mem"].shape[1]) != mem_words:
            raise ValueError("checkpoint lane memory width "
                             f"{resume['lane_mem'].shape[1]} != plan "
                             f"mem_words {mem_words}")
        done_mask = resume["done_mask"].astype(bool).copy()
        for k in base:
            base[k] = resume["val_" + k].astype(base[k].dtype).copy()
        mix_base = resume["mix_g"].astype(np.int64).copy()
        lane_steps = int(resume["counters"][0])
        n_segments = int(resume["counters"][1])
        controller.rate = float(resume["ctrl"][0])
        prev_seg = int(resume["ctrl"][1])
        controller.schedule = [int(x) for x in resume["sched"]]
        g_lane_steps = resume["g_lane_steps"].astype(np.int64).copy()
        g_segments = resume["g_segments"].astype(np.int64).copy()
    retired = int(done_mask.sum())

    # ---- static item->shard partition (§9.12): pending spans plus the
    # in-flight lanes a resume deals onto the new shards
    if resume is None:
        spans = shard_partition(counts, n_shards)
        live = np.zeros(0, np.int64)
        lane_shard = np.zeros(0, np.int64)
    else:
        lane_item = resume["lane_item"].astype(np.int64)
        live = np.nonzero(lane_item >= 0)[0]
        if live.size > chunk:
            raise ValueError(
                f"cannot resume {live.size} in-flight lanes onto a "
                f"{chunk}-lane pool ({n_shards} shards x {spc})")
        # contiguous balanced deal of surviving lanes to new shards
        lane_shard = (np.arange(live.size) * n_shards) // max(
            live.size, 1)
        pend = resume["pending"].astype(np.int64).reshape(-1, 3)
        spans = [_split_spans([(int(lo), int(hi))
                               for g2, lo, hi in pend if g2 == g],
                              n_shards) for g in range(n_groups)]
    infl_items = [resume["lane_item"].astype(np.int64)[
        live[lane_shard == s]] if resume is not None
        else np.zeros(0, np.int64) for s in range(n_shards)]

    # ---- shard-local accumulator layout: shard s owns rows
    # [s*cap, (s+1)*cap); rowmap[global item row] -> acc row
    pend_n = np.array([[sum(hi - lo for lo, hi in spans[g][s])
                        for s in range(n_shards)]
                       for g in range(n_groups)],
                      np.int64).reshape(n_groups, n_shards)
    infl_n = np.array([x.size for x in infl_items], np.int64)
    cap = int(max(int((infl_n + pend_n.sum(0)).max()), 1))
    rowmap = np.full(total, -1, np.int64)
    lbase = np.zeros((n_shards, n_groups), np.int64)
    for s in range(n_shards):
        rowmap[infl_items[s]] = s * cap + np.arange(infl_n[s])
        off = int(infl_n[s])
        for g in range(n_groups):
            lbase[s, g] = off
            items = slot_base[g] + _span_items(spans[g][s])
            rowmap[items] = s * cap + off + np.arange(items.size)
            off += items.size
    row_owner = np.full(n_shards * cap, -1, np.int64)
    have = np.nonzero(rowmap >= 0)[0]
    row_owner[rowmap[have]] = have

    # ---- per-(group, shard) prefetchers over the pending spans
    prefs = [[_Prefetcher(_span_source(groups[g].source, spans[g][s]),
                          int(pend_n[g, s]),
                          block=max(1, min(spc, int(pend_n[g, s]))),
                          background=prefetch)
              for s in range(n_shards)] for g in range(n_groups)]

    # ---- host mirror of the per-shard staged batches (FIFO per shard)
    st_mems = np.zeros((n_shards, spc, mem_words), np.int32)
    st_prog = np.zeros((n_shards, spc), np.int32)
    st_ms = np.zeros((n_shards, spc), np.int32)
    st_slot = np.zeros((n_shards, spc), np.int32)
    staged_n = np.zeros(n_shards, np.int64)
    staged_cursor = np.zeros((n_groups, n_shards), np.int64)
    staged = {"dirty": True, "dev": None}
    stage_sh = None
    if mesh is not None:
        stage_sh = dsharding.stage_shardings(
            mesh, (st_mems, st_prog, st_ms, st_slot))

    def restock():
        changed = False
        for s in range(n_shards):
            free = spc - int(staged_n[s])
            remaining = pend_n[:, s] - staged_cursor[:, s]
            if free <= 0 or int(remaining.sum()) == 0:
                continue
            take = _apportion(free, remaining)
            off = int(staged_n[s])
            for g in np.nonzero(take)[0]:
                k = int(take[g])
                st_mems[s, off:off + k] = 0
                st_mems[s, off:off + k, :groups[g].mem_words] = \
                    prefs[g][s].take(k)
                st_prog[s, off:off + k] = g
                st_ms[s, off:off + k] = ms_of[g]
                st_slot[s, off:off + k] = lbase[s, g] + np.arange(
                    staged_cursor[g, s], staged_cursor[g, s] + k)
                staged_cursor[g, s] += k
                off += k
            if off != staged_n[s]:
                staged_n[s] = off
                changed = True
        if changed:
            staged["dirty"] = True

    def consume(con):
        changed = False
        for s in range(n_shards):
            k = int(con[s])
            if k <= 0:
                continue
            keep = int(staged_n[s]) - k
            for buf in (st_mems, st_prog, st_ms, st_slot):
                buf[s, :keep] = buf[s, k:int(staged_n[s])].copy()
            staged_n[s] = keep
            changed = True
        if changed:
            staged["dirty"] = True

    def upload():
        """Async-stage the batches to device (device_put returns before
        the transfer completes, so this overlaps the running segment).
        Each device receives ONLY its own (spc, ...) slice — staging
        H2D bytes are O(chunk) total, not O(chunk x devices)."""
        if not staged["dirty"] and staged["dev"] is not None:
            return
        arrs = (st_mems.copy(), st_prog.copy(), st_ms.copy(),
                st_slot.copy())
        if mesh is None:
            staged["dev"] = tuple(jax.device_put(a) for a in arrs)
        else:
            staged["dev"] = tuple(jax.device_put(a, s)
                                  for a, s in zip(arrs, stage_sh))
        staged["dirty"] = False

    # ---- device state: the lane pool + result accumulators. Fresh
    # runs start all-parked; a resume re-seats surviving lanes at the
    # head of their new shard's lane block.
    regs_l = np.zeros((chunk, 16), np.int32)
    pc_l = np.zeros(chunk, np.int32)
    mem_l = np.zeros((chunk, mem_words), np.int32)
    halted_l = np.ones(chunk, bool)       # parked lanes never step
    instr_l = np.zeros(chunk, np.int32)
    two_l = np.zeros(chunk, np.int32)
    mix_l = np.zeros((chunk, n_mix), np.int32)
    cyc_l = np.zeros(chunk, np.int32)
    prog_l = np.zeros(chunk, np.int32)
    ms_l = np.zeros(chunk, np.int32)
    slot_l = np.full(chunk, -1, np.int32)
    prev_l = np.zeros(chunk, np.int32)
    if resume is not None:
        for s in range(n_shards):
            old = live[lane_shard == s]
            pos = s * spc + np.arange(old.size)
            regs_l[pos] = resume["lane_regs"][old]
            pc_l[pos] = resume["lane_pc"][old]
            mem_l[pos] = resume["lane_mem"][old]
            halted_l[pos] = resume["lane_halted"][old].astype(bool)
            instr_l[pos] = resume["lane_n_instr"][old]
            two_l[pos] = resume["lane_n_two"][old]
            mix_l[pos] = resume["lane_mix"][old]
            cyc_l[pos] = resume["lane_n_cycles"][old]
            prog_l[pos] = resume["lane_prog"][old]
            ms_l[pos] = resume["lane_ms"][old]
            slot_l[pos] = np.arange(old.size)   # the in-flight rows
            prev_l[pos] = resume["lane_prev"][old]
    state = iss.PackedState(
        lanes=iss.ISSState(
            regs=jnp.asarray(regs_l), pc=jnp.asarray(pc_l),
            mem=jnp.asarray(mem_l), halted=jnp.asarray(halted_l),
            n_instr=jnp.asarray(instr_l), n_two_stage=jnp.asarray(two_l),
            mix=jnp.asarray(mix_l), n_cycles=jnp.asarray(cyc_l)),
        prog_id=jnp.asarray(prog_l), max_steps=jnp.asarray(ms_l))
    item_slot = jnp.asarray(slot_l, iss.I32)
    # resilience state (§9.14): per-lane fault keys/epochs, per-pair
    # retry counters + quarantine flags, the rollback snapshot and the
    # segment's own compare counts
    lane_key = seg_faults = None
    if faults is not None:
        lane_key = jnp.asarray(flexifault.lane_keys(faults.seed, chunk))
        # the seed enters only through the lane keys: one compiled
        # segment serves every seed of a schedule
        seg_faults = dataclasses.replace(faults, seed=0)
    elif dmr:
        lane_key = jnp.zeros(chunk, jnp.uint32)   # unread without faults
    epoch = jnp.zeros(chunk, iss.I32) if (faults is not None or dmr) \
        else None
    retries = jnp.zeros(chunk // 2, iss.I32) if dmr else None
    quar_d = jnp.zeros(chunk // 2, bool) if dmr else None
    snap = jax.tree.map(lambda x: jnp.array(x, copy=True),
                        state.lanes) if dmr else None
    seg_counts = jnp.zeros((n_shards, 2), iss.I32) if dmr else None
    acc = ResidentAcc(
        n_instr=jnp.zeros(n_shards * cap, iss.I32),
        n_two=jnp.zeros(n_shards * cap, iss.I32),
        n_cycles=jnp.zeros(n_shards * cap, iss.I32),
        halted=jnp.zeros(n_shards * cap, bool),
        out=jnp.zeros(n_shards * cap, iss.I32),
        mix_g=jnp.zeros((n_shards, n_groups, n_mix), iss.I32),
        prev_instr=jnp.asarray(prev_l, iss.I32),
        mems=jnp.zeros((n_shards * cap, mem_words), iss.I32)
        if keep_state else None,
        regs=jnp.zeros((n_shards * cap, 16), iss.I32)
        if keep_state else None,
        pc=jnp.zeros(n_shards * cap, iss.I32) if keep_state else None,
        mix_items=jnp.zeros((n_shards * cap, n_mix), iss.I32)
        if keep_state else None)
    if mesh is not None:
        state = jax.tree.map(jax.device_put, state,
                             dsharding.lane_shardings(mesh, state))
        item_slot = jax.device_put(
            item_slot, dsharding.lane_shardings(mesh, item_slot))
        acc = jax.tree.map(jax.device_put, acc,
                           dsharding.lane_shardings(mesh, acc))

        def _lane_put(x):
            return None if x is None else jax.device_put(
                x, dsharding.lane_shardings(mesh, x))

        lane_key = _lane_put(lane_key)
        epoch = _lane_put(epoch)
        retries = _lane_put(retries)
        quar_d = _lane_put(quar_d)
        if seg_counts is not None:
            seg_counts = jax.device_put(
                seg_counts, dsharding.lane_shardings(mesh, seg_counts))
        if snap is not None:
            snap = jax.tree.map(jax.device_put, snap,
                                dsharding.lane_shardings(mesh, snap))
    out_addr_dev = jnp.asarray(out_addr_np)
    # positional on purpose: test_shard_local.py wraps this factory
    # with a *args-only shim to audit the lowered HLO
    refill_fn = _resident_refill_runner(
        mesh, mem_words, n_groups, keep_state, use_pallas,
        faults is not None and not dmr, dmr, max_retries)

    def merged_vals(accv):
        """Per-item results: host `base` where done, else the item's
        accumulator row through the item->row table."""
        idx = np.clip(rowmap, 0, None)
        out = {}
        for k, b in base.items():
            v = accv[k][idx].astype(b.dtype)
            mask = done_mask if b.ndim == 1 else done_mask[:, None]
            out[k] = np.where(mask, b, v)
        return out

    def save_checkpoint():
        """Canonical snapshot at a refill boundary: (state, item_slot,
        acc) here are exactly the inputs the next refill would see, and
        staged-but-unconsumed items roll back into the pending spans
        (they were never stepped, so re-staging them after a resume is
        bit-exact)."""
        lanes = state.lanes
        accv = {k: clock.fetch(getattr(acc, k))
                for k in base}
        slot_h = clock.fetch(item_slot).astype(np.int64)
        prev_h = clock.fetch(acc.prev_instr)
        mix_now = mix_base + clock.fetch(acc.mix_g).astype(
            np.int64).sum(0)
        merged = merged_vals(accv)
        # global item of each in-flight lane, via the row table
        lane_rows = (np.arange(chunk) // spc) * cap + slot_h
        lane_item = np.where(
            slot_h >= 0,
            row_owner[np.clip(lane_rows, 0, n_shards * cap - 1)], -1)
        # pending = staged-but-unconsumed + not-yet-staged remainder
        pend_items = [[] for _ in range(n_groups)]
        for s in range(n_shards):
            k = int(staged_n[s])
            if k:
                srows = s * cap + st_slot[s, :k].astype(np.int64)
                sitems = row_owner[srows]
                for g in range(n_groups):
                    pend_items[g].append(
                        sitems[st_prog[s, :k] == g] - slot_base[g])
            for g in range(n_groups):
                rest = _span_items(spans[g][s])
                pend_items[g].append(rest[int(staged_cursor[g, s]):])
        prows = []
        for g in range(n_groups):
            items = np.sort(np.concatenate(
                [np.zeros(0, np.int64)] + pend_items[g]))
            prows += [(g, lo, hi) for lo, hi in _items_to_spans(items)]
        done_now = np.ones(total, bool)
        done_now[lane_item[lane_item >= 0]] = False
        for g, lo, hi in prows:
            done_now[slot_base[g] + lo:slot_base[g] + hi] = False
        tree = {"counts": counts.copy(), "done_mask": done_now,
                "mix_g": mix_now, "lane_item": lane_item,
                "lane_prev": prev_h,
                "pending": np.asarray(prows, np.int64).reshape(-1, 3),
                "counters": np.array([lane_steps, n_segments],
                                     np.int64),
                "ctrl": np.array([controller.rate, prev_seg],
                                 np.float64),
                "sched": np.array(controller.schedule, np.int64),
                "g_lane_steps": g_lane_steps.copy(),
                "g_segments": g_segments.copy()}
        tree.update({"val_" + k: v for k, v in merged.items()})
        tree.update(
            lane_regs=clock.fetch(lanes.regs),
            lane_pc=clock.fetch(lanes.pc),
            lane_mem=clock.fetch(lanes.mem),
            lane_halted=clock.fetch(lanes.halted),
            lane_n_instr=clock.fetch(lanes.n_instr),
            lane_n_two=clock.fetch(lanes.n_two_stage),
            lane_mix=clock.fetch(lanes.mix),
            lane_n_cycles=clock.fetch(lanes.n_cycles),
            lane_prog=clock.fetch(state.prog_id),
            lane_ms=clock.fetch(state.max_steps))
        dckpt.save(checkpoint_dir, n_segments, tree)

    last_saved = n_segments
    flushed_at = lane_steps
    with clock.span("fleet.stream"):
        try:
            with clock.span("fleet.restock"):
                restock()
            while retired < total:
                if crash_after is not None and n_segments >= crash_after:
                    raise InjectedFault(
                        f"injected fault after segment {n_segments}")
                if checkpoint_dir is not None and checkpoint_every > 0 \
                        and n_segments - last_saved >= checkpoint_every:
                    with clock.span("fleet.checkpoint"):
                        save_checkpoint()
                    last_saved = n_segments
                with clock.span("fleet.upload"):
                    upload()
                with clock.span("fleet.dispatch"):
                    staged_dev_n = jnp.asarray(staged_n, iss.I32)
                    if dmr:
                        (state, item_slot, epoch, retries, quar_d, acc,
                         stats) = refill_fn(
                            state, item_slot, epoch, retries, quar_d,
                            snap, seg_counts, acc, *staged["dev"],
                            staged_dev_n, out_addr_dev)
                    elif faults is not None:
                        state, item_slot, epoch, acc, stats = refill_fn(
                            state, item_slot, epoch, acc, *staged["dev"],
                            staged_dev_n, out_addr_dev)
                    else:
                        state, item_slot, acc, stats = refill_fn(
                            state, item_slot, acc, *staged["dev"],
                            staged_dev_n, out_addr_dev)
                    seg_steps = controller.next_seg()
                    # positional on purpose: test_shard_local.py wraps
                    # this factory with a *args-only shim to audit the
                    # lowered HLO
                    seg_fn = _packed_segment_runner(
                        stepper, chunk, seg_steps, mem_words, n_groups,
                        bank_np.shape[1], mesh, subset, timing,
                        seg_faults, dmr, max_retries if dmr else 0)
                    if dmr:
                        state, snap, epoch, retries, seg_counts = seg_fn(
                            bank, code_len, mem_len, cost, lane_key,
                            epoch, retries, state)
                    elif faults is not None:
                        state = seg_fn(bank, code_len, mem_len, cost,
                                       lane_key, epoch, state)
                    else:
                        state = seg_fn(bank, code_len, mem_len, cost,
                                       state)
                    if hasattr(stats, "copy_to_host_async"):
                        stats.copy_to_host_async()
                # blocks until refill_i only — seg_i is already running;
                # one (n_shards, 3+G) read regardless of device count
                # ((n_shards, 7+G) under DMR: +detected/corrected/
                # quarantined/discarded)
                sv = np.asarray(clock.fetch(stats), np.int64)
                n_ret = int(sv[:, 0].sum())
                if dmr:
                    detected += int(sv[:, 3].sum())
                    corrected += int(sv[:, 4].sum())
                    quarantined += int(sv[:, 5].sum())
                    discarded += int(sv[:, 6].sum())
                    n_quar += sv[:, 5]
                    if (n_quar >= spc // 2).any():
                        raise RuntimeError(
                            f"DMR pool starved: all {spc // 2} lane "
                            f"pairs of a shard are quarantined with "
                            f"items still pending — raise chunk, raise "
                            f"max_retries, or fix the fault rate")
                    act_s = sv[:, 7:]
                else:
                    act_s = sv[:, 3:]
                deltas = sv[:, 2]
                sh_act = act_s.sum(1) > 0
                if sh_act.any():
                    n_segments += 1
                    g_segments += act_s.sum(0) > 0
                    g_lane_steps += (act_s * deltas[:, None]).sum(0)
                    stepped = spc * deltas * sh_act
                    lane_steps += int(stepped.sum())
                    shard_steps += stepped
                controller.record(n_ret, prev_seg)
                prev_seg = seg_steps
                retired += n_ret
                shard_retired += sv[:, 0]
                if mix_room is not None and (
                        lane_steps - flushed_at + chunk * seg_steps
                        > mix_room):
                    mix_base += clock.fetch(acc.mix_g).astype(
                        np.int64).sum(0)
                    acc = acc._replace(mix_g=jax.device_put(
                        np.zeros(acc.mix_g.shape, np.int32),
                        acc.mix_g.sharding))
                    flushed_at = lane_steps
                with clock.span("fleet.restock"):
                    consume(sv[:, 1])
                    restock()
        finally:
            for row in prefs:
                for p in row:
                    p.close()

        # ---- drain: ONE demux of the on-device accumulators, merged with
        # the host base through the item->row table
        with clock.span("fleet.drain"):
            accv = {"n_instr": clock.fetch(acc.n_instr),
                    "n_two": clock.fetch(acc.n_two)}
            accv["n_cycles"] = clock.fetch(acc.n_cycles) if timing \
                else np.zeros(n_shards * cap, np.int64)
            accv["halted"] = clock.fetch(acc.halted)
            accv["out"] = clock.fetch(acc.out)
            res_mix_g = mix_base + clock.fetch(acc.mix_g).astype(
                np.int64).sum(0)
            if keep_state:
                accv["mems"] = clock.fetch(acc.mems)
                accv["regs"] = clock.fetch(acc.regs)
                accv["pc"] = clock.fetch(acc.pc)
                accv["mix_items"] = clock.fetch(acc.mix_items)
            merged = merged_vals(accv)

            r_instr, r_two, r_halt, r_out, r_mix = [], [], [], [], []
            r_cycles = []
            r_mem = r_regs = r_pc = r_mix_items = None
            if keep_state:
                r_mem, r_regs, r_pc, r_mix_items = [], [], [], []
            for g, grp in enumerate(groups):
                sl = slice(int(slot_base[g]), int(slot_base[g] + counts[g]))
                r_instr.append(merged["n_instr"][sl].astype(np.int64))
                r_two.append(merged["n_two"][sl].astype(np.int64))
                r_cycles.append(merged["n_cycles"][sl].astype(np.int64))
                r_halt.append(merged["halted"][sl])
                r_out.append(merged["out"][sl])
                r_mix.append(res_mix_g[g])
                if keep_state:
                    r_mem.append(merged["mems"][sl, :grp.mem_words].copy())
                    r_regs.append(merged["regs"][sl])
                    r_pc.append(merged["pc"][sl])
                    r_mix_items.append(merged["mix_items"][sl])

    return {"r_instr": r_instr, "r_two": r_two, "r_halt": r_halt,
            "r_out": r_out, "r_mix": r_mix, "r_mem": r_mem,
            "r_regs": r_regs, "r_pc": r_pc, "r_mix_items": r_mix_items,
            "r_cycles": r_cycles,
            "g_lane_steps": g_lane_steps, "g_segments": g_segments,
            "lane_steps": lane_steps, "n_segments": n_segments,
            "n_shards": n_shards,
            "shard_retired": shard_retired.tolist(),
            "shard_lane_steps": shard_steps.tolist(),
            "detected": detected, "corrected": corrected,
            "quarantined": quarantined, "discarded": discarded}


def run_workload_stream(w: Workload, n_items: int, *, seed: int = 0,
                        chunk: int = 256, seg_steps: int = 4096,
                        max_steps: Optional[int] = None,
                        keep_state: bool = False,
                        mesh: Optional[Mesh] = None,
                        stepper: Optional[str] = None,
                        prefetch: bool = True,
                        adaptive: bool = False,
                        cost: Optional[np.ndarray] = None,
                        subset: Optional[frozenset] = None,
                        faults: Optional[flexifault.FaultSpec] = None,
                        redundancy: str = "none",
                        max_retries: int = 2) -> FleetResult:
    """Convenience wrapper: stream a FlexiBench workload end to end.

    The branchless/pallas steppers' opcode subset is derived from the
    workload's program text, so the compiled segment contains only the
    ISA subset this workload retires (the RISP specialization knob
    applied to the simulator). `subset` pins it explicitly instead —
    e.g. FlexiLint's reachable-only subset (DESIGN.md §9.11)."""
    return run_stream(
        w.program.code, workload_source(w, seed), n_items=n_items,
        mem_words=w.total_mem_words,
        max_steps=w.max_steps if max_steps is None else max_steps,
        chunk=chunk, subset=subset,
        seg_steps=seg_steps, out_addr=w.out_addr, keep_state=keep_state,
        mesh=mesh, stepper=stepper, prefetch=prefetch,
        adaptive=adaptive, cost=cost, faults=faults,
        redundancy=redundancy, max_retries=max_retries)
