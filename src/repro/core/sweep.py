"""Device-resident Monte Carlo carbon-planner sweep (DESIGN.md §9.13).

The paper's central claim is that the carbon-optimal architecture flips
with deployment lifetime (a 1000X spread) and scale (trillions of
items). `selection.py`/`planner.py` answer one modest point-estimate
grid per host call; this module answers *distributional* what-ifs at
interactive rates: scenario tensors over

    lifetime distribution x task frequency x grid carbon intensity x
    deployment volume x workload x timing model x fault rate
                                     (x core x redundancy, reduced)

evaluated as one fused jitted program, with Monte Carlo lifetime draws
(point / lognormal / Weibull mixtures) over the paper's 1000X lifetime
spread instead of point estimates.

Engine shape (the `fleet/engine.py` streaming discipline, applied to
scenarios instead of items):

- **Streamed tiles, bounded memory, one read-back.** The flat cell
  space is walked in fixed tiles; per-tile device work is O(tile x
  draws x cores). Each tile writes its per-cell summaries into a device
  stats buffer, and two small global accumulators (histogram + Pareto
  bins) are *donated* back to the jitted step every tile. The host reads
  nothing until a run of tiles fills the buffer or the sweep ends, then
  fetches buffer and accumulators in one `jax.device_get`. The buffer
  holds at most `READBACK_BYTES` of stat rows, so device memory is one
  tile plus `SweepAcc` plus that budget however large the sweep, and a
  sweep whose rows fit (every planner what-if of the benchmark) makes a
  single blocking read.
- **Counter-based per-cell seeding.** Scenario (cell, draw) derives its
  uniforms from `fold_in(fold_in(key, cell), draw)` — a pure function
  of the *global* indices, so tiles are order-independent and the whole
  sweep is bit-identical at any tile size (tests/test_sweep.py).
- **On-device reduce.** Core argmin/selection, per-cell draw statistics,
  the log-binned total histogram and the embodied-vs-operational Pareto
  frontier all reduce per tile (`kernels/carbon_sweep.py`, Pallas path
  + bit-exact jnp baseline); the (cells x draws) tensor is never
  materialized.
- **Oracles kept.** The numpy `selection.total_grid` / `planner.plan_grid`
  grids stay as host oracles: on point-mass lifetime distributions the
  sweep's totals/argmin equal `total_grid`/`selection_map` bit-for-bit
  (float64 + `jax.enable_x64(True)`), and `serving_plan_jnp`
  mirrors `plan_grid` exactly on shared grid points.

Timing models ride in as a scenario axis: "base" prices the two-bucket
analytic model (== the paper's Table-7 arithmetic), "dynamic" the §9.10
measured event vectors with dynamic cost rows, "wcet" FlexiLint's §9.11
static worst-case certificates, and "measured" caller-supplied mean
cycles from fleet runs — so one sweep prices measured, base, dynamic and
certified-worst-case carbon in a single pass.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.core.carbon import (REDUNDANCY_MODES, DeviceProfile,
                               operational_kg, redundancy_energy_factor,
                               redundant_embodied_kg, sdc_derating,
                               soc_embodied_kg)
from repro.core.planner import (CHIP_POWER_W, PUE, TPU_EMBODIED_KG,
                                ServeVariant, VARIANTS,
                                tokens_per_s_per_chip)
from repro.flexibits.cycles import CLOCK_HZ, CORES, Core
from repro.kernels import carbon_sweep as csk

I32 = jnp.int32

# lifetime-distribution component kinds
POINT, LOGNORMAL, WEIBULL = 0, 1, 2
TIMING_MODES = ("base", "dynamic", "wcet", "measured")

DAY_S = 86_400.0
YEAR_S = 365.0 * DAY_S
_PCTS = (50, 90, 99)
# per-cell draw statistics, in the row order of the device stats buffer
_STAT_FIELDS = ("mean", "p50", "p90", "p99", "min", "max", "mean_emb",
                "mean_op", "fleet_mean")
# Byte budget of the device stats buffer (stat fields plus int32 counts
# per cell, unpadded; a TPU's 8-row tiles about double it). A sweep whose
# cells fit reads back once; a larger one once per buffer of cells.
READBACK_BYTES = 64 << 20


# --------------------------------------------------------- distributions
@dataclasses.dataclass(frozen=True)
class LifetimeDist:
    """Mixture of point / lognormal / Weibull lifetime components.

    `comps` rows are (kind, p1, p2, weight): point -> (p1=seconds),
    lognormal -> (p1=ln median seconds, p2=sigma of ln), Weibull ->
    (p1=scale seconds, p2=shape k). Weights are normalized at
    construction. Draws use inverse-CDF transforms of counter-based
    uniforms, so a distribution is a pure function of (seed, cell,
    draw).
    """
    name: str
    comps: Tuple[Tuple[int, float, float, float], ...]

    @staticmethod
    def point(seconds: float, name: Optional[str] = None) -> "LifetimeDist":
        return LifetimeDist(name or f"point:{seconds:g}s",
                            ((POINT, float(seconds), 0.0, 1.0),))

    @staticmethod
    def lognormal(median_s: float, sigma: float,
                  name: Optional[str] = None) -> "LifetimeDist":
        """ln L ~ Normal(ln median, sigma). sigma ~ 1.8 spans the
        paper's 1000X lifetime spread at +/-2 sigma."""
        return LifetimeDist(
            name or f"lognormal:{median_s:g}s:{sigma:g}",
            ((LOGNORMAL, math.log(median_s), float(sigma), 1.0),))

    @staticmethod
    def weibull(scale_s: float, shape: float,
                name: Optional[str] = None) -> "LifetimeDist":
        """L ~ Weibull(scale, k): k<1 models infant-mortality-heavy
        deployments, k>1 wear-out-dominated ones."""
        return LifetimeDist(name or f"weibull:{scale_s:g}s:{shape:g}",
                            ((WEIBULL, float(scale_s), float(shape), 1.0),))

    @staticmethod
    def mixture(parts: Sequence[Tuple["LifetimeDist", float]],
                name: Optional[str] = None) -> "LifetimeDist":
        comps, names = [], []
        for d, w in parts:
            for kind, p1, p2, cw in d.comps:
                comps.append((kind, p1, p2, cw * float(w)))
            names.append(f"{d.name}@{w:g}")
        return LifetimeDist(name or "mix(" + "+".join(names) + ")",
                            tuple(comps))

    def normalized(self) -> Tuple[Tuple[int, float, float, float], ...]:
        tot = sum(c[3] for c in self.comps)
        if not (tot > 0):
            raise ValueError(f"distribution {self.name!r} has no weight")
        return tuple((k, p1, p2, w / tot) for k, p1, p2, w in self.comps)

    def support_max(self) -> float:
        """Reference upper lifetime for histogram sizing (draws beyond
        it clamp into the top bin)."""
        hi = 0.0
        for kind, p1, p2, _ in self.comps:
            if kind == POINT:
                hi = max(hi, p1)
            elif kind == LOGNORMAL:
                hi = max(hi, math.exp(p1 + 8.0 * p2))
            else:
                hi = max(hi, p1 * 30.0 ** (1.0 / p2))
        return hi


# ----------------------------------------------------------------- spec
@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """One scenario-sweep request. Cell axes in linear-index order
    (slowest to fastest): dists, execs_per_day, intensities, volumes,
    workloads, timing, fault_rates. Everything is hashable so compiled
    sweep steps cache across calls (`fleet/engine.py`'s lru-cached
    runner idiom).

    `fault_rates` (§9.14) is a scenario axis like intensity: each cell
    prices its candidates under one per-instruction transient-fault
    rate. `redundancies` expands the *reduced candidate* axis instead —
    the kernel argmins over core x redundancy jointly, so each cell
    reports the carbon-optimal (core, redundancy) pair. The defaults
    (one rate of 0.0, `("none",)`) leave every table and reduction
    bitwise identical to a redundancy-free sweep."""
    workloads: Tuple[str, ...]
    profiles: Tuple[DeviceProfile, ...]          # parallel to workloads
    dists: Tuple[LifetimeDist, ...]
    execs_per_day: Tuple[float, ...]
    intensities: Tuple[float, ...]
    volumes: Tuple[float, ...] = (1.0,)
    cores: Tuple[Core, ...] = tuple(CORES.values())
    timing: Tuple[str, ...] = ("base",)
    fault_rates: Tuple[float, ...] = (0.0,)
    redundancies: Tuple[str, ...] = ("none",)
    draws: int = 64
    seed: int = 0
    clock_hz: float = CLOCK_HZ
    # per-(workload, core) cycle overrides, parallel to workloads/cores:
    # required by the "wcet" (FlexiLint certificates, §9.11) and
    # "measured" (fleet-run mean cycles, §9.10) timing modes
    wcet_cycles: Optional[Tuple[Tuple[float, ...], ...]] = None
    measured_cycles: Optional[Tuple[Tuple[float, ...], ...]] = None

    @property
    def axis_sizes(self) -> Tuple[int, int, int, int, int, int, int]:
        return (len(self.dists), len(self.execs_per_day),
                len(self.intensities), len(self.volumes),
                len(self.workloads), len(self.timing),
                len(self.fault_rates))

    @property
    def n_candidates(self) -> int:
        """Width of the reduced axis: core x redundancy pairs. Joint
        candidate j decodes as (redundancy j // C, core j % C)."""
        return len(self.cores) * len(self.redundancies)

    @property
    def n_cells(self) -> int:
        n = 1
        for s in self.axis_sizes:
            n *= s
        return n

    @property
    def n_scenarios(self) -> int:
        return self.n_cells * self.draws

    def validate(self) -> None:
        names = ("dists", "execs_per_day", "intensities", "volumes",
                 "workloads", "timing", "fault_rates")
        for name, size in zip(names, self.axis_sizes):
            if size == 0:
                raise ValueError(f"SweepSpec.{name} is empty")
        if not self.cores:
            raise ValueError("SweepSpec.cores is empty")
        if not self.redundancies:
            raise ValueError("SweepSpec.redundancies is empty")
        if len(self.profiles) != len(self.workloads):
            raise ValueError("profiles must parallel workloads")
        if self.draws < 1:
            raise ValueError("draws must be >= 1")
        for t in self.timing:
            if t not in TIMING_MODES:
                raise ValueError(f"unknown timing mode {t!r}; "
                                 f"expected one of {TIMING_MODES}")
        for r in self.redundancies:
            if r not in REDUNDANCY_MODES:
                raise ValueError(f"unknown redundancy mode {r!r}; "
                                 f"expected one of {REDUNDANCY_MODES}")
        for fr in self.fault_rates:
            if not (fr >= 0.0):
                raise ValueError(f"fault rates must be >= 0, got {fr!r}")
        if "wcet" in self.timing and self.wcet_cycles is None:
            raise ValueError("timing mode 'wcet' needs wcet_cycles "
                             "(see workload_spec)")
        if "measured" in self.timing and self.measured_cycles is None:
            raise ValueError("timing mode 'measured' needs "
                             "measured_cycles")

    def decode_cell(self, idx: int
                    ) -> Tuple[int, int, int, int, int, int, int]:
        D, F, I, V, W, T, FR = self.axis_sizes
        fri = idx % FR
        idx //= FR
        ti = idx % T
        idx //= T
        wi = idx % W
        idx //= W
        vi = idx % V
        idx //= V
        ii = idx % I
        idx //= I
        return (idx // F, idx % F, ii, vi, wi, ti, fri)


# --------------------------------------------------------------- tables
@dataclasses.dataclass(frozen=True)
class SweepTables:
    """Host-side float64 anchors the device sweep consumes.

    The reduced candidate axis is core x redundancy (width
    `spec.n_candidates`, joint index j = r * C + c). `emb[fr, w, j]` is
    `carbon.redundant_embodied_kg` times the SDC derating for
    (redundancy, fault rate); `kwh[t, fr, w, j]` is the intensity-1
    daily-exec operational anchor — literally `operational_kg(core,
    prof, lifetime_s=86400, execs_per_day=1, intensity=1.0)` per timing
    mode, times `carbon.redundancy_energy_factor` and the same derating
    — so the device total ``emb + ((kwh * I) * life_days) * freq``
    retraces the numpy oracle `selection.total_grid` op for op. At the
    default `("none",)` / rate-0 axes every factor is exactly 1.0 and
    the tables are bitwise the redundancy-free ones.
    """
    emb: np.ndarray            # (FR, W, C*R)
    kwh: np.ndarray            # (T, FR, W, C*R)
    kind: np.ndarray           # (D, K) int32
    p1: np.ndarray             # (D, K)
    p2: np.ndarray             # (D, K)
    cum_prev: np.ndarray       # (D, K-1) mixture CDF boundaries
    hist_lo: float
    hist_inv: float
    par_lo: float
    par_inv: float

    def hist_edges(self, n_hist: int) -> np.ndarray:
        return 10.0 ** (self.hist_lo
                        + np.arange(n_hist + 1) / self.hist_inv)


def _mode_kwh(mode: str, core: Core, prof: DeviceProfile,
              clock_hz: float, wcet: Optional[float],
              measured: Optional[float]) -> float:
    if mode == "base":
        prof = dataclasses.replace(prof, dynamic=False)
        cycles = None
    elif mode == "dynamic":
        prof = dataclasses.replace(prof, dynamic=True)
        cycles = None
    elif mode == "wcet":
        cycles = wcet
    else:                                                  # measured
        cycles = measured
    return operational_kg(core, prof, lifetime_s=DAY_S, execs_per_day=1.0,
                          intensity=1.0, clock_hz=clock_hz, cycles=cycles)


def build_tables(spec: SweepSpec, n_hist: int = 64,
                 n_pareto: int = 32) -> SweepTables:
    spec.validate()
    W, C = len(spec.workloads), len(spec.cores)
    T, FR, R = len(spec.timing), len(spec.fault_rates), \
        len(spec.redundancies)
    emb = np.empty((FR, W, C * R))
    kwh = np.empty((T, FR, W, C * R))
    for wi, prof in enumerate(spec.profiles):
        n_instr = prof.n_one_stage + prof.n_two_stage
        for ci, core in enumerate(spec.cores):
            base = np.empty(T)
            for ti, mode in enumerate(spec.timing):
                base[ti] = _mode_kwh(
                    mode, core, prof, spec.clock_hz,
                    spec.wcet_cycles[wi][ci] if spec.wcet_cycles else None,
                    spec.measured_cycles[wi][ci]
                    if spec.measured_cycles else None)
            for ri, red in enumerate(spec.redundancies):
                j = ri * C + ci
                remb = redundant_embodied_kg(core, prof, red)
                for fri, rate in enumerate(spec.fault_rates):
                    rfac = redundancy_energy_factor(
                        red, fault_rate=rate, n_instr=n_instr,
                        width=core.width)
                    derate = sdc_derating(red, fault_rate=rate,
                                          n_instr=n_instr,
                                          width=core.width)
                    # host float64 multiplies; 1.0 is exact identity
                    emb[fri, wi, j] = remb * derate
                    kwh[:, fri, wi, j] = base * rfac * derate

    K = max(len(d.comps) for d in spec.dists)
    D = len(spec.dists)
    kind = np.zeros((D, K), np.int32)
    p1 = np.ones((D, K))
    p2 = np.ones((D, K))
    cum = np.ones((D, K))
    for di, d in enumerate(spec.dists):
        comps = d.normalized()
        for k, (kd, a, b, w) in enumerate(comps):
            kind[di, k], p1[di, k], p2[di, k] = kd, a, b
        cum[di, :len(comps)] = np.cumsum([c[3] for c in comps])
        cum[di, len(comps):] = 1.0

    life_max = max(d.support_max() for d in spec.dists)
    tmin = float(emb.min())
    tmax = float(emb.max() + kwh.max() * max(spec.intensities)
                 * (life_max / DAY_S) * max(spec.execs_per_day))
    hist_lo = math.log10(tmin)
    span = max(math.log10(tmax) - hist_lo, 1e-9)
    par_lo = math.log10(float(emb.min()))
    par_span = max(math.log10(float(emb.max())) - par_lo, 1e-9)
    return SweepTables(emb=emb, kwh=kwh, kind=kind, p1=p1, p2=p2,
                       cum_prev=cum[:, :max(K - 1, 1)],
                       hist_lo=hist_lo, hist_inv=n_hist / span,
                       par_lo=par_lo, par_inv=n_pareto / par_span)


# ------------------------------------------------------- scenario draws
def _uniforms(key, cell: jax.Array, draws: int, dtype) -> jax.Array:
    """(tile, draws, 2) uniforms from counter-based per-cell keys:
    `fold_in(key, global_cell_index)` then a (draws, 2) shaped draw — a
    pure function of the GLOBAL cell index, never of tile boundaries,
    so any tiling replays the same scenarios bit-for-bit."""
    ck = jax.vmap(lambda i: jax.random.fold_in(key, i))(cell)
    return jax.vmap(
        lambda k: jax.random.uniform(k, (draws, 2), dtype))(ck)


def _lifetimes(kind, p1, p2, cum_prev, u) -> jax.Array:
    """Inverse-CDF mixture draw: u[..., 1] picks the component against
    the cumulative weights, u[..., 0] transforms through the component's
    quantile function."""
    dtype = u.dtype
    eps = 1e-12 if dtype == jnp.float64 else 1e-6
    uc = jnp.clip(u[..., 0], eps, 1.0 - eps)
    comp = jnp.sum((u[..., 1][..., None] >= cum_prev[:, None, :])
                   .astype(I32), axis=-1, dtype=I32)       # (tile, N)
    sel = comp[..., None] == jnp.arange(kind.shape[1], dtype=I32)

    def take(tab):
        return jnp.sum(jnp.where(sel, tab[:, None, :], 0), axis=-1,
                       dtype=tab.dtype)

    k = take(kind.astype(I32))
    a = take(p1.astype(dtype))
    b = take(p2.astype(dtype))
    z = jax.scipy.special.ndtri(uc)
    lognorm = jnp.exp(a + b * z)
    weibull = a * (-jnp.log1p(-uc)) ** (1.0 / b)
    return jnp.where(k == POINT, a,
                     jnp.where(k == LOGNORMAL, lognorm, weibull))


# ----------------------------------------------------------- sweep step
@functools.lru_cache(maxsize=8)
def _sweep_step(spec: SweepSpec, tile_cells: int, rows: int, path: str,
                dtype_str: str, n_hist: int, n_pareto: int,
                interpret: Optional[bool]):
    """Compiled streaming step for (spec, tile, buffer rows, path, dtype)
    — cached like `fleet/engine.py`'s segment runners so repeated
    what-ifs on the same spec skip retracing. Returns (jitted step,
    jitted init, tables).

    `step(acc, (stats, counts), start)` prices the tile of cells from
    `start`, folds it into `acc` and writes its per-cell summaries into
    the donated stats buffer at column `start % rows`: `stats` is
    `(len(_STAT_FIELDS), rows)` in the dtype, `counts` `(C*R, rows)`
    int32. Cells run along the minor axis, so a TPU pads the few fields
    to its 8-row tile and not the cells to its 128 lanes. `init()` makes
    a fresh `acc` and buffer in one dispatch (eagerly, each of their
    nine arrays would cost a dispatch of its own)."""
    tables = build_tables(spec, n_hist, n_pareto)
    dtype = jnp.dtype(dtype_str)
    D, F, I, V, W, T, FR = spec.axis_sizes
    n_cells = spec.n_cells
    draws = spec.draws
    emb_d = jnp.asarray(tables.emb, dtype)
    kwh_d = jnp.asarray(tables.kwh, dtype)
    freq_d = jnp.asarray(np.asarray(spec.execs_per_day, np.float64), dtype)
    inten_d = jnp.asarray(np.asarray(spec.intensities, np.float64), dtype)
    vol_d = jnp.asarray(np.asarray(spec.volumes, np.float64), dtype)
    kind_d = jnp.asarray(tables.kind)
    p1_d = jnp.asarray(tables.p1, dtype)
    p2_d = jnp.asarray(tables.p2, dtype)
    cum_d = jnp.asarray(tables.cum_prev, dtype)
    key = jax.random.PRNGKey(spec.seed)
    qidx = tuple(min(draws - 1, max(0, math.ceil(q / 100 * draws) - 1))
                 for q in _PCTS)

    def step(acc: csk.SweepAcc, buf, start):
        cell = start + jnp.arange(tile_cells, dtype=I32)
        valid = cell < n_cells
        c = jnp.where(valid, cell, n_cells - 1)
        fri = c % FR
        c = c // FR
        ti = c % T
        r = c // T
        wi = r % W
        r = r // W
        vi = r % V
        r = r // V
        ii = r % I
        r = r // I
        fi = r % F
        di = r // F
        u = _uniforms(key, cell, draws, dtype)
        life = _lifetimes(kind_d[di], p1_d[di], p2_d[di], cum_d[di], u)
        # seconds -> days ONCE, outside the A/B'd kernel. The barrier on
        # the divisor stops XLA:CPU's context-dependent f32 rewrite of
        # divide-by-constant into reciprocal-multiply, which otherwise
        # makes the jnp and Pallas paths diverge by 1 ulp.
        life_days = life / lax.optimization_barrier(
            jnp.asarray(DAY_S, dtype))
        out, acc = csk.sweep_tile(
            emb_d[fri, wi], kwh_d[ti, fri, wi], inten_d[ii], freq_d[fi],
            life_days,
            valid, cell, acc, hist_lo=tables.hist_lo,
            hist_inv=tables.hist_inv, par_lo=tables.par_lo,
            par_inv=tables.par_inv, path=path, interpret=interpret)
        by_draw = jnp.sort(out.best_total, axis=1)
        mean = out.sum_best / draws
        stats = jnp.stack([
            mean,                                  # in _STAT_FIELDS order
            by_draw[:, qidx[0]],
            by_draw[:, qidx[1]],
            by_draw[:, qidx[2]],
            out.min_best,
            out.max_best,
            out.sum_emb / draws,
            out.sum_op / draws,
            mean * vol_d[vi],
        ])
        col = start % rows
        stats_buf, counts_buf = buf
        counts = out.counts.T.astype(I32)            # int64 under x64
        return acc, (
            lax.dynamic_update_slice_in_dim(stats_buf, stats, col, axis=1),
            lax.dynamic_update_slice_in_dim(counts_buf, counts, col, axis=1))

    def init():
        return (csk.init_acc(n_hist, n_pareto, dtype),
                (jnp.zeros((len(_STAT_FIELDS), rows), dtype),
                 jnp.zeros((spec.n_candidates, rows), I32)))

    return jax.jit(step, donate_argnums=(0, 1)), jax.jit(init), tables


# --------------------------------------------------------------- result
_PAR_FIELDS = ("op", "emb", "life", "cell", "draw", "core")


def _acc_to_host(acc: csk.SweepAcc) -> Dict[str, np.ndarray]:
    """The Pareto arrays of a read-back `SweepAcc` (numpy leaves), keyed
    by `_PAR_FIELDS` (the order of `par_op` ... `par_core`)."""
    return dict(zip(_PAR_FIELDS, acc[1:]))


def _merge_pareto_host(a: Optional[Dict[str, np.ndarray]],
                       b: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Host-side flush merge — the same lexicographic-min rule as
    `carbon_sweep._pareto_merge`, so flush cadence cannot change the
    frontier."""
    if a is None:
        return b
    take_b = (b["op"] < a["op"]) \
        | ((b["op"] == a["op"]) & (b["cell"] < a["cell"])) \
        | ((b["op"] == a["op"]) & (b["cell"] == a["cell"])
           & (b["draw"] < a["draw"]))
    return {k: np.where(take_b, b[k], a[k]) for k in _PAR_FIELDS}


@dataclasses.dataclass
class SweepResult:
    """Streamed sweep summaries. Per-cell arrays have the spec's
    (D, F, I, V, W, T, FR) axis shape; `counts` appends the joint
    core x redundancy candidate axis."""
    spec: SweepSpec
    path: str
    mean: np.ndarray
    p50: np.ndarray
    p90: np.ndarray
    p99: np.ndarray
    min: np.ndarray
    max: np.ndarray
    mean_emb: np.ndarray
    mean_op: np.ndarray
    fleet_mean: np.ndarray
    counts: np.ndarray           # (..., C*R) chosen-candidate draws/cell
    hist: np.ndarray             # (B,) int64 best-total histogram
    hist_edges: np.ndarray       # (B+1,) kg CO2e bin edges
    pareto: Dict[str, np.ndarray]
    n_cells: int
    n_scenarios: int
    wall_s: float
    scenarios_per_s: float
    host_reads: int              # blocking device->host reads of the call

    @property
    def core_share(self) -> np.ndarray:
        return self.counts / self.spec.draws

    @property
    def best_core(self) -> np.ndarray:
        """Modal chosen core per cell (first max on draw-count ties);
        with a redundancy axis, the core half of the joint winner."""
        return np.argmax(self.counts, axis=-1) % len(self.spec.cores)

    @property
    def best_redundancy(self) -> np.ndarray:
        """Redundancy half of the modal joint (core, redundancy) winner
        — index into `spec.redundancies` (all 0 for default specs)."""
        return np.argmax(self.counts, axis=-1) // len(self.spec.cores)

    def quantile(self, q: float) -> float:
        """Whole-sweep best-total quantile from the streamed histogram
        (upper bin edge — exact to bin resolution)."""
        cum = np.cumsum(self.hist)
        i = int(np.searchsorted(cum, q * cum[-1]))
        return float(self.hist_edges[min(i + 1, len(self.hist))])

    def frontier(self) -> List[Dict]:
        """Non-dominated embodied-vs-operational points, ascending in
        embodied kg, annotated with their scenario coordinates."""
        finite = np.isfinite(self.pareto["op"])
        order = np.argsort(self.pareto["emb"][finite], kind="stable")
        rows, best_op = [], np.inf
        for j in np.nonzero(finite)[0][order]:
            op = float(self.pareto["op"][j])
            if op >= best_op:
                continue                      # dominated by a smaller-emb bin
            best_op = op
            cell = int(self.pareto["cell"][j])
            di, fi, ii, vi, wi, ti, fri = self.spec.decode_cell(cell)
            cand = int(self.pareto["core"][j])
            n_cores = len(self.spec.cores)
            rows.append({
                "embodied_kg": float(self.pareto["emb"][j]),
                "operational_kg": op,
                "total_kg": float(self.pareto["emb"][j] + op),
                "lifetime_s": float(self.pareto["life"][j] * DAY_S),
                "core": self.spec.cores[cand % n_cores].name,
                "redundancy": self.spec.redundancies[cand // n_cores],
                "workload": self.spec.workloads[wi],
                "dist": self.spec.dists[di].name,
                "execs_per_day": self.spec.execs_per_day[fi],
                "intensity": self.spec.intensities[ii],
                "volume": self.spec.volumes[vi],
                "timing": self.spec.timing[ti],
                "fault_rate": self.spec.fault_rates[fri],
                "cell": cell,
                "draw": int(self.pareto["draw"][j]),
            })
        return rows


# ----------------------------------------------------------- run_sweep
def run_sweep(spec: SweepSpec, *, path: str = "jnp",
              tile_cells: int = 1024, dtype=np.float32,
              n_hist: int = 64, n_pareto: int = 32,
              interpret: Optional[bool] = None,
              flush_limit: int = 1 << 30) -> SweepResult:
    """Stream the whole scenario space through the fused evaluate-and-
    reduce step in `tile_cells`-cell tiles.

    Every tile is dispatched without a read. Per-cell summaries stay in
    a device stats buffer of `rows = min(n_tiles * tile, cap)` cells,
    `cap` being the whole tiles that fit `READBACK_BYTES` (at least
    one); after each run of `rows` cells, and at the end together with
    the accumulators, one `jax.device_get` brings them back. Device
    memory is one tile plus `SweepAcc` plus at most `READBACK_BYTES`,
    whatever the sweep's size, and a sweep that fits makes one blocking
    read (`SweepResult.host_reads`). The global int32 histogram flushes
    into a host int64 tally (and the Pareto accumulator merges
    host-side) every `flush_limit` scenarios, one read each, so counts
    can never wrap. float64 sweeps (the oracle-parity mode) require
    `jax.enable_x64(True)` around the call.

    The call is a `sweep.whatif` span on the profiler's host plane:
    `sweep.prepare` (validation, the cached step, host and device
    buffers), `sweep.step` per tile, `sweep.readback` per run of tiles
    (its one read), and `sweep.finish` (histogram and Pareto flushes,
    the result).
    """
    with TraceAnnotation("sweep.whatif"):
        with TraceAnnotation("sweep.prepare"):
            spec.validate()
            dtype = np.dtype(dtype)
            if dtype == np.float64 and not jax.config.jax_enable_x64:
                raise ValueError("float64 sweeps need jax.enable_x64(True) "
                                 "around run_sweep")
            n_cells = spec.n_cells
            tile = max(1, min(tile_cells, n_cells))
            C = spec.n_candidates
            row_bytes = len(_STAT_FIELDS) * dtype.itemsize + 4 * C
            cap = max(1, READBACK_BYTES // (row_bytes * tile)) * tile
            rows = min(-(-n_cells // tile) * tile, cap)
            step, init, tables = _sweep_step(spec, tile, rows, path,
                                             dtype.name, n_hist, n_pareto,
                                             interpret)
            host = np.empty((len(_STAT_FIELDS), n_cells), dtype)
            host_counts = np.empty((C, n_cells), np.int32)
            hist64 = np.zeros(n_hist, np.int64)
            par_host: Optional[Dict[str, np.ndarray]] = None
            since_flush = 0
            host_reads = 0

            t0 = time.perf_counter()
            acc, buf = init()
        for start in range(0, n_cells, tile):
            with TraceAnnotation("sweep.step"):
                acc, buf = step(acc, buf, np.int32(start))
            end = min(start + tile, n_cells)
            last = end == n_cells
            if last or end % rows == 0:
                with TraceAnnotation("sweep.readback"):
                    if last:
                        (stats, counts), acc_host = jax.device_get((buf, acc))
                    else:
                        stats, counts = jax.device_get(buf)
                    run0 = start - start % rows
                    host[:, run0:end] = stats[:, :end - run0]
                    host_counts[:, run0:end] = counts[:, :end - run0]
                host_reads += 1
            since_flush += tile * spec.draws
            # the last tile's accumulators come back with the final read
            if since_flush >= flush_limit and not last:
                with TraceAnnotation("sweep.finish"):
                    flushed = jax.device_get(acc)
                    hist64 += flushed.hist
                    par_host = _merge_pareto_host(par_host,
                                                  _acc_to_host(flushed))
                    acc = csk.init_acc(n_hist, n_pareto, jnp.dtype(dtype))
                host_reads += 1
                since_flush = 0
        with TraceAnnotation("sweep.finish"):
            hist64 += acc_host.hist
            par_host = _merge_pareto_host(par_host, _acc_to_host(acc_host))
            wall = time.perf_counter() - t0

            shape = spec.axis_sizes
            return SweepResult(
                spec=spec, path=path,
                **{f: host[i].reshape(shape)
                   for i, f in enumerate(_STAT_FIELDS)},
                counts=host_counts.T.reshape(shape + (C,)),
                hist=hist64, hist_edges=tables.hist_edges(n_hist),
                pareto=par_host, n_cells=n_cells,
                n_scenarios=spec.n_scenarios, wall_s=wall,
                scenarios_per_s=spec.n_scenarios / max(wall, 1e-12),
                host_reads=host_reads)


# ------------------------------------------------- workload spec helper
def workload_spec(keys: Optional[Sequence[str]] = None, *,
                  dists: Sequence[LifetimeDist],
                  execs_per_day: Sequence[float],
                  intensities: Sequence[float],
                  volumes: Sequence[float] = (1.0,),
                  cores: Optional[Sequence[Core]] = None,
                  timing: Sequence[str] = ("base",),
                  fault_rates: Sequence[float] = (0.0,),
                  redundancies: Sequence[str] = ("none",),
                  draws: int = 64, seed: int = 0, n_profile: int = 3,
                  measured_cycles: Optional[Mapping[str, Mapping[
                      str, float]]] = None) -> SweepSpec:
    """Build a SweepSpec from FlexiBench workloads: PyISS-profiled
    DeviceProfiles (measured §9.10 event vectors) and, when the timing
    axis asks for it, FlexiLint WCET certificates (§9.11) priced per
    candidate core under the dynamic cost row."""
    from repro.flexibench.base import all_workloads, get
    from repro.flexibench.memory import profile_memory
    from repro.flexibits import analyze
    from repro.flexibits.cycles import TICKS_PER_CYCLE, cost_row
    from repro.flexibits.pyiss import PyISS

    keys = tuple(w.key for w in all_workloads()) if keys is None \
        else tuple(keys)
    cores = tuple(CORES.values()) if cores is None else tuple(cores)
    timing = tuple(timing)
    profiles, wcet_rows = [], []
    for k in keys:
        w = get(k)
        rng = np.random.default_rng(0)
        n1 = n2 = 0.0
        events = np.zeros_like(np.asarray(
            PyISS(w.program.code, w.total_mem_words,
                  w.initial_memory(w.gen_inputs(rng, 1)[0]))
            .run(w.max_steps).events, np.float64))
        rng = np.random.default_rng(0)
        xs = w.gen_inputs(rng, n_profile)
        for x in xs:
            sim = PyISS(w.program.code, w.total_mem_words,
                        w.initial_memory(x)).run(w.max_steps)
            n1 += sim.n_instr - sim.n_two_stage
            n2 += sim.n_two_stage
            events += np.asarray(sim.events, np.float64)
        mem = profile_memory(w)
        profiles.append(DeviceProfile(
            n_one_stage=n1 / n_profile, n_two_stage=n2 / n_profile,
            vm_kb=mem["vm_kb"], nvm_kb=mem["nvm_kb"],
            events=tuple(events / n_profile)))
        if "wcet" in timing:
            a = analyze.analyze_workload(w)
            row = []
            for core in cores:
                ticks = a.wcet_ticks(cost_row(core, dynamic=True))
                if ticks is None:
                    raise ValueError(f"workload {k!r} has no finite "
                                     f"WCET certificate")
                row.append(ticks / TICKS_PER_CYCLE)
            wcet_rows.append(tuple(row))
    meas = None
    if measured_cycles is not None:
        meas = tuple(tuple(float(measured_cycles[k][c.name])
                           for c in cores) for k in keys)
    return SweepSpec(
        workloads=keys, profiles=tuple(profiles), dists=tuple(dists),
        execs_per_day=tuple(float(f) for f in execs_per_day),
        intensities=tuple(float(i) for i in intensities),
        volumes=tuple(float(v) for v in volumes), cores=cores,
        timing=timing,
        fault_rates=tuple(float(f) for f in fault_rates),
        redundancies=tuple(redundancies),
        draws=draws, seed=seed,
        wcet_cycles=tuple(wcet_rows) if wcet_rows else None,
        measured_cycles=meas)


# ------------------------------------------- serving-planner jnp mirror
def serving_plan_jnp(*, n_params: float, kv_bytes_per_token: float,
                     lifetimes_days, qps_grid,
                     chips_options: Sequence[int] = (8, 16, 32, 64,
                                                     128, 256),
                     intensity: float = 0.367,
                     variants: Sequence[ServeVariant] = VARIANTS) -> Dict:
    """jnp mirror of `planner.plan_grid` — same option vectors, same op
    order, same first-min tie-break — exactly equal to the numpy
    oracle on shared grid points under float64/enable_x64
    (tests/test_sweep.py), and jit/vmap-compatible for distributional
    serving what-ifs (e.g. vmapped over an intensity axis)."""
    if not list(chips_options):
        raise ValueError("chips_options is empty")
    if not list(variants):
        raise ValueError("variants is empty")
    opt_vi, opt_chips, opt_tps = [], [], []
    for vi, v in enumerate(variants):
        for chips in chips_options:
            opt_vi.append(vi)
            opt_chips.append(chips)
            opt_tps.append(tokens_per_s_per_chip(
                n_params, v.weight_bits, kv_bytes_per_token, chips)
                * chips)
    opt_vi = jnp.asarray(np.asarray(opt_vi, np.int32))
    opt_chips = jnp.asarray(np.asarray(opt_chips, np.float64))
    opt_tps = jnp.asarray(np.asarray(opt_tps, np.float64))
    opt_prep = jnp.asarray(np.asarray(
        [variants[v].prep_kg for v in opt_vi], np.float64))

    days = jnp.asarray(lifetimes_days)
    qps = jnp.asarray(qps_grid)
    feasible = opt_tps[None, None, :] >= qps[None, :, None]
    emb = (opt_chips[None, None, :] * TPU_EMBODIED_KG
           * jnp.minimum(days / (3 * 365.0), 1.0)[:, None, None])
    util = jnp.where(feasible, qps[None, :, None] / opt_tps[None, None, :],
                     0.0)
    kwh = (opt_chips[None, None, :] * CHIP_POWER_W * PUE * util
           * days[:, None, None] * 24.0 / 1000.0)
    # both addends are >= 0; `abs` blocks XLA CPU's FMA contraction of
    # the mul-feeding-add so the mirror rounds exactly like numpy
    total = (opt_prep[None, None, :] + jnp.abs(emb)
             + jnp.abs(kwh * intensity))
    total = jnp.where(feasible, total, jnp.inf)
    k = jnp.argmin(total, axis=2)
    best_kg = jnp.take_along_axis(total, k[..., None], axis=2)[..., 0]
    met = jnp.isfinite(best_kg)
    best = jnp.where(met, opt_vi[k], -1).astype(jnp.int32)
    best_chips = jnp.where(met, opt_chips[k], 0).astype(jnp.int32)
    return {"variant_idx": best, "chips": best_chips,
            "total_kg": best_kg,
            "variants": [v.name for v in variants]}
