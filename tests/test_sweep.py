"""Monte Carlo carbon-planner sweep: determinism and oracle parity
(DESIGN.md §9.13).

The contracts pinned here, all as exact array equality:

- same seed + different tile sizes -> bit-identical reductions (the
  counter-based per-cell seeding plus associative accumulators);
- Pallas vs jnp paths bit-exact, at any row-tile size;
- a sweep read back in several runs of tiles (a small device stats
  buffer) equals the same sweep read back once, and `host_reads` counts
  one read per run of tiles plus one per flush;
- on point-mass lifetime distributions the device sweep equals the
  numpy `selection.total_grid` / `selection_map` oracles bit-for-bit
  (float64 under `enable_x64`), and Monte Carlo percentiles collapse
  to the closed-form point estimate;
- `serving_plan_jnp` equals the numpy `planner.plan_grid` oracle on
  shared grid points.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.carbon import DeviceProfile
from repro.core.planner import plan_grid
from repro.core.selection import (crossover_lifetime_s,
                                  crossover_lifetimes, selection_map,
                                  total_grid)
from repro.core import sweep
from repro.core.sweep import (LifetimeDist, SweepSpec, run_sweep,
                              serving_plan_jnp)
from repro.flexibits.cycles import CORES
from repro.kernels import carbon_sweep as csk

PROF = DeviceProfile(n_one_stage=600, n_two_stage=400, vm_kb=0.4,
                     nvm_kb=1.0)
DAY = 86_400.0
FIELDS = ("mean", "p50", "p90", "p99", "min", "max", "mean_emb",
          "mean_op", "fleet_mean", "counts", "hist")


def _mixture_spec(draws=32, seed=7):
    mix = LifetimeDist.mixture(
        [(LifetimeDist.lognormal(DAY * 30, 1.8), 0.7),
         (LifetimeDist.weibull(DAY * 300, 0.8), 0.3)])
    return SweepSpec(
        workloads=("w0", "w1"), profiles=(PROF, PROF),
        dists=(mix, LifetimeDist.point(DAY * 100)),
        execs_per_day=(1.0, 24.0, 96.0),
        intensities=(0.028, 0.367), volumes=(1.0, 1e9),
        draws=draws, seed=seed)


def _point_spec(draws=8, seed=3):
    lifes = [DAY * d for d in (1, 10, 100, 1000)]
    return SweepSpec(
        workloads=("w0",), profiles=(PROF,),
        dists=tuple(LifetimeDist.point(L) for L in lifes),
        execs_per_day=(1.0, 24.0, 96.0), intensities=(0.367,),
        volumes=(1e6,), draws=draws, seed=seed), lifes


def _assert_results_equal(a, b):
    for f in FIELDS:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f), f)
    for k in a.pareto:
        np.testing.assert_array_equal(a.pareto[k], b.pareto[k], k)


# ------------------------------------------------------ determinism
def test_tile_sizes_bit_identical():
    spec = _mixture_spec()
    runs = [run_sweep(spec, path="jnp", tile_cells=t)
            for t in (3, 7, 48, spec.n_cells)]
    for other in runs[1:]:
        _assert_results_equal(runs[0], other)


def test_flush_cadence_bit_identical():
    spec = _mixture_spec()
    a = run_sweep(spec, path="jnp", tile_cells=7)
    b = run_sweep(spec, path="jnp", tile_cells=7, flush_limit=1)
    _assert_results_equal(a, b)


def test_same_seed_reproduces_different_seed_differs():
    spec = _mixture_spec()
    a = run_sweep(spec, path="jnp", tile_cells=16)
    b = run_sweep(spec, path="jnp", tile_cells=16)
    _assert_results_equal(a, b)
    c = run_sweep(dataclasses.replace(spec, seed=spec.seed + 1),
                  path="jnp", tile_cells=16)
    assert not np.array_equal(a.mean, c.mean)


# ----------------------------------------------------------- read-back
def _cap_tiles(monkeypatch, spec, tile, n):
    """Set the stats-buffer budget to just under n + 1 tiles' worth of
    float32 rows, so it rounds down to n whole tiles."""
    row_bytes = len(sweep._STAT_FIELDS) * 4 + 4 * spec.n_candidates
    monkeypatch.setattr(sweep, "READBACK_BYTES",
                        row_bytes * tile * (n + 1) - 1)


@pytest.mark.parametrize("tile,tiles_per_read", [(7, 1), (7, 3), (48, 1)])
def test_capped_readback_bit_identical(monkeypatch, tile, tiles_per_read):
    """108 cells: neither tile size divides them, so the last run of
    tiles is partial."""
    spec = dataclasses.replace(_mixture_spec(),
                               intensities=(0.028, 0.2, 0.367),
                               volumes=(1.0, 1e3, 1e9))
    once = run_sweep(spec, path="jnp", tile_cells=tile)
    _cap_tiles(monkeypatch, spec, tile, tiles_per_read)
    capped = run_sweep(spec, path="jnp", tile_cells=tile)
    n_tiles = -(-spec.n_cells // tile)
    assert once.host_reads == 1
    assert capped.host_reads == -(-n_tiles // tiles_per_read) >= 3
    _assert_results_equal(once, capped)


@pytest.mark.parametrize("path", ["jnp", "pallas"])
def test_host_reads_count_runs_of_tiles_and_flushes(monkeypatch, path):
    spec = _mixture_spec()                       # 48 cells: 3 tiles of 16
    fits = run_sweep(spec, path=path, tile_cells=16)
    assert fits.host_reads == 1
    # a flush after every tile but the last, whose accumulators come
    # back with the final read
    flushed = run_sweep(spec, path=path, tile_cells=16, flush_limit=1)
    assert flushed.host_reads == 1 + 2
    _cap_tiles(monkeypatch, spec, 16, 1)
    both = run_sweep(spec, path=path, tile_cells=16, flush_limit=1)
    assert both.host_reads == 3 + 2
    _assert_results_equal(fits, flushed)
    _assert_results_equal(fits, both)


# --------------------------------------------------- pallas A/B parity
def test_pallas_vs_jnp_bit_exact():
    spec = _mixture_spec()
    a = run_sweep(spec, path="jnp", tile_cells=48)
    b = run_sweep(spec, path="pallas", tile_cells=48)
    _assert_results_equal(a, b)


def test_pallas_row_tiles_bit_exact():
    """Pallas == jnp at every row tiling the cell count derives: one
    whole block (12 cells) and tiles of 8 (136 cells), 72 (144) and
    128 (256)."""
    kw = dict(hist_lo=-4.0, hist_inv=12.8, par_lo=-4.0, par_inv=6.4)
    n_draws, n_cores = 8, 3
    for n_cells, tile in ((12, 12), (136, 8), (144, 72), (256, 128)):
        assert csk._pick_row_tile(n_cells) == tile
        rng = np.random.default_rng(n_cells)
        emb = jnp.asarray(rng.uniform(1e-4, 1e-2, (n_cells, n_cores)),
                          jnp.float32)
        kwh = jnp.asarray(rng.uniform(1e-9, 1e-6, (n_cells, n_cores)),
                          jnp.float32)
        inten = jnp.asarray(rng.uniform(0.01, 1.1, n_cells), jnp.float32)
        freq = jnp.asarray(rng.uniform(0.5, 100, n_cells), jnp.float32)
        life = jnp.asarray(rng.uniform(1, 4000, (n_cells, n_draws)),
                           jnp.float32)  # days — pre-divided like the engine
        valid = jnp.asarray(rng.random(n_cells) < 0.8)
        cell = jnp.arange(n_cells, dtype=jnp.int32)
        acc = csk.init_acc(64, 32, jnp.float32)
        ref_out, ref_acc = csk.sweep_tile(emb, kwh, inten, freq, life,
                                          valid, cell, acc, path="jnp", **kw)
        out, pacc = csk.sweep_tile(emb, kwh, inten, freq, life, valid,
                                   cell, acc, path="pallas", **kw)
        for a, b in zip(ref_out, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(ref_acc, pacc):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unknown_path_raises():
    spec = _mixture_spec()
    with pytest.raises(ValueError, match="unknown sweep path"):
        run_sweep(spec, path="fused")


# ------------------------------------------------- numpy oracle parity
def test_point_mass_equals_total_grid_bitwise():
    """On point-mass lifetime grids, float64 device totals ARE the numpy
    `total_grid` floats and the modal core IS `selection_map`."""
    spec, lifes = _point_spec(draws=8)
    cores = list(CORES.values())
    tg = total_grid(cores, PROF, np.asarray(lifes),
                    np.asarray(spec.execs_per_day))
    best = tg.min(axis=0)
    smap = selection_map(PROF, np.asarray(lifes),
                         np.asarray(spec.execs_per_day))
    with jax.enable_x64(True):
        res = run_sweep(spec, path="jnp", tile_cells=5,
                        dtype=np.float64)
        res1 = run_sweep(dataclasses.replace(spec, draws=1),
                         path="jnp", dtype=np.float64)
        resp = run_sweep(spec, path="pallas", tile_cells=12,
                         dtype=np.float64)
    sq = np.s_[:, :, 0, 0, 0, 0, 0]
    np.testing.assert_array_equal(res.p50[sq], best)
    np.testing.assert_array_equal(res.min[sq], best)
    np.testing.assert_array_equal(res.max[sq], best)
    np.testing.assert_array_equal(res1.mean[sq], best)
    np.testing.assert_array_equal(res.best_core[sq], smap)
    _assert_results_equal(res, resp)           # A/B holds in f64 too


def test_point_mass_percentiles_collapse_to_point_estimate():
    """MC percentiles in the point-mass limit are the closed-form point
    estimate: every order statistic equals every other, bit-for-bit."""
    spec, _ = _point_spec(draws=16)
    res = run_sweep(spec, path="jnp", tile_cells=6)
    for f in ("p50", "p90", "p99", "min", "max"):
        np.testing.assert_array_equal(getattr(res, f), res.min, f)
    assert res.hist.sum() == res.n_scenarios


def test_fleet_mean_scales_with_volume():
    spec = _mixture_spec()
    res = run_sweep(spec, path="jnp", tile_cells=16)
    v = np.asarray(spec.volumes)
    np.testing.assert_array_equal(
        res.fleet_mean,
        (res.mean.astype(np.float64)
         * v[None, None, None, :, None, None, None]).astype(np.float32))


def test_serving_plan_jnp_equals_plan_grid_bitwise():
    kv = 32 * 8 * 128 * 2 * 2
    kw = dict(n_params=8e9, kv_bytes_per_token=kv,
              lifetimes_days=np.array([1.0, 30.0, 365.0, 3 * 365.0]),
              qps_grid=np.logspace(1, 12, 12))
    ref = plan_grid(**kw)
    with jax.enable_x64(True):
        got = serving_plan_jnp(**kw)
    for k in ("variant_idx", "chips", "total_kg"):
        np.testing.assert_array_equal(np.asarray(got[k]), ref[k], k)


# ------------------------------------------------------- timing modes
def test_timing_axis_orders_base_dynamic_wcet():
    """One sweep prices base, dynamic, and certified-worst-case timing;
    with measured event vectors the dynamic price is >= base and the
    WCET certificate bounds both (it is priced from the dynamic cost
    row's static ceiling)."""
    events = [0.0] * 19
    events[0], events[1], events[2] = 600.0, 400.0, 120.0
    # dynamic-only events (taken branches / serial shifts / subword RMW):
    # priced 0 by the base cost row, so dynamic > base strictly.
    events[16], events[17], events[18] = 50.0, 200.0, 30.0
    prof = dataclasses.replace(PROF, events=tuple(events))
    # SERV/QERV/HERV dynamic event cycles are ~44.8k/14.0k/8.9k; the
    # certificate must sit above each core's dynamic-priced measurement
    # to bound it.
    wcet = ((60_000.0, 20_000.0, 12_000.0),)
    spec = SweepSpec(
        workloads=("w0",), profiles=(prof,),
        dists=(LifetimeDist.point(DAY * 100),),
        execs_per_day=(24.0,), intensities=(0.367,),
        timing=("base", "dynamic", "wcet"), wcet_cycles=wcet,
        draws=4, seed=0)
    res = run_sweep(spec, path="jnp")
    base, dyn, wc = (res.mean_op[0, 0, 0, 0, 0, t, 0] for t in range(3))
    assert base < dyn < wc


def test_spec_validation_errors():
    spec = _mixture_spec()
    with pytest.raises(ValueError, match="dists is empty"):
        run_sweep(dataclasses.replace(spec, dists=()))
    with pytest.raises(ValueError, match="draws"):
        run_sweep(dataclasses.replace(spec, draws=0))
    with pytest.raises(ValueError, match="unknown timing"):
        run_sweep(dataclasses.replace(spec, timing=("typical",)))
    with pytest.raises(ValueError, match="unknown redundancy"):
        run_sweep(dataclasses.replace(spec, redundancies=("quad",)))
    with pytest.raises(ValueError, match="fault rates"):
        run_sweep(dataclasses.replace(spec, fault_rates=(-1.0,)))
    with pytest.raises(ValueError, match="wcet"):
        run_sweep(dataclasses.replace(spec, timing=("wcet",)))
    with pytest.raises(ValueError, match="enable_x64"):
        run_sweep(spec, dtype=np.float64)


def test_plan_grid_empty_options_raise():
    kw = dict(n_params=8e9, kv_bytes_per_token=1e5,
              lifetimes_days=np.array([365.0]),
              qps_grid=np.array([100.0]))
    with pytest.raises(ValueError, match="chips_options is empty"):
        plan_grid(chips_options=(), **kw)
    with pytest.raises(ValueError, match="variants is empty"):
        plan_grid(variants=(), **kw)
    with pytest.raises(ValueError, match="chips_options is empty"):
        serving_plan_jnp(chips_options=(), **kw)


def test_plan_grid_no_warnings_on_infeasible():
    """inf/extreme qps demands must not raise numpy warnings: the util
    divide is masked to feasible options."""
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = plan_grid(n_params=8e9, kv_bytes_per_token=1e5,
                         lifetimes_days=np.array([365.0]),
                         qps_grid=np.array([100.0, 1e15, np.inf]))
    assert (plan["variant_idx"][0, 1:] == -1).all()


# ---------------------------------------------- redundancy axis (§9.14)
def test_redundancy_rate_zero_reproduces_selection():
    """At fault rate 0 spare copies only cost, never pay: the joint
    (core, redundancy) argmin picks 'none' everywhere and its core half
    IS `selection_map` — the redundancy-aware planner reproduces
    today's selections exactly."""
    spec, lifes = _point_spec(draws=4)
    spec = dataclasses.replace(spec, fault_rates=(0.0, 1e-4),
                               redundancies=("none", "dmr", "tmr"))
    smap = selection_map(PROF, np.asarray(lifes),
                         np.asarray(spec.execs_per_day))
    with jax.enable_x64(True):
        res = run_sweep(spec, path="jnp", tile_cells=5, dtype=np.float64)
    sq0 = np.s_[:, :, 0, 0, 0, 0, 0]              # fault-rate-0 slice
    np.testing.assert_array_equal(res.best_redundancy[sq0], 0)
    np.testing.assert_array_equal(res.best_core[sq0], smap)


def test_redundancy_expanded_paths_bit_exact():
    """jnp and Pallas reductions stay bit-exact with the candidate axis
    expanded to core x redundancy and a nonzero fault-rate axis."""
    spec = dataclasses.replace(_mixture_spec(draws=16),
                               fault_rates=(0.0, 1e-3),
                               redundancies=("none", "dmr"))
    a = run_sweep(spec, path="jnp", tile_cells=13)
    b = run_sweep(spec, path="pallas", tile_cells=48)
    _assert_results_equal(a, b)
    assert a.counts.shape[-1] == spec.n_candidates


# ------------------------------------------------ crossover vectorized
def test_crossover_matrix_matches_scalar():
    cores = list(CORES.values())
    mat = crossover_lifetimes(PROF, execs_per_day=24.0)
    assert mat.shape == (len(cores), len(cores))
    assert np.isinf(np.diag(mat)).all()
    for a, ca in enumerate(cores):
        for b, cb in enumerate(cores):
            s = crossover_lifetime_s(PROF, ca, cb, execs_per_day=24.0)
            assert mat[a, b] == s, (ca.name, cb.name)
    # a pair crosses in at most one direction
    finite = np.isfinite(mat)
    assert not (finite & finite.T & ~np.eye(len(cores), dtype=bool)).any()


# ------------------------------------------------- frontier extraction
def test_frontier_is_nondominated_and_annotated():
    spec = _mixture_spec(draws=64)
    res = run_sweep(spec, path="jnp", tile_cells=32)
    rows = res.frontier()
    assert rows, "frontier should not be empty"
    embs = [r["embodied_kg"] for r in rows]
    ops = [r["operational_kg"] for r in rows]
    assert embs == sorted(embs)
    assert ops == sorted(ops, reverse=True)       # strictly improving
    for r in rows:
        assert r["workload"] in spec.workloads
        assert r["core"] in [c.name for c in spec.cores]
        di, fi, ii, vi, wi, ti, fri = spec.decode_cell(r["cell"])
        assert spec.workloads[wi] == r["workload"]
        assert spec.dists[di].name == r["dist"]
        assert spec.fault_rates[fri] == r["fault_rate"]


def test_mixture_of_points_hits_both_components():
    """A 50/50 two-point mixture with 64 draws hits both components
    (P[miss] = 2^-63): min/max bracket exactly the two closed-form
    totals of the best core."""
    d1, d2 = DAY * 1.0, DAY * 2000.0
    mix = LifetimeDist.mixture([(LifetimeDist.point(d1), 0.5),
                                (LifetimeDist.point(d2), 0.5)])
    spec = SweepSpec(workloads=("w0",), profiles=(PROF,), dists=(mix,),
                     execs_per_day=(24.0,), intensities=(0.367,),
                     draws=64, seed=1)
    cores = list(CORES.values())
    tg = total_grid(cores, PROF, np.array([d1, d2]), np.array([24.0]))
    lo, hi = tg[:, 0, 0].min(), tg[:, 1, 0].min()
    with jax.enable_x64(True):
        res = run_sweep(spec, path="jnp", dtype=np.float64)
    assert res.min.ravel()[0] == lo
    assert res.max.ravel()[0] == hi
